"""Shadow set selection (paper Algorithm 2) — the ShDE center selector.

Greedy single-pass epsilon-cover: take the first remaining point ``c``, absorb
every point within ``eps = sigma / ell`` (the *shadow* of ``c``), weight ``c``
by the shadow size, repeat until the dataset is exhausted.  Cost O(mn).

Implementations (DESIGN.md §3):
  * ``shadow_select_np``      — numpy oracle, literal Algorithm 2.
  * ``shadow_select``         — jittable ``lax.while_loop`` version with
    static padding (``max_centers``); sequential depth m.
  * ``shadow_select_blocked`` — blocked selector: each round keeps a batch of
    up to B mutually-eps-separated candidates and absorbs all their shadows
    in ONE Pallas assignment pass, cutting sequential depth from m to ~m/B.
  * ``shadow_select_streaming`` — two-level path for data that doesn't fit in
    device memory: per-chunk blocked selection + the streaming merge
    (cover radius degrades to 2*eps; the §5 bounds hold with ell -> ell/2).
  * ``merge_chunk_local``  — the streaming merge: weight-exact, on the
    device, fixed-shape reconciliation of candidate-center buckets into a
    merged set (the level-2 merge of the out-of-core ingest pipeline,
    core/ingest_pipeline.py), with center-budget spill handling;
    ``two_level_merge`` remains the one-shot replicated-merge variant the
    sharded selector uses.

Invariants (property-tested in tests/test_shadow.py):
  * every data point lies strictly within eps of its assigned center;
  * shadow sets partition the data: weights sum to n;
  * centers are pairwise >= eps apart (blocked selection preserves this: the
    batch is pruned to a mutually-separated prefix subset, and later rounds
    only see points no earlier center absorbed);
  * m is monotonically non-increasing in eps ... for the sequential order.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kernel_ops
from repro.obs.trace import span as _span

Array = jax.Array


def shadow_select_np(x: np.ndarray, eps: float):
    """Literal Algorithm 2 (numpy oracle). Returns (centers, weights, assign)."""
    n = x.shape[0]
    alive = np.ones(n, dtype=bool)
    assign = np.full(n, -1, dtype=np.int64)
    centers, weights = [], []
    eps2 = eps * eps
    while alive.any():
        i = int(np.argmax(alive))  # first element of the remaining set
        c = x[i]
        d2 = ((x - c) ** 2).sum(axis=1)
        shadow = alive & (d2 < eps2)  # strict inequality, per Algorithm 2
        assign[shadow] = len(centers)
        centers.append(c)
        weights.append(int(shadow.sum()))
        alive &= ~shadow
    return np.asarray(centers), np.asarray(weights, dtype=np.float64), assign


@partial(jax.jit, static_argnames=("max_centers",))
def shadow_select(x: Array, eps: Array, max_centers: int, valid=None):
    """Jittable Algorithm 2.

    Args:
      x: (n, d) data.
      eps: shadow radius sigma/ell.
      max_centers: static bound on m (use n for exactness).
      valid: optional (n,) bool mask — False rows are padding: never
        selected, never counted (the distributed path pads n to a device
        multiple and masks the tail).

    Returns:
      centers: (max_centers, d), zero-padded beyond m.
      weights: (max_centers,) float32, zero beyond m.  sum == #valid.
      assign:  (n,) int32 data->center map (alpha in §5); -1 on padding.
      m:       int32 number of centers actually selected.
    """
    n, d = x.shape
    xf = x.astype(jnp.float32)
    eps2 = jnp.asarray(eps, jnp.float32) ** 2

    def cond(state):
        alive, *_ = state
        return alive.any()

    def body(state):
        alive, centers, weights, assign, m = state
        i = jnp.argmax(alive)  # first alive index
        c = xf[i]
        d2 = jnp.sum((xf - c[None, :]) ** 2, axis=1)
        shadow = alive & (d2 < eps2)
        centers = centers.at[m].set(c)
        weights = weights.at[m].set(shadow.sum().astype(jnp.float32))
        assign = jnp.where(shadow, m, assign)
        # Guard: if m hits max_centers, absorb everything remaining into the
        # last center so the loop terminates (only possible if max_centers < n
        # and eps is tiny; callers use max_centers = n for exactness).
        overflow = m >= max_centers - 1
        shadow = jnp.where(overflow, alive, shadow)
        assign = jnp.where(overflow & alive, m, assign)
        weights = jnp.where(
            overflow,
            weights.at[m].set(alive.sum().astype(jnp.float32)),
            weights,
        )
        alive = alive & ~shadow
        return alive, centers, weights, assign, m + 1

    state = (
        jnp.ones(n, dtype=bool) if valid is None else valid.astype(bool),
        jnp.zeros((max_centers, d), jnp.float32),
        jnp.zeros((max_centers,), jnp.float32),
        jnp.full((n,), -1, jnp.int32),
        jnp.asarray(0, jnp.int32),
    )
    alive, centers, weights, assign, m = jax.lax.while_loop(cond, body, state)
    return centers, weights, assign.astype(jnp.int32), m


def shadow_select_host(x, eps: float):
    """Convenience host wrapper: jitted select, then slice to the true m."""
    x = jnp.asarray(x)
    centers, weights, assign, m = shadow_select(x, eps, max_centers=x.shape[0])
    m = int(m)
    return np.asarray(centers[:m]), np.asarray(weights[:m]), np.asarray(assign), m


@partial(jax.jit, static_argnames=("block",))
def _blocked_select_device(xf: Array, eps2: Array, block: int,
                           alive0: Array, stop_count: Array, w0=None):
    """Blocked-selection rounds fused in ONE device while_loop, running
    until the alive set drops to ``stop_count`` (0 = exhaust it).

    ``alive0`` lets the caller mark padding rows dead up front (the
    compaction cascade in ``shadow_select_blocked`` pads the shrunken alive
    set to a power of two so re-jits stay bounded).  ``w0`` (optional (n,)
    f32 or int32) gives each point a MASS instead of unit count — the
    streaming merge runs selection over weighted candidate centers, and a
    keeper's weight is then the sum of absorbed masses, in ``w0``'s dtype,
    rather than a point count.

    Per round (the old per-round host loop paid a host sync + numpy
    conversion per round — fusing the loop cut n=32k selection ~2x):

    1. Gather the first ``block`` still-alive points (index order) as the
       candidate batch.
    2. Prune the batch to the greedy prefix-independent subset: candidate j
       is KEPT iff it is >= eps from every kept candidate before it — the
       same rule sequential Algorithm 2 applies, restricted to the batch.
    3. Absorb: one Pallas nearest-center pass of ALL points against the kept
       candidates; any alive point strictly within eps joins the shadow of
       its nearest kept candidate.  Keepers scatter into the preallocated
       (n, d) center buffer at positions m + rank (invalid slots dropped).

    Every alive candidate leaves the alive set each round (kept ones absorb
    themselves; dropped ones are within eps of the keeper that shadowed
    them), so the round count is <= ceil(m/1) and typically ~m/B.

    Returns ``(alive, centers, weights, assign, mr)``: ``mr`` is the int32
    pair (centers selected, rounds run), one small array so the host reads
    both with one scalar fetch.
    """
    n, d = xf.shape
    iota = jnp.arange(n)

    def round_core(alive):
        # indices of the first `block` alive points (dead points sort last)
        order = jnp.argsort(jnp.where(alive, iota, n + iota))
        cand_idx = order[:block]
        cand_alive = alive[cand_idx]
        cand = xf[cand_idx]                                # (B, d)
        d2c = jnp.sum((cand[:, None, :] - cand[None, :, :]) ** 2, axis=-1)
        # candidate i < j blocks j when it is alive and within eps of it;
        # a candidate is decided once every earlier one blocking it is
        # (kept: j drops; all dropped: j is kept), so the loop runs as
        # many steps as the batch's longest chain of conflicts, not
        # ``block`` — the same keepers as a scan over the batch in order
        blocks = (d2c < eps2) & cand_alive[:, None] & (
            jnp.arange(block)[:, None] < jnp.arange(block)[None, :])

        def pick(state):
            keep, open_ = state
            killed = jnp.any(blocks & keep[:, None], axis=0)
            waits = jnp.any(blocks & open_[:, None], axis=0)
            return keep | (open_ & ~killed & ~waits), open_ & ~killed & waits

        keep, _ = jax.lax.while_loop(
            lambda s: s[1].any(), pick,
            (jnp.zeros((block,), bool), cand_alive))

        idx, d2min = kernel_ops.shadow_assign(
            xf, cand, valid=keep.astype(jnp.float32))
        # Candidate rows must resolve against the batch via the
        # direct-difference d2c, which is exact at zero distance: the assign
        # kernel's expansion form rounds off near zero, and at tiny eps a
        # keeper could then fail to absorb even itself and the round would
        # never make progress.  This also guarantees every alive candidate
        # leaves the alive set each round (a dropped candidate is, by the
        # pick rule, within eps of some keeper).
        d2c_kept = jnp.where(keep[:, None], d2c, jnp.inf)  # (B, B)
        idx = idx.at[cand_idx].set(
            jnp.argmin(d2c_kept, axis=0).astype(idx.dtype))
        d2min = d2min.at[cand_idx].set(jnp.min(d2c_kept, axis=0))
        absorbed = alive & (d2min < eps2)
        mass = jnp.where(absorbed, 1.0, 0.0) if w0 is None \
            else jnp.where(absorbed, w0, jnp.zeros_like(w0))
        counts = jnp.zeros((block,), mass.dtype).at[idx].add(mass)
        kept_rank = jnp.cumsum(keep) - 1                   # rank among kept
        return cand, keep, counts, idx, absorbed, kept_rank

    def cond(state):
        alive = state[0]
        return alive.any() & (alive.sum(dtype=jnp.int32) > stop_count)

    def body(state):
        alive, centers, weights, assign, m, rounds = state
        cand, keep, counts, idx, absorbed, kept_rank = round_core(alive)
        pos = jnp.where(keep, m + kept_rank, n)  # n = out-of-bounds: dropped
        centers = centers.at[pos].set(cand, mode="drop")
        weights = weights.at[pos].set(counts, mode="drop")
        assign = jnp.where(absorbed,
                           (m + kept_rank[idx]).astype(jnp.int32), assign)
        alive = alive & ~absorbed
        return alive, centers, weights, assign, \
            m + keep.sum(dtype=jnp.int32), rounds + 1

    state = (
        alive0,
        jnp.zeros((n, d), jnp.float32),
        jnp.zeros((n,), jnp.float32 if w0 is None else w0.dtype),
        jnp.full((n,), -1, jnp.int32),
        jnp.asarray(0, jnp.int32),
        jnp.asarray(0, jnp.int32),
    )
    alive, centers, weights, assign, m, rounds = jax.lax.while_loop(
        cond, body, state)
    return alive, centers, weights, assign, jnp.stack([m, rounds])


def _pow2_ceil(v: int) -> int:
    return 1 << max(v - 1, 0).bit_length()


def _pad_pow2(x: np.ndarray, orig: np.ndarray, w: np.ndarray | None):
    """Pad a working set with dead zero rows to a power of two, so the
    number of distinct jit shapes stays logarithmic in n.  Returns
    (x, padded-row -> original-row map, alive mask, masses or None)."""
    k = x.shape[0]
    npad = _pow2_ceil(k)
    xp = np.zeros((npad, x.shape[1]), np.float32)
    xp[:k] = x
    op = np.zeros((npad,), np.int64)
    op[:k] = orig
    alive = np.zeros((npad,), bool)
    alive[:k] = True
    if w is not None:
        wp = np.zeros((npad,), np.float32)
        wp[:k] = w
        w = wp
    return xp, op, alive, w


def shadow_select_blocked(x, eps: float, block: int | None = None,
                          weights=None):
    """Blocked Algorithm 2: ~m/B sequential rounds instead of m iterations,
    fused in device while_loops (no per-round host sync).

    Work efficiency: every round's absorption pass costs O(alive_now * B),
    but late rounds mostly revisit dead points if the loop keeps the full
    array.  So the device loop runs until the alive set HALVES, the host
    compacts the survivors, and selection resumes on the smaller array —
    total absorption work drops from rounds*n to ~2x the first phase.  The
    input and every compacted set are padded to a power of two, so a stream
    of calls at varying n (the streaming merge's candidate batches) compiles
    a logarithmic number of programs.

    Returns (centers (m, d), weights (m,), assign (n,), m) exactly like
    ``shadow_select_host``.  The center SET differs from the sequential order
    (points absorb to their NEAREST keeper, not the first), but all cover
    invariants hold: strict eps-cover, weights partition n, centers pairwise
    >= eps apart (a later-phase candidate was, by construction, never within
    eps of any earlier keeper).

    ``weights`` (optional (n,) masses) runs the WEIGHTED variant the
    streaming merge needs: each input point carries a mass and a keeper's
    output weight is the sum of absorbed masses (== point count when every
    mass is 1).  Output weights then partition ``sum(weights)`` instead
    of ``n``.
    """
    x_np = np.asarray(x, np.float32)
    n = x_np.shape[0]
    block = 256 if block is None else block
    eps2 = jnp.asarray(eps, jnp.float32) ** 2
    assign = np.full((n,), -1, np.int64)
    centers_out, weights_out = [], []
    m = 0
    # each halving phase's working set: its rows, row -> original-row map,
    # and masses.  Spans (DESIGN.md §16) split a phase into the host's pad,
    # the transfer, the device rounds and the host's compaction.
    cur_x, cur_orig = x_np, np.arange(n)
    cur_w = None if weights is None else np.asarray(weights, np.float32)
    while cur_x.shape[0]:
        k = cur_x.shape[0]
        with _span("select.phase", n_pad=_pow2_ceil(k), n_alive=k) as phase:
            with _span("select.pad"):
                xp, orig, alive0, wp = _pad_pow2(cur_x, cur_orig, cur_w)
            b = max(1, min(block, xp.shape[0]))
            nbytes = xp.nbytes + alive0.nbytes + (0 if wp is None
                                                  else wp.nbytes)
            with _span("select.put", bytes=nbytes) as sp:
                xd, alive_d, wd = sp.sync((
                    jnp.asarray(xp), jnp.asarray(alive0),
                    None if wp is None else jnp.asarray(wp)))
            with _span("select.rounds") as sp:
                alive, c, w, a, mr = sp.sync(_blocked_select_device(
                    xd, eps2, b, alive_d, jnp.asarray(k // 2, jnp.int32),
                    wd))
            del xd, alive_d, wd  # the phase's rows leave the device here
            with _span("select.compact"):
                mm, rounds = (int(v) for v in np.asarray(mr))
                a = np.asarray(a)
                absorbed = a >= 0
                assign[orig[absorbed]] = m + a[absorbed]
                centers_out.append(np.asarray(c[:mm]))
                weights_out.append(np.asarray(w[:mm]))
                m += mm
                still = np.flatnonzero(np.asarray(alive))
                cur_x, cur_orig = xp[still], orig[still]
                cur_w = None if wp is None else wp[still]
            phase.set(centers=mm, rounds=rounds)
    return (np.concatenate(centers_out),
            np.concatenate(weights_out).astype(np.float64),
            assign, m)


#: Rows of one device's level-1 candidates folded per merge step: the
#: fixed-shape candidate bucket of the streaming merge (DESIGN.md §9).  A
#: device with more candidates folds them in several buckets.
MERGE_BUCKET = 8192

#: Merged-set rows per step of the merge's nearest-center scans, which loop
#: over the live prefix only: their work follows m, not the capacity.  A
#: step is one assignment call; few, large steps keep a traced window's
#: device events few (a profile keeps a bounded number of them).
MERGE_TILE = 8192

#: The int32 counters of a merge state, in order.
MERGE_COUNTERS = ("m", "gathered", "absorbed", "kept", "spilled")
#: Its float32 accumulators: the distance pairs the absorb and reselect
#: passes need at least, and the largest squared spill distance.
MERGE_PAIRS = ("absorb_pairs", "reselect_pairs", "spill_d2")


def merge_capacity(bound: int, budget: int | None) -> int:
    """Rows of a merge state that can hold ``bound`` centers (at most
    ``budget``): a power of two, at least 128 — so the scans' tiles divide
    it — and fixed for a budgeted stream whatever m its data gives."""
    if budget is not None:
        bound = min(bound, budget)
    return max(128, _pow2_ceil(bound))


def merge_reserve(state: dict | None, bound: int, rows: int,
                  budget: int | None) -> tuple[int, int]:
    """The capacity a merge state needs before a chunk of ``rows`` more
    rows, and the host's bound on m after that chunk: ``(cap, bound)``.

    ``bound`` is the host's bound on m so far (m itself stays on the
    device).  A chunk keeps at most ``rows`` new centers, so ``bound +
    rows`` rows always suffice.  Within a budget that is at most the
    budget's bucket, and nothing is fetched.  Without one the capacity
    follows m, not the rows ingested: once ``bound + rows`` passes the
    state's rows, the host fetches the one scalar m (which waits for the
    chunks in flight) and grows the state only if m + ``rows`` could
    overflow it, to twice that, so the fetches stay few and the state
    O(m + chunk)."""
    have = 0 if state is None else state["c"].shape[0]
    if budget is not None:
        bound = min(bound + rows, budget)
        return max(have, merge_capacity(bound, budget)), bound
    if bound + rows > have and state is not None:
        bound = int(np.asarray(state["cnt"])[0])
    bound += rows
    return (have if bound <= have else merge_capacity(2 * bound, None),
            bound)


def merge_init(d: int, cap: int) -> dict:
    """An empty merge state of capacity ``cap``: the merged centers
    ``c`` (cap, d) float32 and their masses ``w`` (cap,) int32, live in
    the prefix ``[0, m)``, and the counters."""
    return {"c": jnp.zeros((cap, d), jnp.float32),
            "w": jnp.zeros((cap,), jnp.int32),
            "cnt": jnp.zeros((len(MERGE_COUNTERS),), jnp.int32),
            "pairs": jnp.zeros((len(MERGE_PAIRS),), jnp.float32)}


def merge_grow(state: dict, cap: int) -> dict:
    """The same state in ``cap`` rows (zero rows added; nothing fetched)."""
    extra = cap - state["c"].shape[0]
    if not extra:
        return state
    return dict(state, c=jnp.pad(state["c"], ((0, extra), (0, 0))),
                w=jnp.pad(state["w"], (0, extra)))


def merge_to_host(state: dict) -> dict:
    """The state's live part on the host, in one fetch: ``centers`` (m, d)
    float32, ``weights`` (m,) int64, ``counters`` and ``pairs``."""
    h = jax.device_get(state)
    m = int(h["cnt"][0])
    return {"centers": np.array(h["c"][:m], np.float32),
            "weights": np.array(h["w"][:m], np.int64),
            "counters": np.array(h["cnt"], np.int64),
            "pairs": np.array(h["pairs"], np.float32)}


def merge_from_host(tree: dict, cap: int) -> dict:
    """Inverse of :func:`merge_to_host`, padded to ``cap`` rows."""
    c = np.asarray(tree["centers"], np.float32)
    w = np.asarray(tree["weights"])
    m, d = c.shape
    if w.shape != (m,) or m > cap or int(tree["counters"][0]) != m:
        raise ValueError(f"merge state of {m} centers, {w.shape[0]} masses "
                         f"and m={int(tree['counters'][0])} does not fit "
                         f"{cap} rows")
    cp = np.zeros((cap, d), np.float32)
    cp[:m] = c
    wp = np.zeros((cap,), np.int32)
    wp[:m] = w
    return {"c": jnp.asarray(cp), "w": jnp.asarray(wp),
            "cnt": jnp.asarray(np.asarray(tree["counters"], np.int32)),
            "pairs": jnp.asarray(np.asarray(tree["pairs"], np.float32))}


def _nearest_live(x: Array, c: Array, live: Array):
    """Each row's nearest center among ``c[:live]``: (idx, d2), d2 = inf
    where ``live`` is 0.  The scan steps over ``MERGE_TILE``-row tiles of
    the live prefix only, so the work follows the live count."""
    tile = min(MERGE_TILE, c.shape[0])
    n = x.shape[0]

    def body(state):
        t, best_i, best_d = state
        ct = jax.lax.dynamic_slice_in_dim(c, t * tile, tile)
        ok = (t * tile + jnp.arange(tile)) < live
        i, d2 = kernel_ops.shadow_assign(x, ct, valid=ok.astype(jnp.float32),
                                         tag="ingest")
        better = d2 < best_d
        return (t + 1, jnp.where(better, i + t * tile, best_i),
                jnp.where(better, d2, best_d))

    tiles = (live + tile - 1) // tile
    _, idx, d2 = jax.lax.while_loop(
        lambda s: s[0] < tiles, body,
        (jnp.asarray(0, jnp.int32), jnp.zeros((n,), jnp.int32),
         jnp.full((n,), jnp.inf, jnp.float32)))
    return idx, d2


def _merge_fold(state: dict, xb: Array, wb: Array, eps2: Array, block: int,
                budget: int | None) -> dict:
    """Fold one candidate bucket ``xb`` (k, d) with int32 masses ``wb``
    (0 marks padding) into the merged set:

    1. **Absorb** — a candidate strictly within eps of its nearest merged
       center hands its mass to it.  Duplicates across chunk and device
       boundaries (d2 == 0) merge here.
    2. **Reselect** — the survivors (all >= eps from the merged set) run
       weighted blocked selection among themselves, restoring pairwise
       eps-separation; a keeper's mass is the int32 sum it absorbed.
    3. **Append within budget** — keepers append at ``[m, m + kept)``;
       those over ``budget`` spill their mass to the nearest retained
       center (merged set and the appended keepers) whatever the distance.

    Masses stay int32 throughout, exact to 2^31 rows.
    """
    c, w, cnt, pairs = state["c"], state["w"], state["cnt"], state["pairs"]
    cap, k = c.shape[0], xb.shape[0]
    m = cnt[0]
    live = wb > 0
    idx, d2 = _nearest_live(xb, c, m)
    hit = live & (d2 < eps2)
    w = w.at[jnp.where(hit, idx, cap)].add(wb, mode="drop")
    surv = live & ~hit
    _, kc, kw, _, mr = _blocked_select_device(
        xb, eps2, max(1, min(block, k)), surv, jnp.asarray(0, jnp.int32), wb)
    new = mr[0]
    kept = new if budget is None else jnp.clip(budget - m, 0, new)
    rows = jnp.arange(k)
    pos = jnp.where(rows < kept, m + rows, cap)
    c = c.at[pos].set(kc, mode="drop")
    w = w.at[pos].set(kw, mode="drop")
    spill = (rows >= kept) & (rows < new)

    def spill_into(w):
        sidx, sd2 = _nearest_live(kc, c, m + kept)
        return (w.at[jnp.where(spill, sidx, cap)].add(kw, mode="drop"),
                jnp.max(jnp.where(spill, sd2, 0.0)))

    w, sd2 = jax.lax.cond(spill.any(), spill_into,
                          lambda w: (w, jnp.float32(0.0)), w)
    f32 = jnp.float32
    n_live, n_surv = live.sum(dtype=jnp.int32), surv.sum(dtype=jnp.int32)
    cnt = cnt + jnp.stack([kept, n_live, hit.sum(dtype=jnp.int32), kept,
                           spill.sum(dtype=jnp.int32)])
    pairs = jnp.stack([pairs[0] + n_live.astype(f32) * m.astype(f32),
                       pairs[1] + n_surv.astype(f32) * new.astype(f32),
                       jnp.maximum(pairs[2], sd2)])
    return {"c": c, "w": w, "cnt": cnt, "pairs": pairs}


def merge_chunk_local(state: dict, c1: Array, w1: Array, eps2: Array,
                      block: int, budget: int | None,
                      axis: str | None = None) -> dict:
    """Fold one chunk's level-1 output into the merge state: this device's
    candidates ``c1`` (n_loc, d), live in the prefix where the float
    counts ``w1`` are positive, in ``MERGE_BUCKET``-row buckets.  Under
    ``shard_map`` over ``axis`` each bucket is all-gathered, the devices'
    buckets concatenated in device order, and every device folds that one
    batch, so the replicated state stays identical on every device; with
    ``axis=None`` it is the one-device merge.  The number of buckets is
    the largest device's ``ceil(candidates / bucket)``, on the device:
    nothing is fetched."""
    n_loc, d = c1.shape
    assert n_loc < 1 << 24, "level-1 counts must be exact in float32"
    kb = min(MERGE_BUCKET, n_loc)
    pad = -n_loc % kb
    w1 = w1.astype(jnp.int32)
    if pad:
        c1 = jnp.pad(c1, ((0, pad), (0, 0)))
        w1 = jnp.pad(w1, (0, pad))
    buckets = ((w1 > 0).sum(dtype=jnp.int32) + kb - 1) // kb
    if axis is not None:
        buckets = jax.lax.pmax(buckets, axis)

    def bucket(s, st):
        cs = jax.lax.dynamic_slice_in_dim(c1, s * kb, kb)
        ws = jax.lax.dynamic_slice_in_dim(w1, s * kb, kb)
        if axis is not None:
            cs = jax.lax.all_gather(cs, axis, tiled=True)
            ws = jax.lax.all_gather(ws, axis, tiled=True)
        return _merge_fold(st, cs, ws, eps2, block, budget)

    return jax.lax.fori_loop(0, buckets, bucket, state)


@partial(jax.jit, static_argnames=("block", "budget"))
def _merge_chunk(state, c1, w1, eps2, block: int, budget: int | None):
    """One device's merge program: :func:`merge_chunk_local` jitted."""
    return merge_chunk_local(state, c1, w1, eps2, block, budget)


def shadow_select_streaming(x, eps: float, chunk: int = 8192,
                            block: int = 256, budget: int | None = None):
    """Two-level streaming selection for out-of-memory datasets.

    Level 1 runs blocked selection per fixed-size chunk (only one chunk is
    device-resident at a time); level 2 folds each chunk's centers into the
    device-resident streaming merge (``_merge_chunk``, the same program the
    out-of-core ingest runs) — the merged set is the ONLY cross-chunk
    state, so peak memory is O(chunk + m) however large n grows.  Cover
    radius is 2*eps (triangle inequality), i.e. the §5 bounds hold with
    ell -> ell/2; the final assign map is recovered with one Pallas
    assignment pass per chunk.  ``budget`` caps the merged center count
    (over-budget candidates spill weight-exactly into their nearest
    retained center).

    Returns (centers, weights, assign, m).  Unlike the one-level selectors,
    ``weights`` are the MERGED level-1 shadow masses while ``assign`` maps
    each point to its NEAREST merged center, so ``bincount(assign)`` need
    not equal ``weights`` — both are valid 2*eps quantizations, they just
    answer different questions (density mass vs. nearest-cover membership).
    ``weights.sum() == n`` holds exactly (int32 masses, float64 out).
    """
    x = np.asarray(x, np.float32)
    n, d = x.shape
    block = 256 if block is None else block
    chunk = max(1, min(chunk, n))
    eps2 = jnp.float32(eps) ** 2
    state, bound = None, 0
    for s in range(0, n, chunk):
        xs = x[s: s + chunk]
        k = xs.shape[0]
        xp = np.zeros((chunk, d), np.float32)
        xp[:k] = xs
        b = max(1, min(block, chunk))
        _, c1, w1, _, _ = _blocked_select_device(
            jnp.asarray(xp), eps2, b, jnp.asarray(np.arange(chunk) < k),
            jnp.asarray(0, jnp.int32))
        cap, bound = merge_reserve(state, bound, k, budget)
        state = merge_init(d, cap) if state is None \
            else merge_grow(state, cap)
        state = _merge_chunk(state, c1, w1, eps2, block, budget)
    out = merge_to_host(state)
    centers, m = out["centers"], out["centers"].shape[0]
    assign = np.empty((n,), np.int64)
    for s in range(0, n, chunk):
        idx, _ = kernel_ops.shadow_assign(x[s : s + chunk], centers,
                                          tag="ingest")
        assign[s : s + chunk] = np.asarray(idx)
    return centers, out["weights"].astype(np.float64), assign, m


def two_level_merge(centers: Array, weights: Array, eps: Array,
                    max_centers: int):
    """Second-level shadow pass over candidate centers (distributed variant).

    Runs Algorithm 2 on the *centers* themselves, summing absorbed weights
    instead of counting points.  Quantization error of the two-level scheme is
    at most 2*eps (triangle inequality), i.e. the paper's bounds hold with
    ell -> ell/2 in the worst case (DESIGN.md §3).
    """
    n, d = centers.shape
    cf = centers.astype(jnp.float32)
    eps2 = jnp.asarray(eps, jnp.float32) ** 2
    alive0 = weights > 0  # padded slots carry zero weight

    def cond(state):
        alive, *_ = state
        return alive.any()

    def body(state):
        alive, out_c, out_w, m = state
        i = jnp.argmax(alive)
        c = cf[i]
        d2 = jnp.sum((cf - c[None, :]) ** 2, axis=1)
        shadow = alive & (d2 < eps2)
        out_c = out_c.at[m].set(c)
        out_w = out_w.at[m].set(jnp.where(shadow, weights, 0.0).sum())
        overflow = m >= max_centers - 1
        shadow = jnp.where(overflow, alive, shadow)
        out_w = jnp.where(
            overflow, out_w.at[m].set(jnp.where(alive, weights, 0.0).sum()), out_w
        )
        alive = alive & ~shadow
        return alive, out_c, out_w, m + 1

    state = (
        alive0,
        jnp.zeros((max_centers, d), jnp.float32),
        jnp.zeros((max_centers,), jnp.float32),
        jnp.asarray(0, jnp.int32),
    )
    _, out_c, out_w, m = jax.lax.while_loop(cond, body, state)
    return out_c, out_w, m
