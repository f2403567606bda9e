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
    device memory: per-chunk blocked selection + a ``StreamingMerge`` fold
    (cover radius degrades to 2*eps; the §5 bounds hold with ell -> ell/2).
  * ``StreamingMerge``     — weight-exact streaming reconciliation of
    candidate-center batches (the level-2 merge of the out-of-core ingest
    pipeline, core/ingest_pipeline.py), with center-budget spill handling;
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
    f32) gives each point a MASS instead of unit count — the streaming
    merge runs selection over weighted candidate centers, and a keeper's
    weight is then the sum of absorbed masses rather than a point count.

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

        def pick(j, keep):
            sep = jnp.all(jnp.where(keep, d2c[:, j] >= eps2, True))
            return keep.at[j].set(cand_alive[j] & sep)

        keep = jax.lax.fori_loop(0, block, pick, jnp.zeros((block,), bool))

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
            else jnp.where(absorbed, w0, 0.0)
        counts = jnp.zeros((block,), jnp.float32).at[idx].add(mass)
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
        jnp.zeros((n,), jnp.float32),
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


class StreamingMerge:
    """Weight-exact streaming extension of ``two_level_merge`` (DESIGN.md
    §9): reconcile candidate-center batches ONE BATCH AT A TIME instead of
    requiring every level-1 center in memory at once.

    Per ``update(cand_c, cand_w)``:

    1. **Absorb** — one assignment pass of the candidates against the
       current merged set; any candidate strictly within eps of a merged
       center hands its mass to that center.  Duplicate centers across
       chunk/shard boundaries land here (d2 == 0 < eps^2), so they merge
       instead of accumulating.
    2. **Select** — survivors (all >= eps from every merged center) run
       WEIGHTED blocked selection among themselves, restoring pairwise
       eps-separation; the kept centers append to the merged set.
    3. **Spill** — if appending would exceed ``budget`` centers, the
       over-budget keepers are instead absorbed into their nearest
       retained center (merged set + kept prefix) regardless of distance;
       ``spilled``/``max_spill_dist`` record how much cover quality the
       budget cost.

    Mass bookkeeping is float64 on host, so for integer point masses the
    invariant ``weights.sum() == total ingested mass`` holds EXACTLY up to
    2^53 (the one-shot device merge is only exact to f32's 2^24).  Cover
    radius of the merged set is 2*eps (triangle inequality), exactly like
    ``two_level_merge`` — the §5 bounds hold with ell -> ell/2.
    """

    def __init__(self, d: int, eps: float, budget: int | None = None,
                 block: int | None = 256):
        self.d = int(d)
        self.eps = float(eps)
        self.budget = None if budget is None else int(budget)
        self.block = 256 if block is None else int(block)
        self._c = np.zeros((0, self.d), np.float32)
        self._w = np.zeros((0,), np.float64)
        self.spilled = 0
        self.max_spill_dist = 0.0

    @property
    def m(self) -> int:
        return self._c.shape[0]

    @property
    def centers(self) -> np.ndarray:
        return self._c

    @property
    def weights(self) -> np.ndarray:
        return self._w

    # -- crash-consistent state (DESIGN.md §17) ---------------------------
    # The merge IS the only cross-chunk ingest state, so these two methods
    # are the whole checkpoint/resume contract: state() -> a flat numpy
    # tree checkpoint/store can publish atomically, load_state() -> the
    # bit-identical merge (f32 centers and f64 masses round-trip exactly;
    # the store's numpy-template restore preserves the f64 dtype).

    def state(self) -> dict:
        return {
            "centers": np.array(self._c, np.float32),
            "weights": np.array(self._w, np.float64),
            "spilled": np.asarray(self.spilled, np.int64),
            "max_spill_dist": np.asarray(self.max_spill_dist, np.float64),
        }

    def state_template(self) -> dict:
        """Zero-row tree with the same structure/dtypes as :meth:`state`
        (restore takes shapes from the checkpoint meta, not the template)."""
        return {
            "centers": np.zeros((0, self.d), np.float32),
            "weights": np.zeros((0,), np.float64),
            "spilled": np.asarray(0, np.int64),
            "max_spill_dist": np.asarray(0.0, np.float64),
        }

    def load_state(self, tree: dict) -> None:
        self._c = np.asarray(tree["centers"], np.float32)
        self._w = np.asarray(tree["weights"], np.float64)
        assert self._c.shape[1] == self.d and \
            self._c.shape[0] == self._w.shape[0]
        self.spilled = int(tree["spilled"])
        self.max_spill_dist = float(tree["max_spill_dist"])

    def _absorb_into(self, target_c, target_w, cand_c, cand_w, spill: bool):
        """Assign candidates to nearest target center; within-eps (or ALL,
        when ``spill``) hand over their mass.  Returns the survivor mask."""
        idx, d2 = kernel_ops.shadow_assign(cand_c, target_c, tag="ingest")
        idx, d2 = np.asarray(idx), np.asarray(d2)
        hit = np.ones_like(idx, dtype=bool) if spill \
            else d2 < np.float32(self.eps) ** 2
        np.add.at(target_w, idx[hit], cand_w[hit])
        if spill and hit.any():
            self.spilled += int(hit.sum())
            self.max_spill_dist = max(self.max_spill_dist,
                                      float(np.sqrt(d2[hit].max())))
        return ~hit

    def update(self, cand_c, cand_w) -> None:
        """Fold one batch of candidate centers (zero-weight rows are
        padding and ignored) into the merged set."""
        cand_c = np.asarray(cand_c, np.float32)
        cand_w = np.asarray(cand_w, np.float64)
        live = cand_w > 0
        cand_c, cand_w = cand_c[live], cand_w[live]
        if cand_c.shape[0] == 0:          # empty shard / all-padding batch
            return
        if self.m:
            keep = self._absorb_into(self._c, self._w, cand_c, cand_w,
                                     spill=False)
            cand_c, cand_w = cand_c[keep], cand_w[keep]
            if cand_c.shape[0] == 0:
                return
        c_new, w_new, _, m_new = shadow_select_blocked(
            cand_c, self.eps, block=self.block, weights=cand_w)
        room = m_new if self.budget is None else max(0, self.budget - self.m)
        kept = min(m_new, room)
        kept_c = np.asarray(c_new[:kept], np.float32)
        kept_w = np.asarray(w_new[:kept], np.float64)
        if kept < m_new:                  # center-budget spill
            target_c = np.concatenate([self._c, kept_c]) if self.m else kept_c
            target_w = np.concatenate([self._w, kept_w]) if self.m else kept_w
            if target_c.shape[0] == 0:
                raise ValueError("center budget is 0: nowhere to spill")
            self._absorb_into(target_c, target_w, c_new[kept:], w_new[kept:],
                              spill=True)
            self._c, self._w = target_c, target_w
        else:
            self._c = np.concatenate([self._c, kept_c]) if self.m else kept_c
            self._w = np.concatenate([self._w, kept_w]) if self.m else kept_w


def shadow_select_streaming(x, eps: float, chunk: int = 8192,
                            block: int = 256, budget: int | None = None):
    """Two-level streaming selection for out-of-memory datasets.

    Level 1 runs blocked selection per fixed-size chunk (only one chunk is
    device-resident at a time); level 2 folds each chunk's centers into a
    ``StreamingMerge`` — the merged set is the ONLY cross-chunk state, so
    peak memory is O(chunk + m) however large n grows.  Cover radius is
    2*eps (triangle inequality), i.e. the §5 bounds hold with ell -> ell/2;
    the final assign map is recovered with one Pallas assignment pass per
    chunk.  ``budget`` caps the merged center count (over-budget candidates
    spill weight-exactly into their nearest retained center).

    Returns (centers, weights, assign, m).  Unlike the one-level selectors,
    ``weights`` are the MERGED level-1 shadow masses while ``assign`` maps
    each point to its NEAREST merged center, so ``bincount(assign)`` need
    not equal ``weights`` — both are valid 2*eps quantizations, they just
    answer different questions (density mass vs. nearest-cover membership).
    ``weights.sum() == n`` holds exactly (float64 mass bookkeeping).
    """
    x = np.asarray(x, np.float32)
    n = x.shape[0]
    merge = StreamingMerge(x.shape[1], eps, budget=budget, block=block)
    for s in range(0, n, chunk):
        c, w, _, _ = shadow_select_blocked(x[s : s + chunk], eps, block=block)
        merge.update(c, w)
    m = merge.m
    centers = merge.centers
    assign = np.empty((n,), np.int64)
    for s in range(0, n, chunk):
        idx, _ = kernel_ops.shadow_assign(x[s : s + chunk], centers,
                                          tag="ingest")
        assign[s : s + chunk] = np.asarray(idx)
    return centers, merge.weights, assign, m


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
