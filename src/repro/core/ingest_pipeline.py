"""Out-of-core distributed ingestion: select -> fit at n = 10M+ (DESIGN.md §9).

The paper's Algorithm 2 is what makes huge-n KPCA *possible* (m ~ eps-cover
size, not n), but the seed implementations still assumed the (n, d) array was
resident.  This pipeline removes that assumption end to end:

  * the data source yields fixed-shape HOST chunks (only one-in-flight plus a
    prefetch window ever exists — peak host memory is O(chunk * depth), not
    O(n));
  * a producer thread generates the next chunk and stages it onto the
    device(s) (``jax.device_put``) while the consumer runs blocked selection
    on the current one — the async double-buffered feed.  ``IngestStats``
    records the measured copy/compute overlap fraction;
  * per chunk, selection runs the fused ``_blocked_select_device`` rounds —
    on a mesh, per device shard via ``distributed._chunk_select_sharded`` —
    and a second device program folds the candidate centers into the
    streaming merge (``shadow.merge_chunk_local``: weight-exact int32
    masses, center-budget spill; cover radius 2*eps, so the §5 bounds hold
    with ell -> ell/2).  The merge state lives on the device(s) at a fixed
    capacity; on a mesh the candidates are all-gathered and the state is
    replicated.  The host fetches nothing per chunk: the merged set comes
    back once per stream, or at a checkpoint;
  * the merged center set feeds Algorithm 1 straight from the device
    (``pipeline.fit_centers`` single-device, ``fit_rskpca_sharded`` on a
    mesh), both bucketed by capacity — the dataset is touched exactly once.

This module deliberately takes ANY chunk source (``.chunks()`` method or a
bare iterable of ``(x, n_valid)``) so it never imports ``repro.data``; the
deterministic synthetic source lives in ``data.kpca_datasets.ChunkedDataset``.
"""
from __future__ import annotations

import collections
import dataclasses
import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import shadow as shadow_mod
from repro.core.rsde import RSDE
from repro.core.rskpca import KPCAModel
from repro.obs import metrics as _om
from repro.obs import trace as _trace
from repro.obs.trace import span as _span
from repro.runtime import chaos
from repro.runtime.fault import Preempted, RetryPolicy, retry_call

Array = jax.Array

# fault-path telemetry (DESIGN.md §17): how often the pipeline had to
# recover, and what a preemption/resume cost.
_M_CKPTS = _om.counter("ingest.checkpoints")
_M_RESUMES = _om.counter("ingest.resumes")
_M_STRAGGLERS = _om.counter("ingest.stragglers")

# pipeline telemetry (DESIGN.md §16): the IngestStats fields double as LIVE
# gauges, refreshed per chunk — a 10M-row run is observable while it runs,
# not only from the end-of-run stats object.
_M_CHUNKS = _om.counter("ingest.chunks")
_M_ROWS = _om.counter("ingest.rows")
_M_CHUNK_MS = _om.histogram("ingest.chunk_ms")


def _observe_chunk(stats: "IngestStats") -> None:
    _om.gauge("ingest.feed_s").set(stats.feed_s)
    _om.gauge("ingest.stall_s").set(stats.stall_s)
    _om.gauge("ingest.compute_s").set(stats.compute_s)
    _om.gauge("ingest.overlap_fraction").set(stats.overlap_fraction)
    _om.gauge("ingest.m").set(stats.m)
    _om.gauge("ingest.spilled").set(stats.spilled)


def pad_block(x, rows: int):
    """Zero-pad a ragged (k, d) host block to fixed (rows, d) + valid mask.

    The fixed-shape contract shared by streaming ingest batches and ingest
    chunks: padding rows are masked (never selected, never counted), so one
    compiled program serves every block of a ragged stream.
    """
    x = np.asarray(x, np.float32)
    k = x.shape[0]
    if k == rows:
        return x, np.ones((rows,), bool)
    assert k < rows, f"block of {k} rows exceeds the fixed size {rows}"
    xp = np.zeros((rows, x.shape[1]), np.float32)
    xp[:k] = x
    ok = np.zeros((rows,), bool)
    ok[:k] = True
    return xp, ok


@dataclasses.dataclass
class IngestStats:
    """Measured pipeline counters (the numbers BENCH_rskpca.json records).

    ``feed_s`` is producer busy time (host chunk generation + device staging);
    ``stall_s`` is consumer time blocked waiting on the feed queue.  When the
    feed hides fully behind selection compute, stall collapses to the
    pipeline-fill latency of the first chunk and ``overlap_fraction`` -> 1;
    a transfer-bound pipeline drives it toward 0.
    """
    chunks: int = 0
    rows: int = 0
    m: int = 0
    feed_s: float = 0.0
    stall_s: float = 0.0
    compute_s: float = 0.0
    select_s: float = 0.0     # select+merge wall (includes stalls)
    fit_s: float = 0.0
    wall_s: float = 0.0       # end-to-end select -> fit
    spilled: int = 0
    max_spill_dist: float = 0.0
    # the streaming merge's counters, fetched once per stream: candidates
    # gathered, absorbed by the merged set, kept as new centers; the merge
    # state's capacity; the distance pairs its absorb and reselect passes
    # needed at least (bench/costs/ingest_merge.py reads them)
    gathered: int = 0
    absorbed: int = 0
    kept: int = 0
    cap: int = 0
    absorb_pairs: float = 0.0
    reselect_pairs: float = 0.0

    @property
    def overlap_fraction(self) -> float:
        """Fraction of feed work hidden behind selection compute."""
        if self.feed_s <= 0:
            return 1.0
        return float(np.clip((self.feed_s - self.stall_s) / self.feed_s,
                             0.0, 1.0))

    @property
    def rows_per_s(self) -> float:
        wall = self.wall_s or self.select_s
        return self.rows / wall if wall > 0 else 0.0


_END = object()


class _PrefetchFeed:
    """Producer-thread double buffer: generate + stage chunk i+1 while the
    consumer computes on chunk i.

    The queue holds at most ``depth - 1`` staged chunks (plus the one the
    producer is building), bounding host memory at ``depth`` chunks.  The
    producer's busy time accrues to ``feed_s`` (queue blocking excluded — a
    full queue means the feed is AHEAD, not working); consumer blocking on
    ``get`` accrues to ``stall_s``.  Producer exceptions re-raise at the
    consumer's next pull, so a failing source can't hang the pipeline.

    Fault model (DESIGN.md §17): ``ingest.feed`` is the chaos injection
    site for the staging step — transient faults are retried in place
    (``place`` is a pure device_put of an already-generated host chunk),
    delays model a straggling feed thread (what the consumer-side watchdog
    flags), permanent faults propagate as before.  ``close()`` gives the
    consumer a CLEAN early exit (preemption drain, consumer-side error):
    the producer stops at its next chunk boundary and the thread is
    joined, so no orphan thread keeps staging chunks onto a device the
    resumed process wants.
    """

    def __init__(self, it, place, stats: IngestStats, depth: int = 2):
        self._q: queue.Queue = queue.Queue(maxsize=max(1, depth - 1))
        self._stats = stats
        self._err: BaseException | None = None
        self._stop = threading.Event()
        self._t = threading.Thread(
            target=self._run, args=(iter(it), place), daemon=True)
        self._t.start()

    def _run(self, it, place):
        try:
            k = 0
            while not self._stop.is_set():
                t0 = time.perf_counter()
                with _span("ingest.feed_chunk", chunk=k):
                    try:
                        item = next(it)
                    except StopIteration:
                        break

                    def stage():
                        chaos.inject("ingest.feed")
                        return place(*item)

                    staged = retry_call(stage, key=f"feed{k}")
                # feed_s stops HERE: time blocked on a full queue below is
                # the feed being AHEAD of compute, not the feed working
                # (asserted by the slow-consumer test in tests/test_ingest)
                self._stats.feed_s += time.perf_counter() - t0
                k += 1
                while not self._stop.is_set():
                    try:
                        self._q.put(staged, timeout=0.05)
                        break
                    except queue.Full:  # consumer slow or gone: re-check stop
                        continue
        except BaseException as e:  # re-raised on the consumer side
            self._err = e
        finally:
            self._q.put(_END)

    def close(self) -> None:
        """Stop the producer at its next boundary and join it (drains the
        queue so a producer blocked on put can finish)."""
        self._stop.set()
        while True:
            try:
                if self._q.get_nowait() is _END:
                    break
            except queue.Empty:
                if not self._t.is_alive():
                    break
                time.sleep(0.005)
        self._t.join()

    def __iter__(self):
        while True:
            t0 = time.perf_counter()
            with _span("ingest.feed_wait"):
                item = self._q.get()
            self._stats.stall_s += time.perf_counter() - t0
            if item is _END:
                self._t.join()
                if self._err is not None:
                    raise self._err
                return
            yield item


def _chunk_iter(source, start: int = 0):
    """``.chunks()`` protocol or a bare iterable of (x, n_valid).

    ``start`` is the resume cursor: sources implementing ``chunks(start=)``
    (``data.ChunkedDataset``) seek for free; bare iterables are skipped
    item-by-item (correct, just not cheap — a resumable source should seek).
    """
    if hasattr(source, "chunks"):
        try:
            return source.chunks(start=start)
        except TypeError:  # a chunks() without resume support
            it = source.chunks()
    else:
        it = iter(source)
    import itertools
    return itertools.islice(it, start, None)


#: Ingest-checkpoint schema: what select_streaming persists per cursor.
#: The chunk source contributes NOTHING to the checkpoint — a
#: ``ChunkedDataset`` is a seed, so its "RNG state" is exactly the cursor.
def _ckpt_template(d: int) -> dict:
    return {
        "merge": {"centers": np.zeros((0, d), np.float32),
                  "weights": np.zeros((0,), np.int64),
                  "counters": np.zeros((len(shadow_mod.MERGE_COUNTERS),),
                                       np.int64),
                  "pairs": np.zeros((len(shadow_mod.MERGE_PAIRS),),
                                    np.float32)},
        # the merge state's rows and the host's bound on its m: the
        # capacity a resumed stream continues with, as the uninterrupted
        # one does (shadow.merge_reserve)
        "cap": np.asarray(0, np.int64),
        "bound": np.asarray(0, np.int64),
        "cursor": np.asarray(0, np.int64),      # chunks fully ingested
        "rows": np.asarray(0, np.int64),        # valid rows ingested
        "chunks": np.asarray(0, np.int64),      # == cursor (stats mirror)
    }


def _ckpt_restore(checkpoint_dir: str, d: int):
    """Newest intact ingest checkpoint, walking back over corrupt/torn
    steps (store.CheckpointCorrupt) — the graceful-degradation path of the
    checkpoint stack itself.  Returns ``(tree, step)`` or ``(None, None)``."""
    from repro.checkpoint import store

    for step in reversed(store.available_steps(checkpoint_dir)):
        try:
            tree, _ = store.restore_checkpoint(
                checkpoint_dir, _ckpt_template(d), step=step)
            return tree, step
        except store.CheckpointCorrupt:
            continue
    return None, None


def select_streaming(source, eps: float, *, block: int = 256,
                     budget: int | None = None, mesh=None,
                     axis: str = "data", prefetch: int = 2,
                     checkpoint_dir: str | None = None,
                     checkpoint_every: int = 0, resume: bool = False,
                     guard=None, watchdog=None):
    """Distributed out-of-core shadow selection over a chunk stream.

    Args:
      source: ``.chunks()`` object (e.g. ``data.ChunkedDataset``) or an
        iterable of ``(x (chunk, d) f32, n_valid)`` fixed-shape host chunks.
      eps: shadow radius sigma/ell.
      block: candidate batch size of the blocked selector.
      budget: cap on merged centers (over-budget mass spills weight-exactly
        to the nearest retained center; see ``shadow.merge_chunk_local``).
      mesh: optional device mesh — each chunk's rows shard over ``axis`` and
        every device runs selection on its local rows; chunk size must then
        divide the axis size.
      prefetch: feed depth (chunks of host memory the pipeline may hold).
      checkpoint_dir: enable crash consistency — every ``checkpoint_every``
        chunks the (merge state, chunk cursor) pair publishes atomically
        via ``checkpoint/store``; because the merge is the ONLY cross-chunk
        state and a ``ChunkedDataset`` regenerates any chunk from its seed,
        a resumed run is BIT-EXACT equal to an uninterrupted one (SIGKILL
        subprocess test in tests/test_chaos.py).
      checkpoint_every: checkpoint cadence in chunks (0 with a
        ``checkpoint_dir`` still checkpoints on preemption).
      resume: restore the newest intact checkpoint under ``checkpoint_dir``
        (corrupt/torn steps are skipped) and continue from its cursor.
      guard: optional ``runtime.PreemptionGuard`` — polled per chunk; on
        SIGTERM the loop drains cleanly: final checkpoint, producer thread
        joined, then raises ``runtime.Preempted`` with the resume step.
      watchdog: optional ``runtime.StepWatchdog`` wrapping each chunk's
        pull+select+merge — a straggling feed (slow disk, injected delay)
        flags here and counts into ``ingest.stragglers``.

    Returns ``(RSDE(scheme="shadow-ingest"), IngestStats)``.  Weights are
    int32 on the device, float64 here, and sum EXACTLY to the number of
    ingested rows; cover radius is 2*eps like every two-level path.
    """
    rsde, stats, _ = _select_streaming(
        source, eps, block=block, budget=budget, mesh=mesh, axis=axis,
        prefetch=prefetch, checkpoint_dir=checkpoint_dir,
        checkpoint_every=checkpoint_every, resume=resume, guard=guard,
        watchdog=watchdog)
    return rsde, stats


def _select_streaming(source, eps: float, *, block: int = 256,
                      budget: int | None = None, mesh=None,
                      axis: str = "data", prefetch: int = 2,
                      checkpoint_dir: str | None = None,
                      checkpoint_every: int = 0, resume: bool = False,
                      guard=None, watchdog=None):
    """``select_streaming``, also returning the merge state still on the
    device(s) — what ``ingest_fit`` hands to the fit."""
    stats = IngestStats()
    t_start = time.perf_counter()
    if budget is not None and budget < 1:
        raise ValueError(f"center budget {budget} leaves nowhere to spill")
    eps2 = jnp.float32(eps) ** 2
    stop0 = jnp.asarray(0, jnp.int32)
    ndev = 1 if mesh is None else mesh.shape[axis]
    if mesh is not None:
        from repro.core import distributed
        x_shard = NamedSharding(mesh, P(axis, None))
        v_shard = NamedSharding(mesh, P(axis))
        replicated = NamedSharding(mesh, P())

        def place(x, n_valid):
            assert x.shape[0] % ndev == 0, \
                f"chunk {x.shape[0]} must divide the '{axis}' axis ({ndev})"
            ok = np.arange(x.shape[0]) < n_valid
            return (jax.device_put(x, x_shard),
                    jax.device_put(ok, v_shard), int(n_valid))

        def on_devices(state):
            return jax.device_put(state, replicated)

        def step(state, xd, okd, b):
            c1, w1 = distributed._chunk_select_sharded(xd, okd, eps2, mesh,
                                                       axis, b)
            with _span("ingest.merge"):
                return distributed._merge_chunk_sharded(
                    state, c1, w1, eps2, mesh, axis, block, budget)
    else:
        def place(x, n_valid):
            ok = np.arange(x.shape[0]) < n_valid
            return jax.device_put(x), jax.device_put(ok), int(n_valid)

        def on_devices(state):
            return state

        def step(state, xd, okd, b):
            _, c1, w1, _, _ = shadow_mod._blocked_select_device(
                xd, eps2, b, okd, stop0)
            with _span("ingest.merge"):
                return shadow_mod._merge_chunk(state, c1, w1, eps2, block,
                                               budget)

    # The merge state lives on the device(s).  Its capacity must hold
    # every center the stream can keep: ``budget``'s bucket when one is set
    # (one capacity for the whole stream, whatever m the data gives), else
    # m and a chunk, m fetched only as the host's bound on it outgrows the
    # state (shadow.merge_reserve).
    state, bound = None, 0
    cursor = 0  # chunks FULLY ingested == resume start == checkpoint step
    if resume and checkpoint_dir is not None:
        d = getattr(source, "d", None)
        assert d is not None, \
            "resume requires a source exposing .d (e.g. ChunkedDataset) — " \
            "a bare iterable cannot be replayed from a cursor"
        tree, ck_step = _ckpt_restore(checkpoint_dir, int(d))
        if tree is not None:
            cursor = int(tree["cursor"])
            stats.chunks = int(tree["chunks"])
            stats.rows = int(tree["rows"])
            bound = int(tree["bound"])
            state = on_devices(shadow_mod.merge_from_host(
                tree["merge"], int(tree["cap"])))
            if _om.enabled():
                _M_RESUMES.inc()

    def _save_ckpt() -> None:
        """Atomic-publish the full cross-chunk state at the current cursor.

        The merge state is the ONLY accumulator and ``cursor`` replays the
        source (row i of a ChunkedDataset depends only on (name, seed, i)),
        so this pair IS crash consistency: resume == uninterrupted, bitwise.
        The checkpoint is the one fetch of the merge state before the
        stream ends.
        """
        from repro.checkpoint import store
        tree = {"merge": shadow_mod.merge_to_host(state),
                "cap": np.asarray(state["c"].shape[0], np.int64),
                "bound": np.asarray(bound, np.int64),
                "cursor": np.asarray(cursor, np.int64),
                "rows": np.asarray(stats.rows, np.int64),
                "chunks": np.asarray(stats.chunks, np.int64)}
        store.save_checkpoint(
            checkpoint_dir, cursor, tree,
            extra_meta={"eps": float(eps), "budget": budget, "block": block})
        if _om.enabled():
            _M_CKPTS.inc()

    feed = _PrefetchFeed(_chunk_iter(source, start=cursor), place, stats,
                         depth=prefetch)
    # the states of the chunks dispatched and maybe not yet run: the loop
    # waits (fetching nothing) for the oldest past ``prefetch``, so the
    # chunks the device holds stay O(prefetch) however long the stream
    in_flight: collections.deque = collections.deque()
    for xd, okd, n_valid in feed:
        if guard is not None and guard.should_stop:
            # drain: persist at the last FULLY ingested chunk, stop the
            # producer thread, and hand the resume step to the caller —
            # the pulled-but-unprocessed chunk is regenerated on resume.
            if checkpoint_dir is not None and state is not None:
                _save_ckpt()
            feed.close()
            raise Preempted(f"preempted at chunk {cursor}", step=cursor)
        if watchdog is not None:
            watchdog.start()
            flags0 = len(watchdog.flags)
        t0 = time.perf_counter()
        with _span("ingest.select_chunk", chunk=stats.chunks,
                   rows=int(n_valid)):
            cap, bound = shadow_mod.merge_reserve(state, bound,
                                                  max(n_valid, 1), budget)
            if state is None:
                state = on_devices(shadow_mod.merge_init(xd.shape[1], cap))
            elif cap > state["c"].shape[0]:
                state = on_devices(shadow_mod.merge_grow(state, cap))
            b = max(1, min(block, xd.shape[0] // ndev))

            def fold():
                # inject BEFORE the merge: the state is replaced only when
                # the step returns, so a transient here retries safely
                chaos.inject("ingest.merge")
                return step(state, xd, okd, b)

            state = retry_call(fold, key=f"merge{cursor}")
            in_flight.append(state["cnt"])
            if len(in_flight) > max(1, prefetch):
                in_flight.popleft().block_until_ready()
        cursor += 1
        stats.chunks += 1
        stats.rows += n_valid
        stats.compute_s += time.perf_counter() - t0
        if watchdog is not None:
            watchdog.stop(cursor - 1)
            if _om.enabled() and len(watchdog.flags) > flags0:
                _M_STRAGGLERS.inc(len(watchdog.flags) - flags0)
        if _om.enabled():
            _M_CHUNKS.inc()
            _M_ROWS.inc(n_valid)
            _M_CHUNK_MS.observe((time.perf_counter() - t0) * 1e3)
            _observe_chunk(stats)
        if checkpoint_dir is not None and checkpoint_every \
                and cursor % checkpoint_every == 0:
            _save_ckpt()
    if state is None:
        raise ValueError("empty source: no chunks to ingest")
    if checkpoint_dir is not None:
        _save_ckpt()  # final: a resume of a finished run is a no-op replay
    out = shadow_mod.merge_to_host(state)  # the stream's one fetch
    cnt = dict(zip(shadow_mod.MERGE_COUNTERS, out["counters"].tolist()))
    pairs = dict(zip(shadow_mod.MERGE_PAIRS, out["pairs"].tolist()))
    stats.select_s = time.perf_counter() - t_start
    stats.m = cnt["m"]
    stats.spilled = cnt["spilled"]
    stats.max_spill_dist = float(np.sqrt(pairs["spill_d2"]))
    stats.gathered, stats.absorbed, stats.kept = \
        cnt["gathered"], cnt["absorbed"], cnt["kept"]
    stats.cap = int(state["c"].shape[0])
    stats.absorb_pairs = pairs["absorb_pairs"]
    stats.reselect_pairs = pairs["reselect_pairs"]
    if _om.enabled():
        _observe_chunk(stats)
    rsde = RSDE(centers=out["centers"],
                weights=out["weights"].astype(np.float64), n=stats.rows,
                assign=None, scheme="shadow-ingest")
    return rsde, stats, state


def ingest_fit(source, kernel, rank: int, *, ell: float = 4.0,
               block: int = 256, budget: int | None = None, mesh=None,
               axis: str = "data", prefetch: int = 2,
               matfree: bool | None = None,
               checkpoint_dir: str | None = None,
               checkpoint_every: int = 0, resume: bool = False,
               guard=None, watchdog=None):
    """Single-pass out-of-core select -> fit: the n=10M front door.

    Streams ``source`` through ``select_streaming`` (eps = sigma/ell via
    ``kernel.epsilon``), then fits Algorithm 1 on the merged centers —
    ``pipeline.fit_centers`` on one device, the sharded/matrix-free fit when
    ``mesh`` is given, both fed the merged set from the device and both
    bucketed by capacity.  Returns ``(KPCAModel, IngestStats)``, the model
    carrying the merged masses (``weights``, float64, exact); the dataset is
    generated, staged, and read exactly once.  The fault-tolerance knobs
    (``checkpoint_dir``/``checkpoint_every``/``resume``/``guard``/
    ``watchdog``) pass straight through to ``select_streaming`` — the fit
    itself is a pure function of the selected centers, so select-phase
    crash consistency covers the whole front door.
    """
    from repro.core.pipeline import fit_centers

    t0 = time.perf_counter()
    with _span("ingest.select") as sp:
        rsde, stats, state = _select_streaming(
            source, kernel.epsilon(ell), block=block, budget=budget,
            mesh=mesh, axis=axis, prefetch=prefetch,
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every, resume=resume,
            guard=guard, watchdog=watchdog)
        if _trace.profiling():
            sp.set(m=stats.m, cap=stats.cap, gathered=stats.gathered,
                   absorbed=stats.absorbed, kept=stats.kept,
                   spilled=stats.spilled, absorb_pairs=stats.absorb_pairs,
                   reselect_pairs=stats.reselect_pairs,
                   d=rsde.centers.shape[1])
    t1 = time.perf_counter()
    # the merged set goes to the fit from the device; masses beyond 2^24
    # round in the fit's float32 operator only, never in the RSDE's
    w = state["w"].astype(jnp.float32)
    with _span("ingest.fit", m=rsde.m) as sp:
        if mesh is None:
            model = fit_centers(state["c"], w, rsde.n, kernel, rank,
                                m=rsde.m, matfree=matfree,
                                method="rskpca+shadow-ingest")
        else:
            from repro.core.distributed import fit_rskpca_sharded
            lam, proj = fit_rskpca_sharded(
                state["c"], w, rsde.n, kernel, rank, mesh, axis=axis,
                matfree=matfree, m=rsde.m)
            model = KPCAModel(kernel=kernel, centers=rsde.centers,
                              projector=proj, eigvals=lam,
                              method="rskpca+shadow-ingest")
        sp.sync(model.projector)
    model = dataclasses.replace(model, weights=rsde.weights)
    stats.fit_s = time.perf_counter() - t1
    stats.wall_s = time.perf_counter() - t0
    if _om.enabled():
        _om.gauge("ingest.fit_s").set(stats.fit_s)
        _om.gauge("ingest.rows_per_s").set(stats.rows_per_s)
    return model, stats
