"""Continuous-batching front end for the serving transform (DESIGN.md §8).

Request-at-a-time serving leaves the projection kernel badly underfed: a
single query row still pays a full dispatch, padded to the 128-lane floor,
and concurrent callers serialize on the device anyway.  This front end gives
the transform the batch sizes it was compiled for without giving up latency
SLOs:

  * ``submit`` enqueues a request (one or more query rows) and returns a
    ``concurrent.futures.Future`` immediately;
  * a dispatcher coalesces whatever is pending into ONE transform call,
    padding the fused row count up to the SAME power-of-two buckets the
    compiled projection already serves (``_pow2_ceil`` — the single
    bucketing rule repo-wide), so continuous batching introduces **zero new
    compiled shapes**; the ragged tail is padding rows whose outputs are
    sliced off before scatter (they never reach a caller);
  * coalescing is DEADLINE-AWARE: each request carries an absolute deadline
    (``slo_ms``), and the dispatcher waits for more work only while the
    oldest deadline's slack — minus an EWMA estimate of the bucket's service
    time — allows it.  Under light load that slack is never used (the
    dispatcher is idle, the batch ships at once: request-at-a-time latency);
    under heavy load batches form while the previous batch is in flight,
    which is where the p99 win comes from (measured in
    benchmarks/serve_latency.py).

Hot-swap compatibility: the batch's transform reads the published snapshot
exactly once (swap.HotSwapServer.transform), and ``publish`` is a single
attribute store on the publisher's thread — a publish landing mid-batch
never blocks, and never tears an in-flight batch (it keeps the operator it
already read; the NEXT batch sees the new one).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import Future

import numpy as np

from repro.core.shadow import _pow2_ceil
from repro.obs import metrics as _om
from repro.obs.trace import profiled_span as _profiled_span
from repro.obs.trace import profiling as _profiling
from repro.obs.trace import span as _span
from repro.runtime import chaos
from repro.runtime.fault import RetryPolicy, retry_call

# serving metrics (DESIGN.md §16): created once at import, no-ops until
# obs.enable().  Per-bucket series use the pow2 bucket as the only label —
# bounded cardinality by construction.
_M_REQS = _om.counter("serve.requests")
_M_ROWS = _om.counter("serve.rows")
_M_BATCHES = _om.counter("serve.batches")
_M_ERRORS = _om.counter("serve.errors")
_M_QDEPTH = _om.gauge("serve.queue_depth")
_M_COALESCE = _om.histogram("serve.coalesce_rows", bounds=_om.SIZE_BUCKETS)
_M_SLACK = _om.histogram("serve.deadline_slack_ms")
# failure-path metrics (DESIGN.md §17): load shed at admission, dispatch
# retries that recovered, and batches served against a degraded snapshot.
_M_SHED = _om.counter("serve.shed")
_M_DEGRADED_BATCH = _om.counter("serve.degraded_batches")


class RequestShed(RuntimeError):
    """Admission control rejected the request: the queue was at
    ``max_queue`` when it arrived.  Delivered THROUGH the request's future
    (never raised at ``submit``), so shed and served requests flow through
    one code path on the caller side; a shed request was never queued and
    consumed no device time."""


class ServedRows(np.ndarray):
    """(k, r) result rows, optionally carrying serving metadata.

    ``info`` is a ``streaming.swap.SnapshotInfo`` when the batch was served
    DEGRADED (a failed publish left queries on the last good snapshot —
    ``info.staleness_bound`` is that snapshot's §5 error budget), else
    ``None``.  A plain ndarray subclass so every existing caller keeps
    working unchanged; only fault-aware callers look at ``.info``."""

    info = None  # class-level default: views/copies read as not-degraded

    @classmethod
    def _wrap(cls, z: np.ndarray, info) -> "ServedRows":
        out = z.view(cls)
        out.info = info
        return out

#: EWMA smoothing for the per-bucket service-time estimate.
_EWMA_ALPHA = 0.3
#: Safety margin subtracted from a deadline's slack before choosing to wait:
#: a relative cushion on the service estimate plus a scheduler-jitter floor.
_SLACK_REL = 0.25
_SLACK_ABS_S = 1e-3


@dataclasses.dataclass
class ServeStats:
    """Counters a bench/test can read (guarded by the front end's lock)."""

    requests: int = 0
    rows: int = 0
    batches: int = 0
    batched_rows: int = 0      # rows that shared a batch with another request
    full_dispatches: int = 0   # batches shipped because max_batch was hit
    max_batch_rows: int = 0
    shed: int = 0              # requests rejected at admission (max_queue)
    retries: int = 0           # transient dispatch faults absorbed in place
    degraded_batches: int = 0  # batches served against a stale snapshot
    ewma_service_s: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class _Pending:
    x: np.ndarray        # (k, d) f32 query rows
    future: Future
    deadline: float      # absolute time.monotonic() deadline
    enqueued: float
    seq: int = 0         # admission order: a batch holds a contiguous run


class BatchingFrontEnd:
    """Deadline-aware continuous batching over a hot-swap transform.

    ``server`` needs a ``transform(x) -> (n, r) array`` method (normally a
    ``streaming.HotSwapServer``); anything else rides along untouched.
    ``max_batch`` caps fused rows per dispatch (one oversized request still
    ships, alone).  ``slo_ms`` is the default per-request latency target;
    ``min_wait_ms`` optionally floors the coalescing window (0 = ship as
    soon as the dispatcher is free — the right default, since batches form
    naturally while a previous batch occupies the device).

    ``autostart=False`` skips the dispatcher thread; tests then drive the
    queue deterministically with ``step()``/``drain()``.
    """

    def __init__(self, server, *, max_batch: int = 1024, slo_ms: float = 50.0,
                 min_wait_ms: float = 0.0, autostart: bool = True,
                 max_queue: int | None = None,
                 retry: RetryPolicy | None = None, guard=None):
        assert max_batch >= 1
        self.server = server
        self.max_batch = int(max_batch)
        self.slo_s = float(slo_ms) * 1e-3
        self.min_wait_s = float(min_wait_ms) * 1e-3
        #: admission bound (DESIGN.md §17): beyond ``max_queue`` pending
        #: requests, new arrivals SHED (RequestShed through their future)
        #: instead of queueing into certain SLO violation — bounded queue,
        #: bounded tail latency, and zero non-shed drops by construction.
        self.max_queue = None if max_queue is None else int(max_queue)
        #: transient-dispatch retry schedule; deadline-bounded per batch
        #: (never retries past the newest deadline in the batch).
        self.retry = RetryPolicy() if retry is None else retry
        #: optional runtime.PreemptionGuard: on SIGTERM the dispatcher
        #: closes admission and drains everything already queued.
        self._guard = guard
        self.stats = ServeStats()
        # per-bucket service-time histograms, resolved once per bucket: a
        # registry lookup per dispatch (label-dict alloc + registry lock)
        # is exactly the kind of hot-path cost the <= 2% budget forbids
        self._obs_bucket: dict[int, object] = {}
        self._pending: list[_Pending] = []
        self._seq = 0  # sequence number of the next admitted request
        self._cond = threading.Condition()
        self._closed = False
        self._thread = None
        if autostart:
            self._thread = threading.Thread(
                target=self._loop, name="repro-serve-batcher", daemon=True)
            self._thread.start()

    # -- client side -------------------------------------------------------

    def submit(self, x, slo_ms: float | None = None) -> Future:
        """Enqueue a (k, d) or (d,) query; resolves to its (k, r) rows."""
        x = np.atleast_2d(np.asarray(x, np.float32))
        slo = self.slo_s if slo_ms is None else float(slo_ms) * 1e-3
        fut: Future = Future()
        now = time.monotonic()
        req = _Pending(x=x, future=fut, deadline=now + slo, enqueued=now)
        with self._cond:
            if self._closed:
                raise RuntimeError("submit() on a closed BatchingFrontEnd")
            if self.max_queue is not None \
                    and len(self._pending) >= self.max_queue:
                self.stats.shed += 1
                _M_SHED.inc()
                fut.set_exception(RequestShed(
                    f"queue at max_queue={self.max_queue}; request shed"))
                return fut
            req.seq = self._seq
            self._seq += 1
            self._pending.append(req)
            self.stats.requests += 1
            self.stats.rows += x.shape[0]
            _M_QDEPTH.set(len(self._pending))
            self._cond.notify_all()
        _M_REQS.inc()
        _M_ROWS.inc(x.shape[0])
        return fut

    def snapshot(self) -> ServeStats:
        """Consistent copy of the counters, taken under the front-end lock.

        ``stats`` itself is mutated by the dispatcher thread under the lock
        (``ewma_service_s`` in particular is updated per batch); reading its
        fields directly from another thread can observe a torn view — e.g.
        ``batches`` from before a dispatch with the EWMA from after it.
        Benches and monitors read THIS instead (benchmarks/serve_latency.py
        does)."""
        with self._cond:
            return dataclasses.replace(
                self.stats, ewma_service_s=dict(self.stats.ewma_service_s))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def close(self) -> None:
        """Stop the dispatcher and serve everything still pending."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        self.drain()

    # -- dispatcher --------------------------------------------------------

    def _bucket(self, rows: int) -> int:
        return min(_pow2_ceil(max(1, rows)), _pow2_ceil(self.max_batch))

    def _estimate_s(self, rows: int) -> float:
        est = self.stats.ewma_service_s.get(self._bucket(rows))
        if est is None:
            # no measurement for this bucket yet: fall back to the largest
            # known estimate (pessimistic => dispatches earlier, never later)
            est = max(self.stats.ewma_service_s.values(), default=0.0)
        return est

    def _wait_s_locked(self, now: float) -> float:
        """Seconds the dispatcher may still wait for more work; <= 0 means
        dispatch now.  Never waits past the oldest deadline's slack."""
        if self._closed:
            return 0.0
        rows = sum(p.x.shape[0] for p in self._pending)
        if rows >= self.max_batch:
            return 0.0
        oldest = self._pending[0]
        est = self._estimate_s(rows)
        slack = (oldest.deadline - now) - est * (1.0 + _SLACK_REL) \
            - _SLACK_ABS_S
        window = self.min_wait_s - (now - oldest.enqueued)
        return min(window, slack)

    def _pop_batch_locked(self) -> list[_Pending]:
        """FIFO-coalesce whole requests up to max_batch rows (an oversized
        first request ships alone — transform chunks internally)."""
        batch, rows = [], 0
        if self._pending and _om.enabled():
            # slack left on the OLDEST deadline at dispatch: negative means
            # the request already blew its SLO before the batch even formed
            _M_SLACK.observe(
                (self._pending[0].deadline - time.monotonic()) * 1e3)
        while self._pending:
            nxt = self._pending[0].x.shape[0]
            if batch and rows + nxt > self.max_batch:
                break
            rows += nxt
            batch.append(self._pending.pop(0))
        if rows >= self.max_batch:
            self.stats.full_dispatches += 1
        _M_QDEPTH.set(len(self._pending))
        return batch

    def _serve(self, batch: list[_Pending]) -> None:
        """One fused transform for the whole batch + scatter to futures."""
        # the batch's requests (a contiguous run of admission numbers) and
        # their queue waits, admission to now, for the serve.batch span of
        # a profiled run; FIFO order makes the first request the longest
        # waiting
        ids = {}
        if _profiling():
            now = time.monotonic()
            ids = {"req_lo": batch[0].seq, "req_hi": batch[-1].seq,
                   "wait_ms_sum": 1e3 * (len(batch) * now
                                         - sum(p.enqueued for p in batch)),
                   "wait_ms_max": 1e3 * (now - batch[0].enqueued)}
        with _profiled_span("serve.coalesce", requests=len(batch)):
            xs = np.concatenate([p.x for p in batch], axis=0)
            rows = xs.shape[0]
            bucket = self._bucket(rows)
            if rows < bucket:  # ragged tail: pad rows, mask on the way out
                xs = np.concatenate(
                    [xs, np.zeros((bucket - rows, xs.shape[1]), xs.dtype)])
        t0 = time.monotonic()

        def dispatch():
            # the chaos site fires INSIDE the retried closure, before the
            # (idempotent: pure function of xs + snapshot) transform — a
            # transient here is absorbed by the backoff schedule, bounded
            # by the newest deadline in the batch so retries never burn
            # time no request can use
            chaos.inject("serve.dispatch")
            with _span("serve.batch", rows=rows, bucket=bucket,
                       requests=len(batch), **ids):
                return np.asarray(self.server.transform(xs))[:rows]

        retries = [0]

        def _on_retry(attempt, exc):
            retries[0] = attempt

        try:
            z = retry_call(
                dispatch, policy=self.retry,
                deadline=max(p.deadline for p in batch),
                key=f"batch{self.stats.batches}", on_retry=_on_retry)
        except BaseException as e:  # noqa: BLE001 — every caller must learn
            _M_ERRORS.inc()
            for p in batch:
                p.future.set_exception(e)
            return
        finally:
            if retries[0]:
                with self._cond:
                    self.stats.retries += retries[0]
        dt = time.monotonic() - t0
        with self._cond:
            prev = self.stats.ewma_service_s.get(bucket)
            ewma = dt if prev is None \
                else _EWMA_ALPHA * dt + (1.0 - _EWMA_ALPHA) * prev
            self.stats.ewma_service_s[bucket] = ewma
            self.stats.batches += 1
            self.stats.max_batch_rows = max(self.stats.max_batch_rows, rows)
            if len(batch) > 1:
                self.stats.batched_rows += rows
        _M_BATCHES.inc()
        _M_COALESCE.observe(rows)
        if _om.enabled():  # per-bucket series: one histogram
            hist = self._obs_bucket.get(bucket)
            if hist is None:
                hist = self._obs_bucket.setdefault(
                    bucket,
                    _om.histogram("serve.service_ms", {"bucket": bucket}))
            hist.observe(dt * 1e3)
        info = None
        if getattr(self.server, "degraded", False):
            # stale-snapshot serving (failed publish): tag every response
            # in this batch with the SnapshotInfo carrying the §5
            # staleness error budget, so callers can price the answer
            info = self.server.degraded_info()
            with self._cond:
                self.stats.degraded_batches += 1
            _M_DEGRADED_BATCH.inc()
        off = 0
        for p in batch:
            k = p.x.shape[0]
            out = z[off : off + k]
            if info is not None:
                out = ServedRows._wrap(out, info)
            p.future.set_result(out)
            off += k

    def _loop(self) -> None:
        while True:
            with self._cond:
                while not self._pending and not self._closed:
                    if self._guard is not None and self._guard.should_stop:
                        self._closed = True  # preemption: close admission
                        break
                    self._cond.wait(timeout=0.05 if self._guard else None)
                if self._guard is not None and self._guard.should_stop:
                    # drain mode: everything already admitted still serves
                    # (zero non-shed drops), nothing new gets in
                    self._closed = True
                if self._closed and not self._pending:
                    return
                wait = self._wait_s_locked(time.monotonic())
                if wait > 0:
                    self._cond.wait(timeout=wait)
                    continue  # re-evaluate: arrivals may have filled the batch
                batch = self._pop_batch_locked()
            if batch:
                self._serve(batch)

    # -- deterministic drivers (tests; close()) ----------------------------

    def step(self) -> int:
        """Serve ONE coalesced batch immediately, ignoring the coalescing
        window (deterministic test hook; use autostart=False).  Returns the
        number of real rows served (0 if nothing was pending)."""
        with self._cond:
            batch = self._pop_batch_locked()
        if not batch:
            return 0
        self._serve(batch)
        return sum(p.x.shape[0] for p in batch)

    def drain(self) -> int:
        """step() until the queue is empty; returns total rows served."""
        total = 0
        while True:
            served = self.step()
            if served == 0:
                return total
            total += served
