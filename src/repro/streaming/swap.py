"""Hot-swap serving bridge: publish operator updates without retracing.

A live transform stream must not pay a compile when the operator behind it
evolves.  The contract (the PR-3 bucket-padded serving contract, extended to
the operator itself): the published ``(centers, projector)`` snapshot always
has the state's FIXED buffer shapes — (cap, d) and (cap, rank), with dead
slots carrying zero projector rows so they cannot contribute — and queries
stream through ``kernels.ops.kpca_project`` in fixed chunks.  Publishing a
new snapshot therefore changes only array VALUES, never compiled shapes: the
jitted projection program traced for the first snapshot serves every later
one (compile-count asserted in tests/test_streaming.py).  Only a capacity
change (compaction/growth, logarithmically rare) re-traces, once per bucket.
"""
from __future__ import annotations

import dataclasses
import time

import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kernel_ops
from repro.kernels import quantize
from repro.obs import metrics as _om
from repro.obs.trace import profiled_span as _profiled_span
from repro.obs.trace import span as _span
from repro.runtime import chaos
from repro.streaming.state import StreamingRSKPCA

# publish/serve telemetry (DESIGN.md §16): how often the operator turns
# over, what a publish costs (the quantization pass on int8/fp8 tiers),
# and how stale the snapshot a query just saw was.
_M_PUBLISHES = _om.counter("swap.publishes")
_M_PUB_MS = _om.histogram("swap.publish_ms")
_M_AGE = _om.gauge("swap.snapshot_age_s")
# degradation telemetry (DESIGN.md §17): failed publishes and the §5
# operator-drift budget the stale snapshot is serving under.
_M_PUB_FAIL = _om.counter("swap.publish_failures")
_M_DEGRADED = _om.gauge("swap.degraded")
_M_STALENESS = _om.gauge("swap.staleness_bound")


@dataclasses.dataclass(frozen=True)
class SnapshotInfo:
    """What a reader can learn about the operator it is being served by.

    ``degraded`` flips when a publish FAILED and queries are riding the
    last good snapshot; ``staleness_bound`` is then the Theorem-5.x error
    budget ``kappa * sqrt(2 (1 - t^2))`` (``core.mmd.staleness_bound``) of
    that stale operator against the newest state the server has SEEN —
    finite and usually tiny, because mass updates move the normalized
    operator slowly (that is the paper's whole §5 point, repurposed as a
    serving SLO).  ``inf`` only when no live weights have been seen at all.
    """

    version: int
    published_at: float | None
    degraded: bool
    failed_publishes: int
    staleness_bound: float


class HotSwapServer:
    """Single-writer, many-reader serving handle.

    ``publish`` snapshots the state's padded operator (cheap: two device
    arrays, no copies of the Gram/eigensystem); ``transform`` embeds
    queries under the LATEST published operator.  ``version`` counts
    publishes so readers can tag results with the operator they saw.
    """

    def __init__(self, state: StreamingRSKPCA | None = None,
                 chunk: int = 1024):
        self.chunk = int(chunk)
        self.version = 0
        # (centers, projector, kernel, projector_q), swapped whole
        self._snapshot = None
        #: monotonic timestamp of the last publish; transform reports the
        #: served snapshot's age off it (``swap.snapshot_age_s``)
        self.published_at: float | None = None
        #: degradation bookkeeping (DESIGN.md §17): the mass vector the
        #: live snapshot was published with, the newest mass vector the
        #: server has SEEN (a failed try_publish still updates it — that
        #: is what makes the staleness bound honest), and the consecutive
        #: failed-publish count since the last good publish.
        self._pub_weights: np.ndarray | None = None
        self._cur_weights: np.ndarray | None = None
        self.failed_publishes = 0
        self.degraded = False
        if state is not None:
            self.publish(state)

    def publish(self, state: StreamingRSKPCA) -> int:
        """Atomically swap in the state's current operator: the snapshot is
        a SINGLE attribute store (one tuple), so a concurrent reader sees
        either the old or the new operator, never a mix.

        On a quantized serving tier (kernel.precision int8/fp8) the publish
        also quantizes the projector — one O(cap x rank) jitted pass — and
        caches the (Aq, scales) pair in the swap tuple, so serves never pay
        per-batch quantization and in-flight batches keep the pair they
        already read.

        Fault model: ``swap.publish`` is the chaos injection site, fired
        BEFORE the snapshot store — a failed publish can never tear the
        served operator, it leaves the previous snapshot fully intact (the
        last-good-fallback invariant ``try_publish`` builds on)."""
        t0 = time.monotonic()
        with _span("swap.publish", version=self.version + 1):
            weights = np.asarray(state.weights, np.float64)
            self._cur_weights = weights  # seen, even if the store fails
            centers = jnp.asarray(state.centers)
            projector = jnp.asarray(state.projector)
            kernel = state.kernel
            projector_q = (quantize.quantize_projector(projector,
                                                       kernel.precision)
                           if kernel.precision in quantize.QUANT_PRECISIONS
                           else None)
            chaos.inject("swap.publish")
            self._snapshot = (centers, projector, kernel, projector_q)
        self._pub_weights = weights
        self.published_at = time.monotonic()
        self.version += 1
        self.failed_publishes = 0
        self.degraded = False
        _M_PUBLISHES.inc()
        _M_PUB_MS.observe((self.published_at - t0) * 1e3)
        _M_AGE.set(0.0)  # a fresh snapshot: age restarts from zero
        if _om.enabled():
            _M_DEGRADED.set(0.0)
            _M_STALENESS.set(0.0)
        return self.version

    def try_publish(self, state: StreamingRSKPCA) -> bool:
        """Graceful-degradation publish: on ANY failure keep serving the
        last good snapshot and report the §5 staleness budget instead of
        taking the server down.

        Returns True on a clean publish.  On failure the served operator is
        untouched (``publish`` cannot tear it), ``degraded`` flips, and
        ``degraded_info()`` prices the stale snapshot via
        ``core.mmd.staleness_bound`` against the newest mass vector seen —
        the publisher retries on its own cadence (the next ingest tick),
        so no retry loop lives here."""
        try:
            self.publish(state)
            return True
        except Exception:
            self.failed_publishes += 1
            self.degraded = self._snapshot is not None
            _M_PUB_FAIL.inc()
            if _om.enabled():
                info = self.degraded_info()
                _M_DEGRADED.set(1.0 if info.degraded else 0.0)
                if np.isfinite(info.staleness_bound):
                    _M_STALENESS.set(info.staleness_bound)
            if self._snapshot is None:
                raise  # nothing to fall back to: degrade is impossible
            return False

    def degraded_info(self) -> SnapshotInfo:
        """Current serving health + the stale-operator error budget."""
        bound = 0.0
        if self.degraded:
            if self._pub_weights is None or self._cur_weights is None:
                bound = float("inf")
            else:
                from repro.core.mmd import staleness_bound
                kappa = (self._snapshot[2].kappa
                         if self._snapshot is not None else 1.0)
                bound = staleness_bound(self._pub_weights,
                                        self._cur_weights, kappa=kappa)
        return SnapshotInfo(version=self.version,
                            published_at=self.published_at,
                            degraded=self.degraded,
                            failed_publishes=self.failed_publishes,
                            staleness_bound=bound)

    @property
    def published(self) -> bool:
        return self._snapshot is not None

    def transform(self, x, mesh=None, axis: str = "data") -> np.ndarray:
        """Embed queries under the latest published operator; fixed-chunk
        streaming (ragged tails padded) so any query-size sequence reuses
        one compiled program per bucket."""
        # read the snapshot ONCE: a publish() landing mid-call can never
        # pair the new centers with the old projector
        snapshot = self._snapshot
        assert snapshot is not None, "publish() an operator before serving"
        if _om.enabled() and self.published_at is not None:
            # age of the snapshot SERVED
            _M_AGE.set(time.monotonic() - self.published_at)
        centers, projector, kernel, projector_q = snapshot
        if mesh is not None:
            from repro.core import distributed as dist
            z = dist.sharded_kpca_project(
                x, centers, projector, kernel, mesh,
                axis=axis, chunk=self.chunk)
        else:
            z = kernel_ops.kpca_project(
                x, centers, projector,
                sigma=kernel.sigma, p=kernel.p, chunk=self.chunk,
                precision=kernel.precision, projector_q=projector_q)
        # start the copy out now, so that it follows the device work
        # without waiting for the host to ask; the span is the rest of the
        # device's work and the copy
        z.copy_to_host_async()
        with _profiled_span("swap.fetch"):
            return np.asarray(z)
