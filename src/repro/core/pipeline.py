"""Single-pass select->fit pipeline (DESIGN.md §6).

``fit_shadow_fused`` runs blocked shadow selection (Algorithm 2, §3) and
Algorithm 1's fit as one device-resident dataflow:

  * selection runs to exhaustion inside ONE jitted while_loop
    (``shadow._blocked_select_device`` with ``stop_count=0``) — the accepted
    centers scatter straight into a preallocated (n, d) device buffer;
  * the ONLY host synchronization between the stages is the scalar center
    count m (needed to pick the power-of-two capacity bucket the fit
    compiles against — the same bucketing contract as streaming/serving);
  * the fit consumes a ``cap``-row slice of the selection output directly:
    no host round-trip of the center data, no re-padding — rows beyond m
    carry zero weight, which zeroes their K-tilde rows/columns and their
    projector rows (the established zero-weight-padding invariant);
  * the sliced center/weight buffers are donated into the jitted fit
    (``_fit_rskpca_device``) and XLA reuses their storage (the model's
    center rows are materialized to host BEFORE the donation, since a
    cap == n slice is the selection buffer itself);
  * above the matrix-free crossover (kernels.ops.matfree_fit) the fit's
    eigensolve streams Gram tiles through the fused ``gram_matvec`` kernel —
    the select->fit pipeline then never materializes ANY m x m buffer.

Tradeoff vs ``shadow_select_blocked``: both keep the rows on the device.
The blocked selector's halving cascade (§3) compacts the survivors into a
smaller set each time the alive set halves, at one small fetch per phase,
and so halves late-round absorption work; the fused loop runs every round
at full-n absorption cost but makes no fetch until m, and hands the
selection buffers to the fit without a host round-trip of the centers.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.kernels_math import Kernel
from repro.core import shadow as shadow_mod
from repro.core.shadow import _pow2_ceil
from repro.core.rskpca import KPCAModel, _fit_rskpca_device, _use_matfree
from repro.obs import trace as _trace
from repro.obs.trace import span as _span


def fit_centers(centers, weights, n: int, kernel: Kernel, rank: int, *,
                m: int | None = None, matfree: bool | None = None,
                method: str = "rskpca") -> KPCAModel:
    """Capacity-bucketed Algorithm 1 fit of a selected center set — the
    shared fit tail of the fused (``fit_shadow_fused``) and out-of-core
    (``ingest_pipeline.ingest_fit``) pipelines.

    ``centers``/``weights`` may be a device buffer with ``m`` live rows (the
    fused selector's preallocated (n, d) output, or the streaming merge's
    state, sliced here without a host round-trip) or an exact host (m, d)
    set.  Either way they are sliced/zero-padded to the power-of-two
    capacity bucket — zero-weight rows contribute zero K-tilde rows/columns
    and zero projector rows — so re-jit count stays logarithmic across m.  The cap slices are
    donated into the jitted device fit; ``matfree=None`` consults the
    bytes-budget crossover (above it no m x m buffer ever materializes).

    Spans (DESIGN.md §16): ``fit.stage`` is the transfer, the padding and
    the centers' fetch; ``fit.solve`` the device fit through the fetch of
    its results, with LOBPCG's iteration count (0 on ``eigh``).
    """
    with _span("fit.stage"):
        rows = np.shape(centers)[0]
        m = rows if m is None else int(m)
        rank = min(rank, m)
        cap = min(max(rows, 128), _pow2_ceil(max(m, 128)))
        if rows < cap:  # host center sets arrive exactly (m, d): pad here
            c = np.zeros((cap, np.shape(centers)[1]), np.float32)
            c[:rows] = np.asarray(centers, np.float32)
            w = np.zeros((cap,), np.float32)
            w[:rows] = np.asarray(weights, np.float32)
            c, w = jnp.asarray(c), jnp.asarray(w)
        else:  # a device buffer with m live rows: its cap-row prefix
            c = jnp.asarray(centers, jnp.float32)[:cap]
            w = jnp.asarray(weights, jnp.float32)[:cap]
        # materialize the model's center rows BEFORE the fit: the cap slices
        # are donated into it, and when cap == c.shape[0] jax's full-slice
        # fast path returns `c` ITSELF — reading it after donation would hit
        # a deleted array.  Slices by m are taken on the host: a device
        # slice would compile once per m.
        centers_host = np.asarray(c)[:m]
    use_mf = _use_matfree(kernel, cap, rank, matfree)
    with _span("fit.solve", m=m, cap=cap, matfree=use_mf) as sp:
        lam, proj, iters = sp.sync(_fit_rskpca_device(
            c, w, jnp.float32(n), kernel, rank, matfree=use_mf))
        if _trace.enabled():  # the count is fetched only while tracing
            sp.set(lobpcg_iters=int(iters))
        model = KPCAModel(
            kernel=kernel,
            centers=centers_host,
            projector=np.asarray(proj)[:m],
            eigvals=np.asarray(lam),
            method=method,
        )
    return model


def fit_shadow_fused(x, kernel: Kernel, rank: int, *, ell: float,
                     block: int | None = None,
                     matfree: bool | None = None) -> KPCAModel:
    """ShDE selection + RSKPCA fit with the centers never leaving device.

    Equivalent to ``fit(x, ..., method="shadow", selector="blocked")``
    followed by ``fit_rskpca`` — same cover invariants, same operator — but
    with the intermediate RSDE elided.  ``matfree=None`` consults the
    bytes-budget crossover; the model is materialized to host only at the
    very end (sliced to the true m).
    """
    xf = jnp.asarray(x, jnp.float32)
    n, d = xf.shape
    eps2 = jnp.float32(kernel.epsilon(ell)) ** 2
    b = max(1, min(256 if block is None else block, n))
    _, centers, weights, _, mr = shadow_mod._blocked_select_device(
        xf, eps2, b, jnp.ones((n,), bool), jnp.asarray(0, jnp.int32))
    m = int(np.asarray(mr)[0])  # the pipeline's single host sync: m
    return fit_centers(centers, weights, n, kernel, rank, m=m,
                       matfree=matfree, method="rskpca+shadow-fused")
