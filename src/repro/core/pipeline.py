"""The capacity-bucketed fit of a selected center set (DESIGN.md §6).

``fit_centers`` is Algorithm 1's tail for a selected center set: the
in-memory fit job (``shadow_rsde`` then ``fit_centers``) and the
out-of-core ``ingest_pipeline.ingest_fit``, whose merged set it takes
straight from the device.  The set is cut or zero-padded to the
power-of-two capacity bucket of m — zero-weight rows carry zero K-tilde
rows/columns and zero projector rows — so one compile serves every m in a
bucket; above the matrix-free crossover (kernels.ops.matfree_fit) the
eigensolve streams Gram tiles through the fused ``gram_matvec`` kernel and
no m x m buffer materializes.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.kernels_math import Kernel
from repro.core.shadow import _pow2_ceil
from repro.core.rskpca import KPCAModel, _fit_rskpca_device, _use_matfree
from repro.obs import trace as _trace
from repro.obs.trace import span as _span


def fit_centers(centers, weights, n: int, kernel: Kernel, rank: int, *,
                m: int | None = None, matfree: bool | None = None,
                method: str = "rskpca") -> KPCAModel:
    """Capacity-bucketed Algorithm 1 fit of a selected center set — the
    shared fit tail of the in-memory (``shadow_rsde``) and out-of-core
    (``ingest_pipeline.ingest_fit``) pipelines.

    ``centers``/``weights`` may be a device buffer with ``m`` live rows (the
    streaming merge's state, sliced here without a host round-trip) or an
    exact host (m, d) set.  Either way they are sliced/zero-padded to the power-of-two
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

