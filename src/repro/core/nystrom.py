"""Nystrom-family KPCA on the optimized stack (paper §6; DESIGN.md §15).

* ``fit_nystrom``   — classical Nystrom KPCA with uniformly sampled landmarks
  [Drineas & Mahoney 2005; Williams & Seeger].  Approximate eigensystem of the
  full n x n Gram from the (n x m, m x m) blocks.  NOTE: the extension
  eigenvectors live on the FULL dataset, so the model must retain all n points
  — O(nr) storage and O(kn) test cost (paper Table 2).  This is exactly the
  asymmetry RSKPCA removes.

* ``fit_weighted_nystrom`` — density-weighted Nystrom [Zhang & Kwok 2010]:
  k-means centers c_j with cluster masses w_j define the weighted Gram
  W K^C W / n whose eigensystem extends through k(x, C) — but training still
  requires the k-means passes over all data.

Both now ride the same machinery as the RSKPCA path (ISSUE 8):

  * landmark sampling via ``jax.random`` keyed off ``seed`` — deterministic
    across hosts, no host-side RNG state;
  * the m x m eigensolve follows the repo's solver ladder (LAPACK subset on
    CPU small-m, eigh, LOBPCG) and goes MATRIX-FREE through the fused
    ``gram_matvec`` Pallas kernel above the bytes-budget crossover — the
    m x m landmark Gram never materializes there;
  * the O(nm) extension folds every Nystrom constant into one (m, r) matrix
    B, so proj = K_nm @ B streams through ``gram_matvec`` in fixed-size row
    chunks — the n x m cross-Gram NEVER materializes (each chunk's working
    set is capped at half the bytes budget, and on the Pallas plan the
    chunk x m block stays in VMEM too);
  * ``mesh=`` shards the extension rows (``distributed.sharded_nystrom_extend``)
    and, for wnystrom, the Algorithm-1 fit;
  * ``fit_nystrom_stream`` / ``fit_weighted_nystrom_stream`` take the same
    chunk sources as the ingest pipeline, so both fit out-of-core: device
    residency stays O(chunk + m) while the nystrom model's O(nd) retained
    data fills a host buffer (that buffer IS the model — paper Table 2's
    storage row, measured honestly in benchmarks/methods_bench.py).
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ingest_pipeline import IngestStats, _chunk_iter
from repro.core.kernels_math import Kernel, gram_matrix_dense
from repro.core.rskpca import (KPCAModel, _LOBPCG_MIN_M, _host_subset_eigh,
                               _lobpcg_topk, _top_eigh, _use_matfree)
from repro.core.rsde import kmeans_rsde, kmeans_rsde_stream
from repro.kernels import ops as kernel_ops


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _landmark_idx(n: int, m: int, seed: int) -> np.ndarray:
    """Uniform landmark indices without replacement via ``jax.random`` —
    deterministic across hosts/backends for a given seed (the satellite fix:
    no ``np.random`` state, no host-resident dataset required to sample)."""
    idx = jax.random.choice(jax.random.PRNGKey(seed), n, shape=(m,),
                            replace=False)
    return np.sort(np.asarray(idx))


@partial(jax.jit, static_argnames=("kernel", "rank"))
def _landmark_eigs_matfree(lmk, kernel: Kernel, rank: int):
    """Matrix-free top-``rank`` eigensolve of K_mm / m: LOBPCG's matvec
    recomputes landmark-Gram tiles in VMEM through the fused ``gram_matvec``
    kernel (allow_dense=False — the O(m^2)-free contract is load-bearing;
    the no-m x m certificate is checked on this function's lowered HLO in
    benchmarks/methods_bench.py, PR-5 style)."""
    mm = lmk.shape[0]

    def matvec(v):
        return kernel_ops.gram_matvec(
            lmk, lmk, v, sigma=kernel.sigma, p=kernel.p,
            precision=kernel.precision, allow_dense=False) / mm

    return _lobpcg_topk(matvec, mm, rank)[:2]


def _landmark_eigs(landmarks: np.ndarray, kernel: Kernel, rank: int,
                   matfree: bool | None):
    """Top-``rank`` eigenpairs of K_mm / m through the repo's solver ladder:
    matrix-free LOBPCG above the bytes-budget crossover, LAPACK subset
    driver on CPU at small m, _top_eigh otherwise."""
    from repro.core.kernels_math import gram_matrix

    mm = landmarks.shape[0]
    if _use_matfree(kernel, mm, rank, matfree):
        lam, u = _landmark_eigs_matfree(jnp.asarray(landmarks), kernel, rank)
        return np.asarray(lam), np.asarray(u)
    if jax.default_backend() == "cpu" and mm <= _LOBPCG_MIN_M:
        kt = np.asarray(gram_matrix(kernel, landmarks, landmarks),
                        np.float32) / np.float32(mm)
        top = _host_subset_eigh(kt, rank)
        if top is not None:
            return top
    lam, u, _ = _top_eigh(gram_matrix(kernel, landmarks, landmarks) / mm,
                          rank)
    return np.asarray(lam), np.asarray(u)


def _fold_extension(lam: np.ndarray, u: np.ndarray, n: int,
                    m: int) -> np.ndarray:
    """Fold every Nystrom constant into one (m, r) matrix B so the extension
    is a single cross-Gram matvec:

        v    = sqrt(m/n) (K_nm / m) (u / lam)        [eigenvector extension]
        proj = v / sqrt(lam) / sqrt(n)               [KPCA scaling]
              = K_nm @ B,   B = u * sqrt(m/n) / (m lam^{3/2} sqrt(n))

    which is what lets the n x m block stream through ``gram_matvec``
    without ever materializing."""
    lam = np.maximum(np.asarray(lam, np.float32), 1e-12)
    scale = np.sqrt(m / n) / (m * lam * np.sqrt(lam) * np.sqrt(np.float32(n)))
    return np.asarray(u, np.float32) * scale[None, :].astype(np.float32)


def _extension_rows(m: int, n: int) -> int:
    """Row-chunk size for the streamed extension: the per-chunk chunk x m
    working set stays under HALF the Gram bytes budget, so even the dense
    per-chunk plan (below the autotune crossover) can never approach an
    n x m materialization."""
    budget = kernel_ops.gram_bytes_budget()
    rows = budget // (8 * max(m, 1))
    rows = max(1024, min(65536, rows))
    return min(_round_up(rows, 128), _round_up(n, 128))


def _extend_projector(x, landmarks, bmat, kernel: Kernel, *, mesh=None,
                      axis: str = "data", rows: int | None = None
                      ) -> np.ndarray:
    """proj = K_nm @ B in fixed-shape row chunks — compile once, stream all
    of x.  Pallas backend: fused ``gram_matvec`` per chunk (K tiles stay in
    VMEM); dense backend: the chunked jnp oracle; ``mesh``: rows sharded per
    chunk with landmarks/B replicated."""
    x = np.asarray(x, np.float32)
    n, r = x.shape[0], bmat.shape[1]
    rows = rows or _extension_rows(landmarks.shape[0], n)
    if mesh is not None:
        rows = _round_up(rows, mesh.shape[axis] * 128)
    lj = jnp.asarray(landmarks, jnp.float32)
    bj = jnp.asarray(bmat, jnp.float32)
    out = np.empty((n, r), np.float32)
    for s in range(0, n, rows):
        blk = x[s : s + rows]
        k = blk.shape[0]
        if k < rows:  # zero-pad the ragged tail: one compiled shape
            blk = np.concatenate(
                [blk, np.zeros((rows - k, x.shape[1]), np.float32)])
        if mesh is not None:
            from repro.core import distributed as dist
            z = dist.sharded_nystrom_extend(blk, lj, bj, kernel, mesh,
                                            axis=axis)
        elif kernel.backend == "pallas":
            z = kernel_ops.gram_matvec(blk, lj, bj, sigma=kernel.sigma,
                                       p=kernel.p,
                                       precision=kernel.precision)
        else:
            z = gram_matrix_dense(kernel, jnp.asarray(blk), lj) @ bj
        out[s : s + k] = np.asarray(z)[:k]
    return out


def fit_nystrom(x, kernel: Kernel, rank: int, m: int, seed: int = 0, *,
                mesh=None, axis: str = "data", matfree: bool | None = None,
                rows: int | None = None) -> KPCAModel:
    """Classical Nystrom approximation to KPCA.

    lam_full ~ (n/m) lam_mm;  v_full ~ sqrt(m/n) K_nm u_mm / lam_mm.
    The returned model's ``centers`` are the FULL dataset (test cost O(kn)).

    ``matfree`` (None = bytes-budget policy) controls the m x m eigensolve;
    the n x m extension always streams in row chunks (``rows`` overrides the
    chunk size); ``mesh`` shards the extension rows over ``axis``.
    """
    x = np.asarray(x, np.float32)
    n = x.shape[0]
    idx = _landmark_idx(n, m, seed)
    landmarks = x[idx]
    lam, u = _landmark_eigs(landmarks, kernel, rank, matfree)
    lam = np.maximum(np.asarray(lam, np.float32), 1e-12)
    bmat = _fold_extension(lam, u, n, m)
    proj = _extend_projector(x, landmarks, bmat, kernel, mesh=mesh,
                             axis=axis, rows=rows)
    return KPCAModel(
        kernel=kernel,
        centers=x,                        # full data retained — the point!
        projector=proj,
        eigvals=lam,
        method="nystrom",
    )


def fit_nystrom_stream(source, kernel: Kernel, rank: int, m: int, *,
                       seed: int = 0, mesh=None, axis: str = "data",
                       matfree: bool | None = None, rows: int | None = None):
    """Out-of-core Nystrom over a chunk source (``.chunks()`` protocol or an
    iterable of ``(x, n_valid)``).

    Pass A drains the source into a host (n, d) buffer — which IS the
    model's O(nd) retained data (paper Table 2), not a working-set leak —
    gathering nothing onto device.  Landmarks are then gathered by global
    index (same ``jax.random`` draw as the resident fit, so stream and
    resident fits are bit-identical for one seed), and pass B streams the
    extension in fixed row chunks.  Device residency stays O(chunk + m)
    throughout (the out-of-core certificate measured by methods_bench).
    Returns ``(KPCAModel, IngestStats)``.
    """
    stats = IngestStats()
    t0 = time.perf_counter()
    n_hint = getattr(source, "n", None)
    buf, blocks, seen = None, [], 0
    for xb, nv in _chunk_iter(source):
        xb = np.asarray(xb, np.float32)[: int(nv)]
        if n_hint and buf is None:
            buf = np.empty((int(n_hint), xb.shape[1]), np.float32)
        if buf is not None:
            buf[seen : seen + xb.shape[0]] = xb
        else:
            blocks.append(xb.copy())
        seen += xb.shape[0]
        stats.chunks += 1
    if seen == 0:
        raise ValueError("empty source: no chunks to ingest")
    x_host = buf[:seen] if buf is not None else np.concatenate(blocks)
    del blocks
    stats.rows = seen
    stats.select_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    idx = _landmark_idx(seen, m, seed)
    landmarks = x_host[idx]
    lam, u = _landmark_eigs(landmarks, kernel, rank, matfree)
    lam = np.maximum(np.asarray(lam, np.float32), 1e-12)
    bmat = _fold_extension(lam, u, seen, m)
    proj = _extend_projector(x_host, landmarks, bmat, kernel, mesh=mesh,
                             axis=axis, rows=rows)
    stats.fit_s = time.perf_counter() - t1
    stats.wall_s = time.perf_counter() - t0
    stats.m = m
    model = KPCAModel(kernel=kernel, centers=x_host, projector=proj,
                      eigvals=lam, method="nystrom")
    return model, stats


def fit_weighted_nystrom(x, kernel: Kernel, rank: int, m: int,
                         iters: int = 10, seed: int = 0, *, mesh=None,
                         axis: str = "data",
                         matfree: bool | None = None) -> KPCAModel:
    """Density-weighted Nystrom [20]: k-means RSDE + weighted Gram eigensystem.

    Structurally an RSKPCA with the k-means selector; the difference from the
    paper's ShDE path is the selector cost (iterative k-means over all data)
    and that m must be supplied by the user.  ``mesh``/``matfree`` thread
    into the Algorithm-1 fit exactly as for ``fit_rskpca``.
    """
    from repro.core.rskpca import fit_rskpca

    rsde = kmeans_rsde(x, kernel, m=m, iters=iters, seed=seed)
    model = fit_rskpca(rsde, kernel, rank, mesh=mesh, axis=axis,
                       matfree=matfree)
    return dataclasses.replace(model, method="wnystrom")


def fit_weighted_nystrom_stream(source, kernel: Kernel, rank: int, m: int, *,
                                seed: int = 0, mesh=None,
                                axis: str = "data",
                                matfree: bool | None = None):
    """Out-of-core density-weighted Nystrom: one-pass mini-batch k-means
    over the chunk source (``rsde.kmeans_rsde_stream`` — assignment through
    the Pallas ``shadow_assign`` kernel), then Algorithm 1 on the (m, d)
    centers.  Returns ``(KPCAModel, IngestStats)``."""
    from repro.core.pipeline import fit_centers
    from repro.core.rskpca import fit_rskpca

    t0 = time.perf_counter()
    rsde, stats = kmeans_rsde_stream(source, kernel, m, seed=seed)
    t1 = time.perf_counter()
    if mesh is None:
        model = fit_centers(rsde.centers, rsde.weights, rsde.n, kernel, rank,
                            matfree=matfree, method="wnystrom")
    else:
        model = fit_rskpca(rsde, kernel, rank, mesh=mesh, axis=axis,
                           matfree=matfree)
        model = dataclasses.replace(model, method="wnystrom")
    stats.fit_s = time.perf_counter() - t1
    stats.wall_s = time.perf_counter() - t0
    return model, stats
