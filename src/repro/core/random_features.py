"""Random-Fourier-feature KPCA (Sriperumbudur & Sterge; DESIGN.md §15).

Bochner's theorem: a shift-invariant kernel is the Fourier transform of a
probability measure, so with omega_j ~ spectral measure and b_j ~ U[0, 2pi)

    phi_D(x) = sqrt(2/D) cos(x Omega^T + b),   E[phi_D(x)^T phi_D(y)] = k(x,y).

KPCA in the D-dimensional feature space needs only the feature covariance
C = Z^T Z / n (D x D): its nonzero spectrum equals that of the RFF Gram
Z Z^T / n ~ K / n, and for an eigenpair (lam, u) of C the repo's KPCA-scaled
embedding z(x) = k(x, X) v / sqrt(lam) / sqrt(n) collapses EXACTLY to

    z(x) = phi_D(x) @ u

— no eigenvalue folding at all (substitute v = Z u / sqrt(n lam)).  So the
model stores (Omega, b, U): O(D(d+k)) space and test cost, independent of n,
with accuracy controlled by D (the hypothesis convergence property in
tests/test_methods.py).

Spectral measures for the repo's kernels (kernels_math: k = exp(-||delta||^p
/ sigma^p)):

  * Gaussian p=2: exp(-||delta||^2/sigma^2) has omega ~ N(0, (2/sigma^2) I).
  * Laplacian p=1: exp(-||delta||/sigma) has the multivariate Cauchy measure
    (t distribution with nu=1): omega = z / (|u| sigma), z ~ N(0, I_d),
    u ~ N(0, 1) — its characteristic function is exp(-||t|| sigma^{-1}...).

The fit streams the data in fixed-shape chunks and accumulates C chunk by
chunk (f32 accumulation; bf16 operands under precision="bf16"), so the
(n, D) feature matrix never materializes — the same out-of-core contract as
the ingest pipeline, and ``fit_rff_stream`` takes the same chunk sources.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ingest_pipeline import (IngestStats, _PrefetchFeed,
                                        _chunk_iter, pad_block)
from repro.core.kernels_math import Kernel
from repro.core.rskpca import (KPCAModel, TRANSFORM_CHUNK, _LOBPCG_MIN_M,
                               _host_subset_eigh, _top_eigh)
from repro.kernels import ops as kernel_ops

Array = jax.Array

#: Default feature count: enough for ~1e-2 relative spectral error on the
#: paper-scale datasets (tests/test_methods.py convergence property) while
#: keeping the D x D covariance well under the bytes budget.
DEFAULT_FEATURES = 1024


def sample_rff(kernel: Kernel, d: int, n_features: int, seed: int = 0):
    """Sample (Omega (D, d), phase (D,)) from the kernel's spectral measure.

    ``jax.random`` keyed off ``seed`` — deterministic across hosts and
    backends (the same satellite contract as the Nystrom landmark fix; no
    host-side np.random state involved).
    """
    key = jax.random.PRNGKey(seed)
    kw, kb, ku = jax.random.split(key, 3)
    z = jax.random.normal(kw, (n_features, d), jnp.float32)
    if kernel.p == 2:
        omega = z * (np.sqrt(2.0) / kernel.sigma)
    elif kernel.p == 1:
        u = jax.random.normal(ku, (n_features, 1), jnp.float32)
        omega = z / (jnp.abs(u) * kernel.sigma)
    else:
        raise ValueError(
            f"no spectral measure implemented for p={kernel.p}")
    phase = jax.random.uniform(kb, (n_features,), jnp.float32,
                               maxval=2.0 * np.pi)
    return np.asarray(omega), np.asarray(phase)


@dataclasses.dataclass
class RFFKPCAModel(KPCAModel):
    """RFF-KPCA model behind the KPCAModel interface.

    ``centers`` holds Omega (D, d) and ``projector`` the covariance
    eigenvectors U (D, r), so the base class's storage accounting
    (centers.size + projector.size) reports the honest O(D(d+k)) model size;
    ``phase`` carries the D Fourier phases.  ``eigvals`` approximate the
    spectrum of K/n (same normalization as every other method).
    """

    phase: np.ndarray | None = None

    @property
    def n_features(self) -> int:
        return self.centers.shape[0]

    def transform(self, x, chunk: int = TRANSFORM_CHUNK,
                  mesh=None, axis: str = "data") -> np.ndarray:
        """z = sqrt(2/D) cos(x Omega^T + b) @ U — O(q * D * (d + r)).

        Pallas backend runs the fused kernel (kernels/rff.py: the (chunk, D)
        feature block never leaves VMEM); the dense backend is the jnp
        oracle; ``mesh`` shards query rows with (Omega, b, U) replicated.
        """
        if mesh is not None:
            from repro.core import distributed as dist
            z = dist.sharded_rff_project(
                x, self.centers, self.phase, self.projector, mesh,
                axis=axis, chunk=chunk, precision=self.kernel.precision)
            return np.asarray(z)
        plan = "dense" if self.kernel.backend == "dense" else None
        z = kernel_ops.rff_project(
            x, self.centers, self.phase, self.projector, chunk=chunk,
            precision=self.kernel.precision, plan=plan)
        return np.asarray(z)


@partial(jax.jit, static_argnames=("scale", "precision"),
         donate_argnums=(0,))
def _cov_chunk(cacc, xc, ok, omega, phase, *, scale, precision):
    """cacc += phi(xc)^T phi(xc) over the chunk's VALID rows.

    Padding rows are masked to zero features (cos(b) != 0, so the mask is
    load-bearing); the accumulator is donated — one (D, D) buffer lives for
    the whole pass.  bf16 runs both matmuls on bf16 operands with f32
    accumulation, matching the fit-side gram convention.
    """
    cd = jnp.float32 if precision == "f32" else jnp.bfloat16
    z = kernel_ops.rff_features(xc, omega, phase, scale=scale,
                                precision=precision)
    z = jnp.where(ok[:, None], z, 0.0)
    return cacc + jax.lax.dot_general(
        z.astype(cd), z.astype(cd), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _solve_cov(cov: np.ndarray, rank: int):
    """Top-``rank`` eigenpairs of the (D, D) feature covariance, through the
    same solver ladder as the Gram fits: LAPACK subset driver on CPU at
    small D, else _top_eigh (full eigh below _LOBPCG_MIN_M, LOBPCG above)."""
    nfeat = cov.shape[0]
    if jax.default_backend() == "cpu" and nfeat <= _LOBPCG_MIN_M:
        top = _host_subset_eigh(cov, rank)
        if top is not None:
            lam, u = top
            return np.maximum(lam, 1e-12), u
    lam, u, _ = _top_eigh(jnp.asarray(cov), rank)
    return np.maximum(np.asarray(lam), 1e-12), np.asarray(u)


def _chunk_slices(x: np.ndarray, rows: int):
    """Fixed-shape (rows, d) chunk view of a resident array."""
    for s in range(0, x.shape[0], rows):
        blk = x[s : s + rows]
        yield blk, blk.shape[0]


def fit_rff_stream(source, kernel: Kernel, rank: int, *,
                   n_features: int = DEFAULT_FEATURES, seed: int = 0,
                   mesh=None, axis: str = "data",
                   prefetch: int = 2):
    """Single-pass out-of-core RFF-KPCA over a chunk source.

    Accumulates the (D, D) feature covariance chunk by chunk behind the same
    prefetch double buffer as the ingest pipeline — peak residency is one
    chunk plus the covariance, never the dataset.  Returns
    ``(RFFKPCAModel, IngestStats)`` (``stats.m`` reports D).
    """
    stats = IngestStats()
    t_start = time.perf_counter()
    omega = phase = None
    scale = float(np.sqrt(2.0 / n_features))
    cov = jnp.zeros((n_features, n_features), jnp.float32)
    ndev = 1 if mesh is None else mesh.shape[axis]
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        x_shard = NamedSharding(mesh, P(axis, None))
        v_shard = NamedSharding(mesh, P(axis))

        def place(x, n_valid):
            assert x.shape[0] % ndev == 0, \
                f"chunk {x.shape[0]} must divide the '{axis}' axis ({ndev})"
            ok = np.arange(x.shape[0]) < n_valid
            return (jax.device_put(x, x_shard),
                    jax.device_put(ok, v_shard), int(n_valid))
    else:
        def place(x, n_valid):
            ok = np.arange(x.shape[0]) < n_valid
            return jax.device_put(x), jax.device_put(ok), int(n_valid)

    for xd, okd, n_valid in _PrefetchFeed(_chunk_iter(source), place, stats,
                                          depth=prefetch):
        t0 = time.perf_counter()
        if omega is None:
            omega, phase = sample_rff(kernel, xd.shape[1], n_features, seed)
            omega_j, phase_j = jnp.asarray(omega), jnp.asarray(phase)
        if mesh is not None:
            from repro.core import distributed as dist
            cov = cov + dist.sharded_rff_cov(
                xd, okd, omega_j, phase_j, mesh, axis=axis, scale=scale,
                precision=kernel.precision)
        else:
            cov = _cov_chunk(cov, xd, okd, omega_j, phase_j, scale=scale,
                             precision=kernel.precision)
        stats.chunks += 1
        stats.rows += n_valid
        stats.compute_s += time.perf_counter() - t0
    if omega is None:
        raise ValueError("empty source: no chunks to ingest")
    stats.select_s = time.perf_counter() - t_start
    stats.m = n_features
    t1 = time.perf_counter()
    cov_np = np.asarray(cov) / np.float32(stats.rows)
    lam, u = _solve_cov(cov_np, rank)
    stats.fit_s = time.perf_counter() - t1
    stats.wall_s = time.perf_counter() - t_start
    model = RFFKPCAModel(
        kernel=kernel, centers=omega, projector=u, eigvals=lam,
        method="rff", phase=phase)
    return model, stats


def fit_rff(x, kernel: Kernel, rank: int, *,
            n_features: int = DEFAULT_FEATURES, seed: int = 0,
            chunk: int = 65536, mesh=None, axis: str = "data"
            ) -> RFFKPCAModel:
    """RFF-KPCA on a resident array: O(n D (d + D)) train (streamed in
    ``chunk``-row slices, so peak memory is O(chunk * D + D^2), never n x D),
    O(D^3)-capped eigensolve, O(D(d+k)) model.  ``mesh`` shards each chunk's
    rows with a per-device partial covariance psum."""
    x = np.asarray(x, np.float32)
    rows = min(chunk, x.shape[0])
    if mesh is not None:
        ndev = mesh.shape[axis]
        rows = -(-rows // ndev) * ndev
    src = (pad_block(blk, rows) for blk, _ in _chunk_slices(x, rows))
    model, _ = fit_rff_stream(
        ((xb, nv.sum()) for xb, nv in src), kernel, rank,
        n_features=n_features, seed=seed, mesh=mesh, axis=axis, prefetch=2)
    return model
