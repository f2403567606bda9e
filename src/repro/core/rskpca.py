"""Reduced-Set KPCA (paper Algorithm 1) + the KPCA baselines it is compared to.

Derivation (paper §3).  Discretizing the continuous eigenproblem (3) with the
reduced empirical density p(x) ~ (1/n) sum_i w_i delta(c_i, x) gives

    K-tilde u = (n lambda) u,   K-tilde_ij = sqrt(w_i) k(c_i, c_j) sqrt(w_j)

with u_i = sqrt(w_i) phi(c_i).  The Nystrom-style extension of eigenfunction
iota to a query point x is

    phi_iota(x) = (1 / (n lambda_iota)) sum_i k(x, c_i) sqrt(w_i) u_i^iota

and the KPCA embedding (unit-variance principal axes, matching classical KPCA's
alpha = v / sqrt(lambda_mat) normalization) collapses to

    z(x) = k(x, C) @ A,    A = diag(sqrt(w)) U  Lambda^{-1/2}

where (Lambda, U) is the eigensystem of K-tilde.  With ell -> inf every point
is its own center (w = 1), K-tilde = K and RSKPCA == KPCA exactly — this is
unit-tested.

Training cost O(mn + m^3), evaluation O(km); the original data is DISCARDED
after center selection (unlike Nystrom).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kernels_math import (Kernel, gram_matrix, gram_matrix_dense,
                                     weighted_gram)
from repro.core.rsde import RSDE, make_rsde
from repro.kernels import ops as kernel_ops

Array = jax.Array

#: Query rows are streamed through transform in slices of this size so a huge
#: query set never materializes a full q x m working set on device.
TRANSFORM_CHUNK = 8192


@dataclasses.dataclass
class KPCAModel:
    """A fitted (RS)KPCA model: everything needed to embed new points.

    ``projector`` already folds in the weight/eigenvalue normalization, so
    embedding is a single fused kernel-eval + matmul: z = k(x, centers) @ projector.
    """

    kernel: Kernel
    centers: np.ndarray      # (m, d) — the ONLY data retained
    projector: np.ndarray    # (m, r)
    eigvals: np.ndarray      # (r,) of the (normalized) reduced operator
    method: str = "rskpca"

    @property
    def m(self) -> int:
        return self.centers.shape[0]

    @property
    def rank(self) -> int:
        return self.projector.shape[1]

    def transform(self, x, chunk: int = TRANSFORM_CHUNK,
                  mesh=None, axis: str = "data") -> np.ndarray:
        """Embed query points: O(q * m * (d + r)), streamed in fixed chunks.

        On the Pallas backend the kernel evaluation and the projection matmul
        are fused (repro.kernels.kpca_project) — the (chunk, m) Gram block
        stays in VMEM and only the (chunk, r) embedding is written back.
        The ragged tail chunk is padded to the fixed chunk size, so a stream
        of arbitrary query sizes compiles exactly once (DESIGN.md §5).

        ``mesh`` shards the query rows over the mesh's ``axis`` and runs the
        fused projection per device with the (m, r) projector replicated —
        the embarrassingly-parallel O(qm) path of DESIGN.md §5.
        """
        if mesh is not None:
            from repro.core import distributed as dist
            z = dist.sharded_kpca_project(
                x, self.centers, self.projector, self.kernel, mesh,
                axis=axis, chunk=chunk)
            return np.asarray(z)
        if self.kernel.backend == "pallas":
            # no host roundtrip: device-resident queries go straight through
            z = kernel_ops.kpca_project(
                x, self.centers, self.projector,
                sigma=self.kernel.sigma, p=self.kernel.p, chunk=chunk,
                precision=self.kernel.precision)
            return np.asarray(z)
        x = np.asarray(x, np.float32)
        chunk = x.shape[0] if chunk is None else chunk  # None = unchunked,
        # matching the pallas branch's kpca_project(chunk=None) contract
        out = np.empty((x.shape[0], self.rank), np.float32)
        proj = jnp.asarray(self.projector)
        cj = jnp.asarray(self.centers)
        for s in range(0, x.shape[0], chunk):
            k_xc = gram_matrix_dense(self.kernel, jnp.asarray(x[s : s + chunk]),
                                     cj)
            out[s : s + chunk] = np.asarray(k_xc @ proj)
        return out


#: Above this matrix size the full O(m^3) eigh is replaced by LOBPCG, which
#: only iterates the top-``rank`` invariant subspace (O(m^2 r) per sweep).
#: Kernel spectra decay fast, so it converges in a handful of iterations to
#: ~1e-4 relative error (parity-tested in tests/test_rskpca.py); small
#: problems keep the exact solver so all paper-parity tests run through
#: eigh unchanged.  1024 is where measured eigh cost (~0.3s, with vectors)
#: clears LOBPCG's (~0.01s) by >10x on CPU — see BENCH_rskpca.json.
_LOBPCG_MIN_M = 1024


def _canonicalize_signs(vec: Array) -> Array:
    """Flip each eigenvector so its largest-|.| component is positive.

    eigh/LOBPCG sign choices are implementation details that differ between
    padded/sharded/single-device solves of the SAME operator; pinning the
    sign makes the sharded path bit-comparable to the single-device one
    (tests/test_sharded.py) without affecting any sign-invariant consumer.
    """
    i = jnp.argmax(jnp.abs(vec), axis=0)
    s = jnp.sign(vec[i, jnp.arange(vec.shape[1])])
    return vec * jnp.where(s == 0, 1.0, s)[None, :]


def _lobpcg_topk(operator, m: int, rank: int):
    """Top-``rank`` eigenpairs (descending) of a PSD operator — a matrix or
    a matvec callable — via LOBPCG, with the repo-standard deterministic
    start and sign convention.  Every large-m eigensolve path (materialized,
    matrix-free, sharded, streaming) shares THIS definition, so iteration
    budget / seed / canonicalization can never drift between the paths the
    parity tests compare.  Returns ``(lam, vec, iterations)``."""
    from jax.experimental.sparse.linalg import lobpcg_standard

    x0 = jax.random.normal(jax.random.PRNGKey(0), (m, rank), jnp.float32)
    lam, vec, iters = lobpcg_standard(operator, x0, m=100)
    return lam, _canonicalize_signs(vec), iters


def _top_eigh(mat: Array, rank: int):
    """Top-``rank`` eigenpairs of a symmetric PSD matrix, descending, and
    LOBPCG's iteration count (0 where the exact ``eigh`` solves it)."""
    m = mat.shape[0]
    if m > _LOBPCG_MIN_M and 5 * rank < m:
        return _lobpcg_topk(mat, m, rank)
    lam, vec = jnp.linalg.eigh(mat)  # ascending
    lam = lam[::-1][:rank]
    vec = vec[:, ::-1][:, :rank]
    return lam, _canonicalize_signs(vec), jnp.zeros((), jnp.int32)


def _host_subset_eigh(kt: np.ndarray, rank: int):
    """Top-``rank`` eigenpairs via LAPACK's subset driver (syevr).

    CPU-only fast path: computing just the top-r invariant subspace is ~5x
    faster than the full syevd jnp.linalg.eigh at m ~ 500-1000, which is
    the dominant fit cost at small n (BENCH_rskpca.json n=2048).  Signs are
    canonicalized with the same rule as the device path, so all paths stay
    comparable.  Returns None if scipy is unavailable (callers fall back to
    the fused device fit).
    """
    try:
        from scipy.linalg import eigh as _seigh
    except ImportError:  # pragma: no cover - container ships scipy
        return None
    m = kt.shape[0]
    rank = min(rank, m)  # graceful truncation, matching _top_eigh's slice
    lam, u = _seigh(kt, subset_by_index=[m - rank, m - 1])  # ascending
    lam = np.asarray(lam, np.float32)[::-1]
    u = np.ascontiguousarray(np.asarray(u, np.float32)[:, ::-1])
    # same sign rule as _canonicalize_signs, in numpy (host path stays host)
    s = np.sign(u[np.abs(u).argmax(axis=0), np.arange(u.shape[1])])
    return lam, u * np.where(s == 0, 1.0, s)[None, :].astype(np.float32)


def _fold_projector(lam: np.ndarray, u: np.ndarray, w: np.ndarray, n: float):
    """A = diag(sqrt(w)) U Lambda^{-1/2} / sqrt(n) on host (trivial cost)."""
    lam = np.maximum(lam, 1e-12)
    sw = np.sqrt(w.astype(np.float32))
    proj = (sw[:, None] * u) / np.sqrt(lam)[None, :] / np.sqrt(np.float32(n))
    return lam, proj


@partial(jax.jit, static_argnames=("kernel", "rank", "matfree"),
         donate_argnums=(0, 1))
def _fit_rskpca_device(c: Array, w: Array, n: Array, kernel: Kernel,
                       rank: int, matfree: bool = False):
    """Algorithm 1 on device, end-to-end under one jit: fused W K^C W
    (Pallas on the default backend), eigh, and the projector fold — nothing
    round-trips to host between center selection and the projector.

    ``matfree=True`` (DESIGN.md §6) never materializes the m x m weighted
    Gram: LOBPCG's matvec recomputes kernel tiles on-chip through the fused
    ``gram_matvec`` Pallas kernel, so peak fit memory drops from O(m^2) to
    O(m * block).  The center/weight buffers are donated — callers pass
    freshly created device arrays (fit_rskpca converts from numpy; the fused
    pipeline slices fresh buffers out of the selection output), and XLA
    reuses their storage instead of copying.

    Returns ``(lam, proj, iterations)``: LOBPCG's iteration count, 0 where
    the exact ``eigh`` solved it.
    """
    sw = jnp.sqrt(w)
    if matfree:
        def matvec(v):
            return kernel_ops.gram_matvec(
                c, c, v, wx=w, wy=w, sigma=kernel.sigma, p=kernel.p,
                precision=kernel.precision, allow_dense=False) / n

        lam, u, iters = _lobpcg_topk(matvec, c.shape[0], rank)
    else:
        k_tilde = weighted_gram(kernel, c, w) / n  # normalized (divide by n)
        lam, u, iters = _top_eigh(k_tilde, rank)
    lam = jnp.maximum(lam, 1e-12)
    # A = diag(sqrt(w)) U Lambda^{-1/2} / sqrt(n): z(x) = k(x,C) A has the same
    # scale as classical KPCA's z(x) = k(x,X) V Lambda_mat^{-1/2} (checked in
    # tests/test_rskpca.py::test_limit_equals_kpca).
    proj = (sw[:, None] * u) / jnp.sqrt(lam)[None, :] / jnp.sqrt(n)
    return lam, proj, iters


def _use_matfree(kernel: Kernel, m: int, rank: int,
                 matfree: bool | None) -> bool:
    """Matrix-free engage rule: explicit override, else the bytes-budget
    crossover (kernels.ops.matfree_fit) — and only where LOBPCG is sound
    (rank well below m) on the Pallas backend (the dense backend is the
    materializing oracle by definition).  An explicit ``matfree=True`` that
    LOBPCG cannot honor fails loudly HERE, not with a cryptic error deep in
    the solver — and never silently materializes the Gram the caller asked
    us not to build."""
    if matfree:
        if 5 * rank >= m:
            raise ValueError(
                f"matfree=True needs 5*rank < m for a sound LOBPCG solve "
                f"(got rank={rank}, m={m}); drop the override below the "
                "crossover — the materialized path is exact there")
        return True
    if matfree is not None:  # explicit False
        return False
    return (kernel.backend == "pallas" and 5 * rank < m
            and kernel_ops.matfree_fit(m))


def fit_rskpca(rsde: RSDE, kernel: Kernel, rank: int,
               mesh=None, axis: str = "data",
               matfree: bool | None = None) -> KPCAModel:
    """Algorithm 1: weighted m x m Gram, eigh, fold weights into projector.

    With ``mesh``, the m x m weighted Gram assembly is sharded over center
    ROWS (columns replicated) and the large-m eigensolve runs LOBPCG with a
    row-distributed matvec — only the (m, r) projector is ever replicated
    (DESIGN.md §5).  The result matches the single-device fit to fp noise.

    Above the matrix-free crossover (``matfree=None`` consults the
    bytes-budget policy in kernels.ops; True/False force it) the m x m Gram
    is never materialized at all: LOBPCG's matvec streams kernel tiles
    through the fused ``gram_matvec`` Pallas kernel (DESIGN.md §6).  Below
    the crossover the materialized path runs unchanged, bit-identically.
    """
    # materialize to host FIRST: the single-device fits donate (c, w), and
    # building them from numpy guarantees fresh device buffers even when the
    # caller's RSDE already holds jax arrays (jnp.asarray would alias them
    # and donation would consume the caller's data)
    centers_np = np.asarray(rsde.centers, np.float32)
    c = jnp.asarray(centers_np)
    w = jnp.asarray(np.asarray(rsde.weights, np.float32))
    use_mf = _use_matfree(kernel, c.shape[0], rank, matfree)
    if mesh is not None:
        from repro.core import distributed as dist
        lam, proj = dist.fit_rskpca_sharded(c, w, rsde.n, kernel, rank,
                                            mesh, axis=axis, matfree=matfree)
    elif use_mf:
        lam, proj, _ = _fit_rskpca_device(c, w, jnp.float32(rsde.n), kernel,
                                          rank, matfree=True)
    elif (jax.default_backend() == "cpu" and c.shape[0] <= _LOBPCG_MIN_M):
        # CPU dispatch: fused Gram on device, then the LAPACK subset
        # eigensolve on host — 2x the end-to-end fit at m ~ 500 vs keeping
        # the full eigh inside the jit.  TPU keeps the fused single-jit fit.
        kt = np.asarray(weighted_gram(kernel, c, w)) / np.float32(rsde.n)
        top = _host_subset_eigh(kt, rank)
        if top is None:
            lam, proj, _ = _fit_rskpca_device(c, w, jnp.float32(rsde.n),
                                              kernel, rank)
        else:
            lam, proj = _fold_projector(*top, np.asarray(w), rsde.n)
    else:
        lam, proj, _ = _fit_rskpca_device(c, w, jnp.float32(rsde.n), kernel,
                                          rank)
    return KPCAModel(
        kernel=kernel,
        centers=centers_np,
        projector=np.asarray(proj),
        eigvals=np.asarray(lam),
        method=f"rskpca+{rsde.scheme}",
    )


def fit_kpca(x, kernel: Kernel, rank: int) -> KPCAModel:
    """Classical (uncentered) KPCA baseline: O(n^3) train, O(kn) test.

    The paper's operator view (§2) uses the uncentered Gram matrix — KPCA on
    the kernel mean map — so no Gram centering is applied anywhere.
    """
    x = jnp.asarray(x, jnp.float32)
    n = x.shape[0]
    k = gram_matrix(kernel, x, x) / n
    lam, v, _ = _top_eigh(k, rank)
    lam = jnp.maximum(lam, 1e-12)
    proj = v / jnp.sqrt(lam)[None, :] / np.sqrt(n)
    return KPCAModel(
        kernel=kernel,
        centers=np.asarray(x),
        projector=np.asarray(proj),
        eigvals=np.asarray(lam),
        method="kpca",
    )


def fit_subsampled_kpca(x, kernel: Kernel, rank: int, m: int,
                        seed: int = 0) -> KPCAModel:
    """Uniform-subsample KPCA baseline (paper §6 'subsampled KPCA'):
    unweighted KPCA on m uniformly chosen points."""
    x = np.asarray(x)
    idx = np.asarray(jax.random.choice(
        jax.random.PRNGKey(seed), x.shape[0], (m,), replace=False))
    return dataclasses.replace(fit_kpca(x[idx], kernel, rank), method="uniform")


def fit(x, kernel: Kernel, rank: int, *, method: str = "shadow",
        ell: float | None = None, m: int | None = None,
        backend: str | None = None, precision: str | None = None,
        mesh=None, axis: str = "data", **kw) -> KPCAModel:
    """One-call front door: RSDE scheme name, 'kpca', or 'uniform'.

    ``backend`` overrides the kernel's compute path ("pallas" | "dense") for
    this fit and the returned model — the parity-testing switch of
    DESIGN.md §3.  ``precision`` overrides the MXU operand dtype the same
    way ("f32" | "bf16").  ``mesh`` runs selection (two-level distributed
    ShDE), the Gram assembly, and the eigensolve sharded over the mesh's
    ``axis`` (DESIGN.md §5); the returned model's ``transform`` accepts the
    same ``mesh=`` for sharded serving.
    """
    if backend is not None:
        kernel = kernel.with_backend(backend)
    if precision is not None:
        kernel = kernel.with_precision(precision)
    if method == "auto":
        # measured accuracy/time/memory Pareto from BENCH_rskpca.json
        # mode=methods rows (benchmarks/methods_bench.py); deterministic
        # heuristic when no bench rows exist (core/methods.py)
        from repro.core.methods import select_method
        method = select_method(np.shape(x)[0], np.shape(x)[1], rank,
                               objective=kw.pop("objective", "balanced"))
        if method == "shadow" and ell is None:
            ell = 4.0  # middle of the paper's ell sweep (configs)
    if method == "nystrom":
        from repro.core.nystrom import fit_nystrom
        assert m is not None, "nystrom needs an explicit m"
        return fit_nystrom(x, kernel, rank, m, mesh=mesh, axis=axis, **kw)
    if method == "wnystrom":
        from repro.core.nystrom import fit_weighted_nystrom
        assert m is not None, "weighted nystrom needs an explicit m"
        return fit_weighted_nystrom(x, kernel, rank, m, mesh=mesh,
                                    axis=axis, **kw)
    if method == "rff":
        from repro.core.random_features import DEFAULT_FEATURES, fit_rff
        return fit_rff(x, kernel, rank,
                       n_features=(m or DEFAULT_FEATURES),
                       mesh=mesh, axis=axis, **kw)
    if method in ("kpca", "uniform"):
        if mesh is not None:
            raise ValueError(
                f"method={method!r} is a deliberately single-device "
                "baseline and ignores mesh=; use an RSDE method for the "
                "sharded pipeline")
        if method == "kpca":
            return fit_kpca(x, kernel, rank)
        assert m is not None
        return fit_subsampled_kpca(x, kernel, rank, m, **kw)
    if method == "shadow" and mesh is None and kw.get("selector") == "fused":
        # single-pass select->fit: device-resident blocked selection streams
        # its accepted centers straight into the (matrix-free above the
        # crossover) fit operator — no host round-trip between the stages
        # (DESIGN.md §6; core/pipeline.py)
        assert ell is not None, "shadow RSDE is parameterized by ell"
        from repro.core.pipeline import fit_shadow_fused
        kw2 = {k: v for k, v in kw.items() if k != "selector"}
        return fit_shadow_fused(x, kernel, rank, ell=ell, **kw2)
    if mesh is not None and method == "shadow":
        assert ell is not None, "shadow RSDE is parameterized by ell"
        from repro.core import distributed as dist
        # **kw forwards so distributed selection kwargs (max_local,
        # max_global) work and unsupported single-device selector kwargs
        # raise instead of being silently dropped
        rsde = dist.distributed_shadow_rsde(x, kernel, ell, mesh, axis=axis,
                                            **kw)
        return fit_rskpca(rsde, kernel, rank, mesh=mesh, axis=axis)
    rsde = make_rsde(method, x, kernel, ell=ell, m=m, **kw)
    return fit_rskpca(rsde, kernel, rank, mesh=mesh, axis=axis)


def embedding_alignment_error(ref: np.ndarray, approx: np.ndarray) -> float:
    """Paper §6 eigenembedding metric: min_A ||ref - approx @ A||_F, the
    Frobenius error after the optimal linear alignment (lstsq)."""
    a, *_ = np.linalg.lstsq(approx, ref, rcond=None)
    return float(np.linalg.norm(ref - approx @ a))


def eigenvalue_error(ref: np.ndarray, approx: np.ndarray) -> float:
    """Frobenius distance between (top-r) eigenvalue vectors, zero-padded."""
    r = max(len(ref), len(approx))
    a = np.zeros(r); a[: len(ref)] = ref
    b = np.zeros(r); b[: len(approx)] = approx
    return float(np.linalg.norm(a - b))
