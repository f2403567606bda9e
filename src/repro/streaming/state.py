"""The streaming RSKPCA state: a checkpointable pytree (DESIGN.md §7).

``StreamingRSKPCA`` holds everything needed to evolve a fitted reduced-set
operator in place as the stream drifts:

  * a FIXED-capacity center buffer (``cap`` rows, power-of-two bucketed so
    the serving path never retraces — the same bucket-padded contract as the
    PR-3 ragged-chunk serving) with ``weights == 0`` marking dead slots;
  * the cached unweighted center Gram ``kgram`` (cap x cap), so an update
    touches one ROW (the Pallas ``gram_row`` pass) instead of rebuilding the
    m x m matrix;
  * the cached eigensystem (``eigvals``, ``u``) of the normalized weighted
    operator K-tilde/n = diag(sqrt w) kgram diag(sqrt w) / n — ``rank + 1``
    pairs are kept so the spectral gap below the serving rank is observable;
  * the error budget: ``err_est`` accumulates the closed-form Theorem-5.x
    perturbation bounds (core.mmd.weight_update_bound) of every update since
    the last exact solve; while ``err_est <= budget`` the eigensystem is
    patched by a Rayleigh–Ritz step, beyond it the next maintenance does a
    full re-solve.  ``resid`` is the measured Rayleigh residual
    ||K-tilde/n U - U diag(lam)||_F of the CURRENT eigensystem — the
    a-posteriori certificate the property tests check against.

Static configuration (kernel, rank, eps, budget) rides in the pytree aux
data, so every jitted update function specializes on it automatically.
"""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kernels_math import Kernel, gram_matrix
from repro.core.rsde import RSDE
from repro.core.rskpca import (KPCAModel, _LOBPCG_MIN_M,
                               _canonicalize_signs, _lobpcg_topk, _top_eigh)
from repro.kernels import ops as kernel_ops

Array = jax.Array

#: Default error budget: a full re-solve is forced once the accumulated
#: per-update perturbation bounds exceed this fraction of kappa (= 1).
DEFAULT_BUDGET = 0.05


from repro.core.shadow import _pow2_ceil  # single bucketing rule repo-wide


@dataclasses.dataclass(frozen=True)
class StreamingRSKPCA:
    """Stream masses are SPLIT accumulators: ``wcount``/``ncount`` hold the
    integer unit counts (int32 — exact up to 2^31) and ``wfrac``/``nfrac``
    the fractional residuals (f32).  A single f32 accumulator saturates at
    2^24: ``n + 1.0 == n`` there, so a long-running stream's mass silently
    stops growing and every Theorem-5.x bound (which divides by n) goes
    stale.  Unit-mass ingest adds to the int part — exact at any stream
    length (regression-tested past 2^24 in tests/test_streaming.py); the
    f32 ``weights``/``n`` views below are recomposed on read for the
    normalized operator, where relative (not absolute) error is what
    matters."""

    # --- pytree leaves ---
    centers: Array    # (cap, d) center buffer; dead slots hold stale rows
    wcount: Array     # (cap,) int32 integer part of the shadow masses
    wfrac: Array      # (cap,) f32 fractional residual of the shadow masses
    kgram: Array      # (cap, cap) unweighted k(c_i, c_j) cache
    ncount: Array     # () int32 integer part of the total stream mass
    nfrac: Array      # () f32 fractional residual of the total stream mass
    eigvals: Array    # (rank+1,) eigenvalues of K-tilde/n, descending
    u: Array          # (cap, rank+1) orthonormal eigenvectors
    err_est: Array    # () f32 accumulated perturbation since last exact solve
    resid: Array      # () f32 Rayleigh residual of the current eigensystem
    n_patched: Array  # () int32 updates absorbed by patches since last solve
    # --- static aux (hashable; jit specializes on these) ---
    kernel: Kernel
    rank: int
    eps: float        # online absorption radius sigma/ell (Algorithm 2)
    budget: float     # err_est threshold that forces an exact re-solve

    # -- shapes / masks ----------------------------------------------------
    @property
    def cap(self) -> int:
        return self.centers.shape[0]

    @property
    def d(self) -> int:
        return self.centers.shape[1]

    @property
    def weights(self) -> Array:
        """(cap,) f32 view of the shadow masses (count + residual); 0 marks
        a dead slot.  The split leaves are the source of truth — mutate
        those, never this view."""
        return self.wcount.astype(jnp.float32) + self.wfrac

    @property
    def n(self) -> Array:
        """() f32 view of the total stream mass (weights sum to n)."""
        return self.ncount.astype(jnp.float32) + self.nfrac

    @property
    def alive(self) -> Array:
        return (self.wcount > 0) | (self.wfrac > 0)

    @property
    def m(self) -> int:
        """Number of live centers (host sync)."""
        return int(jnp.sum(self.alive))

    @property
    def gap(self) -> float:
        """Spectral gap below the serving rank (host sync)."""
        return float(self.eigvals[self.rank - 1] - self.eigvals[self.rank])

    # -- serving views -----------------------------------------------------
    @property
    def projector(self) -> Array:
        """(cap, rank) A = diag(sqrt w) U Lambda^{-1/2} / sqrt(n); dead slots
        carry sqrt(0) = 0 rows, so the cap-padded buffer serves directly."""
        lam = jnp.maximum(self.eigvals[: self.rank], 1e-12)
        sw = jnp.sqrt(self.weights)
        return (sw[:, None] * self.u[:, : self.rank]) \
            / jnp.sqrt(lam)[None, :] / jnp.sqrt(self.n)

    def as_rsde(self) -> RSDE:
        """Host snapshot of the live centers as an RSDE — the 'equivalent
        center set' a from-scratch fit would see (property tests)."""
        # recompose masses in f64 on host: exact for any int32 count
        w64 = (np.asarray(self.wcount, np.float64)
               + np.asarray(self.wfrac, np.float64))
        alive = w64 > 0
        return RSDE(
            centers=np.asarray(self.centers)[alive],
            weights=w64[alive],
            n=float(np.float64(int(self.ncount)) + float(self.nfrac)),
            scheme="streaming",
        )

    def to_model(self) -> KPCAModel:
        """Freeze the current operator as a static KPCAModel."""
        return KPCAModel(
            kernel=self.kernel,
            centers=np.asarray(self.centers, np.float32),
            projector=np.asarray(self.projector),
            eigvals=np.asarray(self.eigvals[: self.rank]),
            method="rskpca+streaming",
        )

    def transform(self, x, chunk: int | None = 8192, mesh=None,
                  axis: str = "data"):
        """Embed queries under the CURRENT operator (see swap.HotSwapServer
        for the recompile-free serving loop)."""
        proj = self.projector
        if mesh is not None:
            from repro.core import distributed as dist
            return dist.sharded_kpca_project(
                x, self.centers, proj, self.kernel, mesh, axis=axis,
                chunk=chunk)
        return kernel_ops.kpca_project(
            x, self.centers, proj, sigma=self.kernel.sigma,
            p=self.kernel.p, chunk=chunk, precision=self.kernel.precision)


def _flatten(s: StreamingRSKPCA):
    leaves = (s.centers, s.wcount, s.wfrac, s.kgram, s.ncount, s.nfrac,
              s.eigvals, s.u, s.err_est, s.resid, s.n_patched)
    aux = (s.kernel, s.rank, s.eps, s.budget)
    return leaves, aux


def _unflatten(aux, leaves) -> StreamingRSKPCA:
    return StreamingRSKPCA(*leaves, *aux)


jax.tree_util.register_pytree_node(StreamingRSKPCA, _flatten, _unflatten)


def _solve(kgram: Array, weights: Array, n: Array, rank1: int,
           min_m: int | None = None):
    """Exact top-(rank+1) eigensystem of K-tilde/n (jittable; LOBPCG above
    the same crossover as the batch fit).

    Above the crossover the cached unweighted ``kgram`` is used DIRECTLY as
    the LOBPCG operator — sqrt(w) folds into the matvec — so the budget
    re-solve never materializes a second cap x cap weighted copy on top of
    the cache (DESIGN.md §6's operator-reuse rule applied to streaming).
    """
    sw = jnp.sqrt(weights)
    cap = kgram.shape[0]
    min_m = _LOBPCG_MIN_M if min_m is None else int(min_m)
    if cap > min_m and 5 * rank1 < cap:
        def matvec(v):
            return sw[:, None] * (kgram @ (sw[:, None] * v)) / n

        return _lobpcg_topk(matvec, cap, rank1)[:2]
    kt = sw[:, None] * kgram * sw[None, :] / n
    lam, u, _ = _top_eigh(kt, rank1)
    return lam, _canonicalize_signs(u)


#: Module-level jitted _solve: a fresh ``jax.jit(_solve)`` per call would
#: carry its own compilation cache and re-trace the cap x cap eigensolve
#: every time (from_rsde, ingest compaction, drift refresh all hit this).
solve_jit = jax.jit(_solve, static_argnames=("rank1", "min_m"))


def from_rsde(rsde: RSDE, kernel: Kernel, rank: int, *,
              ell: float | None = None, eps: float | None = None,
              cap: int | None = None,
              budget: float = DEFAULT_BUDGET) -> StreamingRSKPCA:
    """Lift a batch-fitted RSDE into a streaming state.

    ``cap`` (power-of-two bucketed, >= m, min 128) fixes the buffer size —
    and with it every downstream compiled shape; default leaves ~1/3 of the
    buffer free for inserts.  The eigensystem is solved exactly, so the
    state starts with a zero error budget.
    """
    m = rsde.m
    if eps is None:
        assert ell is not None, "pass the absorption radius via ell= or eps="
        eps = kernel.epsilon(ell)
    if cap is None:
        cap = (4 * m) // 3  # ~1/3 free slots before the first compaction
    cap = _pow2_ceil(max(128, cap, m))
    centers = np.zeros((cap, rsde.centers.shape[1]), np.float32)
    centers[:m] = np.asarray(rsde.centers, np.float32)
    # split each mass into int32 count + f32 residual (see the class
    # docstring: single-f32 accumulators saturate at 2^24)
    wf64 = np.asarray(rsde.weights, np.float64)
    wcount = np.zeros((cap,), np.int32)
    wfrac = np.zeros((cap,), np.float32)
    wcount[:m] = np.floor(wf64).astype(np.int32)
    wfrac[:m] = (wf64 - np.floor(wf64)).astype(np.float32)
    ncount = int(np.floor(float(rsde.n)))
    nfrac = float(rsde.n) - ncount
    centers = jnp.asarray(centers)
    weights = jnp.asarray(wcount.astype(np.float32) + wfrac)
    kgram = gram_matrix(kernel, centers, centers)
    n = jnp.asarray(float(rsde.n), jnp.float32)
    lam, u = solve_jit(kgram, weights, n, rank1=rank + 1)
    return StreamingRSKPCA(
        centers=centers, wcount=jnp.asarray(wcount),
        wfrac=jnp.asarray(wfrac), kgram=kgram,
        ncount=jnp.int32(ncount), nfrac=jnp.float32(nfrac),
        eigvals=lam, u=u,
        err_est=jnp.float32(0.0), resid=jnp.float32(0.0),
        n_patched=jnp.int32(0),
        kernel=kernel, rank=int(rank), eps=float(eps), budget=float(budget),
    )


# --------------------------------------------------------------------------
# checkpointing (repro.checkpoint.store: atomic, sharding-agnostic restore)
# --------------------------------------------------------------------------


def _template(cap: int, d: int, kernel: Kernel, rank: int, eps: float,
              budget: float) -> StreamingRSKPCA:
    z = jnp.zeros
    return StreamingRSKPCA(
        centers=z((cap, d), jnp.float32),
        wcount=z((cap,), jnp.int32), wfrac=z((cap,), jnp.float32),
        kgram=z((cap, cap), jnp.float32),
        ncount=jnp.int32(0), nfrac=jnp.float32(0.0),
        eigvals=z((rank + 1,), jnp.float32),
        u=z((cap, rank + 1), jnp.float32),
        err_est=jnp.float32(0.0), resid=jnp.float32(0.0),
        n_patched=jnp.int32(0),
        kernel=kernel, rank=rank, eps=eps, budget=budget,
    )


def save(state: StreamingRSKPCA, directory: str, step: int) -> str:
    """Atomic checkpoint via checkpoint/store.py; static config rides in the
    meta so ``load`` needs nothing but the directory."""
    from repro.checkpoint import store

    extra = {
        "streaming": {
            "kernel": dataclasses.asdict(state.kernel),
            "rank": state.rank, "eps": state.eps, "budget": state.budget,
            "cap": state.cap, "d": state.d,
        }
    }
    return store.save_checkpoint(directory, step, state, extra_meta=extra)


def load(directory: str, step: int | None = None) -> StreamingRSKPCA:
    from repro.checkpoint import store

    if step is None:
        step = store.latest_step(directory)
        assert step is not None, f"no streaming checkpoint under {directory}"
    with open(os.path.join(directory, f"step_{step:08d}", "meta.json")) as f:
        ex = json.load(f)["extra"]["streaming"]
    tmpl = _template(ex["cap"], ex["d"], Kernel(**ex["kernel"]),
                     ex["rank"], ex["eps"], ex["budget"])
    state, _ = store.restore_checkpoint(directory, tmpl, step=step)
    return state
