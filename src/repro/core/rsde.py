"""Reduced-set density estimates (paper §3 Eq. 9 and §6 'different RSDE schemes').

Every scheme produces an ``RSDE(centers, weights, n)`` with weights summing to
``n`` so that p-tilde(x) = (1/n) sum_j w_j k(c_j, x) approximates the KDE.

Schemes (paper §6, Figs. 7-8):
  * shadow   — Algorithm 2 (ShDE), O(mn), m derived from ell.     [this paper]
  * kmeans   — Lloyd centers, weights = cluster sizes, O(mn) per iter.  [20]
  * paring   — uniform subsample, uniform weights n/m, O(m).      [8]
  * herding  — greedy MMD-descent sample from the KDE, O(n^2 m).  [5]
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kernels_math import Kernel, gram_matrix
from repro.core import shadow as shadow_mod
from repro.kernels import ops as kernel_ops

Array = jax.Array


@dataclasses.dataclass
class RSDE:
    centers: np.ndarray  # (m, d)
    weights: np.ndarray  # (m,), sums to n
    n: int               # cardinality of the originating dataset
    assign: np.ndarray | None = None  # (n,) data->center map when available
    scheme: str = "shadow"

    @property
    def m(self) -> int:
        return self.centers.shape[0]

    @property
    def retention(self) -> float:
        """Fraction of the data retained (Fig. 6)."""
        return self.m / self.n


#: One-device crossover: below this n the sequential while_loop beats
#: blocked selection (the per-round assign/prune overhead only amortizes once
#: m is large — measured 2x either way at n=2048 vs n=8192).
_BLOCKED_MIN_N = 4096


def shadow_rsde(x, kernel: Kernel, ell: float, *, block: int | None = None,
                mesh=None, axis: str = "data") -> RSDE:
    """ShDE via Algorithm 2 with eps = sigma/ell — the one in-memory front
    door, and the one place the selection path is chosen (DESIGN.md §3):

      * one device, n <= ``_BLOCKED_MIN_N``: the sequential scan
        (``shadow_select_host``);
      * one device, n above it: the blocked halving cascade
        (``shadow_select_blocked``);
      * ``mesh``: rows split over ``axis`` and selected by the out-of-core
        stream's programs (``ingest_pipeline.select_streaming`` over ``x``
        as one chunk, padded to a multiple of the axis): blocked selection
        per device, then the device merge of the all-gathered candidates.
        Cover radius 2*eps (the §5 bounds hold with ell -> ell/2), masses
        exact, no assign map, scheme ``"shadow-ingest"``.

    Every path gives weights that sum to n.
    """
    eps = kernel.epsilon(ell)
    n = np.shape(x)[0]
    if mesh is not None:
        from repro.core.ingest_pipeline import pad_block, select_streaming
        ndev = mesh.shape[axis]
        xp, _ = pad_block(x, -(-n // ndev) * ndev)
        rsde, _ = select_streaming([(xp, n)], eps,
                                   block=256 if block is None else block,
                                   mesh=mesh, axis=axis)
        return rsde
    if n <= _BLOCKED_MIN_N:
        centers, weights, assign, m = shadow_mod.shadow_select_host(x, eps)
    else:
        centers, weights, assign, m = shadow_mod.shadow_select_blocked(
            x, eps, block=block)
    return RSDE(centers, weights, n=n, assign=assign, scheme="shadow")


@partial(jax.jit, static_argnames=("m", "iters"))
def _kmeans(x: Array, m: int, iters: int, seed: int):
    n, d = x.shape
    key = jax.random.PRNGKey(seed)
    idx = jax.random.choice(key, n, (m,), replace=False)
    centers = x[idx]

    def step(centers, _):
        d2 = (
            jnp.sum(x * x, 1)[:, None]
            + jnp.sum(centers * centers, 1)[None, :]
            - 2.0 * x @ centers.T
        )
        assign = jnp.argmin(d2, axis=1)
        one_hot = jax.nn.one_hot(assign, m, dtype=x.dtype)  # (n, m)
        counts = one_hot.sum(0)
        sums = one_hot.T @ x
        new_centers = sums / jnp.maximum(counts, 1.0)[:, None]
        # keep old center for empty clusters
        new_centers = jnp.where(counts[:, None] > 0, new_centers, centers)
        return new_centers, None

    centers, _ = jax.lax.scan(step, centers, None, length=iters)
    d2 = (
        jnp.sum(x * x, 1)[:, None]
        + jnp.sum(centers * centers, 1)[None, :]
        - 2.0 * x @ centers.T
    )
    assign = jnp.argmin(d2, axis=1)
    counts = jax.nn.one_hot(assign, m, dtype=x.dtype).sum(0)
    return centers, counts, assign


def kmeans_rsde(x, kernel: Kernel, m: int, iters: int = 10, seed: int = 0) -> RSDE:
    """k-means RSDE (density-weighted Nystrom's selector, [20])."""
    x = jnp.asarray(x, jnp.float32)
    centers, counts, assign = _kmeans(x, m, iters, seed)
    return RSDE(
        np.asarray(centers), np.asarray(counts, np.float64),
        n=x.shape[0], assign=np.asarray(assign), scheme="kmeans",
    )


def paring_rsde(x, kernel: Kernel, m: int, seed: int = 0) -> RSDE:
    """KDE paring [8] (simplified): uniform subsample, uniform weights n/m.

    Subsampling via ``jax.random`` keyed off ``seed`` — deterministic across
    hosts/backends, unlike the host ``np.random`` state it replaces.
    """
    x = np.asarray(x)
    idx = np.asarray(jax.random.choice(
        jax.random.PRNGKey(seed), x.shape[0], (m,), replace=False))
    w = np.full(m, x.shape[0] / m, dtype=np.float64)
    return RSDE(x[idx].copy(), w, n=x.shape[0], scheme="paring")


@partial(jax.jit, static_argnames=("m",))
def _kmeans_stream_update(sums, counts, x, ok, idx, m: int):
    """Accumulate per-center sums/counts over one chunk's VALID rows.

    Padding rows route to a discard bucket (segment m) instead of being
    masked by a (rows, m) one-hot — ``segment_sum`` keeps the chunk update
    O(rows * d), which is what lets the stream pass scale to 1M rows.
    """
    okf = ok.astype(x.dtype)
    idx_safe = jnp.where(ok, idx, m)
    sums = sums + jax.ops.segment_sum(
        x * okf[:, None], idx_safe, num_segments=m + 1)[:m]
    counts = counts + jax.ops.segment_sum(
        okf, idx_safe, num_segments=m + 1)[:m]
    return sums, counts


def kmeans_rsde_stream(source, kernel: Kernel, m: int, seed: int = 0):
    """One-pass streaming mini-batch k-means RSDE over a chunk source
    (``.chunks()`` protocol or an iterable of ``(x, n_valid)`` blocks).

    Centers seed from the first chunk (``jax.random`` keyed off ``seed``;
    the first chunk must hold at least m valid rows), each chunk assigns
    through the Pallas ``shadow_assign`` kernel, and centers refresh to the
    running means after every chunk (mini-batch Lloyd).  Weights are the
    final-pass cluster counts, summing exactly to n.  Device residency is
    O(chunk + m*d).  Returns ``(RSDE, IngestStats)``.
    """
    import time

    from repro.core.ingest_pipeline import IngestStats  # lazy: circular

    stats = IngestStats()
    t0 = time.perf_counter()
    chunks = source.chunks() if hasattr(source, "chunks") else iter(source)
    centers = sums = counts = None
    for xb, nv in chunks:
        t1 = time.perf_counter()
        nv = int(nv)
        x = jnp.asarray(np.asarray(xb, np.float32))
        ok = jnp.arange(x.shape[0]) < nv
        if centers is None:
            if nv < m:
                raise ValueError(
                    f"first chunk holds {nv} valid rows < m={m}")
            pick = jax.random.choice(jax.random.PRNGKey(seed), nv, (m,),
                                     replace=False)
            centers = x[pick]
            sums = jnp.zeros((m, x.shape[1]), jnp.float32)
            counts = jnp.zeros((m,), jnp.float32)
        idx, _ = kernel_ops.shadow_assign(x, centers, tag="kmeans")
        sums, counts = _kmeans_stream_update(sums, counts, x, ok, idx, m)
        centers = jnp.where(counts[:, None] > 0,
                            sums / jnp.maximum(counts, 1.0)[:, None],
                            centers)
        stats.chunks += 1
        stats.rows += nv
        stats.compute_s += time.perf_counter() - t1
    if centers is None:
        raise ValueError("empty source: no chunks to ingest")
    stats.m = m
    stats.select_s = time.perf_counter() - t0
    stats.wall_s = stats.select_s
    rsde = RSDE(
        np.asarray(centers), np.asarray(counts, np.float64),
        n=int(stats.rows), scheme="kmeans-stream",
    )
    return rsde, stats


def herding_rsde(x, kernel: Kernel, m: int) -> RSDE:
    """Kernel herding [5]: greedy samples maximizing the herding functional

        c_{t+1} = argmax_{x in X}  mu(x) - (1/(t+1)) sum_{s<=t} k(c_s, x)

    where mu(x) = (1/n) sum_i k(x_i, x) is the KDE.  O(n^2 + nm).
    """
    x = jnp.asarray(x, jnp.float32)
    n = x.shape[0]
    k_full = gram_matrix(kernel, x, x)  # (n, n)
    mu = k_full.mean(axis=1)  # KDE at each candidate

    def step(carry, t):
        acc, chosen = carry  # acc = sum_{s<=t-1} k(c_s, .) over candidates
        score = mu - acc / (t + 1.0)
        score = jnp.where(chosen, -jnp.inf, score)
        i = jnp.argmax(score)
        acc = acc + k_full[i]
        chosen = chosen.at[i].set(True)
        return (acc, chosen), i

    (_, _), idx = jax.lax.scan(
        step,
        (jnp.zeros(n, jnp.float32), jnp.zeros(n, bool)),
        jnp.arange(m, dtype=jnp.float32),
    )
    centers = np.asarray(x[idx])
    w = np.full(m, n / m, dtype=np.float64)  # herding samples are equal-weight
    return RSDE(centers, w, n=int(n), scheme="herding")


_SCHEMES = {
    "shadow": shadow_rsde,
    "kmeans": kmeans_rsde,
    "paring": paring_rsde,
    "herding": herding_rsde,
}


def make_rsde(scheme: str, x, kernel: Kernel, *, ell: float | None = None,
              m: int | None = None, **kw) -> RSDE:
    """Factory. ``shadow`` takes ell; the others take an explicit m (as in the
    paper, where the average shadow m sets m for the competing schemes)."""
    if scheme == "shadow":
        assert ell is not None, "shadow RSDE is parameterized by ell"
        return shadow_rsde(x, kernel, ell, **kw)
    assert m is not None, f"{scheme} RSDE needs an explicit m"
    return _SCHEMES[scheme](x, kernel, m=m, **kw)
