"""The plain reference: RSKPCA written straight from the paper, in jnp and
LAPACK, sharing no code with the program.

* ``select``: blocked Algorithm 2 (the program's documented selection
  semantics, DESIGN.md section 3): each round takes the first ``block`` rows
  still alive, keeps those at least eps from every earlier keeper of the
  round, and every alive row strictly within eps of its nearest keeper
  joins that keeper's shadow.
* ``eig``: the top-``rank`` eigenpairs of K~/n = W^1/2 K(C, C) W^1/2 / n,
  Gram on the device, ``eigh`` in float64 on the host.
* ``transform``: z(x) = k(x, C) A with A = W^1/2 U Lambda^-1/2 / sqrt(n).

Every matrix product takes ``precision``: "highest" is the reference,
"high" (three bfloat16 passes) the control that the limits are set
against.  ``transform_exact`` is the float64 truth that served and fitted
embeddings are compared with.  Arrays whose size is the number of centers m are padded to a
multiple of ``BUCKET`` before they reach the device, so that a run compiles
the same programs whatever m its seed gives.  The comparisons at the bottom
return the numbers that decide ``correct``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

#: Center counts are padded to a multiple of this on the device.
BUCKET = 512

#: A padding center's coordinate: far from every row and every other pad.
_FAR = 1e6


def _bucket(m: int) -> int:
    return max(BUCKET, -(-m // BUCKET) * BUCKET)


def _pad_rows(a: np.ndarray, rows: int, value=0.0) -> np.ndarray:
    out = np.full((rows,) + a.shape[1:], value, a.dtype)
    out[: a.shape[0]] = a
    return out


def _pad_far(c: np.ndarray, rows: int) -> np.ndarray:
    """Centers padded with rows far from the data and from one another."""
    out = _pad_rows(np.asarray(c, np.float32), rows)
    k = rows - c.shape[0]
    if k:
        out[c.shape[0]:, 0] = _FAR * (1.0 + np.arange(k, dtype=np.float32))
    return out


def _split_bf16(a):
    """a = hi + lo + rest with hi and lo bfloat16 numbers, rounded to
    nearest (``reduce_precision``: a bfloat16 round trip may be folded
    away by the compiler)."""
    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    return hi, jax.lax.reduce_precision(a - hi, exponent_bits=8,
                                        mantissa_bits=7)


def dot(a, b, precision: str = "highest"):
    """``a @ b`` in float32 at ``precision``: "highest", or "high", the
    three-pass bfloat16 product of ``Precision.HIGH``.  A TPU runs HIGH
    natively; elsewhere (where HIGH is exact float32) it is written out as
    hi*hi + hi*lo + lo*hi, each pass exact in float32."""
    if precision == "highest":
        return jnp.matmul(a, b, precision=HIGHEST)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    if jax.default_backend() == "tpu":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGH)
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)
    mm = partial(jnp.matmul, precision=HIGHEST)
    return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))


def sq_dists(x, c, precision: str = "highest"):
    xx = jnp.sum(x * x, axis=1)[:, None]
    cc = jnp.sum(c * c, axis=1)[None, :]
    return jnp.maximum(xx + cc - 2.0 * dot(x, c.T, precision), 0.0)


def gram(x, c, sigma: float, precision: str = "highest"):
    """Gaussian kernel k(x, c) = exp(-||x - c||^2 / sigma^2)."""
    return jnp.exp(-sq_dists(x, c, precision) / jnp.float32(sigma) ** 2)


# --------------------------------------------------------------------------
# selection
# --------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("block", "precision"))
def _select(x, eps2, block: int, precision: str):
    n = x.shape[0]

    def body(state):
        alive, centers, weights, assign, m = state
        (cand,) = jnp.nonzero(alive, size=block, fill_value=n)
        cand_ok = cand < n
        cx = x[jnp.minimum(cand, n - 1)]
        d2c = jnp.sum((cx[:, None, :] - cx[None, :, :]) ** 2, axis=-1)

        def pick(j, keep):
            sep = jnp.all(jnp.where(keep, d2c[:, j] >= eps2, True))
            return keep.at[j].set(cand_ok[j] & sep)

        keep = jax.lax.fori_loop(0, block, pick, jnp.zeros((block,), bool))
        d2 = jnp.where(keep[None, :], sq_dists(x, cx, precision), jnp.inf)
        # a candidate row resolves against its batch by direct differences,
        # exact at distance zero, so every keeper absorbs itself
        d2 = d2.at[jnp.where(cand_ok, cand, n)].set(
            jnp.where(keep[None, :], d2c, jnp.inf), mode="drop")
        near = jnp.argmin(d2, axis=1)
        absorbed = alive & (jnp.min(d2, axis=1) < eps2)
        rank = jnp.cumsum(keep) - 1
        counts = jnp.zeros((block,), jnp.int32).at[near].add(
            absorbed.astype(jnp.int32))
        slot = jnp.where(keep, m + rank, n)
        centers = centers.at[slot].set(cand, mode="drop")
        weights = weights.at[slot].set(counts, mode="drop")
        assign = jnp.where(absorbed, m + rank[near], assign)
        return (alive & ~absorbed, centers, weights, assign,
                m + jnp.sum(keep, dtype=jnp.int32))

    state = (jnp.ones((n,), bool), jnp.zeros((n,), jnp.int32),
             jnp.zeros((n,), jnp.int32), jnp.full((n,), -1, jnp.int32),
             jnp.int32(0))
    _, centers, weights, assign, m = jax.lax.while_loop(
        lambda s: jnp.any(s[0]), body, state)
    return centers, weights, assign, m


def select(x, eps: float, block: int = 256, precision: str = "highest"):
    """Blocked Algorithm 2 over the rows ``x`` (device or host, (n, d)).
    Returns (center row indices (m,), integer weights (m,), assign (n,))
    as host arrays."""
    x = jnp.asarray(x, jnp.float32)
    c, w, a, m = _select(x, jnp.float32(eps) ** 2, int(block), precision)
    m = int(m)
    return np.asarray(c)[:m], np.asarray(w)[:m], np.asarray(a)


# --------------------------------------------------------------------------
# eigensystem and transform
# --------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("precision",))
def _weighted_gram(c, w, n, sigma, precision: str):
    sw = jnp.sqrt(w)
    return sw[:, None] * gram(c, c, sigma, precision) * sw[None, :] / n


def eig(centers, weights, n: int, sigma: float, rank: int,
        precision: str = "highest"):
    """Top-``rank`` eigenvalues (descending) and the projector A (m, rank)
    of the reduced set's normalized operator."""
    from scipy.linalg import eigh

    m = len(weights)
    b = _bucket(m)
    c = jnp.asarray(_pad_rows(np.asarray(centers, np.float32), b))
    w = jnp.asarray(_pad_rows(np.asarray(weights, np.float32), b))
    kt = np.asarray(_weighted_gram(c, w, jnp.float32(n), jnp.float32(sigma),
                                   precision), np.float64)[:m, :m]
    lam, u = eigh(kt, subset_by_index=[m - rank, m - 1])
    lam, u = lam[::-1], u[:, ::-1]
    sw = np.sqrt(np.asarray(weights, np.float64))
    proj = sw[:, None] * u / np.sqrt(np.maximum(lam, 1e-300))[None, :] \
        / np.sqrt(float(n))
    return lam, proj.astype(np.float32)


@partial(jax.jit, static_argnames=("precision",))
def _transform_block(x, c, a, sigma, precision: str):
    return dot(gram(x, c, sigma, precision), a, precision)


def transform(x, centers, projector, sigma: float,
              precision: str = "highest", block: int = 8192) -> np.ndarray:
    """z(x) = k(x, C) A, in row blocks of ``block`` (padded, so that one
    program serves every block); padded centers carry zero projector
    rows."""
    x = np.asarray(x, np.float32)
    b = _bucket(len(projector))
    c = jnp.asarray(_pad_rows(np.asarray(centers, np.float32), b))
    a = jnp.asarray(_pad_rows(np.asarray(projector, np.float32), b))
    s = jnp.float32(sigma)
    out = []
    for lo in range(0, x.shape[0], block):
        xs = x[lo: lo + block]
        k = xs.shape[0]
        if k < block:
            xs = _pad_rows(xs, block)
        out.append(np.asarray(_transform_block(jnp.asarray(xs), c, a, s,
                                               precision))[:k])
    return np.concatenate(out) if out else np.zeros((0, a.shape[1]),
                                                    np.float32)


def transform_exact(x, centers, projector, sigma: float,
                    block: int = 1024) -> np.ndarray:
    """z(x) = k(x, C) A in float64 on the host: the truth the served and
    fitted transforms are held to."""
    x = np.asarray(x, np.float64)
    c = np.asarray(centers, np.float64)
    a = np.asarray(projector, np.float64)
    cc = np.sum(c * c, axis=1)[None, :]
    out = np.empty((x.shape[0], a.shape[1]))
    for lo in range(0, x.shape[0], block):
        xs = x[lo: lo + block]
        d2 = np.maximum(np.sum(xs * xs, axis=1)[:, None] + cc
                        - 2.0 * xs @ c.T, 0.0)
        out[lo: lo + block] = np.exp(-d2 / float(sigma) ** 2) @ a
    return out


def fit(x, eps: float, sigma: float, rank: int, block: int = 256,
        precision: str = "highest"):
    """Selection and eigensystem over the rows ``x``: the reference's whole
    fit.  Returns a dict with centers, weights, assign, eigvals, projector."""
    idx, w, assign = select(x, eps, block, precision)
    centers = np.asarray(x, np.float32)[idx]
    lam, proj = eig(centers, w, int(np.shape(x)[0]), sigma, rank, precision)
    return {"centers": centers, "weights": w.astype(np.float64),
            "assign": assign, "eigvals": lam, "projector": proj}


# --------------------------------------------------------------------------
# comparisons: each returns numbers that decide ``correct``
# --------------------------------------------------------------------------


def rel_err(got, want) -> float:
    """Largest absolute difference over the largest reference magnitude."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                   1e-300))


def eig_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / np.abs(want)))


def emb_err(z, z_ref) -> float:
    """Relative Frobenius error of an embedding after the orthogonal map
    that aligns it best with the reference (Procrustes): eigenvectors are
    defined up to sign, and up to rotation among nearly equal eigenvalues,
    where a per-column comparison reads an iterative solver's arbitrary
    choice as error."""
    z, z_ref = np.asarray(z, np.float64), np.asarray(z_ref, np.float64)
    u, _, vt = np.linalg.svd(z.T @ z_ref)
    return float(np.linalg.norm(z @ (u @ vt) - z_ref)
                 / np.linalg.norm(z_ref))


def mass_err(weights, n: int) -> float:
    """|sum of weights - n|, in float64: 0 when the shadow masses partition
    the rows exactly."""
    return float(abs(np.sum(np.asarray(weights, np.float64)) - float(n)))


def count_err(weights, assign) -> float:
    """Largest |weight - rows assigned| over the centers, where the answer
    carries its assignment (0 when each mass is its shadow's count); 0
    where it carries none."""
    if assign is None:
        return 0.0
    a = np.asarray(assign)
    if a.size == 0 or np.any(a < 0) or np.any(a >= len(weights)):
        return float("inf")
    counts = np.bincount(a, minlength=len(weights))
    return float(np.max(np.abs(counts - np.asarray(weights, np.float64))))


@jax.jit
def _assigned_d2(x, c, a):
    return jnp.sum((x - c[a]) ** 2, axis=1)


@jax.jit
def _nearest_d2(x, c):
    return jnp.min(sq_dists(x, c), axis=1)


def cover(x, centers, radius: float, assign=None,
          block: int = 65536) -> tuple[float, float]:
    """(excess, outside) of a cover: the largest (distance to its center) /
    radius - 1 over the rows, below 0 when every row lies within
    ``radius``, and the count of rows at or beyond ``radius``.  With
    ``assign`` a row is measured against the center it was assigned, by
    direct differences; without, against its nearest center."""
    x = np.asarray(x, np.float32)
    if assign is not None and len(assign) != x.shape[0]:
        return float("inf"), float("inf")
    c = jnp.asarray(_pad_far(centers, _bucket(len(centers))))
    r2 = np.float32(radius) ** 2
    worst, outside = 0.0, 0
    for lo in range(0, x.shape[0], block):
        xs = x[lo: lo + block]
        k = xs.shape[0]
        xs = jnp.asarray(_pad_rows(xs, block))
        if assign is None:
            d2 = np.asarray(_nearest_d2(xs, c))[:k]
        else:
            a = np.asarray(assign[lo: lo + block])
            if np.any(a < 0) or np.any(a >= len(centers)):
                return float("inf"), float("inf")
            d2 = np.asarray(_assigned_d2(
                xs, c, jnp.asarray(_pad_rows(a.astype(np.int32), block))))[:k]
        worst = max(worst, float(np.max(d2)))
        outside += int(np.sum(d2 >= r2))
    return float(np.sqrt(worst) / radius - 1.0), float(outside)


@jax.jit
def _pairs_below(a, b, offset, eps2):
    d2 = jnp.sum((a[:, None, :] - b[None, :, :]) ** 2, axis=-1)
    i = jnp.arange(a.shape[0])[:, None] + offset
    j = jnp.arange(b.shape[0])[None, :]
    d2 = jnp.where(i == j, jnp.inf, d2)
    return jnp.min(d2), jnp.sum(d2 < eps2)


def separation(centers, eps: float) -> tuple[float, float]:
    """(deficit, close) of a center set: 1 - (least distance between two
    centers) / eps, at most 0 when the centers are pairwise eps apart, and
    the count of pairs closer than eps; by direct differences."""
    m, d = np.shape(centers)
    if m < 2:
        return -1.0, 0.0
    b = _bucket(m)
    c = _pad_far(centers, b)
    rows = max(1, min(b, (1 << 26) // (b * d)))
    while b % rows:
        rows -= 1
    cd = jnp.asarray(c)
    eps2 = jnp.float32(eps) ** 2
    best, close = np.inf, 0
    for lo in range(0, b, rows):
        lo_d2, below = _pairs_below(jnp.asarray(c[lo: lo + rows]), cd,
                                    jnp.int32(lo), eps2)
        best = min(best, float(lo_d2))
        close += int(below)
    return float(1.0 - np.sqrt(best) / eps), float(close // 2)
