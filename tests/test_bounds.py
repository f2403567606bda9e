"""Property tests for the paper's §5 theorems (hypothesis-driven).

Every bound must hold for ANY dataset, kernel in {gaussian, laplacian},
and ell — this is the strongest validation of the reproduction's math.
"""
import numpy as np

from hypothesis import given, settings, strategies as st

from repro.core import gaussian, laplacian, shadow_select_host
from repro.core import mmd as M


def _data(n, d, seed, spread):
    rng = np.random.default_rng(seed)
    centers = rng.uniform(0, 1, (max(2, n // 10), d))
    idx = rng.integers(0, centers.shape[0], n)
    return (centers[idx] + spread * rng.normal(size=(n, d))).astype(np.float32)


KERNELS = [lambda s: gaussian(s), lambda s: laplacian(s)]


@settings(max_examples=20, deadline=None)
@given(n=st.integers(30, 150), d=st.integers(1, 10),
       ell=st.floats(2.0, 6.0), seed=st.integers(0, 10**6),
       kern=st.integers(0, 1), sigma=st.floats(0.2, 3.0))
def test_thm51_mmd_bound(n, d, ell, seed, kern, sigma):
    x = _data(n, d, seed, 0.1)
    ker = KERNELS[kern](sigma)
    c, w, a, m = shadow_select_host(x, ker.epsilon(ell))
    xq = M.quantized_dataset(x, c, a)
    val = M.mmd_biased(ker, x, xq)
    assert val <= ker.mmd_bound(ell) + 1e-5
    # weighted form computes the same quantity without materializing C-tilde
    assert abs(val - M.mmd_weighted(ker, x, c, w)) < 1e-3


@settings(max_examples=15, deadline=None)
@given(n=st.integers(30, 120), d=st.integers(1, 8),
       ell=st.floats(2.0, 6.0), seed=st.integers(0, 10**6),
       kern=st.integers(0, 1), sigma=st.floats(0.3, 2.0))
def test_thm52_eigenvalue_bound(n, d, ell, seed, kern, sigma):
    x = _data(n, d, seed, 0.08)
    ker = KERNELS[kern](sigma)
    c, w, a, m = shadow_select_host(x, ker.epsilon(ell))
    xq = M.quantized_dataset(x, c, a)
    gap = M.eigenvalue_gap_sq(ker, x, xq)
    assert gap <= ker.eigenvalue_bound(ell) + 1e-6


@settings(max_examples=15, deadline=None)
@given(n=st.integers(30, 100), d=st.integers(1, 8),
       ell=st.floats(2.0, 6.0), seed=st.integers(0, 10**6),
       kern=st.integers(0, 1), sigma=st.floats(0.3, 2.0))
def test_thm53_hs_operator_bound(n, d, ell, seed, kern, sigma):
    x = _data(n, d, seed, 0.08)
    ker = KERNELS[kern](sigma)
    c, w, a, m = shadow_select_host(x, ker.epsilon(ell))
    xq = M.quantized_dataset(x, c, a)
    hs = M.hs_operator_distance(ker, x, xq)
    assert hs <= ker.hs_bound(ell) + 1e-5
    # tighter intermediate: HS distance <= 2 kappa max_i ||eps_i||
    assert hs <= 2.0 * ker.kappa * M.centroid_error_max(ker, x, xq) + 1e-5


def test_thm54_eigenspace_projection_bound():
    # deterministic check (the Cholesky-based projector distance is O(n^3))
    x = _data(80, 5, 1, 0.08)
    for kern in KERNELS:
        ker = kern(1.0)
        for ell in (3.0, 4.0, 5.0):
            c, w, a, m = shadow_select_host(x, ker.epsilon(ell))
            xq = M.quantized_dataset(x, c, a)
            import jax.numpy as jnp
            from repro.core.kernels_math import gram_matrix
            lam = np.linalg.eigvalsh(
                np.asarray(gram_matrix(ker, jnp.asarray(x))) / len(x))[::-1]
            rank = 3
            delta = 0.5 * (lam[rank - 1] - lam[rank])
            eps_max = M.centroid_error_max(ker, x, xq)
            if 2 * np.sqrt(ker.kappa) * eps_max >= delta / 2 or delta <= 1e-9:
                continue  # theorem precondition not met
            dist = M.eigenspace_projection_distance(ker, x, xq, rank)
            bound = 2 * np.sqrt(
                2 * ker.kappa * (ker.kappa - np.exp(-1.0 / ell**ker.p))
            ) / delta
            assert dist <= bound + 1e-4


@settings(max_examples=25, deadline=None)
@given(m=st.integers(3, 40), d=st.integers(1, 6), seed=st.integers(0, 10**6),
       kern=st.integers(0, 1), sigma=st.floats(0.3, 2.0),
       kind=st.integers(0, 2), j=st.integers(0, 10**6))
def test_online_weight_update_bound(m, d, seed, kern, sigma, kind, j):
    """The closed-form rank-two bound behind every streaming update
    (core.mmd.weight_update_bound) must dominate the TRUE Frobenius change
    of the normalized weighted operator for absorb/insert/remove."""
    import jax.numpy as jnp
    from repro.core.kernels_math import gram_matrix

    rng = np.random.default_rng(seed)
    c = rng.normal(size=(m, d)).astype(np.float32)
    w = rng.integers(1, 10, size=m).astype(np.float64)
    j = j % m
    if kind == 1:  # insert: model the new center as a live slot of weight 0
        w[j] = 0.0
    n = w.sum()
    if kind == 2 and n <= w[j]:  # removing the only mass: undefined, skip
        return
    k = np.asarray(gram_matrix(KERNELS[kern](sigma), jnp.asarray(c)),
                   np.float64)
    w2 = w.copy()
    if kind == 0:    # absorb one sample into center j
        w2[j] += 1.0
    elif kind == 1:  # insert a fresh unit-mass center
        w2[j] = 1.0
    else:            # remove center j outright
        w2[j] = 0.0
    n2 = w2.sum()
    kt = np.sqrt(w)[:, None] * k * np.sqrt(w)[None, :] / n
    kt2 = np.sqrt(w2)[:, None] * k * np.sqrt(w2)[None, :] / n2
    true = np.linalg.norm(kt2 - kt)
    bound = float(M.weight_update_bound(n, n2, w[j], w2[j],
                                        kappa=KERNELS[kern](sigma).kappa))
    assert true <= bound + 1e-6, (true, bound, kind)


def test_bounds_tighten_with_ell():
    ker = gaussian(1.0)
    bounds = [ker.mmd_bound(ell) for ell in (2.0, 3.0, 4.0, 6.0, 10.0)]
    assert all(b1 > b2 for b1, b2 in zip(bounds, bounds[1:]))
    assert ker.mmd_bound(1e6) < 1e-2  # vanishes as the cover refines
