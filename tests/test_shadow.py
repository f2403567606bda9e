"""Algorithm 2 (shadow selection): oracle equivalence + invariant properties."""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (shadow_select_np, shadow_select_host,
                        shadow_select_blocked, shadow_select_streaming,
                        gaussian)
from repro.core.shadow import two_level_merge

import jax.numpy as jnp


def _data(n, d, seed):
    rng = np.random.default_rng(seed)
    centers = rng.uniform(0, 1, (max(2, n // 20), d))
    idx = rng.integers(0, centers.shape[0], n)
    return (centers[idx] + 0.05 * rng.normal(size=(n, d))).astype(np.float32)


def test_jax_matches_numpy_oracle():
    x = _data(500, 8, 0)
    for eps in (0.05, 0.1, 0.3, 1.0):
        c_np, w_np, a_np = shadow_select_np(x, eps)
        c_j, w_j, a_j, m = shadow_select_host(x, eps)
        assert m == len(c_np)
        np.testing.assert_allclose(c_j, c_np, atol=1e-6)
        np.testing.assert_allclose(w_j, w_np)
        assert (a_j == a_np).all()


@settings(max_examples=25, deadline=None)
@given(n=st.integers(20, 300), d=st.integers(1, 16),
       eps=st.floats(0.01, 2.0), seed=st.integers(0, 10**6))
def test_shadow_invariants(n, d, eps, seed):
    x = _data(n, d, seed)
    c, w, a, m = shadow_select_host(x, eps)
    # partition: weights sum to n; every point assigned
    assert w.sum() == n
    assert (a >= 0).all() and (a < m).all()
    # coverage: every point strictly within eps of its center
    dist = np.linalg.norm(x - c[a], axis=1)
    assert (dist < eps + 1e-5).all()


@settings(max_examples=25, deadline=None)
@given(n=st.integers(20, 200), d=st.integers(1, 8), seed=st.integers(0, 10**6))
def test_center_separation_and_monotonicity(n, d, seed):
    x = _data(n, d, seed)
    prev_m = None
    for eps in (0.05, 0.1, 0.2, 0.4, 0.8):
        c, w, a, m = shadow_select_host(x, eps)
        if m > 1:
            d2 = ((c[:, None, :] - c[None, :, :]) ** 2).sum(-1)
            np.fill_diagonal(d2, np.inf)
            assert np.sqrt(d2.min()) >= eps - 1e-5  # greedy separation
        if prev_m is not None:
            assert m <= prev_m  # m non-increasing in eps
        prev_m = m


def test_permutation_changes_centers_but_keeps_invariants():
    x = _data(300, 6, 3)
    eps = 0.15
    rng = np.random.default_rng(0)
    perm = rng.permutation(len(x))
    c1, w1, _, m1 = shadow_select_host(x, eps)
    c2, w2, _, m2 = shadow_select_host(x[perm], eps)
    # order-dependent (paper Algorithm 2 takes the *first* element)...
    assert w1.sum() == w2.sum() == len(x)
    # ...but both are eps-covers with separated centers
    for c, m in ((c1, m1), (c2, m2)):
        d2 = ((c[:, None] - c[None]) ** 2).sum(-1)
        np.fill_diagonal(d2, np.inf)
        if m > 1:
            assert np.sqrt(d2.min()) >= eps - 1e-5


def test_two_level_merge_preserves_weight_and_cover():
    x = _data(400, 5, 7)
    eps = 0.2
    # simulate 4 shards
    shards = np.split(x, 4)
    cs, ws = [], []
    for s in shards:
        c, w, _, m = shadow_select_host(s, eps)
        cs.append(c)
        ws.append(w)
    all_c = jnp.asarray(np.concatenate(cs))
    all_w = jnp.asarray(np.concatenate(ws), jnp.float32)
    out_c, out_w, m = two_level_merge(all_c, all_w, jnp.float32(eps),
                                      max_centers=len(all_c))
    m = int(m)
    assert float(out_w[:m].sum()) == len(x)
    # 2-eps cover (DESIGN.md two-level bound)
    d = np.linalg.norm(x[:, None] - np.asarray(out_c[:m])[None], axis=2).min(1)
    assert (d < 2 * eps + 1e-5).all()


@settings(max_examples=15, deadline=None)
@given(n=st.integers(20, 300), d=st.integers(1, 16),
       eps=st.floats(0.01, 2.0), block=st.integers(1, 64),
       seed=st.integers(0, 10**6))
def test_blocked_matches_sequential_invariants(n, d, eps, block, seed):
    """Blocked selection must satisfy the SAME cover invariants as the
    sequential algorithm: strict eps-cover, weights partition n, centers
    pairwise >= eps apart (the center set itself may differ)."""
    x = _data(n, d, seed)
    c, w, a, m = shadow_select_blocked(x, eps, block=block)
    assert w.sum() == n
    assert (a >= 0).all() and (a < m).all()
    dist = np.linalg.norm(x - c[a], axis=1)
    assert (dist < eps + 1e-5).all()
    if m > 1:
        d2 = ((c[:, None] - c[None]) ** 2).sum(-1)
        np.fill_diagonal(d2, np.inf)
        assert np.sqrt(d2.min()) >= eps - 1e-5


def test_blocked_block1_matches_sequential_exactly():
    """With B=1 the blocked selector degenerates to Algorithm 2 verbatim."""
    x = _data(250, 5, 2)
    for eps in (0.1, 0.3, 0.8):
        c_s, w_s, a_s, m_s = shadow_select_host(x, eps)
        c_b, w_b, a_b, m_b = shadow_select_blocked(x, eps, block=1)
        assert m_b == m_s
        np.testing.assert_allclose(c_b, c_s, atol=1e-6)
        np.testing.assert_allclose(w_b, w_s)
        assert (a_b == a_s).all()


@settings(max_examples=10, deadline=None)
@given(n=st.integers(50, 400), d=st.integers(1, 8),
       eps=st.floats(0.05, 1.0), seed=st.integers(0, 10**6))
def test_streaming_two_level_cover(n, d, eps, seed):
    """Streaming selection: weights partition n; 2*eps cover (two-level)."""
    x = _data(n, d, seed)
    c, w, a, m = shadow_select_streaming(x, eps, chunk=max(32, n // 3),
                                         block=32)
    assert abs(w.sum() - n) < 1e-3
    assert (a >= 0).all() and (a < m).all()
    dist = np.linalg.norm(x - c[a], axis=1)
    assert (dist < 2 * eps + 1e-5).all()


def test_streaming_ragged_final_block():
    """The last chunk can be far smaller than ``chunk`` (and smaller than
    ``block``); its centers must still merge into a valid 2*eps cover."""
    x = _data(101, 4, 5)  # chunk=32 -> chunks of 32,32,32,5
    for eps in (0.1, 0.25):
        c, w, a, m = shadow_select_streaming(x, eps, chunk=32, block=8)
        assert abs(w.sum() - 101) < 1e-3
        assert (a >= 0).all() and (a < m).all()
        dist = np.linalg.norm(x - c[a], axis=1)
        assert (dist < 2 * eps + 1e-5).all()


def test_two_level_merge_block_fully_absorbed():
    """A partition whose centers are ALL within eps of an earlier
    partition's centers must contribute zero surviving centers — only its
    weight mass."""
    x = _data(200, 4, 8)
    eps = 0.2
    c1, w1, _, m1 = shadow_select_host(x, eps)
    # second "shard" re-selects the SAME data: every candidate lies within
    # eps of (in fact on top of) a first-shard center
    all_c = jnp.asarray(np.concatenate([c1, c1]))
    all_w = jnp.asarray(np.concatenate([w1, w1]), jnp.float32)
    out_c, out_w, m = two_level_merge(all_c, all_w, jnp.float32(eps),
                                      max_centers=len(all_c))
    m = int(m)
    assert m == m1  # zero survivors from the absorbed block
    np.testing.assert_allclose(np.asarray(out_c[:m]), c1, atol=1e-6)
    assert abs(float(out_w[:m].sum()) - 2 * len(x)) < 1e-3  # mass conserved


def test_two_level_merge_unequal_weight_partitions():
    """Shards of very different sizes (so very different weight scales)
    must merge into a cover that conserves total mass exactly."""
    x = _data(330, 3, 12)
    eps = 0.25
    parts = [x[:10], x[10:50], x[50:]]  # 10 / 40 / 280 rows
    cs, ws = [], []
    for part in parts:
        c, w, _, _ = shadow_select_host(part, eps)
        cs.append(c)
        ws.append(w)
    all_c = jnp.asarray(np.concatenate(cs))
    all_w = jnp.asarray(np.concatenate(ws), jnp.float32)
    out_c, out_w, m = two_level_merge(all_c, all_w, jnp.float32(eps),
                                      max_centers=len(all_c))
    m = int(m)
    assert abs(float(out_w[:m].sum()) - len(x)) < 1e-3
    assert (np.asarray(out_w[:m]) > 0).all()
    d = np.linalg.norm(x[:, None] - np.asarray(out_c[:m])[None], axis=2).min(1)
    assert (d < 2 * eps + 1e-5).all()


def test_blocked_whole_block_absorbed_in_one_round():
    """eps larger than the data diameter: the first round's single keeper
    absorbs every row (no survivors for later rounds)."""
    rng = np.random.default_rng(0)
    x = (0.01 * rng.normal(size=(150, 3))).astype(np.float32)
    c, w, a, m = shadow_select_blocked(x, 10.0, block=64)
    assert m == 1 and w.sum() == 150 and (a == 0).all()


def test_blocked_weighted_masses_conserved():
    """The weighted variant (the streaming merge's level-2 selector): unit
    masses reduce to the unweighted selector bit-exactly; arbitrary masses
    keep the SAME centers/assignment and partition sum(masses)."""
    x = _data(300, 5, 21)
    masses = np.random.default_rng(0).integers(1, 9, 300).astype(np.float32)
    for eps in (0.1, 0.3):
        c_u, w_u, a_u, m_u = shadow_select_blocked(x, eps, block=32)
        c_1, w_1, a_1, m_1 = shadow_select_blocked(
            x, eps, block=32, weights=np.ones(300, np.float32))
        assert m_1 == m_u and (a_1 == a_u).all()
        np.testing.assert_array_equal(c_1, c_u)
        np.testing.assert_allclose(w_1, w_u)
        c_m, w_m, a_m, m_m = shadow_select_blocked(x, eps, block=32,
                                                   weights=masses)
        assert m_m == m_u and (a_m == a_u).all()
        np.testing.assert_array_equal(c_m, c_u)
        assert w_m.sum() == masses.sum()
        ref = np.zeros(m_m)
        np.add.at(ref, a_m, masses)  # mass really lands on the absorber
        np.testing.assert_allclose(w_m, ref)


def test_streaming_budget_caps_centers():
    """``budget`` makes m deterministic: over-budget candidates spill
    weight-exactly into the nearest retained center."""
    x = _data(500, 4, 13)
    c, w, a, m = shadow_select_streaming(x, 0.05, chunk=128, block=16,
                                         budget=32)
    assert m == 32 and c.shape[0] == 32
    assert w.sum() == 500.0  # exact (f64 mass bookkeeping)
    assert (a >= 0).all() and (a < 32).all()
    c2, w2, _, m2 = shadow_select_streaming(x, 0.05, chunk=128, block=16)
    assert m2 > 32  # the budget really was binding


def test_max_centers_overflow_guard():
    x = _data(100, 4, 11)
    c, w, a, m = (None,) * 4
    import jax
    from repro.core.shadow import shadow_select
    c, w, a, m = jax.jit(
        lambda x: shadow_select(x, 1e-9, max_centers=10))(jnp.asarray(x))
    assert int(m) == 10 and float(w.sum()) == 100  # absorbed remainder


def _blocked_np(x, eps, block):
    """Blocked Algorithm 2 in numpy with the batch pruned by an in-order
    scan: each round's candidates are the first ``block`` alive rows, kept
    when >= eps from every kept candidate before them."""
    n = x.shape[0]
    alive = np.ones(n, bool)
    centers, weights = [], []
    while alive.any():
        cand = np.flatnonzero(alive)[:block]
        keep = []
        for j in cand:
            if all(np.sum((x[j] - x[i]) ** 2) >= eps * eps for i in keep):
                keep.append(j)
        d2 = ((x[:, None, :] - x[keep][None, :, :]) ** 2).sum(-1)
        near, dmin = d2.argmin(1), d2.min(1)
        absorbed = alive & (dmin < eps * eps)
        for r, j in enumerate(keep):
            centers.append(x[j])
            weights.append(int(np.sum(absorbed & (near == r))))
        alive &= ~absorbed
    return np.asarray(centers), np.asarray(weights, np.float64)


@pytest.mark.parametrize("eps", [0.15, 0.4])
def test_blocked_batch_pick_matches_an_in_order_scan(eps):
    """Dense batches (many candidates within eps of one another) keep
    exactly the candidates an in-order scan keeps."""
    x = _data(600, 3, 21)
    c, w, _, m = shadow_select_blocked(x, eps, block=128)
    c_ref, w_ref = _blocked_np(x, eps, 128)
    assert m == len(w_ref)
    np.testing.assert_array_equal(c[:m], c_ref)
    np.testing.assert_array_equal(w[:m], w_ref)
