"""Matrix-free fit: gram_matvec parity, LOBPCG eigenpair property tests,
the capacity-bucketed fit tail (fit_centers), and the donation (no-copy)
contracts (DESIGN.md §6)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

MV_SHAPES = [(64, 16, 8, 4), (100, 37, 24, 8), (513, 129, 16, 5),
             (256, 250, 96, 1)]  # incl. ragged (non-pow2, non-128-mult) m


@pytest.mark.parametrize("n,m,d,r", MV_SHAPES)
@pytest.mark.parametrize("p", [2, 1])
def test_gram_matvec_parity_f32(n, m, d, r, p):
    """gram_matvec == weighted_gram(...) @ V for every plan, f32."""
    rng = np.random.default_rng(hash((n, m, d, r, p)) % 2**32)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.normal(size=(m, d)).astype(np.float32)
    v = rng.normal(size=(m, r)).astype(np.float32)
    wx = rng.uniform(0.5, 3, n).astype(np.float32)
    wy = rng.uniform(0.5, 3, m).astype(np.float32)
    want = np.asarray(ref.gram_ref(jnp.asarray(x), jnp.asarray(y), 2.5, p,
                                   jnp.asarray(wx), jnp.asarray(wy))) @ v
    for plan in ("pallas", "pallas_fat", "dense"):
        got = np.asarray(ops.gram_matvec(x, y, v, sigma=2.5, p=p, wx=wx,
                                         wy=wy, plan=plan))
        np.testing.assert_allclose(got, want, atol=5e-4, rtol=5e-4,
                                   err_msg=plan)


@pytest.mark.parametrize("p", [2, 1])
def test_gram_matvec_parity_unweighted(p):
    """Unweighted ragged m: the zero v-row padding must make padded centers
    contribute exactly nothing (k(x, 0-pad) != 0 for the Gaussian!)."""
    rng = np.random.default_rng(7)
    x = rng.normal(size=(130, 24)).astype(np.float32)
    y = rng.normal(size=(37, 24)).astype(np.float32)  # pads up to 128 rows
    v = rng.normal(size=(37, 5)).astype(np.float32)
    want = np.asarray(ref.gram_ref(jnp.asarray(x), jnp.asarray(y), 1.5, p)) @ v
    for plan in ("pallas", "pallas_fat", "dense"):
        got = np.asarray(ops.gram_matvec(x, y, v, sigma=1.5, p=p, plan=plan))
        np.testing.assert_allclose(got, want, atol=5e-4, rtol=5e-4,
                                   err_msg=plan)


def test_gram_matvec_bf16_tolerance():
    """bf16 operands, f32 accumulation: same tolerance class as the bf16
    Gram (tests/test_precision.py)."""
    rng = np.random.default_rng(3)
    c = rng.normal(size=(200, 32)).astype(np.float32)
    w = rng.uniform(0.5, 3, 200).astype(np.float32)
    v = rng.normal(size=(200, 8)).astype(np.float32)
    want = np.asarray(ref.gram_ref(jnp.asarray(c), jnp.asarray(c), 2.0, 2,
                                   jnp.asarray(w), jnp.asarray(w))) @ v
    got = np.asarray(ops.weighted_gram_matvec(c, w, v, sigma=2.0,
                                              precision="bf16",
                                              plan="pallas"))
    assert np.abs(got - want).max() <= 3e-2 * np.abs(want).max()


def test_gram_matvec_zero_weight_rows_are_inert():
    """Zero-weight centers (the fit path's capacity padding) must not move
    the matvec: appending them changes nothing."""
    rng = np.random.default_rng(11)
    c = rng.normal(size=(90, 12)).astype(np.float32)
    w = rng.uniform(1, 5, 90).astype(np.float32)
    v = rng.normal(size=(90, 4)).astype(np.float32)
    cpad = np.concatenate([c, rng.normal(size=(38, 12)).astype(np.float32)])
    wpad = np.concatenate([w, np.zeros(38, np.float32)])
    vpad = np.concatenate([v, rng.normal(size=(38, 4)).astype(np.float32)])
    base = np.asarray(ops.gram_matvec(c, c, v, sigma=1.5, wx=w, wy=w,
                                      plan="pallas"))
    padded = np.asarray(ops.gram_matvec(cpad, cpad, vpad, sigma=1.5,
                                        wx=wpad, wy=wpad, plan="pallas"))
    # padded-out rows: sqrt(0) kills them; live rows match the unpadded run
    np.testing.assert_allclose(padded[:90], base, atol=5e-5, rtol=5e-5)
    np.testing.assert_allclose(padded[90:], 0.0, atol=5e-6)


@settings(max_examples=10, deadline=None)
@given(m=st.integers(min_value=60, max_value=220),
       rank=st.integers(min_value=2, max_value=6),
       seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_matvec_lobpcg_eigenpairs_match_dense_eigh(m, rank, seed):
    """Property: LOBPCG driven purely by gram_matvec recovers the top-r
    eigenpairs of the dense weighted Gram (the matfree fit's soundness)."""
    from jax.experimental.sparse.linalg import lobpcg_standard

    rng = np.random.default_rng(seed)
    d = 8
    c = rng.normal(size=(m, d)).astype(np.float32) * 2.0
    w = rng.uniform(0.5, 4, m).astype(np.float32)
    n = float(w.sum())
    kt = np.asarray(ref.gram_ref(jnp.asarray(c), jnp.asarray(c), 1.5, 2,
                                 jnp.asarray(w), jnp.asarray(w))) / n
    lam_ref = np.linalg.eigvalsh(kt)[::-1][:rank]

    def matvec(v):
        return ops.gram_matvec(c, c, v, sigma=1.5, p=2, wx=w, wy=w,
                               plan="pallas") / np.float32(n)

    x0 = jax.random.normal(jax.random.PRNGKey(0), (m, rank), jnp.float32)
    lam, u, _ = lobpcg_standard(matvec, x0, m=100)
    lam, u = np.asarray(lam), np.asarray(u)
    np.testing.assert_allclose(lam, lam_ref, rtol=5e-3, atol=1e-5)
    # eigenpair residual of the MATVEC operator (not just the values)
    resid = kt @ u - u * lam[None, :]
    assert np.linalg.norm(resid) <= 1e-3 * max(1.0, np.linalg.norm(lam))


def test_matfree_fit_matches_materialized(monkeypatch):
    """fit_rskpca(matfree=True) == the materialized path: eigvals and the
    aligned embedding, at a small m where both are cheap."""
    from repro.core import (gaussian, shadow_rsde, fit_rskpca,
                            embedding_alignment_error)
    from repro.data import make_dataset

    x, _, sigma = make_dataset("german", seed=0, n=400)
    ker = gaussian(sigma)
    rsde = shadow_rsde(x, ker, 3.0)
    dense = fit_rskpca(rsde, ker, 5)
    mf = fit_rskpca(rsde, ker, 5, matfree=True)
    np.testing.assert_allclose(mf.eigvals, dense.eigvals, rtol=1e-3)
    q = x[:80]
    ref_z = dense.transform(q)
    err = embedding_alignment_error(ref_z, mf.transform(q))
    assert err <= 1e-3 * np.linalg.norm(ref_z)


def test_matfree_crossover_policy(monkeypatch):
    """Default policy: materialized below the bytes budget (bit-identical
    contract), matrix-free above it; env overrides force the threshold."""
    monkeypatch.delenv("REPRO_MATFREE_MIN_M", raising=False)
    monkeypatch.delenv("REPRO_GRAM_BYTES_BUDGET", raising=False)
    assert not ops.matfree_fit(4096)   # 64 MB Gram: stays materialized
    assert ops.matfree_fit(8192)       # 256 MB Gram: goes matrix-free
    monkeypatch.setenv("REPRO_MATFREE_MIN_M", "100")
    assert ops.matfree_fit(100) and not ops.matfree_fit(99)
    monkeypatch.delenv("REPRO_MATFREE_MIN_M", raising=False)
    monkeypatch.setenv("REPRO_GRAM_BYTES_BUDGET", str(4 * 512 * 512))
    assert ops.matfree_fit(513) and not ops.matfree_fit(512)


def test_forced_matfree_with_unsound_rank_fails_loudly():
    """matfree=True where LOBPCG is unsound (5*rank >= m) must raise a
    clear error at the API boundary — never a cryptic solver failure, never
    a silent fall-back to the materialized Gram the caller forbade."""
    from repro.core import gaussian, fit_rskpca
    from repro.core.rsde import RSDE

    rng = np.random.default_rng(8)
    rsde = RSDE(rng.normal(size=(16, 4)).astype(np.float32),
                np.ones(16), n=64.0, scheme="bench")
    with pytest.raises(ValueError, match="5\\*rank < m"):
        fit_rskpca(rsde, gaussian(1.0), 4, matfree=True)


def test_inmem_fit_centers_matches_front_door():
    """The in-memory fit's pair, shadow_rsde then the capacity-bucketed
    fit_centers, gives the same center set and an equivalent model as the
    front door fit(method="shadow")."""
    from repro.core import (gaussian, fit, fit_centers, shadow_rsde,
                            embedding_alignment_error)
    from repro.data import make_dataset

    x, _, sigma = make_dataset("german", seed=0, n=400)
    ker = gaussian(sigma)
    rsde = shadow_rsde(x, ker, 6.0)
    pair = fit_centers(rsde.centers, rsde.weights, rsde.n, ker, 4)
    front = fit(x, ker, 4, method="shadow", ell=6.0)
    assert pair.m == front.m == rsde.m
    q = x[:100]
    ref_z = front.transform(q)
    err = embedding_alignment_error(ref_z, pair.transform(q))
    assert err <= 1e-3 * np.linalg.norm(ref_z)


def test_fit_centers_full_capacity_alias_survives():
    """Regression: a device buffer whose rows equal its capacity bucket
    (128) is the cap slice ITSELF (jax full-slice fast path), and with
    rank == d XLA aliases the donated buffer into the projector output —
    the model's centers must be materialized BEFORE that donation.  This
    is the merge state ingest_fit hands over."""
    import jax.numpy as jnp
    from repro.core import gaussian, fit_centers, shadow_rsde

    rng = np.random.default_rng(5)
    x = rng.normal(size=(128, 4)).astype(np.float32)
    ker = gaussian(1.0)
    rsde = shadow_rsde(x, ker, 4.0)
    c = jnp.zeros((128, 4), jnp.float32).at[:rsde.m].set(rsde.centers)
    w = jnp.zeros((128,), jnp.float32).at[:rsde.m].set(rsde.weights)
    mdl = fit_centers(c, w, 128, ker, 4, m=rsde.m)
    assert mdl.centers.shape[1] == 4 and mdl.m >= 1
    np.testing.assert_array_equal(mdl.centers, rsde.centers)
    assert np.isfinite(mdl.transform(x[:16])).all()


def test_fit_centers_matfree_end_to_end():
    """One shadow_rsde fitted through fit_centers twice: the matrix-free
    eigensolve (forced) against the default materialized one."""
    from repro.core import (gaussian, fit_centers, shadow_rsde,
                            embedding_alignment_error)
    from repro.data import make_dataset

    x, _, sigma = make_dataset("german", seed=1, n=400)
    ker = gaussian(sigma)
    rsde = shadow_rsde(x, ker, 5.0)
    mf = fit_centers(rsde.centers, rsde.weights, rsde.n, ker, 4,
                     matfree=True)
    base = fit_centers(rsde.centers, rsde.weights, rsde.n, ker, 4)
    q = x[:100]
    ref_z = base.transform(q)
    err = embedding_alignment_error(ref_z, mf.transform(q))
    assert err <= 1e-2 * np.linalg.norm(ref_z)


def test_sharded_matfree_matches_single_device():
    """Row-tile-distributed matvec LOBPCG == single-device matfree fit
    (1-device mesh in-process; the 8-device variant runs in
    tests/test_sharded.py's subprocess harness)."""
    from repro.launch.mesh import make_mesh
    from repro.core import gaussian
    from repro.core.distributed import fit_rskpca_sharded
    from repro.core.rskpca import _fit_rskpca_device

    rng = np.random.default_rng(2)
    c = rng.normal(size=(160, 12)).astype(np.float32)
    w = rng.uniform(1, 6, 160).astype(np.float32)
    n = float(w.sum())
    ker = gaussian(1.5)
    mesh = make_mesh((1,), ("data",))
    lam_s, proj_s = fit_rskpca_sharded(c, w, n, ker, 4, mesh,
                                       lobpcg_min_m=64, matfree=True)
    # both fits solve at the capacity bucket of m (zero-weight rows), as
    # pipeline.fit_centers does: LOBPCG then starts from the same block
    cp = np.zeros((256, 12), np.float32)
    cp[:160] = c
    wp = np.zeros((256,), np.float32)
    wp[:160] = w
    lam_1, proj_1, _ = _fit_rskpca_device(jnp.asarray(cp), jnp.asarray(wp),
                                          jnp.float32(n), ker, 4,
                                          matfree=True)
    np.testing.assert_allclose(np.asarray(lam_s), np.asarray(lam_1),
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(proj_s), np.asarray(proj_1)[:160],
                               atol=1e-4, rtol=1e-3)


def test_streaming_solve_reuses_cached_gram_operator():
    """Above the crossover the streaming re-solve must run LOBPCG straight
    off the cached unweighted kgram (weights folded into the matvec) and
    agree with the materialized small-cap solve."""
    from repro.streaming.state import _solve

    rng = np.random.default_rng(4)
    cap = 256
    c = rng.normal(size=(cap, 10)).astype(np.float32)
    w = np.zeros(cap, np.float32)
    w[:200] = rng.uniform(1, 5, 200).astype(np.float32)  # dead tail slots
    kgram = np.asarray(ref.gram_ref(jnp.asarray(c), jnp.asarray(c), 1.5, 2))
    n = jnp.float32(w.sum())
    lam_mat, u_mat = _solve(jnp.asarray(kgram), jnp.asarray(w), n, 5,
                            min_m=10**9)   # force the materialized branch
    lam_mf, u_mf = _solve(jnp.asarray(kgram), jnp.asarray(w), n, 5,
                          min_m=32)        # force the matvec-reuse branch
    np.testing.assert_allclose(np.asarray(lam_mf), np.asarray(lam_mat),
                               rtol=1e-4)
    np.testing.assert_allclose(np.abs(np.asarray(u_mf)),
                               np.abs(np.asarray(u_mat)), atol=1e-3)


# --------------------------------------------------------------------------
# donation (no-copy) contracts
# --------------------------------------------------------------------------


def test_fit_donates_and_aliases_center_buffer():
    """With d == rank the projector output matches the donated center
    buffer's shape, so XLA aliases it in place: the input buffer must be
    CONSUMED (deleted) — the asserted no-copy contract."""
    from repro.core import gaussian
    from repro.core.rskpca import _fit_rskpca_device

    rng = np.random.default_rng(0)
    ker = gaussian(1.0)
    c = jnp.asarray(rng.normal(size=(256, 8)).astype(np.float32))
    w = jnp.asarray(rng.uniform(1, 5, 256).astype(np.float32))
    lam, proj, _ = _fit_rskpca_device(c, w, jnp.float32(1000.0), ker, 8)
    jax.block_until_ready(proj)
    assert c.is_deleted(), "donated center buffer was copied, not aliased"
    assert np.isfinite(np.asarray(proj)).all()


def test_fit_rskpca_survives_device_resident_rsde():
    """Regression: an RSDE already holding jax f32 arrays must not be
    consumed by the donating fit — jnp.asarray would alias the caller's
    buffers, so fit_rskpca builds its device operands from a host copy."""
    from repro.core import gaussian, fit_rskpca
    from repro.core.rsde import RSDE

    rng = np.random.default_rng(6)
    c = jnp.asarray(rng.normal(size=(96, 8)).astype(np.float32))
    w = jnp.asarray(rng.uniform(1, 5, 96).astype(np.float32))
    rsde = RSDE(centers=c, weights=w, n=500.0, scheme="bench")
    mdl = fit_rskpca(rsde, gaussian(1.0), 8)  # rank == d: alias-capable
    assert not c.is_deleted() and not w.is_deleted()
    np.testing.assert_allclose(np.asarray(c), mdl.centers, atol=0)
    assert np.isfinite(mdl.transform(np.asarray(c[:10]))).all()


def test_transform_never_consumes_caller_buffer():
    """kpca_project donates its internal padded chunk, but a caller-owned
    device array — even one whose shape could alias — must survive."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(256, 128)).astype(np.float32))
    c = rng.normal(size=(64, 128)).astype(np.float32)
    a = rng.normal(size=(64, 128)).astype(np.float32)
    z = ops.kpca_project(x, c, a, sigma=1.0, plan="pallas")
    jax.block_until_ready(z)
    assert not x.is_deleted()
    # and the result still matches the oracle
    want = np.asarray(ref.kpca_project_ref(x, jnp.asarray(c), jnp.asarray(a),
                                           1.0, 2))
    np.testing.assert_allclose(np.asarray(z), want, atol=5e-4, rtol=5e-4)
