"""RSKPCA (Algorithm 1) correctness + baselines."""
import numpy as np
import pytest

from repro.core import (
    RSDE, gaussian, laplacian, shadow_rsde, fit_rskpca, fit_kpca,
    fit_subsampled_kpca, fit_nystrom, fit_weighted_nystrom, fit,
    embedding_alignment_error, make_rsde, pad_block, select_streaming,
    shadow_select_blocked, shadow_select_host,
    reduced_laplacian_eigenmaps, reduced_diffusion_maps,
)
from repro.data import make_dataset


@pytest.fixture(scope="module")
def data():
    x, y, sigma = make_dataset("german", seed=0, n=400)
    return x, y, sigma


def test_limit_equals_kpca(data):
    """ell -> inf: every point its own center, RSKPCA == KPCA exactly."""
    x, _, sigma = data
    x = x[:150]
    ker = gaussian(sigma)
    rsde = shadow_rsde(x, ker, ell=1e9)
    assert rsde.m == len(x) and (rsde.weights == 1).all()
    rs = fit_rskpca(rsde, ker, rank=5)
    kp = fit_kpca(x, ker, rank=5)
    np.testing.assert_allclose(rs.eigvals, kp.eigvals, rtol=1e-4)
    q = x[:40]
    err = embedding_alignment_error(kp.transform(q), rs.transform(q))
    assert err <= 1e-3 * np.linalg.norm(kp.transform(q))


def test_rskpca_approaches_kpca_as_ell_grows(data):
    x, _, sigma = data
    ker = gaussian(sigma)
    kp = fit_kpca(x, ker, rank=5)
    ref = kp.transform(x[:100])
    errs = []
    for ell in (2.0, 4.0, 8.0, 16.0):
        mdl = fit_rskpca(shadow_rsde(x, ker, ell), ker, rank=5)
        errs.append(embedding_alignment_error(ref, mdl.transform(x[:100])))
    assert errs[-1] < errs[0]  # error shrinks with finer cover
    assert errs[-1] < 0.1 * np.linalg.norm(ref)


def test_weights_matter_rskpca_beats_uniform(data):
    """Paper §6: subsampled KPCA performs worse than any weighted method."""
    x, _, sigma = data
    ker = gaussian(sigma)
    kp = fit_kpca(x, ker, rank=5)
    ref = kp.transform(x[:100])
    errs_sh, errs_un = [], []
    for seed in range(3):
        rsde = shadow_rsde(x, ker, 3.5)
        sh = fit_rskpca(rsde, ker, rank=5)
        un = fit_subsampled_kpca(x, ker, rank=5, m=rsde.m, seed=seed)
        errs_sh.append(embedding_alignment_error(ref, sh.transform(x[:100])))
        errs_un.append(embedding_alignment_error(ref, un.transform(x[:100])))
    assert np.mean(errs_sh) < np.mean(errs_un)


def test_nystrom_variants(data):
    x, _, sigma = data
    ker = gaussian(sigma)
    kp = fit_kpca(x, ker, rank=5)
    ref = kp.transform(x[:80])
    ny = fit_nystrom(x, ker, rank=5, m=80)
    wy = fit_weighted_nystrom(x, ker, rank=5, m=80)
    for mdl, max_rel in ((ny, 0.8), (wy, 0.8)):
        err = embedding_alignment_error(ref, mdl.transform(x[:80]))
        assert err < max_rel * np.linalg.norm(ref), mdl.method
    # storage asymmetry (paper Table 2): Nystrom keeps all n, RSKPCA keeps m
    assert ny.centers.shape[0] == len(x)
    assert wy.centers.shape[0] == 80


def test_front_door_and_schemes(data):
    x, _, sigma = data
    ker = gaussian(sigma)
    for method, kw in [("kpca", {}), ("shadow", dict(ell=4.0)),
                       ("uniform", dict(m=40)), ("kmeans", dict(m=40)),
                       ("paring", dict(m=40)), ("herding", dict(m=40))]:
        mdl = fit(x[:200], ker, 4, method=method, **kw)
        z = mdl.transform(x[:10])
        assert z.shape == (10, 4) and np.isfinite(z).all(), method


def test_backend_switch_parity(data):
    """fit(..., backend=...) must give numerically matching models, and the
    backend must propagate to the returned model's transform path."""
    x, _, sigma = data
    ker = gaussian(sigma)
    mp = fit(x, ker, 5, method="shadow", ell=3.0, backend="pallas")
    md = fit(x, ker, 5, method="shadow", ell=3.0, backend="dense")
    assert mp.kernel.backend == "pallas" and md.kernel.backend == "dense"
    np.testing.assert_allclose(mp.eigvals, md.eigvals, rtol=1e-4)
    q = x[:64]
    np.testing.assert_allclose(mp.transform(q), md.transform(q),
                               atol=1e-4, rtol=1e-3)


def test_selector_variants_fit_equivalently(data):
    """The sequential scan, the blocked cascade and the chunk stream (over
    128-row chunks of x) each feed fit_rskpca a usable center set, with
    comparable embedding quality."""
    x, _, sigma = data
    ker = gaussian(sigma)
    eps = ker.epsilon(6.0)
    ref = fit_kpca(x, ker, rank=4).transform(x[:100])

    def rsde_of(select):
        c, w, a, _ = select(x, eps)
        return RSDE(c, w, n=len(x), assign=a)

    chunks = [(pad_block(x[s:s + 128], 128)[0], min(128, len(x) - s))
              for s in range(0, len(x), 128)]
    rsdes = {"sequential": rsde_of(shadow_select_host),
             "blocked": rsde_of(shadow_select_blocked),
             "streaming": select_streaming(chunks, eps)[0]}
    errs = {}
    for sel, rsde in rsdes.items():
        mdl = fit_rskpca(rsde, ker, 4)
        errs[sel] = embedding_alignment_error(ref, mdl.transform(x[:100]))
    scale = np.linalg.norm(ref)
    assert all(e < 0.5 * scale for e in errs.values()), errs


def test_top_eigh_lobpcg_branch_matches_eigh():
    """The large-m LOBPCG path (unreachable from the small fixtures) must
    agree with exact eigh on a kernel-shaped spectrum."""
    import jax.numpy as jnp
    from repro.core.rskpca import _top_eigh, _LOBPCG_MIN_M

    m = _LOBPCG_MIN_M + 150
    rng = np.random.default_rng(0)
    q, _ = np.linalg.qr(rng.normal(size=(m, 40)))
    lam_true = 2.0 ** -np.arange(40)  # fast-decaying, like a kernel spectrum
    mat = jnp.asarray((q * lam_true) @ q.T, jnp.float32)
    lam, vec, _ = _top_eigh(mat, 6)
    assert vec.shape == (m, 6)
    np.testing.assert_allclose(np.asarray(lam), lam_true[:6], rtol=5e-4)


def test_rank_exceeding_m_truncates_gracefully(data):
    """rank > m must truncate to m components on every eigensolver path
    (the CPU subset-eigh fast path regressed this once)."""
    x, _, sigma = data
    ker = gaussian(sigma)
    rsde = shadow_rsde(x[:60], ker, ell=1.5)  # coarse cover -> tiny m
    assert rsde.m < 10
    mdl = fit_rskpca(rsde, ker, rank=rsde.m + 4)
    assert mdl.rank == rsde.m
    assert np.isfinite(mdl.transform(x[:5])).all()


def test_laplacian_kernel_works(data):
    x, _, sigma = data
    ker = laplacian(sigma)
    mdl = fit(x[:200], ker, 4, method="shadow", ell=4.0)
    assert np.isfinite(mdl.transform(x[:10])).all()


def test_kmla_reduced_embeddings(data):
    x, _, sigma = data
    ker = gaussian(sigma)
    rsde = shadow_rsde(x[:300], ker, 4.0)
    le = reduced_laplacian_eigenmaps(rsde, ker, rank=3)
    dm = reduced_diffusion_maps(rsde, ker, rank=3)
    for mdl in (le, dm):
        assert mdl.embedding.shape == (rsde.m, 3)
        assert np.isfinite(mdl.embedding).all()
        assert (mdl.eigvals <= 1.0 + 1e-5).all()  # normalized operators
