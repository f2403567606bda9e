"""Method zoo (ISSUE 8): nystrom / wnystrom / rff on the optimized stack.

Parity (Pallas vs dense, f32 and bf16), RFF spectral convergence (hypothesis
property), sharded-fit equivalence per method, stream-vs-resident
equivalence, the fit() front door dispatch, and the measured-Pareto method
selector."""
import json
import os

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

import repro.core as core
from repro.core import gaussian, laplacian
from repro.core.nystrom import _landmark_eigs_matfree
from repro.core.ingest_pipeline import pad_block
from repro.core.random_features import sample_rff
from repro.data import make_dataset
from repro.launch.mesh import data_mesh


@pytest.fixture(scope="module")
def data():
    x, y, sigma = make_dataset("pendigits", seed=0, n=600)
    return np.asarray(x, np.float32), gaussian(sigma)


def _chunks(x, rows=256):
    for s in range(0, len(x), rows):
        xb, ok = pad_block(x[s : s + rows], rows)
        yield xb, int(ok.sum())


# ---------------------------------------------------------------- parity


def test_nystrom_pallas_dense_parity_f32(data):
    x, ker = data
    a = core.fit_nystrom(x, ker, 5, 96, seed=3)
    b = core.fit_nystrom(x, ker.with_backend("dense"), 5, 96, seed=3)
    # jax.random landmarks are backend-independent -> same subproblem
    np.testing.assert_allclose(a.eigvals, b.eigvals, rtol=1e-4)
    np.testing.assert_allclose(a.projector, b.projector, atol=1e-5)
    np.testing.assert_allclose(a.transform(x[:64]), b.transform(x[:64]),
                               atol=1e-4)


def test_nystrom_bf16_close_to_f32(data):
    x, ker = data
    a = core.fit_nystrom(x, ker, 5, 96, seed=3)
    c = core.fit_nystrom(x, ker.with_precision("bf16"), 5, 96, seed=3)
    # bf16 operands, f32 accumulation: same eigensystem to ~1e-2
    np.testing.assert_allclose(c.eigvals, a.eigvals, rtol=5e-2)
    scale = np.abs(a.projector).max()
    assert np.abs(c.projector - a.projector).max() < 5e-2 * scale


def test_nystrom_keeps_full_data_and_chunking_invariance(data):
    x, ker = data
    a = core.fit_nystrom(x, ker, 5, 80)
    assert a.centers.shape[0] == len(x)          # O(n) storage — the point
    b = core.fit_nystrom(x, ker, 5, 80, rows=128)
    np.testing.assert_allclose(b.projector, a.projector, atol=1e-5)


def test_wnystrom_pallas_dense_parity(data):
    x, ker = data
    a = core.fit_weighted_nystrom(x, ker, 5, 64, seed=1)
    b = core.fit_weighted_nystrom(x, ker.with_backend("dense"), 5, 64,
                                  seed=1)
    assert a.method == b.method == "wnystrom"
    assert a.centers.shape == (64, x.shape[1])
    np.testing.assert_allclose(a.eigvals, b.eigvals, rtol=1e-3)
    np.testing.assert_allclose(np.abs(a.projector), np.abs(b.projector),
                               atol=1e-4)


def test_rff_pallas_dense_parity(data):
    x, ker = data
    a = core.fit_rff(x, ker, 5, n_features=256, seed=0)
    b = core.fit_rff(x, ker.with_backend("dense"), 5, n_features=256, seed=0)
    # the fit is backend-independent (chunked covariance); the transform
    # runs the fused Pallas kernel vs the jnp oracle
    np.testing.assert_allclose(a.projector, b.projector, atol=1e-5)
    np.testing.assert_allclose(a.transform(x[:100]), b.transform(x[:100]),
                               atol=1e-4)


def test_rff_bf16_close_to_f32(data):
    x, ker = data
    a = core.fit_rff(x, ker, 5, n_features=256, seed=0)
    c = core.fit_rff(x, ker.with_precision("bf16"), 5, n_features=256,
                     seed=0)
    za, zc = a.transform(x[:100]), c.transform(x[:100])
    assert np.abs(za - zc).max() < 5e-2 * max(np.abs(za).max(), 1e-6)


# ------------------------------------------------------------ rff math


def test_rff_gram_approximates_kernel(data):
    x, ker = data
    q = x[:32]
    omega, phase = sample_rff(ker, q.shape[1], 4096, seed=0)
    feat = np.sqrt(2.0 / 4096) * np.cos(q @ omega.T + phase[None, :])
    from repro.core.kernels_math import gram_matrix
    k_true = np.asarray(gram_matrix(ker.with_backend("dense"), q, q))
    assert np.abs(feat @ feat.T - k_true).max() < 0.08


def test_rff_laplacian_spectral_measure(data):
    x, _ = data
    ker = laplacian(2.0)
    mdl = core.fit_rff(x, ker, 4, n_features=512, seed=1)
    z = mdl.transform(x[:50])
    assert z.shape == (50, 4) and np.isfinite(z).all()
    q = x[:24]
    omega, phase = sample_rff(ker, q.shape[1], 8192, seed=0)
    feat = np.sqrt(2.0 / 8192) * np.cos(q @ omega.T + phase[None, :])
    from repro.core.kernels_math import gram_matrix
    k_true = np.asarray(gram_matrix(ker.with_backend("dense"), q, q))
    # Cauchy spectral draws are heavy-tailed: looser tolerance than Gaussian
    assert np.abs(feat @ feat.T - k_true).max() < 0.2


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(min_value=0, max_value=50))
def test_rff_eigenvalues_converge_with_features(seed):
    """Property: the RFF eigenvalue error vs exact KPCA shrinks (weakly) as
    D grows — D=2048 must not be worse than D=128 beyond noise."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(256, 8)).astype(np.float32)
    ker = gaussian(2.0)
    lam_ref = core.fit_kpca(x, ker, 4).eigvals
    errs = {}
    for nfeat in (128, 2048):
        lam = core.fit_rff(x, ker, 4, n_features=nfeat, seed=seed).eigvals
        errs[nfeat] = float(np.linalg.norm(lam - lam_ref))
    tol = 0.1 * float(np.linalg.norm(lam_ref))
    assert errs[2048] <= errs[128] + tol, (errs, seed)


# ------------------------------------------------------- sharded parity


def test_sharded_equivalence_per_method(data):
    x, ker = data
    mesh = data_mesh(1)
    for fitter in (
        lambda **kw: core.fit_nystrom(x, ker, 5, 96, seed=2, **kw),
        lambda **kw: core.fit_weighted_nystrom(x, ker, 5, 64, seed=1, **kw),
        lambda **kw: core.fit_rff(x, ker, 5, n_features=256, seed=0, **kw),
    ):
        a, b = fitter(), fitter(mesh=mesh)
        np.testing.assert_allclose(b.eigvals, a.eigvals, rtol=1e-4)
        np.testing.assert_allclose(np.abs(b.projector),
                                   np.abs(a.projector), atol=1e-4)


def test_sharded_rff_transform_matches(data):
    x, ker = data
    mesh = data_mesh(1)
    mdl = core.fit_rff(x, ker, 5, n_features=256, seed=0)
    np.testing.assert_allclose(mdl.transform(x[:200], mesh=mesh),
                               mdl.transform(x[:200]), atol=1e-5)


# ------------------------------------------------------ streaming fits


def test_nystrom_stream_equals_resident(data):
    x, ker = data
    a = core.fit_nystrom(x, ker, 5, 96, seed=2)
    b, stats = core.fit_nystrom_stream(_chunks(x), ker, 5, 96, seed=2)
    # same jax.random landmark draw over the same n -> identical fit
    np.testing.assert_allclose(b.projector, a.projector, atol=1e-6)
    assert stats.rows == len(x) and stats.m == 96


def test_rff_stream_equals_resident(data):
    x, ker = data
    a = core.fit_rff(x, ker, 5, n_features=256, seed=0, chunk=256)
    b, stats = core.fit_rff_stream(_chunks(x), ker, 5, n_features=256,
                                   seed=0)
    np.testing.assert_allclose(b.projector, a.projector, atol=1e-5)
    assert stats.rows == len(x) and stats.m == 256


def test_kmeans_rsde_stream_weights_sum_to_n(data):
    x, ker = data
    rsde, stats = core.kmeans_rsde_stream(_chunks(x), ker, 48, seed=0)
    assert rsde.centers.shape == (48, x.shape[1])
    assert rsde.weights.sum() == pytest.approx(len(x))
    assert rsde.n == len(x) == stats.rows
    assert np.isfinite(rsde.centers).all()


def test_fit_stream_front_door_all_methods(data):
    x, ker = data
    for method, kw in (("nystrom", dict(m=96)), ("wnystrom", dict(m=48)),
                       ("rff", dict(m=128)), ("shadow", dict(ell=4.0))):
        mdl, stats = core.fit_stream(_chunks(x), ker, 5, method=method, **kw)
        assert stats.rows == len(x)
        z = mdl.transform(x[:32])
        assert z.shape == (32, 5) and np.isfinite(z).all()
    with pytest.raises(ValueError):
        core.fit_stream(_chunks(x), ker, 5, method="nope")


# ------------------------------------------------- dispatch + selector


def test_fit_front_door_dispatch(data):
    x, ker = data
    for method, kw, mcls in (
        ("nystrom", dict(m=96), core.KPCAModel),
        ("wnystrom", dict(m=48), core.KPCAModel),
        ("rff", dict(m=128), core.RFFKPCAModel),
    ):
        mdl = core.fit(x, ker, 5, method=method, **kw)
        assert mdl.method == method and isinstance(mdl, mcls)
        assert mdl.projector.shape[1] == 5


def test_fit_auto_uses_measured_rows(data, tmp_path, monkeypatch):
    x, ker = data
    rows = [
        dict(mode="methods", n=600, method="rff", fit_s=0.1, knn_acc=0.95,
             model_bytes=1000),
        dict(mode="methods", n=600, method="nystrom", fit_s=1.0,
             knn_acc=0.95, model_bytes=100000),   # dominated by rff
        dict(mode="methods", n=600, method="wnystrom", fit_s=0.5,
             knn_acc=0.99, model_bytes=2000),
    ]
    p = tmp_path / "bench.json"
    p.write_text(json.dumps({"rows": rows}))
    monkeypatch.setenv("REPRO_BENCH_JSON", str(p))
    assert core.select_method(600, 16, 5, objective="accuracy") == "wnystrom"
    assert core.select_method(600, 16, 5, objective="memory") == "rff"
    # the dominated method never wins under any objective
    for obj in ("balanced", "accuracy", "speed", "memory"):
        assert core.select_method(600, 16, 5, objective=obj) != "nystrom"
    mdl = core.fit(x, ker, 5, method="auto", m=64, objective="memory")
    assert mdl.method == "rff"


def test_select_method_heuristic_without_rows(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_BENCH_JSON", str(tmp_path / "missing.json"))
    assert core.select_method(600, 16, 5) in core.METHODS
    assert core.select_method(600, 16, 5, objective="memory") == "rff"
    with pytest.raises(ValueError):
        core.select_method(600, 16, 5, objective="nope")


def test_methods_registry_cost_models():
    assert set(core.METHODS) == {"shadow", "nystrom", "wnystrom", "rff"}
    for spec in core.METHODS.values():
        assert spec.train and spec.test and spec.space


# ----------------------------------------------------- determinism + structure


def test_landmarks_deterministic_across_calls(data):
    x, ker = data
    a = core.fit_nystrom(x, ker, 5, 64, seed=7)
    b = core.fit_nystrom(x, ker, 5, 64, seed=7)
    np.testing.assert_array_equal(a.projector, b.projector)
    c = core.fit_nystrom(x, ker, 5, 64, seed=8)
    assert np.abs(a.projector - c.projector).max() > 0


def test_matfree_landmark_eigensolve_no_mxm_buffer(data):
    """PR-5 style structural check: the matrix-free landmark eigensolve
    lowers with no m x m tensor in the HLO."""
    import jax.numpy as jnp
    x, ker = data
    # m must dodge the Pallas tile extents (512/128): a (512, 512) VMEM
    # tile is legal and would false-positive the string match
    m = 768
    lowered = _landmark_eigs_matfree.lower(
        jnp.concatenate([jnp.asarray(x), jnp.asarray(x[:168])]), ker, 5)
    assert f"{m}x{m}" not in lowered.as_text()
