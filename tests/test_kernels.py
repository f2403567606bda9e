"""Pallas kernel sweeps: shapes x dtypes x kernel families vs jnp oracles
(interpret=True executes the kernel bodies on CPU)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ops, ref

SHAPES = [(64, 16, 8), (100, 37, 24), (513, 129, 16), (256, 256, 256),
          (1000, 7, 96)]
#: ragged m spanning several center tiles of the kernels that sweep the
#: operator along a grid axis (ops.CENTER_TILE = 512)
MULTI_TILE_SHAPES = [(300, 1100, 16), (129, 1537, 40)]


@pytest.mark.parametrize("n,m,d", SHAPES)
@pytest.mark.parametrize("p", [2, 1])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_gram_sweep(n, m, d, p, dtype):
    rng = np.random.default_rng(hash((n, m, d, p)) % 2**32)
    x = rng.normal(size=(n, d)).astype(dtype)
    y = rng.normal(size=(m, d)).astype(dtype)
    wx = rng.uniform(0.5, 3, n).astype(np.float32)
    wy = rng.uniform(0.5, 3, m).astype(np.float32)
    got = np.asarray(ops.gram(x, y, sigma=2.5, p=p, wx=wx, wy=wy,
                              plan="pallas"))
    want = np.asarray(ref.gram_ref(jnp.asarray(x), jnp.asarray(y), 2.5, p,
                                   jnp.asarray(wx), jnp.asarray(wy)))
    tol = 2e-5 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


@pytest.mark.parametrize("n,m,d", SHAPES)
def test_gram_unweighted(n, m, d):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.normal(size=(m, d)).astype(np.float32)
    got = np.asarray(ops.gram(x, y, sigma=1.5, plan="pallas"))
    want = np.asarray(ref.gram_ref(jnp.asarray(x), jnp.asarray(y), 1.5, 2))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("m,d", [(64, 8), (100, 37), (513, 129)])
@pytest.mark.parametrize("p", [2, 1])
def test_gram_row_sweep(m, d, p):
    """Rank-one Gram-row kernel (the streaming update hot path): both plans
    must match the full-Gram oracle row and the raw squared distances."""
    rng = np.random.default_rng(hash((m, d, p)) % 2**32)
    x = rng.normal(size=(d,)).astype(np.float32)
    c = rng.normal(size=(m, d)).astype(np.float32)
    w = rng.uniform(0.5, 3, m).astype(np.float32)
    want_k = np.asarray(ref.gram_ref(jnp.asarray(x[None]), jnp.asarray(c),
                                     2.5, p))[0]
    want_d2 = ((c - x[None]) ** 2).sum(1)
    for plan in ("pallas", "dense"):
        krow, d2 = ops.gram_row(x, c, sigma=2.5, p=p, plan=plan)
        np.testing.assert_allclose(np.asarray(krow), want_k,
                                   atol=3e-5, rtol=3e-5)
        np.testing.assert_allclose(np.asarray(d2), want_d2,
                                   atol=1e-3, rtol=1e-4)
        # weighted form fuses Algorithm 1's sqrt(w) column factor
        krow_w, _ = ops.gram_row(x, c, w, sigma=2.5, p=p, plan=plan)
        np.testing.assert_allclose(np.asarray(krow_w), want_k * np.sqrt(w),
                                   atol=3e-5, rtol=3e-5)


def test_weighted_gram_is_algorithm1_ktilde():
    """ops.weighted_gram == W K^C W of Algorithm 1 (vs core implementation)."""
    from repro.core.kernels_math import weighted_gram as core_wg, gaussian
    rng = np.random.default_rng(3)
    c = rng.normal(size=(57, 12)).astype(np.float32)
    w = rng.uniform(1, 9, 57).astype(np.float32)
    got = np.asarray(ops.weighted_gram(c, w, sigma=2.0, plan="pallas"))
    want = np.asarray(core_wg(gaussian(2.0), jnp.asarray(c), jnp.asarray(w)))
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("n,m,d", SHAPES + MULTI_TILE_SHAPES)
def test_shadow_assign_sweep(n, m, d):
    rng = np.random.default_rng(hash((n, m)) % 2**32)
    x = rng.normal(size=(n, d)).astype(np.float32)
    c = rng.normal(size=(m, d)).astype(np.float32)
    idx, d2 = ops.shadow_assign(x, c, m, plan="pallas")
    idx_r, d2_r = ref.shadow_assign_ref(jnp.asarray(x), jnp.asarray(c), m)
    assert (np.asarray(idx) == np.asarray(idx_r)).all()
    np.testing.assert_allclose(np.asarray(d2), np.asarray(d2_r),
                               atol=1e-4, rtol=1e-4)


def test_shadow_assign_padding_mask():
    """Padded (invalid) centers must never win the argmin."""
    rng = np.random.default_rng(5)
    x = rng.normal(size=(100, 8)).astype(np.float32)
    c = np.concatenate([rng.normal(size=(5, 8)),
                        np.zeros((10, 8))]).astype(np.float32)
    idx, _ = ops.shadow_assign(x, c, m_valid=5, plan="pallas")
    assert (np.asarray(idx) < 5).all()


@pytest.mark.parametrize("n,m,d", SHAPES + MULTI_TILE_SHAPES)
@pytest.mark.parametrize("r", [1, 5, 8])
def test_kpca_project_sweep(n, m, d, r):
    rng = np.random.default_rng(hash((n, m, r)) % 2**32)
    x = rng.normal(size=(n, d)).astype(np.float32)
    c = rng.normal(size=(m, d)).astype(np.float32)
    a = rng.normal(size=(m, r)).astype(np.float32)
    got = np.asarray(ops.kpca_project(x, c, a, sigma=2.0, plan="pallas"))
    want = np.asarray(ref.kpca_project_ref(jnp.asarray(x), jnp.asarray(c),
                                           jnp.asarray(a), 2.0, 2))
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("precision", ["f32", "int8"])
def test_kpca_project_dense_pads_no_kernel_operand(monkeypatch, precision):
    """The dense plan reads the caller's centers and projector as they are:
    it builds none of the Pallas kernel's padded operands, which the
    kernel's plan does build."""
    padded = []
    real = ops._pad_rows

    def pad_rows(a, mult, value=0.0):
        padded.append(a.shape)
        return real(a, mult, value)

    monkeypatch.setattr(ops, "_pad_rows", pad_rows)
    rng = np.random.default_rng(7)
    x = rng.normal(size=(100, 16)).astype(np.float32)
    c = rng.normal(size=(37, 16)).astype(np.float32)
    a = rng.normal(size=(37, 5)).astype(np.float32)
    ops.kpca_project(x, c, a, sigma=2.0, precision=precision, plan="dense")
    assert padded == []
    ops.kpca_project(x, c, a, sigma=2.0, precision=precision, plan="pallas")
    assert c.shape in padded


DISPATCH_SHAPES = [(64, 16, 8), (100, 37, 24), (513, 129, 16), (1000, 7, 96)]


@pytest.mark.parametrize("n,m,d", DISPATCH_SHAPES)
@pytest.mark.parametrize("p", [2, 1])
@pytest.mark.parametrize("weighted", [False, True])
def test_backend_dispatch_parity(n, m, d, p, weighted):
    """kernel.backend='pallas' and 'dense' agree to 1e-5 through the public
    gram_matrix / weighted_gram dispatch (non-block-multiple shapes incl.)."""
    from repro.core.kernels_math import (make_kernel, gram_matrix,
                                         weighted_gram)
    rng = np.random.default_rng(hash((n, m, d, p, weighted)) % 2**32)
    x = rng.normal(size=(n, d)).astype(np.float32)
    name = "gaussian" if p == 2 else "laplacian"
    kp = make_kernel(name, 1.7, backend="pallas")
    kd = make_kernel(name, 1.7, backend="dense")
    if weighted:
        w = rng.uniform(0.5, 5, n).astype(np.float32)
        got = np.asarray(weighted_gram(kp, jnp.asarray(x), jnp.asarray(w)))
        want = np.asarray(weighted_gram(kd, jnp.asarray(x), jnp.asarray(w)))
    else:
        y = rng.normal(size=(m, d)).astype(np.float32)
        got = np.asarray(gram_matrix(kp, jnp.asarray(x), jnp.asarray(y)))
        want = np.asarray(gram_matrix(kd, jnp.asarray(x), jnp.asarray(y)))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_default_backend_never_calls_dense_gram(monkeypatch):
    """Acceptance guard: on the default backend neither fit_rskpca, fit_kpca,
    herding, nor transform may touch kernels_math's dense oracle — everything
    must route through the repro.kernels.ops dispatch layer (whose autotuned
    dense FALLBACK is its own policy and deliberately not patched here)."""
    from repro.core import kernels_math, rskpca, rsde

    def boom(*a, **kw):
        raise AssertionError("dense gram_matrix called on default backend")

    monkeypatch.setattr(kernels_math, "gram_matrix_dense", boom)
    monkeypatch.setattr(kernels_math, "pairwise_sq_dists", boom)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(300, 6)).astype(np.float32)
    ker = kernels_math.gaussian(1.0)
    assert ker.backend == "pallas"
    mdl = rskpca.fit(x, ker, 4, method="shadow", ell=3.0)
    z = mdl.transform(x[:50])
    assert np.isfinite(z).all()
    mdl2 = rskpca.fit_kpca(x[:100], ker, 4)
    assert np.isfinite(mdl2.transform(x[:10])).all()
    r = rsde.herding_rsde(x[:100], ker, m=10)
    assert r.m == 10


def test_transform_chunked_matches_unchunked():
    """Streaming transform in small fixed chunks == one-shot transform."""
    from repro.core import gaussian, fit
    rng = np.random.default_rng(1)
    x = rng.normal(size=(700, 12)).astype(np.float32)
    mdl = fit(x, gaussian(1.5), 5, method="shadow", ell=3.0)
    q = rng.normal(size=(1111, 12)).astype(np.float32)
    np.testing.assert_allclose(mdl.transform(q, chunk=128),
                               mdl.transform(q, chunk=10**9),
                               atol=1e-5, rtol=1e-5)


def test_shadow_assign_dynamic_valid_mask():
    """A dynamic per-center mask must behave exactly like the static prefix."""
    rng = np.random.default_rng(9)
    x = rng.normal(size=(200, 8)).astype(np.float32)
    c = rng.normal(size=(17, 8)).astype(np.float32)
    mask = (rng.random(17) > 0.4).astype(np.float32)
    idx, d2 = ops.shadow_assign(x, c, valid=mask, plan="pallas")
    dense = np.linalg.norm(x[:, None] - c[None], axis=2) ** 2
    dense[:, mask == 0] = np.inf
    assert (np.asarray(idx) == dense.argmin(1)).all()
    np.testing.assert_allclose(np.asarray(d2), dense.min(1), atol=1e-4,
                               rtol=1e-4)


def test_ragged_transform_compiles_once(monkeypatch):
    """Recompile-free serving: a stream of ragged query sizes through the
    fixed-chunk transform path must compile the projection exactly ONCE —
    the tail slice is padded UP to the chunk size, never traced at its own
    shape.  Autotune measurement is disabled so the compile count is
    deterministic."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    from repro.core import gaussian, fit
    rng = np.random.default_rng(2)
    x = rng.normal(size=(400, 12)).astype(np.float32)
    mdl = fit(x, gaussian(1.5), 5, method="shadow", ell=3.0)
    queries = [rng.normal(size=(qn, 12)).astype(np.float32)
               for qn in (500, 700, 901, 1000)]
    before = ops.projection_compile_count()
    outs = [mdl.transform(q, chunk=384) for q in queries]
    after = ops.projection_compile_count()
    assert after - before == 1, (before, after)
    for q, z in zip(queries, outs):
        assert z.shape == (q.shape[0], 5)
    # the padded tail must not perturb the embedding
    np.testing.assert_allclose(outs[0], mdl.transform(queries[0], chunk=None),
                               atol=1e-5, rtol=1e-5)


def test_block_size_selection_respects_vmem_budget():
    from repro.kernels.ops import pick_gram_blocks
    for d in (8, 64, 512, 4096, 8192):
        bn, bm, bk = pick_gram_blocks(d)
        assert (2 * bn * bk + bn * bm) * 4 <= 8 * 1024 * 1024
        assert bn % 128 == 0 and bm % 128 == 0 and bk <= max(d, 128)
        # K-chunking must preserve the big output tile even at large d
        assert bn == 512, (d, bn)
