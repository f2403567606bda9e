"""Quantized serving-tier (int8/fp8 projector) parity and bound tests.

The contract (DESIGN.md §8, kernels/quantize.py): only the projector
contraction drops precision — distances, the exp nonlinearity, and the
accumulator stay f32 — and the per-channel rounding error of the projection
is bounded by ``projection_error_bound``, a budget the caller can weigh
against the §5 eigenvalue slack.  Three layers are pinned here:

  * BITWISE pallas/dense parity for int8 (both paths round Gram values with
    the identical expression and accumulate in int32, so the dense oracle
    and the kernel must agree to the last bit — not approximately);
  * measured error vs the f32 oracle stays within the reported bound, on
    pow2 bucket shapes AND ragged tails, for both precisions (property
    swept hypothesis-style over random shapes/scales);
  * the publish-time cache path: a pre-quantized ``projector_q`` must give
    exactly the per-call-quantized answer, and the chunked ragged stream
    must stay recompile-free.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from hypothesis import given, settings, strategies as st

from repro.kernels import ops, quantize, ref

SIGMA = 1.7

# pow2 bucket shapes and ragged tails (n % 128 != 0, odd m/r)
SHAPES = [(256, 128, 16, 8), (512, 256, 32, 16), (300, 190, 24, 11)]


def _problem(n, m, d, r, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    c = rng.normal(size=(m, d)).astype(np.float32)
    a = (rng.normal(size=(m, r)) / np.sqrt(m)).astype(np.float32)
    return x, c, a


def _oracle(x, c, a):
    return np.asarray(ref.kpca_project_ref(jnp.asarray(x), jnp.asarray(c),
                                           jnp.asarray(a), SIGMA, 2))


@pytest.mark.parametrize("n,m,d,r", SHAPES + [(300, 1100, 16, 8)])
def test_int8_pallas_dense_bitwise(n, m, d, r):
    """int8 rounds the Gram with one shared expression and accumulates in
    int32, so the pallas kernel and the dense oracle are integer-exact:
    equality to the last bit, not a tolerance."""
    x, c, a = _problem(n, m, d, r)
    zs = [np.asarray(ops.kpca_project(x, c, a, sigma=SIGMA, precision="int8",
                                      plan=plan))
          for plan in ("pallas", "dense")]
    np.testing.assert_array_equal(zs[0], zs[1])


@pytest.mark.parametrize("n,m,d,r", SHAPES)
@pytest.mark.parametrize("prec", quantize.QUANT_PRECISIONS)
@pytest.mark.parametrize("plan", ["pallas", "dense"])
def test_quantized_error_within_reported_bound(n, m, d, r, prec, plan):
    x, c, a = _problem(n, m, d, r, seed=hash((n, m, prec)) % 2**32)
    got = np.asarray(ops.kpca_project(x, c, a, sigma=SIGMA, precision=prec,
                                      plan=plan))
    err = np.abs(got - _oracle(x, c, a)).max(axis=0)      # per channel
    bound = np.asarray(quantize.projection_error_bound(a, prec))
    assert err.shape == bound.shape == (r,)
    assert np.all(err <= bound), (err, bound)
    assert np.all(np.isfinite(bound)) and np.all(bound > 0)


@settings(max_examples=15, deadline=None)
@given(m=st.integers(8, 200), r=st.integers(1, 24),
       scale=st.floats(1e-3, 30.0), seed=st.integers(0, 2**16))
def test_bound_property_random_projectors(m, r, scale, seed):
    """Property: for ANY projector magnitude the dense quantized projection
    errs within projection_error_bound — the Theorem-5.x-style budget the
    swap publisher reports must never under-promise."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(64, 6)).astype(np.float32)
    c = rng.normal(size=(m, 6)).astype(np.float32)
    a = (rng.normal(size=(m, r)) * scale).astype(np.float32)
    want = _oracle(x, c, a)
    for prec in quantize.QUANT_PRECISIONS:
        got = np.asarray(ops.kpca_project(x, c, a, sigma=SIGMA,
                                          precision=prec, plan="dense"))
        err = np.abs(got - want).max(axis=0)
        bound = np.asarray(quantize.projection_error_bound(a, prec))
        assert np.all(err <= bound), (prec, err, bound)


@pytest.mark.parametrize("prec", quantize.QUANT_PRECISIONS)
def test_quantize_projector_roundtrip_and_zero_channels(prec):
    rng = np.random.default_rng(5)
    a = (rng.normal(size=(90, 7)) * 3.0).astype(np.float32)
    a[:, 3] = 0.0                       # an all-zero channel must not NaN
    q, s = quantize.quantize_projector(a, prec)
    s = np.asarray(s)
    assert s.shape == (7,) and np.all(s > 0) and s[3] == 1.0
    deq = np.asarray(quantize.dequantize_projector(q, s))
    np.testing.assert_array_equal(deq[:, 3], 0.0)
    if prec == "int8":
        assert np.asarray(q).dtype == np.int8
        # symmetric rounding: dequantized entries within half a step
        assert np.abs(deq - a).max() <= (s / 2 + 1e-7).max()
    else:
        assert np.abs(deq - a).max() <= np.abs(a).max() * quantize.FP8_U


@pytest.mark.parametrize("prec", quantize.QUANT_PRECISIONS)
def test_publish_time_projector_q_matches_per_call(prec):
    """The snapshot-publish cache (swap.py stores (Aq, s) once) must be a
    pure caching move: identical output to quantizing inside the call."""
    x, c, a = _problem(320, 150, 12, 6, seed=9)
    pq = quantize.quantize_projector(a, prec)
    for plan in ("pallas", "dense"):
        per_call = np.asarray(ops.kpca_project(
            x, c, a, sigma=SIGMA, precision=prec, plan=plan))
        cached = np.asarray(ops.kpca_project(
            x, c, a, sigma=SIGMA, precision=prec, plan=plan, projector_q=pq))
        np.testing.assert_array_equal(per_call, cached)


def test_projector_q_rejected_for_full_precision():
    x, c, a = _problem(64, 32, 4, 3)
    pq = quantize.quantize_projector(a, "int8")
    with pytest.raises(ValueError):
        ops.kpca_project(x, c, a, sigma=SIGMA, precision="f32",
                         projector_q=pq)


def test_quantized_chunked_stream_recompile_free():
    """Ragged quantized query streams ride the same fixed-chunk padding as
    f32: after the first (compile) call, arbitrary ragged row counts add
    ZERO compiled shapes — the serving contract of DESIGN.md §8."""
    _, c, a = _problem(1, 100, 8, 5, seed=2)
    pq = quantize.quantize_projector(a, "int8")
    rng = np.random.default_rng(3)

    def go(n):
        x = rng.normal(size=(n, 8)).astype(np.float32)
        return np.asarray(ops.kpca_project(
            x, c, a, sigma=SIGMA, precision="int8", plan="pallas",
            chunk=128, projector_q=pq))

    go(128)  # warm the single (chunk, m_pad) shape
    before = ops.projection_compile_count()
    for n in (17, 128, 300, 513):
        z = go(n)
        assert z.shape == (n, 5)
    assert ops.projection_compile_count() == before


def test_swap_publish_caches_quantized_projector():
    """HotSwapServer.publish quantizes ONCE per snapshot for quantized-tier
    kernels (and not at all for f32), and the served tier stays close to
    the f32 oracle."""
    from repro import streaming
    from repro.core import gaussian
    from repro.core.rsde import RSDE

    rng = np.random.default_rng(11)
    c = rng.normal(size=(60, 5)).astype(np.float32)
    w = np.ones(60, np.float64)
    rsde = RSDE(c, w, n=60.0, scheme="test")

    def server(precision):
        ker = gaussian(1.0, precision=precision)
        st_ = streaming.from_rsde(rsde, ker, 4, eps=0.5, cap=60)
        return streaming.HotSwapServer(st_)

    x = rng.normal(size=(32, 5)).astype(np.float32)
    s32, s8 = server("f32"), server("int8")
    assert s32._snapshot[3] is None
    q, s = s8._snapshot[3]
    assert np.asarray(q).dtype == np.int8 and np.asarray(s).ndim == 1
    z32, z8 = np.asarray(s32.transform(x)), np.asarray(s8.transform(x))
    # the served tier's deviation from the f32 oracle stays inside the
    # per-channel budget publish reported for this exact projector
    bound = np.asarray(quantize.projection_error_bound(
        np.asarray(s8._snapshot[1]), "int8"))
    assert np.all(np.abs(z8 - z32).max(axis=0) <= bound)


def test_fp8_refused_without_fp8_mxu(monkeypatch):
    """A chip without an fp8 MXU would only emulate the tier: the compiled
    path refuses it instead (v5e is such a chip)."""
    assert "TPU v5 lite".startswith(ops._NO_FP8_MXU)
    monkeypatch.setattr(ops, "fp8_mxu", lambda: False)
    x, c, a = _problem(8, 16, 4, 3)
    with pytest.raises(ValueError, match="fp8 MXU"):
        ops.kpca_project(x, c, a, sigma=SIGMA, precision="fp8",
                         interpret=False)
