"""Blocked selection's working set on the device (DESIGN.md §3): the
device cascade of ``shadow_select_blocked`` against the host cascade it
replaced (``tests/select_reference.py``), bit for bit, and what crosses
between host and device in one call."""
import logging

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import shadow as shadow_mod
from repro.core.shadow import _pow2_ceil, shadow_select_blocked
from repro.obs import trace
from select_reference import shadow_select_blocked as host_cascade


def _data(n, d, seed):
    rng = np.random.default_rng(seed)
    centers = rng.uniform(0, 1, (max(2, n // 20), d))
    idx = rng.integers(0, centers.shape[0], n)
    return (centers[idx] + 0.05 * rng.normal(size=(n, d))).astype(np.float32)


def _masses(kind, n):
    rng = np.random.default_rng(n)
    if kind == "f32":
        return rng.uniform(0.5, 3.0, n).astype(np.float32)
    if kind == "i32":
        return rng.integers(1, 9, n).astype(np.int32)
    return None


@pytest.fixture
def spans():
    trace.clear()
    obs.enable()
    yield
    obs.disable()
    trace.clear()


def _phases():
    return [e for e in trace.events() if e["name"] == "select.phase"]


@pytest.mark.parametrize("masses", [None, "f32", "i32"])
@pytest.mark.parametrize("block", [1, 16, 64])
@pytest.mark.parametrize("n", [300, 4096, 5000])
def test_device_cascade_matches_the_host_cascade_bitwise(n, block, masses):
    """The same rows reach the same rounds at the same padded shapes, so
    the centers, weights, assign map and m are the host cascade's, bit
    for bit, at n a power of two and not, weighted or not."""
    x, w = _data(n, 5, n), _masses(masses, n)
    c, wt, a, m = shadow_select_blocked(x, 0.15, block=block, weights=w)
    c_h, wt_h, a_h, m_h = host_cascade(x, 0.15, block=block, weights=w)
    assert m == m_h == c.shape[0] > 1
    assert (c.dtype, wt.dtype, a.dtype) == (c_h.dtype, wt_h.dtype, a_h.dtype)
    np.testing.assert_array_equal(c, c_h)
    np.testing.assert_array_equal(wt, wt_h)
    np.testing.assert_array_equal(a, a_h)


@pytest.mark.parametrize("masses", [None, "i32"])
def test_rows_cross_to_the_device_once(spans, masses):
    """One ``select.put`` a call, on the first phase, of the rows (and
    masses) once, unpadded: n = 5000 pads to 8192 on the device."""
    n, d = 5000, 7
    shadow_select_blocked(_data(n, d, 1), 0.15, block=16,
                          weights=_masses(masses, n))
    evs = trace.events()
    phases = _phases()
    assert len(phases) >= 3 and phases[0]["n_pad"] == 8192
    (put,) = [e for e in evs if e["name"] == "select.put"]
    assert put["parent"] == phases[0]["id"]
    assert put["bytes"] == 4 * n * d + (0 if masses is None else 4 * n)


def test_later_phases_fetch_no_rows_assign_or_alive(spans, monkeypatch):
    """Every host fetch of a call: one (centers, rounds, alive rows) triple
    a phase, then the assign map and each phase's power-of-two prefix of
    centers and weights, once.  No row block, mask or per-phase assign
    map comes back."""
    n, d = 5000, 7
    x = _data(n, d, 2)
    fetched = []
    get = jax.device_get

    def spy_get(tree):
        fetched.append([(v.shape, v.dtype) for v in jax.tree.leaves(tree)
                        if isinstance(v, jax.Array)])
        return get(tree)

    class SpyNumpy:  # the selector's own numpy calls on device arrays
        def __getattr__(self, name):
            fn = getattr(np, name)

            def call(*args, **kwargs):
                fetched.extend([[(v.shape, v.dtype)] for v in args
                                if isinstance(v, jax.Array)])
                return fn(*args, **kwargs)
            return fn if isinstance(fn, type) or not callable(fn) else call

    monkeypatch.setattr(jax, "device_get", spy_get)
    monkeypatch.setattr(shadow_mod, "np", SpyNumpy())
    _, _, a, m = shadow_select_blocked(x, 0.15, block=16)
    monkeypatch.undo()
    phases = _phases()
    assert len(phases) >= 3
    heads = [_pow2_ceil(p["centers"]) for p in phases]
    int32, float32 = np.dtype(np.int32), np.dtype(np.float32)
    assert fetched[:-1] == [[((3,), int32)]] * len(phases)
    assert sorted(fetched[-1]) == sorted(
        [((n,), int32)] + [((h, d), float32) for h in heads]
        + [((h,), float32) for h in heads])
    assert sum(heads) < 2 * m and (a >= 0).all()


def test_a_new_m_at_the_same_n_compiles_nothing(spans, caplog):
    """Shapes follow n and powers of two, never m: after one call, a call
    on the same rows at another eps (another m, the same phase sizes and
    center buckets) compiles no program."""
    x = _data(4096, 5, 4096)

    def run(eps):
        trace.clear()
        m = shadow_select_blocked(x, eps, block=16)[3]
        return m, [(p["n_pad"], _pow2_ceil(p["centers"])) for p in _phases()]

    m0, shapes0 = run(0.144)
    with jax.log_compiles(), caplog.at_level(logging.WARNING):
        caplog.clear()
        m1, shapes1 = run(0.148)
    assert m1 != m0 and shapes1 == shapes0
    compiles = [r.getMessage() for r in caplog.records
                if "Compiling" in r.getMessage()]
    assert compiles == []
