"""The measured compute-plan autotuner (repro.kernels.autotune)."""
import json
import os

import numpy as np
import pytest
import jax.numpy as jnp

from repro import obs
from repro.kernels import autotune, ops, quantize, ref
from repro.obs import metrics


@pytest.fixture
def fresh_cache(tmp_path, monkeypatch):
    """Isolate both cache layers: empty disk file in tmp, empty memory."""
    path = str(tmp_path / "autotune.json")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    autotune.clear(in_memory_only=False)
    yield path
    autotune.clear(in_memory_only=False)


def test_bucket_pow2_ceiling():
    assert autotune.bucket(1) == 128          # lo clip
    assert autotune.bucket(128) == 128
    assert autotune.bucket(129) == 256
    assert autotune.bucket(1000) == 1024
    assert autotune.bucket(10**9) == 1 << 17  # hi clip
    assert autotune.bucket(24, lo=8) == 32


def test_best_measures_once_and_caches(fresh_cache):
    calls = {"a": 0, "b": 0}

    def mk(name, cost):
        def thunk():
            calls[name] += 1
            import time
            time.sleep(cost)
        return thunk

    cands = {"a": mk("a", 0.0), "b": mk("b", 0.01)}
    assert autotune.best("k1", cands, default="b") == "a"
    first_calls = dict(calls)
    assert first_calls["a"] >= 2 and first_calls["b"] >= 2  # warmup + reps
    # second request: served from memory, thunks untouched
    assert autotune.best("k1", cands, default="b") == "a"
    assert calls == first_calls


def test_best_persists_to_disk_and_reloads(fresh_cache):
    autotune.best("k2", {"fast": lambda: None,
                         "slow": lambda: __import__("time").sleep(0.01)},
                  default="slow")
    disk = json.load(open(fresh_cache))
    assert disk["schema"] == autotune._SCHEMA
    assert disk["plans"][autotune.qualified("k2")]["winner"] == "fast"
    # a fresh process (cleared memory) must reload the winner WITHOUT
    # measuring: a candidate that ran would raise
    autotune.clear(in_memory_only=False)

    def boom():
        raise AssertionError("re-measured despite disk cache")

    assert autotune.best("k2", {"fast": boom, "slow": boom},
                         default="slow") == "fast"


def test_keys_qualified_by_device_and_jax_version(fresh_cache):
    """Persisted plans must carry the device kind AND jax version, so a
    cache file copied across machines/upgrades can never be replayed."""
    import jax

    autotune.best("kq", {"a": lambda: None,
                         "b": lambda: __import__("time").sleep(0.005)},
                  default="b")
    (key,) = json.load(open(fresh_cache))["plans"].keys()
    assert jax.devices()[0].device_kind.replace(" ", "_") in key
    assert f"jax{jax.__version__}" in key


def test_old_schema_cache_invalidated(fresh_cache):
    """A pre-versioned (schema-1 flat dict) cache file must be ignored on
    load and overwritten on save — stale plans never replay."""
    stale_key = autotune.qualified("kold")
    with open(fresh_cache, "w") as f:
        json.dump({stale_key: {"winner": "slow"}}, f)  # schema-1 layout
    autotune.clear(in_memory_only=False)
    assert autotune.best("kold", {"fast": lambda: None,
                                  "slow": lambda: __import__("time")
                                  .sleep(0.01)},
                         default="slow") == "fast"  # re-measured, not replayed
    disk = json.load(open(fresh_cache))
    assert disk["schema"] == autotune._SCHEMA
    assert disk["plans"][stale_key]["winner"] == "fast"


def test_single_candidate_skips_measurement(fresh_cache):
    calls = []
    assert autotune.best("k3", {"only": lambda: calls.append(1)},
                         default="only") == "only"
    assert not calls


@pytest.mark.parametrize("roofline", [False, True])
def test_failing_candidate_raises(fresh_cache, roofline):
    """A plan the op may run that raises is a fault: it surfaces with the
    key and the candidate's name, and the other candidate never wins by
    default."""
    def boom():
        raise RuntimeError("no backend")

    cands = {"ok": lambda: None, "bad": boom}
    with pytest.raises(autotune.CandidateFailed, match="'bad'.*k4"):
        if roofline:
            autotune.best_roofline("k4", cands,
                                   {"ok": (1.0, 1.0), "bad": (1.0, 1.0)},
                                   default="ok")
        else:
            autotune.best("k4", cands, default="ok")
    assert autotune.qualified("k4") not in autotune._MEM


def test_measurement_runs_eagerly_inside_jit(fresh_cache):
    """A plan asked for while an outer jit traces the op is measured on
    concrete arrays: the candidates really run, so the ranking is the
    device's, not the tracer's."""
    import jax
    seen = []

    def cand():
        seen.append(isinstance(jax.numpy.ones(3) + 1, jax.core.Tracer))

    @jax.jit
    def outer(x):
        autotune.best("k_jit", {"a": cand, "b": cand}, default="a")
        return x + 1

    outer(np.ones(2, np.float32))
    assert seen and not any(seen)


def test_measurement_disabled_uses_heuristic(fresh_cache, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    assert not autotune.measurement_enabled()
    # small problem, interpret mode -> dense; huge -> pallas
    assert autotune.heuristic_plan(100, 100, interpret=True) == "dense"
    assert autotune.heuristic_plan(10**5, 10**5, interpret=True) == "pallas"
    # ops must not record anything while disabled
    rng = np.random.default_rng(0)
    x = rng.normal(size=(100, 8)).astype(np.float32)
    ops.gram(x, x, sigma=1.0)
    assert not os.path.exists(fresh_cache)


def test_autotuned_gram_matches_ref(fresh_cache):
    """Whatever plan wins the measurement, the result is the same Gram."""
    rng = np.random.default_rng(1)
    x = rng.normal(size=(200, 16)).astype(np.float32)
    y = rng.normal(size=(90, 16)).astype(np.float32)
    got = np.asarray(ops.gram(x, y, sigma=1.7))
    want = np.asarray(ref.gram_ref(jnp.asarray(x), jnp.asarray(y), 1.7, 2))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # and the measurement was recorded under a gram| key
    disk = json.load(open(fresh_cache))
    assert any(k.startswith("gram|") for k in disk["plans"])


def test_disk_cache_defaults_off_under_pytest(monkeypatch):
    """Without an explicit REPRO_AUTOTUNE_CACHE, a pytest process must
    neither read nor write the repo-root cache file (hermetic test runs);
    the in-process cache still works."""
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE", raising=False)
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    assert os.environ.get("PYTEST_CURRENT_TEST")  # pytest sets this
    assert not autotune._disk_enabled()
    autotune.clear(in_memory_only=False)
    key = "hermetic-probe-key"
    assert autotune.best(
        key, {"a": lambda: None,
              "b": lambda: __import__("time").sleep(0.005)},
        default="b") == "a"
    # memory has it, the repo-root disk file does not
    assert autotune._MEM[autotune.qualified(key)]["winner"] == "a"
    try:
        with open(autotune._cache_path()) as f:
            disk = json.load(f)
        assert autotune.qualified(key) not in disk.get("plans", disk)
    except OSError:
        pass  # no cache file at all: equally hermetic
    autotune.clear(in_memory_only=False)


def test_dense_candidate_capped_for_huge_problems(monkeypatch):
    """Beyond DENSE_MAX_CELLS the dense path must not even be a measurement
    candidate (its intermediates would not fit); the plan must come back
    pallas-tiled."""
    seen = {}

    def fake_best(key, candidates, default):
        seen[key] = set(candidates)
        return "pallas"

    monkeypatch.setattr(autotune, "best", fake_best)
    kind, blocks = ops._gram_plan(1 << 16, 1 << 14, 64, "f32",
                                  interpret=True)
    assert kind == "pallas" and blocks is not None
    (names,) = seen.values()
    assert "dense" not in names and "pallas" in names


def test_assign_plan_tag_namespaces_key(fresh_cache, monkeypatch):
    """The chunked-ingest assign path measures under its own ``|ingest``
    key: tagged and untagged requests at one shape must not share (or
    clobber) a cache entry."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    autotune.clear(in_memory_only=False)
    ops._assign_plan(256, 128, 8, True)
    ops._assign_plan(256, 128, 8, True, tag="ingest")
    keys = [k for k in autotune._MEM
            if k.startswith("assign|n256|m128|d8|interp")]
    assert len(keys) == 2
    assert sum("|ingest|" in k for k in keys) == 1


#: Every plan function at one small interpret-mode shape (n 200 -> bucket
#: 256, m 90 -> 128, d 16, r 4 -> 8).
_PLANS = {
    "gram": lambda: ops._gram_plan(200, 90, 16, "f32", True),
    "gram_matvec": lambda: ops._matvec_plan(200, 90, 16, 4, "f32", True),
    "shadow_assign": lambda: ops._assign_plan(200, 90, 16, True),
    "kpca_project": lambda: ops._project_plan(200, 90, 16, 4, "f32", True),
    "gram_row": lambda: ops._gram_row_plan(90, 16, True),
    "rff_project": lambda: ops._rff_plan(200, 90, 16, 4, "f32", True),
}


@pytest.fixture
def counting(fresh_cache, monkeypatch):
    """Counts the plan cache's hits and misses and every measurement
    operand ``ops._bench_rows`` builds (as (n, d) pairs)."""
    built = []
    real = ops._bench_rows

    def bench_rows(n, d):
        built.append((n, d))
        return real(n, d)

    monkeypatch.setattr(ops, "_bench_rows", bench_rows)
    metrics.clear()
    obs.enable()
    yield built
    obs.disable()
    metrics.clear()


@pytest.mark.parametrize("op", sorted(_PLANS))
def test_plan_cache_hit_builds_no_operands(counting, op):
    """A plan the cache holds is answered with a key and a dict read: the
    lookup builds no measurement operand, only a miss does."""
    hits = metrics.counter("autotune.plan_hits")
    misses = metrics.counter("autotune.plan_misses")
    first = _PLANS[op]()
    assert counting and misses.value == 1 and hits.value == 0
    n_built = len(counting)
    assert _PLANS[op]() == first
    assert len(counting) == n_built
    assert (hits.value, misses.value) == (1, 1)


def _deterministic_measure(monkeypatch):
    """Replace the timing with one run of each candidate and fixed times by
    candidate order, so a winner depends only on the candidates and costs."""
    def measure(key, candidates):
        for thunk in candidates.values():
            thunk()
        return {name: (i + 1) * 1e-3 for i, name in enumerate(candidates)}

    monkeypatch.setattr(autotune, "_measure", measure)


@pytest.mark.parametrize("op,precision", [("kpca_project", "f32"),
                                          ("kpca_project", "int8"),
                                          ("shadow_assign", "f32")])
def test_plan_miss_measures_the_eager_operands(fresh_cache, monkeypatch, op,
                                               precision):
    """A miss keys, builds and costs what the plan functions built eagerly
    before they built lazily: the operands from ``_bench_rows`` at the
    buckets, the same candidates and costs, so the same winner under the
    same cache key (a persisted entry keeps answering the same plan)."""
    _deterministic_measure(monkeypatch)
    nb, mb, db, rb = 256, 128, 16, 8
    x, c = ops._bench_rows(nb, db), ops._bench_rows(mb, db)
    a = ops._bench_rows(c.shape[0], rb)
    seen, costs_seen = [], []
    real_op = getattr(ops, op)

    def recording_op(*args, **kwargs):
        seen.append((args, kwargs))
        return real_op(*args, **kwargs)

    monkeypatch.setattr(ops, op, recording_op)
    real_roofline = autotune.best_roofline

    def recording_roofline(key, candidates, costs, default):
        costs_seen.append(costs)
        return real_roofline(key, candidates, costs, default)

    monkeypatch.setattr(autotune, "best_roofline", recording_roofline)
    if op == "kpca_project":
        winner = ops._project_plan(200, 90, 16, 4, precision, True)
        key = f"project|n{nb}|m{mb}|d{db}|r{rb}|{precision}|interp"
        names = [f"pallas:{t}" for t in ops._PROJECT_TILES_INTERPRET]
        want_ops = (x, c, a)
        costs = {n: ops._project_costs(
            x.shape[0], c.shape[0], db, rb,
            min(int(n.split(":")[1]), ops._round_up(x.shape[0], 128)),
            dense=False, precision=precision) for n in names}
        costs["dense"] = ops._project_costs(x.shape[0], c.shape[0], db, rb,
                                            0, dense=True,
                                            precision=precision)
        assert costs_seen == [costs]
        want = real_roofline("eager-reference", {n: lambda: None
                                                 for n in costs},
                             costs, default=names[0])
    else:
        winner = ops._assign_plan(200, 90, 16, True)
        key = f"assign|n{nb}|m{mb}|d{db}|interp"
        names = ["pallas"]
        want_ops = (x, c)
        want = autotune.best("eager-reference",
                             {"pallas": lambda: None, "dense": lambda: None},
                             default="pallas")
    assert winner == want
    assert autotune.qualified(key) in autotune._MEM
    assert sorted(autotune._MEM[autotune.qualified(key)]["us"]) \
        == sorted(names + ["dense"])
    assert len(seen) == len(names) + 1  # one run of each candidate
    for args, kwargs in seen:
        assert len(args) == len(want_ops)
        for got, ref_arr in zip(args, want_ops):
            assert got.shape == ref_arr.shape
            np.testing.assert_array_equal(got, ref_arr)
        if op == "kpca_project":
            q = kwargs["projector_q"]
            if precision == "int8":
                want_q = quantize.quantize_projector(a, precision)
                for got, ref_arr in zip(q, want_q):
                    np.testing.assert_array_equal(np.asarray(got),
                                                  np.asarray(ref_arr))
            else:
                assert q is None


@pytest.mark.parametrize("env_set", [False, True])
def test_compilation_cache_location(monkeypatch, tmp_path, env_set):
    """JAX's own variable wins untouched; otherwise a fixed directory at
    the checkout root (never a temporary or per-process path)."""
    import jax
    prev = jax.config.jax_compilation_cache_dir
    try:
        if env_set:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert autotune.enable_compilation_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == prev
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = os.path.join(autotune.repo_root(), ".jax_cache")
            assert autotune.enable_compilation_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
            assert os.path.isfile(os.path.join(autotune.repo_root(),
                                               "chip_smoke.py"))
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
