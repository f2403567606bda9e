"""Unified telemetry layer (DESIGN.md §16): span tracing, the metric
registry, spectral health gauges, the instrumented serving/streaming/ingest
paths, and the bench-row provenance stamp.

Every test that enables observability goes through the ``obs_on`` fixture,
which resets metric values and the trace ring on both sides — the layer is
process-global state, and leaking an enabled flag or a counter value into
an unrelated test would be exactly the kind of action at a distance the
off-by-default design exists to prevent."""
import contextlib
import json
import threading
import time

import numpy as np
import pytest
import jax.numpy as jnp

from repro import obs, streaming
from repro.core import gaussian, shadow_rsde
from repro.obs import metrics, trace
from repro.obs.spectral import SpectralHealth
from repro.serving import BatchingFrontEnd
from repro.streaming import updates
from repro.streaming.drift import DriftDetector
from repro.streaming.ingest import ingest

ELL = 1.6
SIGMA = 1.5
RANK = 4


@pytest.fixture
def obs_on():
    metrics.clear()
    trace.clear()
    obs.enable()
    yield
    obs.disable()
    metrics.clear()
    trace.clear()


def _blobs(n, d=6, seed=0, shift=0.0):
    rng = np.random.default_rng(seed)
    centers = rng.uniform(0, 4, (8, d))
    idx = rng.integers(0, 8, n)
    return (centers[idx] + 0.3 * rng.normal(size=(n, d))
            + shift).astype(np.float32)


def _state(precision="f32", budget=0.05, n=300, seed=0):
    x = _blobs(n, seed=seed)
    ker = gaussian(SIGMA, precision=precision)
    rsde = shadow_rsde(x, ker, ell=ELL)
    return x, ker, streaming.from_rsde(rsde, ker, RANK, ell=ELL,
                                       budget=budget)


# -------------------------------------------------------------------------
# disabled-mode contract
# -------------------------------------------------------------------------


def test_disabled_by_default_everything_is_noop():
    assert not obs.enabled()
    # span() hands out ONE shared null object — no allocation per site
    s1 = obs.span("x.y", a=1)
    s2 = obs.span("z.w")
    assert s1 is s2
    with s1 as sp:
        sp.set(found=3)
        assert sp.sync(123) == 123
    assert trace.events() == []
    c = metrics.counter("noop.c")
    g = metrics.gauge("noop.g")
    h = metrics.histogram("noop.h")
    c.inc()
    g.set(5.0)
    h.observe(1.0)
    assert c.value == 0 and g.value == 0.0 and h.count == 0


def test_enable_disable_roundtrip(obs_on):
    assert obs.enabled() and trace.enabled() and metrics.enabled()
    obs.disable()
    assert not (obs.enabled() or trace.enabled() or metrics.enabled())
    obs.enable()
    metrics.counter("rt.c").inc(3)
    assert metrics.counter("rt.c").value == 3


# -------------------------------------------------------------------------
# spans + exporters
# -------------------------------------------------------------------------


def test_span_nesting_depth_and_attrs(obs_on):
    with obs.span("outer.op", chunk=1):
        with obs.span("inner.op") as sp:
            sp.set(rows=7)
    evs = trace.events()
    assert [e["name"] for e in evs] == ["inner.op", "outer.op"]  # exit order
    inner, outer = evs
    assert inner["depth"] == 1 and outer["depth"] == 0
    assert inner["rows"] == 7 and outer["chunk"] == 1
    assert outer["dur_s"] >= inner["dur_s"] >= 0.0


def test_span_records_error_and_reraises(obs_on):
    with pytest.raises(ValueError, match="boom"):
        with obs.span("bad.op"):
            raise ValueError("boom")
    (ev,) = trace.events()
    assert ev["error"] == "ValueError"


def test_span_sync_blocks_device_work(obs_on):
    with obs.span("dev.op") as sp:
        z = sp.sync(jnp.arange(8) * 2)
    np.testing.assert_array_equal(np.asarray(z), np.arange(8) * 2)
    (ev,) = trace.events()
    assert ev["sync_s"] >= 0.0 and ev["dur_s"] >= ev["sync_s"]


def test_ring_bound_drops_oldest(obs_on):
    trace.set_ring(8)
    try:
        for k in range(20):
            with obs.span("ring.op", k=k):
                pass
        evs = trace.events()
        assert len(evs) == 8
        assert [e["k"] for e in evs] == list(range(12, 20))  # oldest gone
    finally:
        trace.set_ring(trace._DEFAULT_RING)


def test_chrome_and_jsonl_export(tmp_path, obs_on):
    def worker():
        with obs.span("thread.op"):
            pass

    with obs.span("main.op", rows=4):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    chrome = tmp_path / "trace.json"
    flat = tmp_path / "trace.jsonl"
    assert trace.export_chrome(str(chrome)) == 2
    assert trace.export_jsonl(str(flat)) == 2
    doc = json.loads(chrome.read_text())
    by_name = {e["name"]: e for e in doc["traceEvents"]}
    assert by_name["main.op"]["ph"] == "X"
    assert by_name["main.op"]["args"]["rows"] == 4
    # one track per thread
    assert by_name["main.op"]["tid"] != by_name["thread.op"]["tid"]
    lines = [json.loads(ln) for ln in flat.read_text().splitlines()]
    assert {ln["name"] for ln in lines} == {"main.op", "thread.op"}


# -------------------------------------------------------------------------
# metric registry
# -------------------------------------------------------------------------


def test_registry_get_or_create_identity(obs_on):
    assert metrics.counter("id.c") is metrics.counter("id.c")
    assert metrics.counter("id.c", {"a": 1}) is not metrics.counter("id.c")
    # label ORDER does not split series
    assert metrics.gauge("id.g", {"a": 1, "b": 2}) \
        is metrics.gauge("id.g", {"b": 2, "a": 1})


def test_clear_keeps_handle_identity(obs_on):
    c = metrics.counter("keep.c")
    c.inc(5)
    metrics.clear()
    obs.enable()  # clear() drops hooks/values, not the enabled flag
    assert metrics.counter("keep.c") is c  # still registered
    assert c.value == 0
    c.inc(2)
    assert "keep_c 2" in metrics.dump()


def test_histogram_buckets_and_quantiles(obs_on):
    h = metrics.histogram("q.h", bounds=(1.0, 2.0, 4.0, 8.0))
    assert h.quantile(0.5) == 0.0  # empty
    for v in (0.5, 1.5, 1.5, 3.0, 3.0, 3.0, 5.0, 9.0):
        h.observe(v)
    assert h.count == 8 and h.sum == pytest.approx(26.5)
    assert h.counts == [1, 2, 3, 1, 1]  # (..1], (1..2], (2..4], (4..8], inf
    q50 = h.quantile(0.5)
    assert 2.0 < q50 <= 4.0  # rank 4 lands in the (2, 4] bucket
    assert h.quantile(0.99) >= q50
    assert h.quantile(1.0) == 8.0  # top finite bound caps the estimate


def test_prometheus_dump_shape(obs_on):
    metrics.counter("serve.req-total").inc(3)
    metrics.gauge("g.v", {"k": 2}).set(1.5)
    h = metrics.histogram("lat.ms", bounds=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    text = metrics.dump()
    assert "# TYPE serve_req_total counter" in text  # sanitized name
    assert "serve_req_total 3" in text
    assert 'g_v{k="2"} 1.5' in text
    assert 'lat_ms_bucket{le="1.0"} 1' in text
    assert 'lat_ms_bucket{le="10.0"} 2' in text  # cumulative
    assert 'lat_ms_bucket{le="+Inf"} 2' in text
    assert "lat_ms_count 2" in text
    assert 'lat_ms{quantile="0.5"}' in text


def test_snapshot_and_hooks(obs_on):
    calls = []

    def sampler():
        calls.append(1)
        metrics.gauge("hook.g").set(42.0)

    def broken():
        raise RuntimeError("sampler on fire")

    metrics.add_hook(sampler)
    metrics.add_hook(sampler)  # idempotent
    metrics.add_hook(broken)   # must not kill the scrape
    snap = metrics.snapshot()
    assert snap["hook_g"] == 42.0 and len(calls) == 1
    metrics.remove_hook(sampler)
    metrics.gauge("hook.g").set(0.0)
    metrics.snapshot()
    assert metrics.gauge("hook.g").value == 0.0  # sampler no longer runs


def test_reporter_periodic_dump(tmp_path, obs_on):
    metrics.counter("rep.c").inc()
    path = tmp_path / "metrics.txt"
    rep = metrics.start_reporter(str(path), interval_s=0.02)
    try:
        deadline = time.monotonic() + 2.0
        while not path.exists() and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        rep.stop()
    assert "rep_c 1" in path.read_text()  # stop() always writes a final dump


def test_thread_safety_exact_counts(obs_on):
    c = metrics.counter("mt.c")
    h = metrics.histogram("mt.h")

    def work():
        for _ in range(1000):
            c.inc()
            h.observe(1.0)

    ts = [threading.Thread(target=work) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == 4000 and h.count == 4000


# -------------------------------------------------------------------------
# instrumented subsystems
# -------------------------------------------------------------------------


class _StubServer:
    def transform(self, x):
        x = np.asarray(x)
        return np.stack([x.sum(axis=1), np.zeros(x.shape[0])], 1)


def test_serve_frontend_metrics_and_spans(obs_on):
    fe = BatchingFrontEnd(_StubServer(), max_batch=64, autostart=False)
    futs = [fe.submit(np.ones((k, 3), np.float32)) for k in (1, 4, 2)]
    assert fe.step() == 7
    for f in futs:
        f.result(timeout=0)
    assert metrics.counter("serve.requests").value == 3
    assert metrics.counter("serve.rows").value == 7
    assert metrics.counter("serve.batches").value == 1
    assert metrics.gauge("serve.queue_depth").value == 0.0
    assert metrics.histogram("serve.coalesce_rows",
                             bounds=metrics.SIZE_BUCKETS).count == 1
    assert metrics.histogram("serve.deadline_slack_ms").count == 1
    # per-bucket series: 7 rows pad to the pow2 bucket 8
    assert metrics.histogram("serve.service_ms", {"bucket": 8}).count == 1
    # the bucket's smoothed service time lives in the front end's stats
    assert fe.snapshot().ewma_service_s[8] > 0.0
    names = [e["name"] for e in trace.events()]
    assert "serve.batch" in names


def test_serve_error_counter(obs_on):
    class Bad:
        def transform(self, x):
            raise RuntimeError("dead operator")

    fe = BatchingFrontEnd(Bad(), max_batch=8, autostart=False)
    f = fe.submit(np.ones((2, 3), np.float32))
    fe.step()
    with pytest.raises(RuntimeError, match="dead operator"):
        f.result(timeout=0)
    assert metrics.counter("serve.errors").value == 1


def test_serve_stats_snapshot_is_consistent_copy():
    fe = BatchingFrontEnd(_StubServer(), max_batch=64, autostart=False)
    fe.submit(np.ones((4, 3), np.float32))
    fe.step()
    snap = fe.snapshot()
    assert snap.batches == 1 and snap.rows == 4
    assert snap.ewma_service_s == fe.stats.ewma_service_s
    # the copy is detached: mutating it cannot corrupt the live stats
    snap.ewma_service_s[4] = 99.0
    snap.batches = 77
    assert fe.stats.batches == 1
    assert 99.0 not in fe.stats.ewma_service_s.values()


def test_swap_publish_metrics_and_age_gauge_resets(obs_on):
    _, _, st = _state()
    srv = streaming.HotSwapServer(st)  # publishes once in __init__
    assert metrics.counter("swap.publishes").value == 1
    age = metrics.gauge("swap.snapshot_age_s")
    assert age.value == 0.0
    time.sleep(0.01)
    srv.transform(np.zeros((4, 6), np.float32))
    # the fetch's span records only under a profiler trace
    assert "swap.fetch" not in [e["name"] for e in trace.events()]
    served_age = age.value
    assert served_age > 0.0  # transform saw a snapshot published earlier
    # REGRESSION: a publish must reset the age gauge, not leave the last
    # served age dangling until the next transform happens to overwrite it
    srv.publish(st)
    assert metrics.counter("swap.publishes").value == 2
    assert age.value == 0.0
    assert metrics.histogram("swap.publish_ms").count == 2
    names = [e["name"] for e in trace.events()]
    assert names.count("swap.publish") == 2


def test_streaming_ingest_metrics(obs_on):
    _, _, st = _state(budget=0.05)
    xs = _blobs(64, seed=5, shift=0.5)
    st = ingest(st, xs, batch=32)
    assert metrics.counter("stream.batches").value == 2
    assert metrics.counter("stream.rows").value == 64
    ins = metrics.counter("stream.updates", {"kind": "insert"}).value
    absorbed = metrics.counter("stream.updates", {"kind": "absorb"}).value
    assert ins + absorbed == 64 and ins >= 0 and absorbed >= 0
    # every batch logged exactly one maintenance decision
    n_patch = metrics.counter("stream.maintenance",
                              {"decision": "patch"}).value
    n_resolve = metrics.counter("stream.maintenance",
                                {"decision": "resolve"}).value
    assert n_patch + n_resolve == 2
    assert metrics.gauge("stream.m").value == st.m
    assert 0.0 < metrics.gauge("stream.fill_fraction").value <= 1.0
    assert metrics.histogram("stream.ingest_batch_ms").count == 2
    names = [e["name"] for e in trace.events()]
    assert names.count("stream.ingest_batch") == 2


def test_update_kind_counters(obs_on):
    _, _, st = _state()
    st2 = updates.remove(st, 0)
    updates.replace(st2, 1, jnp.zeros((6,), jnp.float32))
    assert metrics.counter("stream.updates", {"kind": "remove"}).value == 1
    assert metrics.counter("stream.updates", {"kind": "replace"}).value == 1


def test_autotune_plan_cache_counters(obs_on):
    from repro.kernels import autotune

    key = "obstest|n256|m128"
    hits0 = metrics.counter("autotune.plan_hits").value
    miss0 = metrics.counter("autotune.plan_misses").value
    cands = {"a": lambda: None, "b": lambda: time.sleep(0.002)}
    w1 = autotune.best(key, cands, default="a")
    assert w1 == "a"  # the faster thunk wins
    assert metrics.counter("autotune.plan_misses").value == miss0 + 1
    w2 = autotune.best(key, cands, default="b")
    assert w2 == w1
    assert metrics.counter("autotune.plan_hits").value == hits0 + 1


# -------------------------------------------------------------------------
# spectral health
# -------------------------------------------------------------------------


def test_spectral_health_gauges(obs_on):
    _, ker, st = _state(budget=0.05)
    box = {"st": st}
    sh = SpectralHealth(get_state=lambda: box["st"])
    sh.observe()
    lam = np.asarray(st.eigvals)
    for k in range(min(RANK, 16)):
        assert metrics.gauge("spectral.eigval", {"k": k}).value \
            == pytest.approx(float(lam[k]))
    assert metrics.gauge("spectral.gap").value \
        == pytest.approx(float(lam[RANK - 1] - lam[RANK]))
    assert metrics.gauge("spectral.m").value == st.m
    assert metrics.gauge("spectral.budget_ratio").value == 0.0  # fresh solve
    # install(): a metrics scrape self-refreshes from the CURRENT state
    sh.install()
    try:
        box["st"] = updates.ingest_batch(
            st, jnp.asarray(_blobs(8, seed=7, shift=1.0)))
        snap = metrics.snapshot()
        assert snap["spectral_n"] == float(box["st"].n) != float(st.n)
    finally:
        sh.uninstall()


def test_spectral_health_disabled_noop():
    _, _, st = _state()
    SpectralHealth(get_state=lambda: st).observe()
    assert metrics.gauge("spectral.m").value == 0.0


def test_spectral_health_mmd_and_quant_headroom(obs_on):
    x, ker, st = _state(precision="int8", budget=0.05)
    srv = streaming.HotSwapServer(st)
    det = DriftDetector(ker, ELL, window=64)
    sh = SpectralHealth(get_state=lambda: st, server=srv, detector=det)
    sh.observe()
    # window not full yet: no MMD series
    assert metrics.gauge("spectral.mmd").value == 0.0
    det.push(x[:64])
    sh.observe()
    assert det.full
    assert metrics.gauge("spectral.mmd").value > 0.0
    assert metrics.gauge("spectral.mmd_ratio").value > 0.0
    # int8 tier published a quantized projector: bound + headroom present
    qmax = metrics.gauge("spectral.quant_bound_max").value
    assert qmax > 0.0
    assert metrics.gauge("spectral.budget_headroom").value \
        == pytest.approx(float(st.budget) - float(st.err_est) - qmax)


# -------------------------------------------------------------------------
# bench-row provenance (benchmarks/common.py)
# -------------------------------------------------------------------------


def test_merge_rows_stamps_fresh_rows_only():
    from benchmarks import common

    common.set_run_stamp(git_sha="abc1234", measured_at="2026-01-01T00:00")
    try:
        old = [{"mode": "fit", "n": 1, "git_sha": "old"},
               {"mode": "fit", "n": 2, "stale": True}]
        fresh = [{"mode": "fit", "n": 2, "fit_speedup": 1.5}]
        out = common.merge_rows(old, fresh)
        assert len(out) == 2
        kept = next(r for r in out if r["n"] == 1)
        new = next(r for r in out if r["n"] == 2)
        assert kept["git_sha"] == "old"  # untouched rows keep their stamp
        assert new["git_sha"] == "abc1234"
        assert new["measured_at"] == "2026-01-01T00:00"
        assert not new.get("stale")  # re-measured pair drops the stale row
    finally:
        common.set_run_stamp()


def test_merge_rows_without_stamp_adds_nothing():
    from benchmarks import common

    common.set_run_stamp()  # library replay: no ambient stamp
    out = common.merge_rows([], [{"mode": "fit", "n": 4}])
    assert out == [{"mode": "fit", "n": 4}]
    explicit = common.merge_rows([], [{"mode": "fit", "n": 4}],
                                 stamp={"git_sha": "zzz"})
    assert explicit[0]["git_sha"] == "zzz"


def test_make_stamp_shape():
    from benchmarks import common

    stamp = common.make_stamp()
    assert set(stamp) == {"git_sha", "measured_at"}
    assert stamp["git_sha"]  # short sha in a checkout, "unknown" outside
    assert "T" in stamp["measured_at"]


# -------------------------------------------------------------------------
# end-to-end acceptance: one enabled run, all three subsystems visible
# -------------------------------------------------------------------------


def test_end_to_end_trace_and_metrics(tmp_path, obs_on):
    from repro.core.ingest_pipeline import select_streaming

    # ingest: out-of-core selection over a 3-chunk stream
    x = _blobs(192, seed=3)
    chunks = [(x[s : s + 64], 64) for s in range(0, 192, 64)]
    select_streaming(iter(chunks), 0.4, block=32)

    # streaming: operator maintenance + hot-swap publish
    _, _, st = _state()
    srv = streaming.HotSwapServer(st)
    st = ingest(st, _blobs(32, seed=8, shift=0.3), batch=16, server=srv)

    # serving: batched dispatch through the published operator
    sh = SpectralHealth(get_state=lambda: st).install()
    try:
        with BatchingFrontEnd(srv, max_batch=64, autostart=False) as fe:
            futs = [fe.submit(_blobs(4, seed=20 + k)) for k in range(3)]
            fe.drain()
            for f in futs:
                assert f.result(timeout=0).shape == (4, RANK)
        text = metrics.dump()
    finally:
        sh.uninstall()

    chrome = tmp_path / "trace.json"
    assert trace.export_chrome(str(chrome)) > 0
    names = {e["name"] for e in json.loads(chrome.read_text())["traceEvents"]}
    # nested spans from ALL THREE subsystems in one trace
    assert {"ingest.select_chunk", "ingest.merge", "stream.ingest_batch",
            "swap.publish", "serve.batch"} <= names

    # the metrics dump carries spectral health AND per-bucket serving series
    assert "spectral_eigval" in text and 'k="0"' in text
    assert "spectral_err_est" in text
    assert 'serve_service_ms_bucket{bucket="16"' in text
    assert "ingest_overlap_fraction" in text
    assert "stream_m" in text


# -------------------------------------------------------------------------
# span ids and parents, the profiler bridge, and the layer spans that the
# benchmark's per-layer metrics read
# -------------------------------------------------------------------------


def test_disabled_span_is_the_shared_null():
    assert not obs.enabled()
    assert obs.span("x.y", rows=3) is trace._NULL
    assert trace.span("z.w") is trace._NULL


def test_span_ids_and_parents_across_nesting_and_threads(obs_on):
    seen = {}

    def worker():
        with obs.span("thread.op") as sp:
            seen["thread"] = (sp.id, sp.parent)

    with obs.span("outer.op") as outer:
        with obs.span("first.child") as a:
            with obs.span("grand.child") as g:
                pass
        with obs.span("second.child") as b:
            # a span opened on another thread while outer.op is open here
            # has no parent: parents are per thread
            t = threading.Thread(target=worker)
            t.start()
            t.join()
    with obs.span("next.root") as nxt:
        pass
    assert outer.parent == 0 and nxt.parent == 0
    assert a.parent == b.parent == outer.id
    assert g.parent == a.id
    assert seen["thread"][1] == 0
    ids = [outer.id, a.id, g.id, b.id, nxt.id, seen["thread"][0]]
    assert len(set(ids)) == len(ids) and min(ids) > 0
    evs = {e["name"]: e for e in trace.events()}
    assert (evs["grand.child"]["id"], evs["grand.child"]["parent"]) == \
        (g.id, a.id)
    assert [evs[n]["depth"] for n in ("outer.op", "first.child",
                                      "grand.child", "thread.op")] == \
        [0, 1, 2, 0]


def test_chrome_export_carries_ids_and_parents(tmp_path, obs_on):
    with obs.span("outer.op") as outer:
        with obs.span("inner.op", rows=2) as inner:
            pass
    path = tmp_path / "trace.json"
    trace.export_chrome(str(path))
    args = {e["name"]: e["args"]
            for e in json.loads(path.read_text())["traceEvents"]}
    assert args["inner.op"] == {"rows": 2, "id": inner.id,
                                "parent": outer.id}
    assert args["outer.op"]["parent"] == 0


def _host_event_names(trace_dir) -> set:
    import glob
    import os

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                        recursive=True)
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(ev.name for ev in line.events)
    return names


def test_enabled_spans_land_on_the_profilers_host_line(tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("obs.test_disabled", rows=1):
            jnp.arange(4).block_until_ready()
        obs.enable()
        try:
            with obs.span("obs.test_enabled", rows=2):
                jnp.arange(4).block_until_ready()
        finally:
            obs.disable()
            trace.clear()
    finally:
        jax.profiler.stop_trace()
    names = _host_event_names(tmp_path)
    assert "obs.test_enabled" in names
    assert "obs.test_disabled" not in names


@contextlib.contextmanager
def _profiled(trace_dir):
    """A ``jax.profiler`` trace around the block."""
    import jax

    jax.profiler.start_trace(str(trace_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def test_profiled_span_records_only_under_a_profile(tmp_path):
    assert trace.profiled_span("x.y") is trace._NULL  # disabled
    obs.enable()
    try:
        assert trace.profiled_span("x.y") is trace._NULL  # no profile
        assert not trace.profiling()
        with _profiled(tmp_path):
            assert trace.profiling()
            with trace.profiled_span("obs.test_profiled", rows=3) as sp:
                pass
    finally:
        obs.disable()
    assert sp is not trace._NULL
    (ev,) = [e for e in trace.events() if e["name"] == "obs.test_profiled"]
    assert ev["rows"] == 3
    trace.clear()
    assert "obs.test_profiled" in _host_event_names(tmp_path)


def test_serve_batch_carries_request_ids_and_waits(tmp_path, obs_on):
    fe = BatchingFrontEnd(_StubServer(), max_batch=8, autostart=False)
    futs = [fe.submit(np.ones((k, 3), np.float32)) for k in (2, 3, 2, 4, 1)]
    with _profiled(tmp_path):
        assert fe.step() == 7   # 2 + 3 + 2; the 4-row request would overflow
        assert fe.step() == 5
    for f in futs:
        f.result(timeout=0)
    batches = [e for e in trace.events() if e["name"] == "serve.batch"]
    assert [(e["req_lo"], e["req_hi"]) for e in batches] == [(0, 2), (3, 4)]
    for e in batches:
        assert e["requests"] == e["req_hi"] - e["req_lo"] + 1
        assert 0.0 <= e["wait_ms_max"] <= e["wait_ms_sum"]
        assert e["wait_ms_sum"] <= e["requests"] * e["wait_ms_max"]
    # the second batch waited through the first one's service
    assert batches[1]["wait_ms_max"] >= batches[0]["wait_ms_max"]


def test_serve_batch_children_on_a_published_operator(tmp_path, obs_on):
    _, _, st = _state()
    srv = streaming.HotSwapServer(st)
    fe = BatchingFrontEnd(srv, max_batch=64, autostart=False)
    fe.submit(_blobs(5, seed=4))
    fe.submit(_blobs(3, seed=5))
    with _profiled(tmp_path):
        assert fe.step() == 8
    evs = trace.events()
    (batch,) = [e for e in evs if e["name"] == "serve.batch"]
    (coalesce,) = [e for e in evs if e["name"] == "serve.coalesce"]
    assert coalesce["parent"] == 0 and coalesce["requests"] == 2
    kids = {e["name"]: e for e in evs if e["parent"] == batch["id"]}
    assert set(kids) == {"project.prep", "project.launch", "swap.fetch"}
    assert kids["project.launch"]["chunks"] == 1
    assert kids["swap.fetch"]["sync_s"] == 0.0  # no sync of its own
    assert sum(k["dur_s"] for k in kids.values()) <= batch["dur_s"]


def _reference_rounds(x, eps, block):
    """Rounds and centers of blocked selection, run to exhaustion in float64
    with direct differences: each round takes the first ``block`` alive
    rows, keeps the greedy eps-separated prefix, and absorbs every alive
    row strictly within eps of a keeper."""
    x = np.asarray(x, np.float64)
    alive = np.ones(len(x), bool)
    rounds = m = 0
    while alive.any():
        kept = []
        for j in np.flatnonzero(alive)[:block]:
            if all(np.sum((x[j] - x[k]) ** 2) >= eps ** 2 for k in kept):
                kept.append(j)
        d2 = ((x[:, None, :] - x[kept][None]) ** 2).sum(-1).min(axis=1)
        alive &= ~(d2 < eps ** 2)
        rounds += 1
        m += len(kept)
    return rounds, m


@pytest.mark.parametrize("block", [1, 16])
def test_select_phase_rounds_match_a_reference_count(obs_on, block):
    from repro.core.shadow import shadow_select_blocked

    x, eps = _blobs(300, seed=11), 0.5
    centers, weights, _, m = shadow_select_blocked(x, eps, block=block)
    ref_rounds, ref_m = _reference_rounds(x, eps, block)
    evs = trace.events()
    phases = [e for e in evs if e["name"] == "select.phase"]
    assert len(phases) >= 2  # the alive set halves at least once
    assert sum(e["rounds"] for e in phases) == ref_rounds
    if block == 1:
        assert ref_rounds == ref_m  # one keeper a round
    assert m == ref_m == centers.shape[0] == sum(e["centers"]
                                                  for e in phases)
    assert weights.sum() == 300
    assert phases[0]["n_alive"] == 300 and phases[0]["n_pad"] == 512
    for i, ph in enumerate(phases):
        kids = [e["name"] for e in evs if e["parent"] == ph["id"]]
        # the rows cross once, on the first phase, and are padded there
        assert kids == (["select.put", "select.pad"] if i == 0 else []) + [
            "select.rounds", "select.compact"]
    (put,) = [e for e in evs if e["name"] == "select.put"]
    assert put["bytes"] == 300 * 6 * 4  # the rows, once, unpadded


@pytest.mark.parametrize("m,lobpcg", [(1100, True), (200, False)])
def test_fit_solve_counts_lobpcg_iterations(obs_on, m, lobpcg):
    from repro.core.pipeline import fit_centers

    rng = np.random.default_rng(m)
    c = rng.normal(size=(m, 4)).astype(np.float32) * 3.0
    w = rng.integers(1, 5, m).astype(np.float32)
    model = fit_centers(c, w, int(w.sum()), gaussian(1.0, backend="dense"),
                        4, matfree=False)
    assert model.projector.shape == (m, 4)
    evs = trace.events()
    (solve,) = [e for e in evs if e["name"] == "fit.solve"]
    (stage,) = [e for e in evs if e["name"] == "fit.stage"]
    # an exact host set of m >= 128 rows is fitted at its own size
    assert (solve["m"], solve["cap"], solve["matfree"]) == (m, m, False)
    if lobpcg:
        assert solve["lobpcg_iters"] > 0
    else:
        assert solve["lobpcg_iters"] == 0
    assert stage["t_s"] + stage["dur_s"] <= solve["t_s"]


def test_disabled_spans_never_sync(monkeypatch):
    """Off, the serve, selection and fit spans add no device sync: the
    null span's ``sync`` hands its value back untouched."""
    import jax

    from repro.core.pipeline import fit_centers
    from repro.core.shadow import shadow_select_blocked

    assert not obs.enabled()
    _, _, st = _state()
    srv = streaming.HotSwapServer(st)
    fe = BatchingFrontEnd(srv, max_batch=64, autostart=False)

    def no_sync(value):
        raise AssertionError("a disabled span blocked on the device")

    monkeypatch.setenv("REPRO_AUTOTUNE", "0")  # no plan measurement
    monkeypatch.setattr(jax, "block_until_ready", no_sync)
    fut = fe.submit(_blobs(5, seed=6))
    assert fe.step() == 5 and fut.result(timeout=0).shape == (5, RANK)
    c, w, _, m = shadow_select_blocked(_blobs(200, seed=7), 0.5, block=16)
    assert w.sum() == 200
    model = fit_centers(c, w, 200, gaussian(SIGMA), 2)
    assert model.projector.shape == (m, 2)
    assert trace.events() == []
