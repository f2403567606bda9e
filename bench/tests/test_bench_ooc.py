"""The out-of-core fit cells at a tiny size (interpret mode): the twin end
to end, the four-chip cell on four host devices, the faults their check
has to catch, the two-level reference against the program, and the
readers of their per-layer metrics."""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import tiny
from bench import harness, reference_ooc, run, trace

SEED = tiny.SEED
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def cell(name="pendigits-d16.fit-ooc", n=8192, chunk=2048):
    """A committed out-of-core cell at ``n`` rows in chunks of ``chunk``."""
    c = harness.load_cell(name)
    c.config = json.loads(json.dumps(c.config))
    c.config["n"] = n
    c.mix = dict(c.mix, chunk=chunk, check_rows=256)
    return c


def _run(c=None):
    result, ctx = run.run(c or cell(), SEED, 0.5, False, run.T_START)
    json.dumps(result)
    return result, ctx


def test_fit_ooc_cell_runs_and_is_correct():
    c = cell()
    result, ctx = _run(c)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "fit_rows_per_s"}
    assert set(result["checks"]) == set(c.limits)
    job = ctx["jobs"][0]
    assert job["m"] > 0 and job["merge"]["kept"] == job["m"]
    assert job["merge"]["gathered"] == job["merge"]["absorbed"] + job["m"]


def test_the_fits_readers_read_on_the_twin():
    """The twin fits through ``fit_centers``, so the fit-inmem cell's
    ``lobpcg_iters`` and ``fit_mfu_pct`` read there too."""
    from repro import obs
    from repro.obs import trace as otrace

    otrace.set_ring(1 << 16)
    obs.enable()
    try:
        result, ctx = _run()
    finally:
        obs.disable()
    assert result["correct"], result["checks"]
    spans = otrace.events()
    assert any(e["name"] == "fit.solve" for e in spans)
    assert harness.reader("lobpcg_iters.fit")({"spans": spans}) >= 0
    ctx["peaks"] = {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}
    assert harness.reader("fit_mfu_pct.fit")(ctx) > 0
    names = {m["name"] for m in
             harness.load_cell("pendigits-d16.fit-ooc").per_layer}
    assert {"lobpcg_iters.fit", "fit_mfu_pct.fit"} <= names


def test_fit_ooc_4chip_cell_runs_on_four_devices():
    """The four-chip cell through ``run.run`` on four host devices: the
    chunks split over a mesh, and the check passes."""
    code = f"""
import json, sys
sys.path[:0] = [{os.path.dirname(os.path.abspath(__file__))!r}]
import tiny
from bench import run
import test_bench_ooc as t
result, ctx = run.run(t.cell("pendigits-d16.fit-ooc-4chip", 8192, 4096),
                      t.SEED, 0.5, False, run.T_START)
assert result["correct"], result["checks"]
assert ctx["jobs"][0]["merge"]["gathered"] > ctx["jobs"][0]["m"]
print("OK", json.dumps(result["checks"]))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0 and "OK" in r.stdout, \
        (r.stdout[-2000:], r.stderr[-3000:])


def _patched(monkeypatch, change):
    """``ingest_fit`` whose model ``change`` alters."""
    import repro.core as core
    orig = core.ingest_fit

    def altered(source, *a, **kw):
        model, st = orig(source, *a, **kw)
        return change(model, source), st

    monkeypatch.setattr(core, "ingest_fit", altered)


def test_an_added_mass_is_caught(monkeypatch):
    def change(model, _):
        w = np.array(model.weights)
        w[0] += 1.0
        return dataclasses.replace(model, weights=w)

    _patched(monkeypatch, change)
    result, _ = _run()
    assert not result["correct"]
    assert result["checks"]["mass_err"]["value"] == 1.0


def test_a_chunk_left_out_is_caught(monkeypatch):
    import repro.core as core
    orig = core.ingest_fit

    class SkipsOne:
        def __init__(self, src):
            self.src, self.d = src, src.d

        def chunks(self, start=0):
            for k, item in enumerate(self.src.chunks(start)):
                if k != 1:
                    yield item

    monkeypatch.setattr(core, "ingest_fit",
                        lambda src, *a, **kw: orig(SkipsOne(src), *a, **kw))
    result, _ = _run()
    assert not result["correct"]
    assert result["checks"]["mass_err"]["value"] == 2048.0


def test_a_center_moved_by_three_eps_is_caught(monkeypatch):
    eps = {}

    def change(model, _):
        c = np.array(model.centers)
        c[int(np.argmax(model.weights)), 0] += 3.0 * eps["eps"]
        return dataclasses.replace(model, centers=c)

    c = cell()
    from bench import data
    eps["eps"] = data.bandwidth(c.config["dataset"]) / c.config["ell"]
    _patched(monkeypatch, change)
    result, _ = _run(c)
    assert not result["correct"]
    assert result["checks"]["cover_excess"]["value"] > \
        result["checks"]["cover_excess"]["limit"]


def test_an_altered_eigenvalue_is_caught(monkeypatch):
    def change(model, _):
        lam = np.array(model.eigvals)
        lam[0] *= 1.1
        return dataclasses.replace(model, eigvals=lam)

    _patched(monkeypatch, change)
    result, _ = _run()
    assert not result["correct"]
    assert result["checks"]["eig_err"]["value"] > \
        result["checks"]["eig_err"]["limit"]


@pytest.mark.parametrize("chips", [1, 2])
def test_two_level_reference_equals_the_program(chips):
    """The plain two-level reference over chunks of 1024 rows split over
    ``chips`` keeps the centers and masses of the program's merge (no
    budget reached).  Two chips run here as a mesh's merge folds them:
    level 1 on each share, the shares' candidate buffers side by side in
    chip order, one fold a chunk."""
    import jax.numpy as jnp

    from bench import data
    from repro.core import shadow

    ds = cell().config["dataset"]
    x = np.asarray(data.rows(ds, 4096, SEED, 0))
    eps = data.bandwidth(ds) / cell().config["ell"]
    ref_c, ref_w = reference_ooc.select(x, eps, 1024, chips, block=64)
    eps2 = jnp.float32(eps) ** 2
    per = 1024 // chips
    state = shadow.merge_init(16, shadow.merge_capacity(4096, None))
    for lo in range(0, 4096, 1024):
        cs, ws = [], []
        for j in range(chips):
            _, c1, w1, _, _ = shadow._blocked_select_device(
                jnp.asarray(x[lo + j * per: lo + (j + 1) * per]), eps2, 64,
                jnp.ones((per,), bool), jnp.asarray(0, jnp.int32))
            cs.append(c1)
            ws.append(w1)
        state = shadow._merge_chunk(state, jnp.concatenate(cs),
                                    jnp.concatenate(ws), eps2, 64, None)
    got = shadow.merge_to_host(state)
    np.testing.assert_array_equal(got["centers"], ref_c)
    np.testing.assert_array_equal(got["weights"], ref_w)
    assert ref_w.sum() == 4096


# --------------------------------------------------------------------------
# readers
# --------------------------------------------------------------------------


def _span(name, dur_s=0.0, **attrs):
    return dict(name=name, dur_s=dur_s, t_s=0.0, sync_s=0.0, **attrs)


def _ooc_ctx():
    """Two jobs of 0.5 s and 0.3 s, three chunks each."""
    spans = []
    for k, chunk_s in enumerate((0.02, 0.03, 0.04, 0.01, 0.02, 0.03)):
        spans += [_span("ingest.feed_wait", 0.005),
                  _span("ingest.merge", 0.001),
                  _span("ingest.select_chunk", chunk_s, chunk=k % 3)]
    sel = dict(m=100, cap=1024, gathered=300, absorbed=200, kept=100,
               spilled=0, absorb_pairs=1e6, reselect_pairs=2e5, d=16)
    spans += [_span("ingest.select", 0.40, **sel),
              _span("ingest.fit", 0.10, m=100),
              _span("ingest.select", 0.25, **sel),
              _span("ingest.fit", 0.05, m=100)]
    return {"spans": spans,
            "jobs": [{"wall_s": 0.5}, {"wall_s": 0.3}],
            "trace": {"busy_s": 2.0, "window_s": 10.0,
                      "kernel_s": {"ingest_merge": 0.5, "all_gather": 0.1}},
            "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}}


def test_ingest_readers_by_hand():
    ctx = _ooc_ctx()
    # 6 chunk spans, 0.15 s in all
    assert harness.reader("ingest_chunk_ms.ooc")(ctx) == pytest.approx(25.0)
    # 6 waits of 5 ms over 0.8 s of jobs
    assert harness.reader("ingest_stall_pct.ooc")(ctx) == pytest.approx(3.75)
    # fits 0.15 s of 0.8 s
    assert harness.reader("ingest_fit_pct.ooc")(ctx) == pytest.approx(18.75)
    # per stream: 2*16*1.2e6 = 3.84e7 ops -> 38.4 us at 1e12; bytes
    # 4*17*300 = 20400 -> 20.4 us at 1e9: compute-bound, 38.4 us a stream,
    # two streams over 0.5 s of merge device time
    assert harness.reader("merge_roofline.ooc")(ctx) == \
        pytest.approx(100 * 2 * 38.4e-6 / 0.5)
    assert harness.reader("allgather_pct.ooc4")(ctx) == pytest.approx(5.0)
    assert harness.reader("device_idle_pct.ooc")(ctx) == pytest.approx(80.0)


def test_ingest_readers_read_nothing_without_their_spans():
    bare = {"spans": [], "jobs": [{"wall_s": 1.0}], "trace": None,
            "peaks": None}
    for name in ("ingest_chunk_ms.ooc", "ingest_stall_pct.ooc",
                 "ingest_fit_pct.ooc", "merge_roofline.ooc",
                 "allgather_pct.ooc4", "chip_busy_skew.ooc4"):
        assert harness.reader(name)(bare) is None, name


def _four_device_profile():
    """A window of 100 us and four device planes: ops busy 40, 20, 20 and
    20 us, the merge program running 30, 10, 10 and 10 us; device 0 runs a
    10 us all-gather."""
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "four_device.xspace.txt")) as f:
        return ProfileData.from_text_proto(f.read())


def test_chip_busy_skew_and_allgather_on_four_devices():
    pd = _four_device_profile()
    # programs: busiest 30 us over the mean (30 + 10 + 10 + 10) / 4 = 15
    assert harness.reader("chip_busy_skew.ooc4")({"profile": pd}) == \
        pytest.approx(2.0)
    red = trace.reduce(pd, {"all_gather": harness.cost("all_gather"),
                            "ingest_merge": harness.cost("ingest_merge")})
    assert red["devices"] == 4
    assert red["busy_s"] == pytest.approx(25e-6)
    # the all-gather's 10 us on one device, averaged over four
    assert red["kernel_s"]["all_gather"] == pytest.approx(2.5e-6)
    # the merge module spans 30 us on device 0 and 10 us on the others
    assert red["kernel_s"]["ingest_merge"] == pytest.approx(15e-6)
    assert harness.reader("allgather_pct.ooc4")({"trace": red}) == \
        pytest.approx(10.0)


def test_the_kinds_setup_refuses_a_program_without_the_device_merge(
        monkeypatch):
    import repro.core.ingest_pipeline as ip

    @dataclasses.dataclass
    class Old:
        chunks: int = 0

    monkeypatch.setattr(ip, "IngestStats", Old)
    drv = harness.load_class("fit_ooc")(cell(), SEED)
    with pytest.raises(RuntimeError, match="no on-device streaming merge"):
        drv.setup()
