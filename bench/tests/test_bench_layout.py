"""The benchmark's files: BENCHMARK.json against its contract, the kernel
count against a hand count, the peak table, the exit without a TPU, and a
cell, a mix, a load and a metric added as new files only."""
from __future__ import annotations

import hashlib
import json
import os
import re
import shutil

import pytest

import tiny
from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _bench():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_keeps_its_contract():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "bench/run.py"] and b["paths"] == ["bench"]
    assert 1 <= b["run_seconds"] <= 51
    cells = {w["name"]: w for w in b["workloads"]}
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert os.path.exists(os.path.join(tiny.ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        harness.load_cell(w["name"])  # config, mix and limits are there
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in b["end_to_end"]}
    assert e2e == {"setup_s", "serve_rows_per_s", "fit_rows_per_s"}
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and set(m["workloads"]) <= set(cells)
        harness.reader(m["name"])  # its reader exists
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for name in cells:
        cell = harness.load_cell(name)
        got = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in got and len(got) >= 2, name
        assert cell.per_layer, name
        assert cell.limits, name
    assert len(json.dumps(b)) < 64 * 1024


def test_kpca_project_count_by_hand():
    c = harness.cost("kpca_project")
    # 10 rows in 2 batches against m=4 centers, d=3, r=2:
    # each row 2*4*3 + 2*4*2 = 40 FLOPs
    assert c.flops(10, 4, 3, 2) == 400.0
    # rows in and out: 4*10*(3+2) = 200 bytes; the operator per batch:
    # 4*4*(3+2) = 80 bytes, twice
    assert c.bytes_moved(10, 2, 4, 3, 2) == 360.0
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 1000.0}
    assert c.least_time(10, 2, 4, 3, 2, peaks) == 4.0      # compute-bound
    peaks["bf16_flops_per_s"] = 1e6
    assert c.least_time(10, 2, 4, 3, 2, peaks) == 0.36     # memory-bound


def test_peak_table_rejects_an_unknown_device():
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        harness.peaks("TPU v9 imaginary")


def test_main_exits_without_a_tpu(capsys):
    from bench import run

    rc = run.main(["--workload", "pendigits-d16.serve-bulk", "--seed",
                   "1", "--seconds", "1"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert "no TPU" in err
    assert "{" not in out  # no result line


def _digest(root):
    out = {}
    for dirpath, _, files in os.walk(root):
        for f in files:
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_cell_mix_and_metric_are_added_as_new_files(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(tiny.ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), root)
    before = _digest(root / "bench")
    b = bench = _bench()

    cfg = json.loads((root / "bench/configs/pendigits-d16-n1m.json")
                     .read_text())
    cfg["name"], cfg["n"] = "pendigits-d16-n64k", 65536
    (root / "bench/configs/pendigits-d16-n64k.json").write_text(
        json.dumps(cfg))
    (root / "bench/traffic/serve-bulk-small.json").write_text(json.dumps(
        {"kind": "serve_closed", "clients": 4, "rows": 8, "max_batch": 512,
         "slo_ms": 50.0, "pool_rows": 4096, "max_requests": 1000,
         "check_requests": 8, "sample_of": 64}))
    # a new kind of load, with a mix of its own
    (root / "bench/kinds/serve_replay.py").write_text(
        "from bench.loads import Serve\n"
        "class ServeReplay(Serve):\n"
        "    pass\n"
        "LOAD = ServeReplay\n")
    (root / "bench/traffic/replay.json").write_text(json.dumps(
        {"kind": "serve_replay", "pool_rows": 4096}))
    (root / "bench/limits/pendigits-d16-n64k.serve-small.json").write_text(
        json.dumps({"served_rel_err": {"limit": 1e-5}}))
    (root / "bench/metrics/serve_requests_per_batch.py").write_text(
        "def read(ctx):\n"
        "    s = ctx.get('serve')\n"
        "    return s['requests'] / s['batches'] if s else None\n")
    bench["configs"].append({"name": "pendigits-d16-n64k", "source": "x",
                             "file": "bench/configs/pendigits-d16-n64k.json",
                             "reduced": ["n"], "why": "x"})
    bench["workloads"].append({"name": "pendigits-d16-n64k.serve-small",
                               "config": "pendigits-d16-n64k",
                               "traffic": "serve-bulk-small", "chips": 1,
                               "why": "x"})
    bench["end_to_end"][1]["workloads"].append(
        "pendigits-d16-n64k.serve-small")
    bench["per_layer"].append({"name": "serve_requests_per_batch.small",
                               "unit": "requests", "better": "higher",
                               "source": "program_counter",
                               "layer": "serving front end",
                               "moves": "serve_rows_per_s",
                               "workloads": ["pendigits-d16-n64k.serve-small"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    cell = harness.load_cell("pendigits-d16-n64k.serve-small", str(root))
    assert cell.config["n"] == 65536 and cell.mix["rows"] == 8
    assert [m["name"] for m in cell.end_to_end] == ["setup_s",
                                                    "serve_rows_per_s"]
    from bench import traffic
    assert traffic.load(str(root / "bench"), "replay")["kind"] == \
        "serve_replay"
    assert harness.load_class("serve_replay", str(root)).__name__ == \
        "ServeReplay"
    assert [m["name"] for m in cell.per_layer] == \
        ["serve_requests_per_batch.small"]
    from bench import run
    got = run.per_layer(cell, {"serve": {"requests": 6, "batches": 3}},
                        str(root))
    assert got == {"serve_requests_per_batch.small":
                   {"value": 2.0, "unit": "requests"}}
    after = _digest(root / "bench")
    assert {k: after[k] for k in before} == before  # nothing edited
