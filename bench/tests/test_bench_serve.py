"""The serve cells end to end at a tiny size (interpret mode), and the
faults and the control that their check has to catch."""
from __future__ import annotations

import json

import numpy as np
import pytest

import tiny
from bench import reference, run

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def _run(kind, monkeypatch=None, transform=None):
    cell = tiny.cell(kind)
    if transform is not None:
        from repro.streaming import HotSwapServer
        orig = HotSwapServer.transform
        monkeypatch.setattr(HotSwapServer, "transform",
                            lambda self, x, **kw: transform(self, orig, x))
    result, ctx = run.run(cell, tiny.SEED, 1.5, False, run.T_START)
    json.dumps(result)  # one JSON line
    return cell, result, ctx


@pytest.mark.parametrize("kind", ["serve_closed"])
def test_serve_cell_runs_and_is_correct(kind):
    cell, result, ctx = _run(kind)
    assert list(result) == KEYS
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert set(result["checks"]) == set(cell.limits)
    assert ctx["serve"]["rows"] > 0 and ctx["serve"]["batches"] > 0


def test_setup_leaves_out_the_reference(monkeypatch):
    """The seconds the plain reference spends making the served operator
    are not the program's set-up."""
    import time

    from bench import harness

    orig = reference.fit

    def slow_fit(*a, **kw):
        time.sleep(3.0)
        return orig(*a, **kw)

    monkeypatch.setattr(reference, "fit", slow_fit)
    t0 = harness.clock()
    result, ctx = run.run(tiny.cell("serve_closed"), tiny.SEED, 0.3, False, t0)
    total = harness.clock() - t0
    assert ctx["reference_s"] >= 3.0
    # set-up, reference, window and check add up to the whole run
    assert result["metrics"]["setup_s"]["value"] <= total - 3.0 - 0.3


def test_an_altered_answer_is_caught(monkeypatch):
    def alter(server, orig, x):
        z = np.array(orig(server, x))
        z[0, 0] += 1e-3 * np.max(np.abs(z))  # the batch's first row
        return z

    _, result, _ = _run("serve_closed", monkeypatch, alter)
    assert not result["correct"]
    assert result["checks"]["served_rel_err"]["value"] > \
        result["checks"]["served_rel_err"]["limit"]


def test_half_of_the_batch_left_out_is_caught(monkeypatch):
    def half(server, orig, x):
        z = np.array(orig(server, x))
        z[z.shape[0] // 2:] = 0.0
        return z

    _, result, _ = _run("serve_closed", monkeypatch, half)
    assert not result["correct"]


def test_the_control_separates():
    """The reference at Precision.HIGH in the program's place reads well
    above the program on the same requests.  On the CPU, HIGH is the
    three-pass emulation, which reads about a quarter of the chip's native
    HIGH; the chip's readings set the limit (PERF.md)."""
    from bench import harness

    cell = tiny.cell("serve_closed")
    drv = harness.load_class("serve_closed")(cell, tiny.SEED)
    drv.setup()
    drv.window(1.0)
    drv.close()
    prog, ctrl = drv.check(), drv.control()
    assert prog["served_rel_err"] <= cell.limits["served_rel_err"]["limit"]
    assert ctrl["served_rel_err"] > 3 * prog["served_rel_err"]


def test_reference_high_precision_is_three_passes():
    """The control's product drops only the low-by-low term."""
    rng = np.random.default_rng(0)
    a = rng.normal(size=(64, 32)).astype(np.float32)
    b = rng.normal(size=(32, 16)).astype(np.float32)
    exact = a.astype(np.float64) @ b.astype(np.float64)
    hi = np.asarray(reference.dot(a, b, "high"))
    err = np.max(np.abs(hi - exact)) / np.max(np.abs(exact))
    assert 1e-7 < err < 1e-4
    assert np.max(np.abs(np.asarray(reference.dot(a, b)) - exact)) \
        / np.max(np.abs(exact)) < 1e-6
