"""The traffic generator and the closed loop: seeded requests, a mix whose
load does not exist refused, and rows counted only for requests completed
inside the window."""
from __future__ import annotations

import json
import threading
from concurrent.futures import Future
from types import SimpleNamespace

import numpy as np
import pytest

import tiny
from bench import harness, traffic

MIX = {"kind": "serve_closed", "clients": 2, "rows": 8, "max_batch": 512,
       "slo_ms": 50.0, "pool_rows": 4096, "max_requests": 1000,
       "check_requests": 4, "sample_of": 16}


def test_closed_offsets_are_seeded_and_in_range():
    a = traffic.closed_offsets(MIX, tiny.SEED, 500, 4096)
    np.testing.assert_array_equal(a, traffic.closed_offsets(MIX, tiny.SEED,
                                                            500, 4096))
    assert a.min() >= 0 and a.max() + MIX["rows"] <= 4096
    assert not np.array_equal(a, traffic.closed_offsets(MIX, 2 ** 40 + 7,
                                                        500, 4096))


def test_sample_is_seeded_and_bounded():
    a = traffic.sample(5, 100, 10)
    np.testing.assert_array_equal(a, traffic.sample(5, 100, 10))
    assert len(set(a.tolist())) == 10 and a.max() < 100
    assert len(traffic.sample(5, 3, 10)) == 3


def test_an_unknown_kind_is_refused(tmp_path):
    for folder in ("traffic", "kinds"):
        (tmp_path / folder).mkdir()
    (tmp_path / "kinds" / "serve_closed.py").write_text("LOAD = None\n")
    for name, mix in (("sideways", dict(MIX, kind="serve_sideways")),
                      ("none", {k: v for k, v in MIX.items()
                                if k != "kind"})):
        (tmp_path / "traffic" / f"{name}.json").write_text(json.dumps(mix))
        with pytest.raises(ValueError, match=f"traffic '{name}': no load"):
            traffic.load(str(tmp_path), name)
    (tmp_path / "traffic" / "ok.json").write_text(json.dumps(MIX))
    assert traffic.load(str(tmp_path), "ok") == MIX


class _SlowFrontEnd:
    """Answers each request ``delay_s`` after it was submitted, or fails
    it when ``fail`` is set."""

    def __init__(self, delay_s: float, fail: bool = False):
        self.delay_s, self.fail = delay_s, fail
        self.calls, self.lock = 0, threading.Lock()
        self.timers = []

    def submit(self, x):
        with self.lock:
            self.calls += 1
        f = Future()
        if self.fail:
            f.set_exception(RuntimeError("shed"))
            return f
        t = threading.Timer(self.delay_s, f.set_result,
                            [np.zeros((x.shape[0], 2), np.float32)])
        self.timers.append(t)
        t.start()
        return f

    def snapshot(self):
        return SimpleNamespace(rows=8 * self.calls, batches=self.calls,
                               requests=self.calls)


def _closed(fe, **mix):
    cls = harness.load_class("serve_closed")
    drv = cls.__new__(cls)
    drv.mix, drv.seed = dict(MIX, **mix), 3
    drv.pool = np.zeros((4096, 2), np.float32)
    drv.fe = fe
    return drv


def test_closed_loop_counts_only_requests_completed_in_the_window():
    fe = _SlowFrontEnd(delay_s=0.1)
    drv = _closed(fe)
    t_open, t_end = drv.window(1.0)
    # two clients, 0.1 s a request: about ten each inside the second; the
    # requests in flight as the window closes complete after it and do
    # not count
    in_window = drv.rows_per_s * 1.0 / MIX["rows"]
    assert in_window == int(in_window) and 14 <= in_window <= 20
    assert drv.attempted == fe.calls and drv.failed == 0
    assert drv.attempted >= in_window + 1
    assert t_end - t_open >= 1.0
    assert set(drv.results) <= set(range(drv.attempted))


def test_closed_loop_counts_failed_requests():
    drv = _closed(_SlowFrontEnd(delay_s=0.0, fail=True))
    drv.window(0.2)
    assert drv.attempted == 2 and drv.failed == 2 and drv.rows_per_s == 0


def test_closed_loop_refuses_to_run_out_of_requests():
    drv = _closed(_SlowFrontEnd(delay_s=0.0), max_requests=5)
    with pytest.raises(RuntimeError, match="raise max_requests"):
        drv.window(0.5)
