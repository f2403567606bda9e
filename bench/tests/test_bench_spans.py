"""The per-layer metrics that read the program's spans, on hand-built
``ctx["spans"]`` with the numbers worked by hand, and nothing read where a
program records none of their spans."""
from __future__ import annotations

import pytest

import tiny  # noqa: F401  (puts the checkout on the path)
from bench import harness


def _span(name, dur_s=0.0, **attrs):
    return dict(name=name, dur_s=dur_s, t_s=0.0, sync_s=0.0, **attrs)


def _serve_spans():
    """Two served batches: 3 requests that waited 30 ms in all, then 1
    request that waited 12 ms."""
    return [
        _span("serve.coalesce", 0.0002, requests=3),
        _span("project.prep", 0.0004),
        _span("project.launch", 0.0020, chunks=1),
        _span("swap.fetch", 0.0006),
        _span("serve.batch", 0.0031, rows=1536, requests=3, req_lo=0,
              req_hi=2, wait_ms_sum=30.0, wait_ms_max=14.0),
        _span("serve.coalesce", 0.0001, requests=1),
        _span("project.prep", 0.0003),
        _span("project.launch", 0.0018, chunks=1),
        _span("swap.fetch", 0.0004),
        _span("serve.batch", 0.0027, rows=512, requests=1, req_lo=3,
              req_hi=3, wait_ms_sum=12.0, wait_ms_max=12.0),
    ]


def _fit_ctx():
    """Two jobs of 0.5 s and 0.3 s; the first ran two selection phases,
    the second one, and both fits ran LOBPCG."""
    spans = [
        _span("select.pad", 0.10), _span("select.put", 0.05),
        _span("select.rounds", 0.12), _span("select.compact", 0.02),
        _span("select.phase", 0.30, n_pad=1024, n_alive=1000, centers=40,
              rounds=7),
        _span("select.pad", 0.01), _span("select.put", 0.01),
        _span("select.rounds", 0.04), _span("select.compact", 0.01),
        _span("select.phase", 0.08, n_pad=512, n_alive=500, centers=10,
              rounds=3),
        _span("fit.solve", 0.05, m=50, cap=128, matfree=False,
              lobpcg_iters=12),
        _span("select.pad", 0.06), _span("select.put", 0.03),
        _span("select.rounds", 0.10), _span("select.compact", 0.03),
        _span("select.phase", 0.22, n_pad=1024, n_alive=1000, centers=45,
              rounds=8),
        _span("fit.solve", 0.05, m=45, cap=128, matfree=False,
              lobpcg_iters=20),
    ]
    return {"spans": spans, "jobs": [{"wall_s": 0.5}, {"wall_s": 0.3}]}


CASES = {
    # (30 + 12) ms over 4 requests
    "serve_queue_ms.bulk": (lambda: {"spans": _serve_spans()}, 10.5),
    # (0.2 + 0.4 + 0.1 + 0.3) ms over 2 batches
    "serve_prep_ms.bulk": (lambda: {"spans": _serve_spans()}, 0.5),
    # (2.0 + 1.8) ms over 2 batches
    "serve_launch_ms.bulk": (lambda: {"spans": _serve_spans()}, 1.9),
    # (0.6 + 0.4) ms over 2 batches
    "serve_fetch_ms.bulk": (lambda: {"spans": _serve_spans()}, 0.5),
    # pad + put: 0.10 + 0.05 + 0.01 + 0.01 + 0.06 + 0.03 = 0.26 s of 0.8 s
    "select_stage_pct.fit": (_fit_ctx, 32.5),
    # rounds: 0.12 + 0.04 + 0.10 = 0.26 s of 0.8 s
    "select_device_pct.fit": (_fit_ctx, 32.5),
    # 7 + 3 + 8 rounds over 2 jobs
    "select_rounds.fit": (_fit_ctx, 9.0),
    # (12 + 20) / 2 solves
    "lobpcg_iters.fit": (_fit_ctx, 16.0),
}


@pytest.mark.parametrize("metric", sorted(CASES))
def test_reader_by_hand(metric):
    ctx, want = CASES[metric]
    assert harness.reader(metric)(ctx()) == pytest.approx(want)


#: What a program without this tracing records: the harness's job timings
#: and the one serve span it has, without request ids or waits.
_OLD = {"spans": [_span("serve.batch", 0.003, rows=512, bucket=512,
                        requests=1)],
        "jobs": [{"wall_s": 0.5}]}


@pytest.mark.parametrize("metric", sorted(CASES))
@pytest.mark.parametrize("ctx", [{}, {"spans": None, "jobs": None},
                                 {"spans": [], "jobs": []}, _OLD],
                         ids=["empty", "none", "no-spans", "older-program"])
def test_reader_reads_nothing_without_its_spans(metric, ctx):
    assert harness.reader(metric)(ctx) is None


def test_metrics_are_listed_for_the_cells_that_record_them():
    serve = {m["name"] for m in
             harness.load_cell("pendigits-d16.serve-bulk").per_layer}
    fit = {m["name"] for m in
           harness.load_cell("usps-d256.fit-inmem").per_layer}
    assert {n for n in CASES if n.endswith(".bulk")} <= serve
    assert {n for n in CASES if n.endswith(".fit")} <= fit
