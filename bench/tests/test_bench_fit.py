"""The fit cells end to end at a tiny size (interpret mode), and the
faults and the control that their check has to catch."""
from __future__ import annotations

import json

import numpy as np
import pytest

import tiny
from bench import reference, run


def _run(kind):
    result, ctx = run.run(tiny.cell(kind), tiny.SEED, 0.5, False,
                          run.T_START)
    json.dumps(result)
    return result, ctx


@pytest.mark.parametrize("kind", ["fit_inmem"])
def test_fit_cell_runs_and_is_correct(kind):
    cell = tiny.cell(kind)
    result, ctx = _run(kind)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "fit_rows_per_s"}
    assert ctx["reference_s"] == 0.0  # the fit cells' set-up is all program
    assert set(result["checks"]) == set(cell.limits)
    assert all(j["m"] > 0 for j in ctx["jobs"])


def test_an_altered_weight_is_caught(monkeypatch):
    import repro.core as core
    orig = core.shadow_rsde

    def altered(*a, **kw):
        rsde = orig(*a, **kw)
        rsde.weights = np.array(rsde.weights)
        rsde.weights[0] += 1.0
        return rsde

    monkeypatch.setattr(core, "shadow_rsde", altered)
    result, _ = _run("fit_inmem")
    assert not result["correct"]
    assert result["checks"]["mass_err"]["value"] == 1.0


@pytest.mark.parametrize("kind", ["fit_inmem"])
def test_half_of_the_rows_left_out_is_caught(monkeypatch, kind):
    import repro.core as core
    orig = core.shadow_rsde

    def half(src, *a, **kw):
        return orig(src[: len(src) // 2], *a, **kw)

    monkeypatch.setattr(core, "shadow_rsde", half)
    result, _ = _run(kind)
    assert not result["correct"]
    assert result["checks"]["mass_err"]["value"] > 0


def test_an_altered_eigenvalue_is_caught(monkeypatch):
    import dataclasses
    import repro.core as core
    orig = core.fit_centers

    def altered(*a, **kw):
        model = orig(*a, **kw)
        lam = np.array(model.eigvals)
        lam[0] *= 1.1
        return dataclasses.replace(model, eigvals=lam)

    monkeypatch.setattr(core, "fit_centers", altered)
    result, _ = _run("fit_inmem")
    assert not result["correct"]
    assert result["checks"]["eig_err"]["value"] > \
        result["checks"]["eig_err"]["limit"]


def test_reference_selection_partitions_and_covers():
    from bench import data

    ds = tiny.cell("serve_closed").config["dataset"]
    x = np.asarray(data.rows(ds, 2048, tiny.SEED, 0))
    eps = data.bandwidth(ds) / 3.0
    idx, w, a = reference.select(x, eps)
    assert w.sum() == 2048 and np.all(a >= 0)
    np.testing.assert_array_equal(np.bincount(a, minlength=len(w)), w)
    excess, outside = reference.cover(x, x[idx], eps, assign=a)
    assert excess < 0 and outside == 0
    deficit, close = reference.separation(x[idx], eps)
    assert deficit <= 1e-6 and close == 0


def test_the_control_separates():
    """The reference's own fit at Precision.HIGH in the program's place
    reads well above the program's fit on the held-out transform (CPU:
    the three-pass emulation; the chip's native readings set the limit)."""
    from bench import harness

    cell = tiny.cell("fit_inmem")
    drv = harness.load_class("fit_inmem")(cell, tiny.SEED)
    drv.setup()
    drv.window(0.2)
    prog, ctrl = drv.check(), drv.control()
    ok, _ = harness.compare(prog, cell.limits)
    assert ok, prog
    assert ctrl["transform_err"] > 3 * prog["transform_err"]
