"""The trace reduction on a small trace kept in ``data/``: a host window
annotation, one TPU plane with modules and ops, numbers worked by hand."""
from __future__ import annotations

import os
from types import SimpleNamespace

import pytest

import tiny
from bench import harness, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "synthetic.xspace.txt")) as f:
        return ProfileData.from_text_proto(f.read())


def _kernels():
    """The transform's count module, and a kernel named both by its
    modules and by its op inside a loop (as a Pallas kernel is)."""
    assign = SimpleNamespace(MODULES=(r"^jit__assign_call",),
                             OPS=(r"^%_assign_call",))
    return {"kpca_project": harness.cost("kpca_project"),
            "shadow_assign": assign}


def test_reduction_by_hand(synthetic):
    red = trace.reduce(synthetic, _kernels())
    # window: the host annotation, 100 us from t=1 ms
    assert red["window_s"] == pytest.approx(100e-6)
    # ops [5,11] and [9,15] overlap -> 10 us; [40,50] -> 10 us; [95,105]
    # is cut at the window's end -> 5 us
    assert red["busy_s"] == pytest.approx(25e-6)
    # the transform is its XLA module; the assign kernel is its op [40, 50]
    # and a module of its own [42, 52], counted once where they overlap
    assert red["kernel_s"]["kpca_project"] == pytest.approx(10e-6)
    assert red["kernel_s"]["shadow_assign"] == pytest.approx(12e-6)
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["%fusion.4 = f32[512,4096] fusion(...)"] == pytest.approx(11e-6)
    # the longest gap [50, 95] lies mostly under the fit span, the next
    # [15, 40] under the select span, the first [0, 5] under none
    gaps = red["breakdown"]["idle_gaps"]
    assert [g[0] for g in gaps] == ["bench.job.fit", "bench.job.select",
                                    "no host span"]
    assert [g[1] for g in gaps] == pytest.approx([45e-6, 25e-6, 5e-6])


def test_extra_spans_label_gaps(synthetic):
    lo, _ = trace.window(synthetic)
    red = trace.reduce(synthetic, _kernels(),
                       [("serve.batch", lo, lo + 5000.0)])
    assert red["breakdown"]["idle_gaps"][2][0] == "serve.batch"


def test_union_clips_and_merges():
    u = trace.union([(0, 10), (5, 20), (30, 40), (50, 60)], 2, 55)
    assert u.tolist() == [[2, 20], [30, 40], [50, 55]]
    assert trace.union([], 0, 1).size == 0


def test_a_trace_without_a_device_is_refused(synthetic):
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "synthetic.xspace.txt")) as f:
        text = f.read().replace('"/device:TPU:0"', '"/device:CPU:0"')
    with pytest.raises(ValueError, match="no TPU device plane"):
        trace.reduce(ProfileData.from_text_proto(text), _kernels())


def test_idle_share_reader():
    read = harness.reader("device_idle_pct.fit")
    assert read({"trace": {"busy_s": 1.0, "window_s": 4.0}}) == 75.0
    assert read({"trace": None}) is None
    assert tiny.ROOT


def test_busy_is_averaged_over_the_chips_used():
    """A four-chip host where one chip did the work: the idle chips do not
    dilute busy time or kernel time."""
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "synthetic.xspace.txt")) as f:
        text = f.read()
    idle = "".join(f'planes {{ id: {9 + i} name: "/device:TPU:{i}" }}\n'
                   for i in (1, 2, 3))
    red = trace.reduce(ProfileData.from_text_proto(text + idle), _kernels())
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(25e-6)
    assert red["kernel_s"]["kpca_project"] == pytest.approx(10e-6)


def test_a_trace_cut_short_is_refused():
    """Device events that stop well before the window closes read the
    lost time as idle: the reduction refuses them."""
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "synthetic.xspace.txt")) as f:
        text = f.read()
    # without the op that runs up to the window's end, the device's last
    # op ends at 50 us of the 100 us window
    cut = text.replace(
        "    events { metadata_id: 20 offset_ps: 95000000 duration_ps: "
        "10000000 }\n", "")
    assert cut != text
    pd = ProfileData.from_text_proto(cut)
    with pytest.raises(trace.TraceCutShort, match="cut short"):
        trace.reduce(pd, _kernels(), tail_s=0.0)
    # a tail under either threshold is idle time, not a cut
    assert trace.reduce(pd, _kernels(), tail_s=1e-4)["busy_s"] == \
        pytest.approx(20e-6)
    assert trace.reduce(pd, _kernels(), tail_s=0.0, tail_share=0.6)


def test_a_trace_that_reaches_the_window_end_passes(synthetic):
    red = trace.reduce(synthetic, _kernels(), tail_s=0.0, tail_share=0.0)
    assert red["busy_s"] == pytest.approx(25e-6)
