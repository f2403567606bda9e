"""The whole serving step's share of the chip's peak: the transform's
least operations per served row (``bench/costs/kpca_project.py``) times
rows served, over the window, over the bfloat16 peak."""

from bench import harness


def read(ctx):
    s, op, peaks = ctx.get("serve"), ctx.get("op"), ctx.get("peaks")
    if not s or not op or not peaks or s["rows"] <= 0:
        return None
    flops = harness.cost("kpca_project").flops(s["rows"], op["m"], op["d"],
                                               op["r"])
    return 100.0 * flops / ctx["window_s"] / peaks["bf16_flops_per_s"]
