"""Share of its roofline that the served transform reached: the least time
of the window's work (``bench/costs/kpca_project.py``) over the transform's
summed device time in the trace."""

from bench import harness


def read(ctx):
    t, s, op = ctx.get("trace"), ctx.get("serve"), ctx.get("op")
    if not t or not s or not op:
        return None
    spent = t["kernel_s"].get("kpca_project", 0.0)
    if spent <= 0 or s["rows"] <= 0:
        return None
    least = harness.cost("kpca_project").least_time(
        s["rows"], s["batches"], op["m"], op["d"], op["r"], ctx["peaks"])
    return 100.0 * least / spent
