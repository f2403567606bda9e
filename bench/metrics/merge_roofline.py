"""Share of its roofline that the ingest's streaming merge reached: the
least time of the window's live merge work (``bench/costs/ingest_merge.py``,
from the counters each stream attaches to its ``ingest.select`` span) over
the merge programs' device time in the trace."""

from bench import harness


def read(ctx):
    t, peaks = ctx.get("trace"), ctx.get("peaks")
    sel = [e for e in ctx.get("spans") or ()
           if e["name"] == "ingest.select" and "absorb_pairs" in e]
    if not t or not peaks or not sel:
        return None
    spent = t["kernel_s"].get("ingest_merge", 0.0)
    if spent <= 0:
        return None
    cost = harness.cost("ingest_merge")
    least = sum(cost.least_time(e["absorb_pairs"], e["reselect_pairs"],
                                e["gathered"], e["d"], peaks) for e in sel)
    return 100.0 * least / spent
