"""Share of the fit jobs' time spent staging rows for the selection
rounds: the host's padding of each phase's working set (``select.pad``)
and its transfer to the device (``select.put``), over the jobs' time."""

PARTS = ("select.pad", "select.put")


def read(ctx):
    jobs = ctx.get("jobs")
    stage = [e["dur_s"] for e in ctx.get("spans") or ()
             if e["name"] in PARTS]
    wall = sum(j["wall_s"] for j in jobs or ())
    if not stage or wall <= 0:
        return None
    return 100.0 * sum(stage) / wall
