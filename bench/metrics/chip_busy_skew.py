"""How unevenly the chips of a mesh are loaded: the busiest chip's busy
seconds in the traced window over the mean of the chips in use (1.0 when
they share the work evenly).  A chip's busy time here is the union of its
``XLA Modules`` intervals (its programs' runs) inside the window: a few
events a chunk, where its ops are thousands.  Read from the run's profile
(``ctx["profile"]`` where a caller has it loaded, else the traced run's
directory)."""

import numpy as np

from bench import trace


def read(ctx):
    if not ctx.get("trace") and ctx.get("profile") is None:
        return None
    pd = ctx.get("profile")
    if pd is None:
        from bench.run import TRACE_DIR
        pd = trace.load(TRACE_DIR)
    lo, hi = trace.window(pd)
    busy = []
    for plane in trace.devices(pd):
        _, st, en = trace._arrays(plane, trace.MODULES_LINE)
        u = trace.union(np.stack([st, en], axis=1), lo, hi)
        if u.size:
            busy.append(float(np.sum(u[:, 1] - u[:, 0])))
    if len(busy) < 2:
        return None
    return max(busy) / (sum(busy) / len(busy))
