"""Time per served batch in the calls of the jitted projection
(``project.launch``: dispatch, and the kernel where the call waits for
it), in ms, divided by the ``serve.batch`` spans."""


def read(ctx):
    spans = ctx.get("spans") or ()
    batches = sum(e["name"] == "serve.batch" for e in spans)
    launch = [e["dur_s"] for e in spans if e["name"] == "project.launch"]
    if not batches or not launch:
        return None
    return 1e3 * sum(launch) / batches
