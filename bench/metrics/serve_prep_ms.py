"""Host time per served batch before the kernel call, in ms: the front
end's ``serve.coalesce`` (concatenating the batch's requests and padding
them to the bucket) and the transform's ``project.prep`` (operand
conversion, plan lookup, the operator's padding), summed and divided by
the ``serve.batch`` spans."""

PARTS = ("serve.coalesce", "project.prep")


def read(ctx):
    spans = ctx.get("spans") or ()
    batches = sum(e["name"] == "serve.batch" for e in spans)
    seen = {e["name"] for e in spans if e["name"] in PARTS}
    if not batches or seen != set(PARTS):
        return None
    return 1e3 * sum(e["dur_s"] for e in spans if e["name"] in PARTS) \
        / batches
