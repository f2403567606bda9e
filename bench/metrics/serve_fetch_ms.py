"""Time per served batch fetching the embedding to the host
(``swap.fetch``: the rest of the device's work and the copy out, which
the program starts before it waits), in ms, divided by the ``serve.batch``
spans."""


def read(ctx):
    spans = ctx.get("spans") or ()
    batches = sum(e["name"] == "serve.batch" for e in spans)
    fetch = [e["dur_s"] for e in spans if e["name"] == "swap.fetch"]
    if not batches or not fetch:
        return None
    return 1e3 * sum(fetch) / batches
