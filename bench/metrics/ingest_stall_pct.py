"""Share of the fit jobs' time the ingest loop waited on its prefetch feed
(``ingest.feed_wait``: the next staged chunk not ready yet)."""


def read(ctx):
    wait = [e["dur_s"] for e in ctx.get("spans") or ()
            if e["name"] == "ingest.feed_wait"]
    wall = sum(j["wall_s"] for j in ctx.get("jobs") or ())
    if not wait or wall <= 0:
        return None
    return 100.0 * sum(wait) / wall
