"""Share of the fit jobs' time in the fit after the stream
(``ingest.fit``: Algorithm 1 on the merged set, through its results'
fetch)."""


def read(ctx):
    fit = [e["dur_s"] for e in ctx.get("spans") or ()
           if e["name"] == "ingest.fit"]
    wall = sum(j["wall_s"] for j in ctx.get("jobs") or ())
    if not fit or wall <= 0:
        return None
    return 100.0 * sum(fit) / wall
