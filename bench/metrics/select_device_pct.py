"""Share of the fit jobs' time spent in the selection rounds on the device
(``select.rounds``, synced to the rounds' end), over the jobs' time."""


def read(ctx):
    jobs = ctx.get("jobs")
    rounds = [e["dur_s"] for e in ctx.get("spans") or ()
              if e["name"] == "select.rounds"]
    wall = sum(j["wall_s"] for j in jobs or ())
    if not rounds or wall <= 0:
        return None
    return 100.0 * sum(rounds) / wall
