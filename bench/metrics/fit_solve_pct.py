"""Share of the fit jobs' time spent in the eigensolve stage
(``fit_centers``), from the harness's timing of each stage."""


def read(ctx):
    jobs = ctx.get("jobs")
    if not jobs:
        return None
    wall = sum(j["wall_s"] for j in jobs)
    return 100.0 * sum(j["fit_s"] for j in jobs) / wall if wall > 0 else None
