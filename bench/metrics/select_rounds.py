"""Selection rounds per fit job: the ``rounds`` of every ``select.phase``
span, summed and divided by the jobs."""


def read(ctx):
    jobs = ctx.get("jobs")
    rounds = [e["rounds"] for e in ctx.get("spans") or ()
              if e["name"] == "select.phase" and "rounds" in e]
    if not rounds or not jobs:
        return None
    return sum(rounds) / len(jobs)
