"""Share of the devices' busy time in all-gather collectives
(``bench/costs/all_gather.py``), both averaged over the chips in use."""


def read(ctx):
    t = ctx.get("trace")
    if not t or t["busy_s"] <= 0:
        return None
    spent = t["kernel_s"].get("all_gather", 0.0)
    return 100.0 * spent / t["busy_s"] if spent > 0 else None
