"""The fit's share of the chip's peak: least work per job, every row
against the final reduced set and one m x m Gram, 2*n*m*d + 2*m*m*d
operations, summed over the window's jobs, over the window, over the
bfloat16 peak.  It depends only on (n, m, d)."""


def read(ctx):
    jobs, peaks = ctx.get("jobs"), ctx.get("peaks")
    if not jobs or not peaks:
        return None
    n, d = ctx["n"], ctx["d"]
    flops = sum(2.0 * n * j["m"] * d + 2.0 * j["m"] ** 2 * d for j in jobs)
    return 100.0 * flops / ctx["fit_elapsed_s"] / peaks["bf16_flops_per_s"]
