"""Mean LOBPCG iterations of the fit's eigensolve, from the
``lobpcg_iters`` of the ``fit.solve`` spans (0 where the exact ``eigh``
solved it)."""


def read(ctx):
    iters = [e["lobpcg_iters"] for e in ctx.get("spans") or ()
             if e["name"] == "fit.solve" and "lobpcg_iters" in e]
    return sum(iters) / len(iters) if iters else None
