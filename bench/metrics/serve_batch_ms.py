"""Mean duration of the program's ``serve.batch`` span (one fused transform
of a coalesced batch, device to host), in ms; the span is recorded in
traced runs only."""


def read(ctx):
    spans = [e["dur_s"] for e in ctx.get("spans") or ()
             if e["name"] == "serve.batch"]
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
