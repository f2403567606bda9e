"""Mean queue wait of a served request, in ms: from its admission to the
start of its batch, summed over the ``serve.batch`` spans'
``wait_ms_sum`` and divided by the requests they carry.  The spans are
recorded in traced runs only; a program whose spans carry no waits reads
nothing."""


def read(ctx):
    waits = requests = 0
    for e in ctx.get("spans") or ():
        if e["name"] == "serve.batch" and "wait_ms_sum" in e:
            waits += e["wait_ms_sum"]
            requests += e["requests"]
    return waits / requests if requests else None
