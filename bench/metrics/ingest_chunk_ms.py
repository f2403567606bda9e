"""Mean host time per ingested chunk, in ms: the ``ingest.select_chunk``
spans (dispatch of the chunk's selection and merge programs, and any wait
on the device), over their count."""


def read(ctx):
    spans = [e["dur_s"] for e in ctx.get("spans") or ()
             if e["name"] == "ingest.select_chunk"]
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
