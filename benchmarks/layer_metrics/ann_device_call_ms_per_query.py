"""ann_device_call_ms_per_query: host milliseconds per vector query around
the device: the jitted call with the matrix's upload and the dispatch
(`index.ann.call`) and the blocking pull of the answer (`index.ann.pull`);
mean over the window's queries, from the program's span ring."""
SPANS = ("index.ann.call", "index.ann.pull")


def read(ctx):
    ops = ctx.window.get("ops")
    if not ops:
        return None
    import program_spans
    return program_spans.mean_ms_per_query(ops, SPANS)
