"""ann_host_prepare_ms_per_query: host milliseconds per vector query spent
before the device call: assembling the matrix (`index.ann.gather`) and
re-normalising it (`index.ann.normalise`); mean over the window's queries,
from the program's span ring."""
SPANS = ("index.ann.gather", "index.ann.normalise")


def read(ctx):
    ops = ctx.window.get("ops")
    if not ops:
        return None
    import program_spans
    return program_spans.mean_ms_per_query(ops, SPANS)
