"""ann_rows_read_ms_per_query: host milliseconds per vector query spent
after the index answered, reading the hits' rows back one partition each
(`cql.ann.rows`, in `cql/execution.py`); mean over the window's queries,
from the program's span ring."""
SPANS = ("cql.ann.rows",)


def read(ctx):
    ops = ctx.window.get("ops")
    if not ops:
        return None
    import program_spans
    return program_spans.mean_ms_per_query(ops, SPANS)
