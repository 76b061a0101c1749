"""ann_resident_hit_pct (PR 32): of the window's vector queries that looked
the matrix up on the device (`index.ann.resident` in
`cassandra_tpu/index/manager.py`, one per query around the version check),
the share that found it there and uploaded nothing (no `index.ann.upload`,
which a fill alone opens), per hundred; from the program's span ring. None
from a program without the span (the parent of PR 32), as from an empty
window."""
LOOKUP = "index.ann.resident"
UPLOAD = "index.ann.upload"


def read(ctx):
    ops = ctx.window.get("ops")
    if not ops:
        return None
    import program_spans
    queries = program_spans.window_queries(ops)
    looked = [q for q in queries or [] if LOOKUP in q]
    if not looked:
        return None
    return 100.0 * sum(UPLOAD not in q for q in looked) / len(looked)
