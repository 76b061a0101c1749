"""ycsb_ops_s_outside_compaction: acknowledged operations answered while
no served compaction was open, per second of that time: with
ycsb_ops_s_during_compaction, the interference in the users' unit. The
time outside is what the window has after the compaction's end plus the
fraction of a second between the release and the task's start: where the
compaction outlasts the window only that fraction is left, and the rate
is read over it."""


def read(ctx):
    import ycsb_spans
    split = ycsb_spans.ops_split(ctx.window)
    if split is None or split["outside"][1] <= 0:
        return None
    return split["outside"][0] / split["outside"][1]
