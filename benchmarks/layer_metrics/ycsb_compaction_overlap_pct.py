"""ycsb_compaction_overlap_pct: share of the window (release to the last
completion) during which a served `compaction.task` was open."""


def read(ctx):
    import ycsb_spans
    over = ycsb_spans.overlap_s(ctx.window)
    if over is None or over[1] <= 0:
        return None
    return 100.0 * over[0] / over[1]
