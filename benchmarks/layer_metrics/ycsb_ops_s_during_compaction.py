"""ycsb_ops_s_during_compaction: acknowledged operations answered while a
served `compaction.task` was open, per second of that time."""


def read(ctx):
    import ycsb_spans
    split = ycsb_spans.ops_split(ctx.window)
    if split is None or split["during"][1] <= 0:
        return None
    return split["during"][0] / split["during"][1]
