"""rf3_engine_read_ms_per_read: milliseconds of one `engine.read` (one
ColumnFamilyStore.read_partition on one replica: the coordinator's own or
a READ_REQ's), mean over the replica reads of the window. A QUORUM read
makes two; a digest mismatch's second round two more."""
SPAN = "engine.read"


def read(ctx):
    import rf3_spans
    mine = rf3_spans.in_window(ctx.window, [SPAN])
    return rf3_spans.ms_per(ctx.window, [SPAN], len(mine) if mine else 0)
