"""ycsb_engine_read_ms_per_read: milliseconds of `engine.read` (one
ColumnFamilyStore.read_partition: memtable probe, sstable walk, merge) per
read request of the window, mean. None from a program without the span
(the parent of PR 27)."""
SPAN = "engine.read"


def read(ctx):
    import ycsb_spans
    return ycsb_spans.mean_ms_per_request(ctx.window, SPAN, "read")
