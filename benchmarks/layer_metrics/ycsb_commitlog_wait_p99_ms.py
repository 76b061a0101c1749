"""ycsb_commitlog_wait_p99_ms: 99th percentile, over the window's updates,
of `commitlog.wait`: the durability wait the commitlog's sync mode imposes
between the memtable apply and the acknowledgement (periodic: none)."""
SPAN = "commitlog.wait"


def read(ctx):
    import ycsb_spans
    waits = ycsb_spans.span_values_ms(ctx.window, SPAN, "update")
    return ctx.stats.percentile(waits, 99) if waits else None
