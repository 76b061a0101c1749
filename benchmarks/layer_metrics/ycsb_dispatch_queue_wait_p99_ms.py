"""ycsb_dispatch_queue_wait_p99_ms: 99th percentile, over the window's
requests, of the time between the event loop's submit and a `cql-exec`
worker taking the request (`transport.queue_wait`, stamped at submit):
eight connections over four workers, and the compaction's threads on the
same GIL."""
SPAN = "transport.queue_wait"


def read(ctx):
    import ycsb_spans
    waits = ycsb_spans.span_values_ms(ctx.window, SPAN)
    return ctx.stats.percentile(waits, 99) if waits else None
