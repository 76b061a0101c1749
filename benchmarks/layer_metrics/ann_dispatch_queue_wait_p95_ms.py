"""ann_dispatch_queue_wait_p95_ms: 95th percentile, over the window's
vector queries, of the time a request waited between the event loop's
submit and a `cql-exec` worker taking it (`transport.queue_wait`, stamped
at submit), from the program's span ring."""
SPAN = "transport.queue_wait"


def read(ctx):
    ops = ctx.window.get("ops")
    if not ops:
        return None
    import program_spans
    waits = [1000.0 * q[SPAN]
             for q in program_spans.window_queries(ops) or [] if SPAN in q]
    return ctx.stats.percentile(waits, 95) if waits else None
