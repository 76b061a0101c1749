"""rf3_dispatch_queue_wait_p99_ms: 99th percentile, over the window's
requests at all three front doors, of the time between an event loop's
submit and a `cql-exec` worker taking the request
(`transport.queue_wait`): eight connections over three nodes' workers,
every one of them on the one GIL. ycsb_dispatch_queue_wait_p99_ms's
arithmetic: the three servers write into one ring."""


def read(ctx):
    return ctx.load("layer_metrics", "ycsb_dispatch_queue_wait_p99_ms").read(ctx)
