"""rf3_update_p99_ms: 99th percentile of the closed loop's QUORUM update
latency (sent to answered, the generator's clock) over every acknowledged
update of the window: ycsb_update_p99_ms's arithmetic over this cell's
operations."""


def read(ctx):
    return ctx.load("layer_metrics", "ycsb_update_p99_ms").read(ctx)
