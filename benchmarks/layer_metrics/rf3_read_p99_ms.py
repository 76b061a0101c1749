"""rf3_read_p99_ms: 99th percentile of the closed loop's QUORUM read
latency (sent to answered, the generator's clock) over every acknowledged
read of the window, whichever coordinator it went through:
ycsb_read_p99_ms's arithmetic over this cell's operations."""


def read(ctx):
    return ctx.load("layer_metrics", "ycsb_read_p99_ms").read(ctx)
