"""ycsb_compact_w_off_cpu_pct: write_lane_off_cpu_pct for the served
compaction: the part of ycsb_compact_w_busy_pct during which `compact-w`
did not run, the pulls that wait for the device left out, over the served
tasks' wall. Under the readers' convoy every eager dispatch is a GIL
hand-off. None from a program whose spans carry no `cpu` (the parent of
PR 35)."""


def read(ctx):
    import cpu_spans
    import ycsb_spans
    return cpu_spans.thread_off_cpu_share(
        ycsb_spans.task_records(ctx.window), cpu_spans.WRITE_LANE,
        cpu_spans.DEVICE_PULLS)
