"""write_lane_off_cpu_pct: the part of write_lane_busy_pct during which
the write lane's thread (`compact-w`) did NOT run: over the wall of the
window's compaction tasks, the sum over its busy spans of self
(wall - cpu), leaving out the two spans that wait for the device by design
(`write.lane.cut.pull_lanes`, `.kernel_pull`: write_lane_pull_pct). What
is left is waiting for the GIL (an eager dispatch lets it go), for a lock
or for I/O. None from a program whose spans carry no `cpu` (the parent of
PR 35)."""


def read(ctx):
    import cpu_spans
    return cpu_spans.thread_off_cpu_share(
        cpu_spans.stamped(ctx.window.get("ops")), cpu_spans.WRITE_LANE,
        cpu_spans.DEVICE_PULLS)
