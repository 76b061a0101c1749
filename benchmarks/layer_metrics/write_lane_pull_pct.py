"""write_lane_pull_pct: the write lane's device-to-host pulls (the
segment's lanes, then the serialize kernel's block and its seven scalars;
`write.lane.cut.pull_lanes` and `.kernel_pull`, on `compact-w`) over the
wall of the window's compaction tasks, from the program's span ring."""
SPANS = ("write.lane.cut.pull_lanes", "write.lane.cut.kernel_pull")


def read(ctx):
    ops = ctx.window.get("ops")
    if not ops:
        return None
    import program_spans
    return program_spans.share_of_task_wall(ops, SPANS)
