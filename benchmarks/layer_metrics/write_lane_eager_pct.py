"""write_lane_eager_pct: the write lane's eager device programs (the
concatenation of a round onto the pending columns, the segment's slices;
`write.lane.append` and `write.lane.cut.slice`, on `compact-w`) over the
wall of the window's compaction tasks, from the program's span ring. The
spans cover the dispatches, not the device's time."""
SPANS = ("write.lane.append", "write.lane.cut.slice")


def read(ctx):
    ops = ctx.window.get("ops")
    if not ops:
        return None
    import program_spans
    return program_spans.share_of_task_wall(ops, SPANS)
