"""twcs_write_lane_busy_pct: busy self-seconds of the write lane's thread
(`compact-w`) over the wall of the window's merge tasks, as
write_lane_busy_pct reads it for stcs_lz4.major. One thread: at most
100."""
THREAD = "compact-w"


def read(ctx):
    ops = ctx.window.get("ops")
    if not ops:
        return None
    import program_spans
    return program_spans.thread_busy_share(ops, THREAD)
