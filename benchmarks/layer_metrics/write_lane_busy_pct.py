"""write_lane_busy_pct: busy self-seconds of the write lane's thread
(`compact-w`: every busy span's time less its children's, so its stalls and
its parked time are left out) over the wall of the window's compaction
tasks, from the program's span ring. One thread: at most 100."""
THREAD = "compact-w"


def read(ctx):
    ops = ctx.window.get("ops")
    if not ops:
        return None
    import program_spans
    return program_spans.thread_busy_share(ops, THREAD)
