"""ycsb_compact_w_busy_pct: busy self-seconds of the write lane's thread
(`compact-w`) over the wall of the served compactions, as
write_lane_busy_pct reads it for stcs_lz4.major. One thread: at most
100."""
THREAD = "compact-w"


def read(ctx):
    import program_spans
    import ycsb_spans
    recs = ycsb_spans.task_records(ctx.window)
    if not recs:
        return None
    wall = program_spans.task_wall(recs)
    mine = [r for r in recs if r["thread"] == THREAD]
    if wall <= 0 or not mine:
        return None
    own = program_spans.self_seconds(mine)
    return 100.0 * sum(own[r["id"]] for r in mine
                       if r["kind"] == "busy") / wall
