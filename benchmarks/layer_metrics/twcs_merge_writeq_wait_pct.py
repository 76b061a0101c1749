"""twcs_merge_writeq_wait_pct: the merge thread blocked on the write lane
(`compaction.writeq.put_wait`, `compaction.writeq.drain`) over the wall of
the window's merge tasks, as merge_writeq_wait_pct reads it for
stcs_lz4.major."""
SPANS = ("compaction.writeq.put_wait", "compaction.writeq.drain")


def read(ctx):
    ops = ctx.window.get("ops")
    if not ops:
        return None
    import program_spans
    return program_spans.share_of_task_wall(ops, SPANS)
