"""merge_writeq_wait_pct: the merge thread blocked on the write lane (a
round put on the full write queue, `compaction.writeq.put_wait`; the
sentinel put and the join at the end, `compaction.writeq.drain`) over the
wall of the window's compaction tasks, from the program's span ring."""
SPANS = ("compaction.writeq.put_wait", "compaction.writeq.drain")


def read(ctx):
    ops = ctx.window.get("ops")
    if not ops:
        return None
    import program_spans
    return program_spans.share_of_task_wall(ops, SPANS)
