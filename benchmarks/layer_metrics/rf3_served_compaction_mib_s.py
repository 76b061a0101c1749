"""rf3_served_compaction_mib_s: MiB of input sstable bytes of the three
nodes' served compactions (the `compaction.task` spans' bytes) over the
UNION of their walls: what one chip and one interpreter compact while
three coordinators serve, beside ycsb_served_compaction_mib_s for one
node."""


def read(ctx):
    import rf3_spans
    import ycsb_spans
    tasks = ycsb_spans.served_tasks(ctx.window)
    if not tasks:
        return None
    wall = rf3_spans.union_s(tasks)
    if wall <= 0:
        return None
    return sum(t["bytes"] for t in tasks) / 2.0 ** 20 / wall
