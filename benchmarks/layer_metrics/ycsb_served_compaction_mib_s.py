"""ycsb_served_compaction_mib_s: MiB of input sstable bytes of the served
compactions (the `compaction.task` span's bytes) over their wall, under
the window's load: beside compaction_mib_s of stcs_lz4.major, which runs
alone."""


def read(ctx):
    import ycsb_spans
    tasks = ycsb_spans.served_tasks(ctx.window)
    if not tasks:
        return None
    wall = sum(t["end"] - t["start"] for t in tasks)
    if wall <= 0:
        return None
    return sum(t["bytes"] for t in tasks) / 2.0 ** 20 / wall
