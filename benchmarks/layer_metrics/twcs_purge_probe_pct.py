"""twcs_purge_probe_pct: the seconds the window's merge tasks spent in the
purge guard (`compaction.purge.probe`, one span per call of
CompactionController.purgeable_ts_fn that a live sstable outside the
compaction switches on: here every round, since every cell carries a TTL
and the other window is always outside) over the tasks' wall."""
SPANS = ("compaction.purge.probe",)


def read(ctx):
    ops = ctx.window.get("ops")
    if not ops:
        return None
    import program_spans
    return program_spans.share_of_task_wall(ops, SPANS)
