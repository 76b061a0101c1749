"""ycsb_purge_probe_pct: the seconds the served compactions spent in the
purge guard (`compaction.purge.probe`, one span per call of
CompactionController.purgeable_ts_fn that a non-empty memtable or an
outside sstable switches on) over their wall. None for a program without
the span (the parent walked every partition inside
`merge.resident.pack`, unnamed)."""
SPAN = "compaction.purge.probe"


def read(ctx):
    import program_spans
    import ycsb_spans
    recs = ycsb_spans.task_records(ctx.window)
    if not recs:
        return None
    wall = program_spans.task_wall(recs)
    mine = [r for r in recs if r["name"] == SPAN]
    if wall <= 0 or not mine:
        return None
    return 100.0 * sum(r["end"] - r["start"] for r in mine) / wall
