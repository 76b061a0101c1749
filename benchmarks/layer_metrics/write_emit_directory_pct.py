"""write_emit_directory_pct: the `write.emit.directory` spans (ordering
guard, zone map, the partition directory's loop over partition starts,
bloom, stats; one span per segment, on `compact-w`) over the wall of the
window's compaction tasks, from the program's span ring."""
SPANS = ("write.emit.directory",)


def read(ctx):
    ops = ctx.window.get("ops")
    if not ops:
        return None
    import program_spans
    return program_spans.share_of_task_wall(ops, SPANS)
