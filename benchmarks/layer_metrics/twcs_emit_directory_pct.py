"""twcs_emit_directory_pct: the `write.emit.directory` spans (one per
output segment: ordering guard, zone map, partition directory, stats) over
the wall of the window's merge tasks, as write_emit_directory_pct reads it
for stcs_lz4.major. Here a segment opens four partitions of 17,280 cells
where standard1's opens 13,107 of five: the branch that continues a
partition from the previous segment runs in every segment."""
SPANS = ("write.emit.directory",)


def read(ctx):
    ops = ctx.window.get("ops")
    if not ops:
        return None
    import program_spans
    return program_spans.share_of_task_wall(ops, SPANS)
