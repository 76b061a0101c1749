"""ucs_shard_roll_ms_per_compaction: what rolling from one shard's output
to the next costs a compaction: the seconds of `compaction.shard.roll`
(on `compact-w`: the device lane's carry flushed into the finishing
writer, its finish — the pool drained, the bloom filter and the
components sealed — and the next shard's writer opened; three a
compaction of four shards) summed over the window's cycles, per merge
task, in milliseconds. None for a program without the span."""
SPAN = "compaction.shard.roll"


def read(ctx):
    ops = ctx.window.get("ops")
    if not ops:
        return None
    import program_spans
    hit = [r for r in program_spans.in_operations(ops) or []
           if r["name"] == SPAN]
    tasks = sum(1 for o in ops for t in o["tasks"] if not t.get("dropped"))
    if not hit or not tasks:
        return None
    return 1000.0 * sum(r["end"] - r["start"] for r in hit) / tasks
