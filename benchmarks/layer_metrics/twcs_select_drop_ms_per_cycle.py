"""twcs_select_drop_ms_per_cycle: what the strategy and the drop cost a
cycle: the seconds of `compaction.select` (each pick of
TimeWindowCompactionStrategy, the fully-expired test and the engine
choice inside it; four a cycle, the last finding nothing) and of
`compaction.drop` (the rewrite-free drop of the expired window), summed
over the window's cycles, per cycle, in milliseconds."""
SPANS = ("compaction.select", "compaction.drop")


def read(ctx):
    ops = ctx.window.get("ops")
    if not ops:
        return None
    import program_spans
    hit = [r for r in program_spans.in_operations(ops) or []
           if r["name"] in SPANS]
    if not hit:
        return None
    return 1000.0 * sum(r["end"] - r["start"] for r in hit) / len(ops)
