"""ucs_rounds_cut_at_shard_pct: the merge rounds whose cut a shard
boundary capped (`compaction.round.cut` records with `items` = 1: the
round stops at the shard's last token, short of the cells the cursors
buffered) per hundred `compaction.round.cut` records inside the window's
cycles. Such a round runs at a padded shape of its own. None for a
program without the span, or one that never caps a cut (the mark is
absent there)."""
SPAN = "compaction.round.cut"


def read(ctx):
    ops = ctx.window.get("ops")
    if not ops:
        return None
    import program_spans
    cuts = [r for r in program_spans.in_operations(ops) or []
            if r["name"] == SPAN]
    capped = sum(1 for r in cuts if r["items"] == 1)
    if not cuts or not capped:
        return None
    return 100.0 * capped / len(cuts)
