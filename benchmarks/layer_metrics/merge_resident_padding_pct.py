"""merge_resident_padding_pct: cells the resident merge program sorts that
are padding: (padded - real) over padded, summed over the window's rounds.
`merge.resident.pack` records the round's cells and the bucket
(`ops/merge._bucket`) the program runs at, as the span's `items`."""
SPAN = "merge.resident.pack"


def read(ctx):
    ops = ctx.window.get("ops")
    if not ops:
        return None
    import program_spans
    recs = program_spans.in_operations(ops)
    packs = [r for r in recs or [] if r["name"] == SPAN and r["items"] > 0]
    if not packs:
        return None
    padded = sum(r["items"] for r in packs)
    return 100.0 * (padded - sum(r["cells"] for r in packs)) / padded
