"""twcs_kept_cells_pct: the cells the window's merge rounds kept per
hundred they read, from the `merge.resident.gather` spans of the window's
cycles (`cells` = kept, `items` = read). 100 here: the repaired window's
cells are converted, not purged. None for a program whose gather span
does not say what the round read."""
SPAN = "merge.resident.gather"


def read(ctx):
    ops = ctx.window.get("ops")
    if not ops:
        return None
    import program_spans
    recs = [r for r in program_spans.in_operations(ops) or []
            if r["name"] == SPAN]
    read_cells = sum(r["items"] or 0 for r in recs)
    if not read_cells:
        return None
    return 100.0 * sum(r["cells"] or 0 for r in recs) / read_cells
