"""merge_resident_passes_skipped_pct (PR 34): LSD sort passes the resident
merge program did NOT run because their key was the same in every valid
cell of the round, per hundred passes its sort has, summed over the
window's rounds. `merge.resident.wait` (`ops/device_write.py`) records the
passes the sort has as the span's `items` and the passes the program ran
(a scalar it returns) as `cells`. None from a program whose span sets no
`items` (the parent of PR 34), as from an empty window."""
SPAN = "merge.resident.wait"


def read(ctx):
    ops = ctx.window.get("ops")
    if not ops:
        return None
    import program_spans
    recs = program_spans.in_operations(ops)
    waits = [r for r in recs or [] if r["name"] == SPAN and r["items"] > 0]
    if not waits:
        return None
    have = sum(r["items"] for r in waits)
    return 100.0 * (have - sum(r["cells"] for r in waits)) / have
