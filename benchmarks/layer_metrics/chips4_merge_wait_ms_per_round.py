"""chips4_merge_wait_ms_per_round: the mean wall of `merge.resident.wait`
(the host blocked on a round's program, its one pull of four scalars) over
the served tasks' rounds that began inside the window: three compactions
queueing on one device would show here as a longer wait a round. None
from a ring without the spans."""
WAIT = "merge.resident.wait"


def read(ctx):
    import ycsb_spans
    recs, bounds = ycsb_spans.task_records(ctx.window), \
        ycsb_spans.window_bounds(ctx.window)
    if not recs or bounds is None:
        return None
    waits = [r["end"] - r["start"] for r in recs if r["name"] == WAIT
             and bounds[0] <= r["start"] <= bounds[1]]
    if not waits:
        return None
    return 1000.0 * sum(waits) / len(waits)
