"""rf3_digest_mismatch_pct: the window's rise of the counter
`reads.digest_mismatches` (a QUORUM read whose data and digest disagreed:
a write to that key was on one of the two replicas and not yet on the
other) per hundred acknowledged reads. None from a program without the
counter (the driver reports no rise at all)."""
COUNTER = "reads.digest_mismatches"


def read(ctx):
    import rf3_spans
    counters = ctx.window.get("counters")
    reads = rf3_spans.answered(ctx.window, "read")
    if not counters or COUNTER not in counters or not reads:
        return None
    return 100.0 * counters[COUNTER] / reads
