"""ucs_merge_resident_roofline: the least time the chip could take for
the merge rounds of the traced cycle's compaction over the device time of
the merge.resident executable in the trace, computed as
twcs_merge_resident_roofline.py computes it for its cycle (every cell
read once, every kept cell written once, at this table's lane count, over
the HBM rate of peaks.json). A sharded compaction's rounds read against
the same floor as stcs_lz4.major's single-output ones. Memory-bound;
None, never 0, when the executable is not in the trace."""


def read(ctx):
    return ctx.load("layer_metrics", "twcs_merge_resident_roofline").read(ctx)
