"""rf3_compaction_overlap_pct: share of the window (release to the last
completion) during which at least one node's served `compaction.task` was
open: ycsb_compaction_overlap_pct's arithmetic (it takes the union) over
the three nodes' tasks."""


def read(ctx):
    return ctx.load("layer_metrics", "ycsb_compaction_overlap_pct").read(ctx)
