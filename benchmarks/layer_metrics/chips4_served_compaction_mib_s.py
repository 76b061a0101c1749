"""chips4_served_compaction_mib_s: rf3_served_compaction_mib_s's
arithmetic (the three nodes' served compactions' input bytes over the
union of their walls) in the cell where each node compacts on a chip of
its own."""


def read(ctx):
    return ctx.load("layer_metrics",
                    "rf3_served_compaction_mib_s").read(ctx)
