"""chips4_merge_resident_roofline: rf3_merge_resident_roofline's
arithmetic over the rounds of the three nodes' served tasks packed and
gathered inside the traced slice, against the device time of the
merge.resident executable summed over the chips it ran on. No new kernel.
None, never 0, when the executable is not in the trace."""


def read(ctx):
    return ctx.load("layer_metrics",
                    "rf3_merge_resident_roofline").read(ctx)
