"""rf3_merge_resident_roofline: ycsb_merge_resident_roofline's arithmetic
(kernels/merge_resident.py's least bytes over the HBM rate, over the
device time of the merge.resident executable in the traced slice) over
the rounds of ALL THREE nodes' served tasks packed and gathered inside the
slice: the tasks share one ring and one device. No new kernel. None, never
0, when the executable is not in the trace."""


def read(ctx):
    return ctx.load("layer_metrics", "ycsb_merge_resident_roofline").read(ctx)
