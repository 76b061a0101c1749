"""merge.resident: the fused sort + reconcile + purge + compaction round
of the device engine (cassandra_tpu/ops/device_write.py).

From the program the benchmark takes only the registry name and the name
XLA gives the executable in a profiler trace.
"""
PROGRAM = "merge.resident"
TRACE_MODULE = "jit__resident_program"     # "XLA Modules" event prefix
BOUND = "memory"                           # a sort moves bytes, no MXU


def least_bytes(cells_in: int, cells_out: int, lanes: int) -> int:
    """Bytes every round of a compaction must at least move through HBM:
    each input cell's `lanes` uint32 identity lanes read once, each kept
    cell's written once. Timestamps, flags and the sort's passes are NOT
    counted, so the share of the roofline this gives is a floor."""
    return (int(cells_in) + int(cells_out)) * int(lanes) * 4
