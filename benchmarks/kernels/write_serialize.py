"""write.serialize: the fused kernel that serializes one segment's META
block on the device (cassandra_tpu/ops/device_write.py)."""
PROGRAM = "write.serialize"
TRACE_MODULE = "jit__meta_block_kernel"    # "XLA Modules" event prefix
BOUND = "memory"


def least_bytes(cells: int, lanes: int) -> int:
    """Each kept cell's lanes read once; the block written is smaller."""
    return int(cells) * int(lanes) * 4
