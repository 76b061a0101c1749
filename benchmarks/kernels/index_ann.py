"""index.ann: scores = M @ q, then top-k (cassandra_tpu/index/manager.py)."""
PROGRAM = "index.ann"
TRACE_MODULE = "jit_program"               # "XLA Modules" event prefix
BOUND = "memory"     # 2*N*D flops over N*D*4 bytes: 0.5 flop/byte


def least_bytes(rows: int, dim: int) -> int:
    """One query reads the whole f32 matrix once; q and the k results are
    noise beside it."""
    return int(rows) * int(dim) * 4


def flops(rows: int, dim: int) -> int:
    return 2 * int(rows) * int(dim)
