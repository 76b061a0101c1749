"""Profile merge_sorted_device sub-phases (dev tool).

Defaults to production-like round sizes (4 x 64K cells = one pipelined
CompactionTask round). CTPU_PROF_CELLS overrides per-run cells — note
XLA's sort COMPILE time grows with N (~1 min at 1M cells cold), so big
sizes are slow on the first run; warm dispatch is what this measures."""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax

from cassandra_tpu.utils import compile_cache

compile_cache.configure()

from cassandra_tpu.ops import merge as dmerge
from cassandra_tpu.storage import cellbatch as cb
from cassandra_tpu.tools import bulk
from cassandra_tpu.schema import make_table, TableParams
from cassandra_tpu.ops.codec import CompressionParams

N_RUNS = 4
CELLS = int(os.environ.get("CTPU_PROF_CELLS", 65_536))
VB = 64
NPART = 4096

table = make_table("bench", "stress", pk=["id"], ck=["c"],
                   cols={"id": "int", "c": "int", "v": "blob"},
                   params=TableParams(compression=CompressionParams("LZ4Compressor")))

rng = np.random.default_rng(2)
batches = []
for run in range(N_RUNS):
    pk = rng.integers(0, NPART, CELLS)
    ck = rng.integers(1, 10_000, CELLS)
    vals = rng.integers(0, 256, (CELLS, VB), dtype=np.uint8)
    ts = rng.integers(1, 1 << 40, CELLS).astype(np.int64)
    b = bulk.build_int_batch(table, pk, ck, vals, ts)
    batches.append(cb.merge_sorted([b]))


def one(tag):
    """Profile the ACTIVE device path (v3 fast planes when the round
    qualifies, else v2) through the shipped submit/collect API."""
    t = {}
    prof = {}
    t0 = time.perf_counter()
    cat = cb.CellBatch.concat(batches)
    n = len(cat)
    t["concat"] = time.perf_counter() - t0

    fast = dmerge._plane_pack_fast(cat, batches)
    if fast is not None:
        push_bytes = fast[0].nbytes
    else:
        planes, _cfg = dmerge._plane_pack_v2(cat, batches)
        push_bytes = sum(v.nbytes for v in planes.values()
                         if hasattr(v, "nbytes"))

    t0 = time.perf_counter()
    h = dmerge.submit_merge(batches, prof=prof)
    t["submit"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    merged = dmerge.collect_merge(h)
    t["collect"] = time.perf_counter() - t0

    print(tag, f"mode={h.mode} n={n} push_bytes={push_bytes} "
          f"({push_bytes/n:.1f} B/cell)",
          {k: round(v, 3) for k, v in t.items()},
          {k: round(v, 3) for k, v in prof.items()},
          f"kept={len(merged)}")


one("cold")
one("warm1")
one("warm2")
one("warm3")

# The same runs as the device-program registry sees them
# (service/profiling.py — system_views.device_programs): compile vs
# warm-dispatch vs execute split, live tracked shapes, recompile count
# past the budget, and XLA cost analysis where the backend reports it.
from cassandra_tpu.service import profiling  # noqa: E402

snap = profiling.GLOBAL.snapshot()
for name, k in sorted(snap["kernels"].items()):
    print(f"{name}: calls={k['calls']} compiles={k['compiles']} "
          f"shapes={k['shape_count']} evictions={k['shape_evictions']} "
          f"retraces={k['retraces']} compile={k['compile_s']:.3f}s "
          f"dispatch={k['dispatch_s']:.3f}s execute={k['execute_s']:.3f}s "
          f"flops={k['cost_flops']:.0f} bytes={k['cost_bytes']:.0f}")
for phase, secs in sorted(snap["phases"].items()):
    print(f"phase {phase}: {secs:.3f}s")
