"""Headline benchmark: STCS major-compaction throughput.

Mirrors the reference's measurement (BASELINE.md): cassandra-stress-style
data (default columns are blob() = uniform random bytes, matching the
reference stress defaults; CTPU_BENCH_TEXT=1 for compressible text) ->
N sstables -> major compaction; throughput = input bytes / wall seconds,
the "Read Throughput" the reference logs per compaction
(db/compaction/CompactionTask.java:252-266). vs_baseline compares against
the reference's default compaction_throughput throttle of 64 MiB/s
(conf/cassandra.yaml:1243) — the reference repo publishes no absolute
numbers (BASELINE.json.published = {}).

Engine selection (CTPU_BENCH_ENGINE = native | device | numpy):
  native  C++ k-way streaming merge + inline reconcile (default). A HOST
          engine: the process pins jax to the CPU and never touches a
          chip; its numbers are CPU numbers.
  device  the jax device engine (ops/merge.py, ops/device_write.py).
          Refuses to run unless jax's backend is a TPU — a device
          benchmark that fell back to XLA's CPU backend measures nothing
          a user runs.
  numpy   the reference host implementation (executable spec).
All three are tested bit-identical (tests/test_merge_device.py,
tests/test_merge_fastpath.py, tests/test_host_merge.py). No number from
this file has been taken on an attached chip yet; ROADMAP A1 replaces
it with the on-chip benchmark, and `chip_smoke.py` is the proof that the
device path runs there. Phase timings are in detail.phases; the
write leg reports `compress` and `io_write` separately (plus `seal` for
the final fsync/rename) since the pipelined executor split them onto
their own threads — CTPU_BENCH_PIPELINED=0 A/Bs the serial write path.

Prints ONE json line. The device kernel is warmed on a separate copy of
the data so compile time is excluded.
"""
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

VALUE_BYTES = 64
N_PARTITIONS = 4096

# CTPU_BENCH_CONFIG selects the workload shape (BASELINE.json configs):
#   stcs  (default) STCS major, 4-way, LZ4 16KiB, random-blob values —
#         the headline number the driver records.
#   lcs   LCS-shape many-way merge (L0 overlap + L1 disjoint runs),
#         Snappy 16KiB, compressible text values.
#   twcs  TWCS time-series: per-window runs, expired TTLs + gc_before in
#         the past — measures the tombstone/TTL purge pipeline.
#   ucs   UCS-shape mixed-density runs, Zstd 64KiB chunks.
CONFIGS = {
    "stcs": {"desc": "STCS major, 4-way, LZ4 16KiB",
             "compressor": ("LZ4Compressor", 16 * 1024),
             "runs": [262_144] * 4, "values": "blob"},
    "lcs": {"desc": "LCS many-way (4xL0 + 6xL1), Snappy 16KiB, text",
            "compressor": ("SnappyCompressor", 16 * 1024),
            "runs": [131_072] * 4, "l1_runs": 6, "values": "text"},
    "twcs": {"desc": "TWCS time-series, TTL purge, LZ4 16KiB",
             "compressor": ("LZ4Compressor", 16 * 1024),
             "runs": [262_144] * 4, "values": "points", "ttl": True},
    "ucs": {"desc": "UCS mixed-density (Ws T4,T2,L4), Zstd 64KiB",
            "compressor": ("ZstdCompressor", 64 * 1024),
            "runs": [524_288, 262_144, 131_072, 65_536, 65_536],
            "values": "blob",
            # per-level scaling vector recorded on the table: densities
            # in this workload span 3 levels of the mixed geometry
            "compaction": {"class": "UnifiedCompactionStrategy",
                           "scaling_parameters": "T4, T2, L4",
                           "base_shard_count": 4}},
}


def _values(rng, n, kind):
    if kind == "text":     # compressible lowercase text
        return rng.integers(97, 122, (n, VALUE_BYTES), dtype=np.uint8)
    if kind == "points":   # 8-byte time-series points
        return rng.integers(0, 256, (n, 8), dtype=np.uint8)
    return rng.integers(0, 256, (n, VALUE_BYTES), dtype=np.uint8)


def build_inputs(data_dir, table, seed, cfg):
    from cassandra_tpu.storage import cellbatch as cb
    from cassandra_tpu.storage.sstable import Descriptor, SSTableWriter
    from cassandra_tpu.tools import bulk

    rng = np.random.default_rng(seed)
    os.makedirs(data_dir, exist_ok=True)
    gen = 0
    now = int(time.time())
    for run_cells in cfg["runs"]:
        n = run_cells
        # zipf-ish overlap across runs: same partition space, random rows
        pk = rng.integers(0, N_PARTITIONS, n)
        if cfg.get("ttl"):
            # per-window timelines: each run is one time window; half the
            # windows are fully past their TTL at compaction time
            ck = (gen * 100_000 + rng.integers(0, 50_000, n))
        else:
            ck = rng.integers(1, 10_000, n)
        vals = _values(rng, n, cfg["values"])
        ts = rng.integers(1, 1 << 40, n).astype(np.int64)
        batch = bulk.build_int_batch(table, pk, ck, vals, ts)
        if cfg.get("ttl"):
            ttl_s = 3600
            expired = gen < len(cfg["runs"]) // 2   # old windows: expired
            write_age = ttl_s * 3 if expired else 0
            batch.ttl[:] = ttl_s
            batch.ldt[:] = now - write_age + ttl_s
            batch.flags[:] |= cb.FLAG_EXPIRING
        merged = cb.merge_sorted([batch])
        gen += 1
        w = SSTableWriter(Descriptor(data_dir, gen), table,
                          estimated_partitions=N_PARTITIONS)
        w.append(merged)
        w.finish()
    # LCS shape: add one disjoint-partition-range layer of L1 runs
    for i in range(cfg.get("l1_runs", 0)):
        n = 131_072
        lo = i * (N_PARTITIONS // cfg["l1_runs"])
        hi = lo + N_PARTITIONS // cfg["l1_runs"]
        pk = rng.integers(lo, hi, n)
        ck = rng.integers(1, 10_000, n)
        vals = _values(rng, n, cfg["values"])
        ts = rng.integers(1, 1 << 40, n).astype(np.int64)
        merged = cb.merge_sorted([bulk.build_int_batch(table, pk, ck,
                                                       vals, ts)])
        gen += 1
        w = SSTableWriter(Descriptor(data_dir, gen), table,
                          estimated_partitions=N_PARTITIONS)
        w.append(merged)
        w.level = 1
        w.finish()


def _task_knobs():
    """Env-gated pipeline knobs shared by the headline + sweep legs:
    CTPU_BENCH_PIPELINED=0 disables the threaded compress->io_write
    split; CTPU_BENCH_COMPRESSORS=0 keeps the serial compress thread,
    =N pins a private N-worker pool, unset = the shared auto-sized
    pool. Decode-ahead follows the `compaction_decode_ahead` config
    knob (its default — on — for the bench's standalone stores; the
    old CTPU_BENCH_DECODE_AHEAD env gate is gone, the knob is the only
    switch); legs that must isolate it pass decode_ahead=False
    explicitly. Output bytes are identical for every combination
    (scripts/check_compaction_ab.py proves it)."""
    pipelined = os.environ.get("CTPU_BENCH_PIPELINED", "1") != "0"
    # None = knob-inherited: the bench's standalone stores resolve it
    # through ColumnFamilyStore.decode_ahead_fn, which reads the
    # `compaction_decode_ahead` config default
    decode_ahead = None
    comp = os.environ.get("CTPU_BENCH_COMPRESSORS")
    pool = None
    if not pipelined:
        # PIPELINED=0 means the fully serial write leg: a pool would
        # force threaded_io back on and corrupt the A/B
        pool = 0
    elif comp is not None:
        n = int(comp)
        if n <= 0:
            pool = 0
        else:
            pool = _pinned_pool(n)
    return {"pipelined_io": pipelined, "decode_ahead": decode_ahead,
            "compress_pool": pool}


_PINNED_POOLS: dict = {}


def _pinned_pool(n: int):
    """One pinned pool per worker count for the whole bench process —
    repeated _task_knobs calls (warm + timed legs) must not leak a
    fresh set of polling daemon threads each time."""
    from cassandra_tpu.storage.sstable.compress_pool import CompressorPool

    if n not in _PINNED_POOLS:
        _PINNED_POOLS[n] = CompressorPool(n)
    return _PINNED_POOLS[n]


def _compact_dir(base_dir, table, cfs=None, **task_kw):
    """Compact whatever sstables live in base_dir (or under an already
    constructed cfs); returns stats with wall + per-phase profile +
    per-phase MiB/s (input bytes over phase seconds — phases on
    different threads overlap, so these are per-stage capacities, not
    additive wall shares)."""
    from cassandra_tpu.compaction.task import CompactionTask
    from cassandra_tpu.storage.table import ColumnFamilyStore

    if cfs is None:
        cfs = ColumnFamilyStore(table, base_dir, commitlog=None)
    cfs.reload_sstables()
    inputs = cfs.tracker.view()
    # legs may pin their own engine (the sweep's device-compress leg);
    # everything else inherits the CTPU_BENCH_ENGINE default
    task_kw.setdefault("engine",
                       os.environ.get("CTPU_BENCH_ENGINE", "native"))
    task_kw.setdefault("use_device", task_kw["engine"] == "device")
    task = CompactionTask(cfs, inputs, **task_kw)
    t0 = time.time()
    stats = task.execute()
    stats["wall"] = time.time() - t0
    stats["profile"] = {k: round(v, 3)
                        for k, v in sorted(task.profile.items())}
    walls = getattr(task, "mesh_shard_walls", None)
    if walls and any(w > 0 for w in walls):
        # mesh-mode forensics: overlap_factor is lane-EXCLUSIVE work
        # (per-shard decode+merge busy seconds) over the fan-out's
        # elapsed wall — > 1 only when lanes really ran concurrently
        # (a 1-lane or serialized run measures ~1; sum/max of the walls
        # would "pass" for a sequential loop too). Cell spread is the
        # boundary planner's balance.
        from cassandra_tpu.parallel.boundaries import shard_imbalance
        live = [w for w in walls if w > 0]
        cells = [c for c in task.mesh_shard_cells if c]
        produce_s = getattr(task, "mesh_produce_seconds", 0.0)
        stats["mesh"] = {
            "shards": len(live),
            "max_shard_wall_s": round(max(live), 4),
            "overlap_factor": round(
                sum(task.mesh_shard_busy) / produce_s, 2)
            if produce_s > 0 else 1.0,
            "shard_cells_imbalance": round(shard_imbalance(cells), 3),
        }
    mib = stats["bytes_read"] / 2**20
    stats["phase_mib_s"] = {k: round(mib / v, 1)
                            for k, v in stats["profile"].items() if v > 0}
    return stats


def run_compaction(base_dir, table, seed, cfg):
    from cassandra_tpu.storage.table import ColumnFamilyStore

    cfs = ColumnFamilyStore(table, base_dir, commitlog=None)
    build_inputs(cfs.directory, table, seed, cfg)
    return _compact_dir(base_dir, table, cfs=cfs, **_task_knobs())


def run_compressor_sweep(base_dir, table, cfg, workers=(1, 2, 4)):
    """compressor_threads sweep on ONE fixture (copied per leg): the
    serial-compress leg (workers=0) against pinned pools. Shows where
    the compress stage stops being the wall — scaling flattens once
    the pipeline is bounded by decode/merge CPU or the disk.
    decode_ahead is held OFF on every leg so the sweep isolates
    compress-pool scaling (the prefetch is a separate lever, on by
    default via the `compaction_decode_ahead` knob)."""
    import shutil as _sh

    from cassandra_tpu.storage.sstable.compress_pool import CompressorPool
    from cassandra_tpu.storage.table import ColumnFamilyStore

    pristine = os.path.join(base_dir, "pristine")
    cfs = ColumnFamilyStore(table, pristine, commitlog=None)
    build_inputs(cfs.directory, table, 3, cfg)
    out = {}
    # discarded warm-up leg: the first measured leg must not pay the
    # cold page-cache read of the pristine fixture that later legs
    # copy from warm
    warm_dir = os.path.join(base_dir, "warmup")
    _sh.copytree(pristine, warm_dir)
    _compact_dir(warm_dir, table, compress_pool=0, decode_ahead=False)
    _sh.rmtree(warm_dir, ignore_errors=True)
    for w in (0,) + tuple(workers):
        leg_dir = os.path.join(base_dir, f"w{w}")
        _sh.copytree(pristine, leg_dir)
        pool = CompressorPool(w) if w > 0 else 0
        stats = _compact_dir(leg_dir, table, compress_pool=pool,
                             decode_ahead=False)
        if w > 0:
            pool.shutdown(timeout=5.0)
        mib_s = stats["bytes_read"] / 2**20 / stats["wall"]
        key = "serial" if w == 0 else f"workers_{w}"
        out[key] = {"mib_s": round(mib_s, 2),
                    "wall_s": round(stats["wall"], 3),
                    "compress_s": stats["profile"].get("compress", 0.0)}
        _sh.rmtree(leg_dir, ignore_errors=True)
    # device-compress leg (ops/device_compress.py): full segments hand
    # the io thread FINISHED compressed bytes, so the host compress
    # stage drops out of the pipeline — its residual compress_s is the
    # device scan + emission, billed where the pool legs bill packing.
    # Byte identity with every host leg is CI-checked by the
    # device-compress legs of scripts/check_compaction_ab.py.
    leg_dir = os.path.join(base_dir, "device")
    _sh.copytree(pristine, leg_dir)
    stats = _compact_dir(leg_dir, table, compress_pool=0,
                         decode_ahead=False, engine="device",
                         use_device=True, device_compress=True)
    out["device"] = {
        "mib_s": round(stats["bytes_read"] / 2**20 / stats["wall"], 2),
        "wall_s": round(stats["wall"], 3),
        "compress_s": stats["profile"].get("compress", 0.0),
        "io_write_s": stats["profile"].get("io_write", 0.0)}
    _sh.rmtree(leg_dir, ignore_errors=True)
    return out


def _geomean(xs) -> float:
    return float(np.exp(np.mean(np.log(np.asarray(xs, dtype=float)))))


def paired_ab(run_a, run_b, rounds: int = 3) -> dict:
    """Paired interleaved A/B: A and B run back-to-back within each
    round (order alternating round to round), and the headline is the
    GEOMEAN of the per-round B/A ratios. This box's throughput drifts
    ~2x run-to-run (PR 7 measured 43-100 MiB/s on identical code);
    pairing cancels the drift because both legs of a pair see the same
    momentary box, and the geomean is the right average for ratios —
    a single A-then-B comparison can report a 2x win or loss that is
    pure scheduling noise."""
    a_vals, b_vals, ratios = [], [], []
    for r in range(rounds):
        if r % 2 == 0:
            a, b = run_a(), run_b()
        else:
            b, a = run_b(), run_a()
        a_vals.append(a)
        b_vals.append(b)
        ratios.append(b / a)
    return {"a_geomean": round(_geomean(a_vals), 2),
            "b_geomean": round(_geomean(b_vals), 2),
            "speedup_geomean": round(_geomean(ratios), 3),
            "rounds": rounds}


# ------------------------------------------------------------ mesh bench --

MESH_LANE_COUNTS = (1, 2, 4, 8)
MESH_READ_PARTITIONS = 2048
MESH_READ_ROWS = 48
MESH_READ_BATCH = 512


def run_mesh_bench(base_dir: str, table, cfg) -> dict:
    """Mesh data-plane scaling curve (docs/multichip.md): compaction
    MiB/s and batched-read rows/s at 1/2/4/8 mesh lanes vs the serial
    path. Lanes here are GIL-releasing host threads under the native
    engine (the device engine fans the same shards across jax devices;
    the virtual-mesh curve lives in __graft_entry__.dryrun_multichip).
    Output bytes are identical to serial for every lane count
    (scripts/check_compaction_ab.py mesh legs pin it). The headline
    serial-vs-mesh number goes through paired_ab so box drift can't
    fake (or hide) the win; curve legs are single runs — read their
    trend, not any one point. max_shard_wall_s is the per-device wall:
    it must FALL as lanes rise (each device owns less data), and
    overlap_factor (lane-exclusive busy seconds over the fan-out's
    elapsed wall) > 1 proves lanes ran concurrently — a sequential
    loop over shards measures ~1."""
    import shutil as _sh

    from cassandra_tpu.parallel import fanout
    from cassandra_tpu.storage.table import ColumnFamilyStore

    # half the headline fixture: the curve runs 1 + len(counts) +
    # 2*rounds compactions — trend resolution, not wall-clock pain
    mesh_cfg = dict(cfg)
    mesh_cfg["runs"] = [n // 2 for n in cfg["runs"]]
    pristine = os.path.join(base_dir, "pristine")
    cfs = ColumnFamilyStore(table, pristine, commitlog=None)
    build_inputs(cfs.directory, table, 5, mesh_cfg)

    knobs = dict(pipelined_io=True, compress_pool=0, decode_ahead=False)

    mesh_stats: dict = {}

    def compact_leg(lanes: int) -> float:
        leg = os.path.join(base_dir, f"lanes{lanes}")
        _sh.copytree(pristine, leg)
        stats = _compact_dir(leg, table, mesh_devices=lanes, **knobs)
        _sh.rmtree(leg, ignore_errors=True)
        if "mesh" in stats:
            mesh_stats[lanes] = stats["mesh"]
        return stats["bytes_read"] / 2**20 / stats["wall"]

    compact_leg(0)   # discarded warm-up: cold page cache + jit
    # every lane count is PAIRED against a serial run (alternating
    # order) — a lone curve leg on this box is 2x noise, the pairwise
    # ratio is the signal. NOTE the ceiling on this box: the mesh
    # parallelizes decode+merge, which is ~40% of this pipeline's wall
    # (compress+io on the writer thread bound the rest), so the curve
    # here proves overlap + byte identity at realistic cost, while the
    # chips-vs-throughput scaling proof is the virtual-mesh curve in
    # __graft_entry__.dryrun_multichip (pure merge, per-device walls
    # asserted strictly decreasing)
    curve = {}
    for n in MESH_LANE_COUNTS:
        pair = paired_ab(lambda: compact_leg(0),
                         lambda n=n: compact_leg(n), rounds=3)
        curve[f"lanes_{n}"] = {
            "serial_mib_s": pair["a_geomean"],
            "mesh_mib_s": pair["b_geomean"],
            "speedup_vs_serial": pair["speedup_geomean"],
            **mesh_stats.get(n, {}),
        }

    # batched reads: every partition once, MESH_READ_BATCH keys per
    # read_partitions call, overlapping sstables so the merge is real
    rd = os.path.join(base_dir, "read")
    rcfs = ColumnFamilyStore(table, rd, commitlog=None)
    rng = np.random.default_rng(13)
    from cassandra_tpu.storage import cellbatch as cb
    from cassandra_tpu.storage.sstable import Descriptor, SSTableWriter
    from cassandra_tpu.tools import bulk
    for gen in (1, 2, 3):
        n = MESH_READ_PARTITIONS * MESH_READ_ROWS
        pk = rng.integers(0, MESH_READ_PARTITIONS, n)
        ck = rng.integers(0, 10_000, n)
        vals = rng.integers(0, 256, (n, VALUE_BYTES), dtype=np.uint8)
        ts = rng.integers(1, 1 << 40, n).astype(np.int64)
        w = SSTableWriter(Descriptor(rcfs.directory, gen), table,
                          estimated_partitions=MESH_READ_PARTITIONS)
        w.append(cb.merge_sorted([bulk.build_int_batch(table, pk, ck,
                                                       vals, ts)]))
        w.finish()
    rcfs.reload_sstables()
    pks = [table.serialize_partition_key([p])
           for p in range(MESH_READ_PARTITIONS)]
    now = int(time.time())

    def read_leg(lanes: int) -> float:
        fanout.configure(lanes)
        try:
            rows = 0
            t0 = time.perf_counter()
            for i in range(0, len(pks), MESH_READ_BATCH):
                res = rcfs.read_partitions(pks[i:i + MESH_READ_BATCH],
                                           now=now)
                rows += sum(len(b) for _, b in res)
            return rows / (time.perf_counter() - t0)
        finally:
            fanout.configure(0)

    read_leg(0)   # warm-up
    reads = {}
    # lanes_1 is omitted: the read route needs >= 2 non-empty shards
    # (_mesh_read_shards), so a 1-lane "mesh" read IS the serial path —
    # pairing it against serial would print box noise as a speedup
    for n in MESH_LANE_COUNTS:
        if n < 2:
            continue
        pair = paired_ab(lambda: read_leg(0), lambda n=n: read_leg(n),
                         rounds=2)
        reads[f"lanes_{n}"] = {
            "serial_rows_s": int(pair["a_geomean"]),
            "mesh_rows_s": int(pair["b_geomean"]),
            "speedup_vs_serial": pair["speedup_geomean"],
        }

    return {
        "compaction_mib_s": curve,
        "batch_read_rows_s": reads,
        "fixture": {"compaction_cells": sum(mesh_cfg["runs"]),
                    "read_partitions": MESH_READ_PARTITIONS,
                    "read_rows_per_sstable": MESH_READ_ROWS,
                    "read_sstables": 3,
                    "read_batch_keys": MESH_READ_BATCH},
    }


def run_pipeline_bench(base_dir: str, table, cfg) -> dict:
    """Pipeline-ledger section (docs/observability.md): the unified
    per-stage accounting table — busy/stall/idle seconds, items/bytes
    and queue high-water — for one compaction, one pipelined flush and
    one mesh (2-lane) compaction, plus a reconciliation of the ledger's
    write-leg busy seconds against the task profile's phase split
    (write-phase stall attribution: the phases overlap on different
    threads, so the ledger's per-stage numbers are the capacities and
    the stalls say which stage the wall actually waited on). This is
    the where-did-the-wall-go table ROADMAP item 1 navigates by."""
    from cassandra_tpu.storage.table import ColumnFamilyStore
    from cassandra_tpu.utils import pipeline_ledger

    small = {k: v for k, v in cfg.items() if k != "l1_runs"}
    small["runs"] = [131_072] * 3
    pipeline_ledger.reset_all()

    # --- compaction leg (serial data plane, pipelined write leg)
    cdir = os.path.join(base_dir, "compact")
    cfs = ColumnFamilyStore(table, cdir, commitlog=None)
    build_inputs(cfs.directory, table, 7, small)
    stats = _compact_dir(cdir, table, cfs=cfs, **_task_knobs())
    compaction_stages = pipeline_ledger.ledger("compaction").snapshot()
    pool_stage = pipeline_ledger.ledger("compress_pool").snapshot()

    # reconcile ledger vs the profile phase split: same clock, same
    # boundaries — they must agree within noise for the serialize/
    # compress/io_write stages the writer accounts to both
    prof = stats["profile"]
    reconcile = {}
    for stage in ("serialize", "compress", "io_write"):
        led_s = compaction_stages.get(stage, {}).get("busy_s", 0.0)
        reconcile[stage] = {
            "profile_s": round(prof.get(stage, 0.0), 3),
            "ledger_busy_s": round(led_s, 3),
        }
    # the decode stage bills the SAME dt to the profile (io_decode +
    # decode_ahead) and to its ledger busy at every cursor fetch, so
    # these reconcile exactly, not just within noise
    reconcile["decode"] = {
        "profile_s": round(prof.get("io_decode", 0.0)
                           + prof.get("decode_ahead", 0.0), 3),
        "ledger_busy_s": round(
            compaction_stages.get("decode", {}).get("busy_s", 0.0), 3),
    }

    # --- mesh leg: 2 lanes through the same ledger (decode/merge)
    mdir = os.path.join(base_dir, "mesh")
    mcfs = ColumnFamilyStore(table, mdir, commitlog=None)
    build_inputs(mcfs.directory, table, 8, small)
    _compact_dir(mdir, table, cfs=mcfs, mesh_devices=2, **_task_knobs())
    mesh_stages = pipeline_ledger.ledger("mesh").snapshot()

    # --- flush leg: drain -> serialize -> compress -> io_write
    flush_stats = _flush_leg(os.path.join(base_dir, "flush"), True,
                             2048, 16)
    flush_stages = pipeline_ledger.ledger("flush").snapshot()

    return {
        "compaction": compaction_stages,
        "flush": flush_stages,
        "mesh": mesh_stages,
        "compress_pool": pool_stage,
        "reconcile_write_phase": reconcile,
        "flush_leg": flush_stats,
        "compaction_wall_s": round(stats["wall"], 3),
    }


def run_codec_bench():
    """compress_iov micro-benchmark: the native zero-copy FFI path vs
    the generic Python fallback (now also staging-copy-free on the
    input side) — codec regressions on either path are visible here."""
    from cassandra_tpu.ops.codec import Compressor, get_compressor

    rng = np.random.default_rng(11)
    frame_kib = 256
    frames = [rng.integers(97, 122, frame_kib * 1024, dtype=np.uint8)
              for _ in range(48)]
    total_mib = sum(f.nbytes for f in frames) / 2**20
    lz4 = get_compressor("LZ4Compressor")
    out = {"frames": len(frames), "frame_kib": frame_kib}
    for tag, fn in (
            ("iov_native", lambda: lz4.compress_iov(frames)),
            # the base-class fallback bound to the same codec: one
            # compress() FFI call per frame, zero-copy input views
            ("iov_fallback", lambda: Compressor.compress_iov(lz4, frames))):
        fn()   # warm
        t0 = time.perf_counter()
        fn()
        out[f"{tag}_mib_s"] = round(total_mib /
                                    (time.perf_counter() - t0), 1)
    return out


# ----------------------------------------------------------- write bench --

WRITE_THREADS = 8
WRITE_VALUE = 64


def _write_leg(base_dir: str, fast: bool, threads: int, n_total: int,
               sync: str = "batch") -> dict:
    """mutations/s through StorageEngine.apply with `threads` writers,
    commitlog in a durable mode — the group-commit + sharded-memtable
    surface. Returns rate + commitlog sync stats for the leg."""
    import threading

    from cassandra_tpu.schema import Schema, make_table
    from cassandra_tpu.storage.engine import StorageEngine
    from cassandra_tpu.storage.mutation import Mutation

    os.environ["CTPU_WRITE_FASTPATH"] = "1" if fast else "0"
    d = os.path.join(base_dir,
                     f"{'fast' if fast else 'naive'}-{sync}-{threads}t")
    schema = Schema()
    schema.create_keyspace("wb")
    table = make_table("wb", "t", pk=["id"], ck=["c"],
                       cols={"id": "int", "c": "int", "v": "blob"})
    schema.add_table(table)
    engine = StorageEngine(d, schema, commitlog_sync=sync)
    vcol = table.columns["v"].column_id
    rng = np.random.default_rng(5)
    vals = rng.integers(0, 256, (n_total, WRITE_VALUE), dtype=np.uint8)
    muts = []
    for i in range(n_total):
        m = Mutation(table.id, table.serialize_partition_key([i % 512]))
        m.add(table.serialize_clustering([i]), vcol, b"",
              vals[i].tobytes(), 1_000_000 + i)
        muts.append(m)
    cl = engine.commitlog
    syncs0 = cl._sync_hist.count
    t0 = time.perf_counter()
    if threads == 1:
        for m in muts:
            engine.apply(m)
    else:
        def worker(sl):
            for m in sl:
                engine.apply(m)
        ts = [threading.Thread(target=worker, args=(muts[i::threads],))
              for i in range(threads)]
        for th in ts:
            th.start()
        for th in ts:
            th.join()
    wall = time.perf_counter() - t0
    out = {"mutations_per_s": round(n_total / wall, 1),
           "wall_s": round(wall, 3),
           "mutations": n_total,
           # naive durable modes fsync inline, once per mutation (those
           # don't route through the sync-latency hist)
           "fsyncs": (cl._sync_hist.count - syncs0) if fast else n_total}
    engine.close()
    return out


def _flush_leg(base_dir: str, fast: bool, n_parts: int,
               rows_per_part: int) -> dict:
    """Flush MiB/s: fill one memtable through the real ingest path
    (apply_batch, no commitlog), then time ColumnFamilyStore.flush
    (fast lane = shard-drain -> compress -> io_write pipeline; naive =
    sort-everything-then-serial-write)."""
    from cassandra_tpu.schema import make_table
    from cassandra_tpu.storage.mutation import Mutation
    from cassandra_tpu.storage.table import ColumnFamilyStore

    os.environ["CTPU_WRITE_FASTPATH"] = "1" if fast else "0"
    table = make_table("wb", "flush" + ("f" if fast else "n"),
                       pk=["id"], ck=["c"],
                       cols={"id": "int", "c": "int", "v": "blob"})
    cfs = ColumnFamilyStore(table, base_dir, commitlog=None)
    vcol = table.columns["v"].column_id
    rng = np.random.default_rng(9)
    vals = rng.integers(0, 256,
                        (n_parts * rows_per_part, WRITE_VALUE),
                        dtype=np.uint8)
    muts, i = [], 0
    for p in range(n_parts):
        m = Mutation(table.id, table.serialize_partition_key([p]))
        for r in range(rows_per_part):
            m.add(table.serialize_clustering([r]), vcol, b"",
                  vals[i].tobytes(), 1_000_000 + i)
            i += 1
        muts.append(m)
    for j in range(0, len(muts), 256):
        cfs.apply_batch(muts[j:j + 256])
    n_cells = len(cfs.memtable)
    t0 = time.perf_counter()
    reader = cfs.flush()
    wall = time.perf_counter() - t0
    data_mib = reader.data_size / 2**20
    for s in cfs.live_sstables():
        s.close()
    return {"cells": n_cells, "sstable_mib": round(data_mib, 2),
            "wall_s": round(wall, 3),
            "mib_per_s": round(data_mib / wall, 2)}


def run_write_bench(base_dir: str) -> dict:
    """Write-path section: group-commit + sharded-memtable mutations/s
    at 1 and 8 writer threads (CTPU_WRITE_FASTPATH A/B, batch-durable
    commitlog), flush MiB/s (pipelined vs serial), commitlog sync
    latency histograms, and the group-window mode. The A/B content
    identity itself is CI-enforced by scripts/check_writepath_ab.py."""
    from cassandra_tpu.service.metrics import GLOBAL as METRICS

    prev = os.environ.get("CTPU_WRITE_FASTPATH")
    try:
        naive1 = _write_leg(base_dir, False, 1, 400)
        naive8 = _write_leg(base_dir, False, WRITE_THREADS, 400)
        fast1 = _write_leg(base_dir, True, 1, 1200)
        fast8 = _write_leg(base_dir, True, WRITE_THREADS, 4000)
        group8 = _write_leg(base_dir, True, WRITE_THREADS, 1500,
                            sync="group")
        flush_naive = _flush_leg(os.path.join(base_dir, "fln"), False,
                                 4096, 48)
        flush_fast = _flush_leg(os.path.join(base_dir, "flf"), True,
                                4096, 48)
    finally:
        if prev is None:
            os.environ.pop("CTPU_WRITE_FASTPATH", None)
        else:
            os.environ["CTPU_WRITE_FASTPATH"] = prev
    return {
        "mutations_per_s": {
            "naive": {"1_thread": naive1, "8_threads": naive8},
            "fastpath": {"1_thread": fast1, "8_threads": fast8},
            "group_mode_8_threads": group8,
        },
        "speedup_8_threads": round(
            fast8["mutations_per_s"] / max(naive8["mutations_per_s"],
                                           0.1), 2),
        "flush": {"naive": flush_naive, "pipelined": flush_fast,
                  "speedup": round(flush_fast["mib_per_s"]
                                   / max(flush_naive["mib_per_s"], 0.01),
                                   2)},
        "commitlog": {
            "sync_latency_us":
                METRICS.hist("commitlog.sync_latency").summary(),
            "waiting_on_commit_us":
                METRICS.hist("commitlog.waiting_on_commit").summary(),
        },
    }


# ------------------------------------------------------------ read bench --

READ_PARTITIONS = 192
READ_ROWS = 8
READ_ROUNDS = 5          # live sstables in the fixture
READ_SAMPLES = 1200


def _build_read_fixture(cfs, table, now: int) -> None:
    """Freshest-sstable-wins fixture: every round fully supersedes each
    partition (partition deletion + re-insert, newer timestamps) and
    flushes, so the newest sstable's deletion covers everything older —
    the workload timestamp-skip collation exists for. gc_grace keeps the
    deletions un-purged at read time."""
    from cassandra_tpu.storage import cellbatch as cb
    from cassandra_tpu.storage.cellbatch import CellBatchBuilder
    from cassandra_tpu.storage.sstable import Descriptor, SSTableWriter

    vcol = table.columns["v"].column_id
    rng = np.random.default_rng(7)
    for r in range(READ_ROUNDS):
        b = CellBatchBuilder(table)
        ts0 = (r + 1) * 1_000_000
        for p in range(READ_PARTITIONS):
            pk = table.serialize_partition_key([p])
            b.add_partition_deletion(pk, ts0, ldt=now)
            for c in range(READ_ROWS):
                ck = table.serialize_clustering([c])
                b.add_row_liveness(pk, ck, ts0 + 1 + c)
                b.add_cell(pk, ck, vcol,
                           rng.integers(0, 256, VALUE_BYTES,
                                        dtype=np.uint8).tobytes(),
                           ts0 + 1 + c)
        merged = cb.merge_sorted([b.seal()], now=now)
        gen = cfs.next_generation()
        w = SSTableWriter(Descriptor(cfs.directory, gen), table,
                          estimated_partitions=READ_PARTITIONS)
        w.append(merged)
        w.finish()
    cfs.reload_sstables()


def run_read_bench(base_dir: str) -> dict:
    """Read-path section: single-partition p50/p99 and batched
    multi-partition reads, fastpath (CTPU_READ_FASTPATH=1: timestamp-
    skip collation + batched segment gather) A/B'd against the naive
    collation — results must be bit-identical; the fixture also proves
    mean sstables_consulted collapses to ~1 with READ_ROUNDS live
    sstables."""
    from cassandra_tpu.schema import make_table
    from cassandra_tpu.service.metrics import GLOBAL as METRICS
    from cassandra_tpu.storage.cellbatch import content_digest
    from cassandra_tpu.storage.row_cache import RowCache
    from cassandra_tpu.storage.table import ColumnFamilyStore

    table = make_table("bench", "readfix", pk=["id"], ck=["c"],
                       cols={"id": "int", "c": "int", "v": "blob"})
    cfs = ColumnFamilyStore(table, base_dir, commitlog=None)
    now = int(time.time())
    _build_read_fixture(cfs, table, now)
    pks = [table.serialize_partition_key([p])
           for p in range(READ_PARTITIONS)]
    rng = np.random.default_rng(11)
    seq = [pks[i] for i in rng.integers(0, len(pks), READ_SAMPLES)]
    hist = METRICS.hist("table.bench.readfix.sstables_per_read")

    def leg(env_val: str, batch_k: int = 0):
        prev = os.environ.get("CTPU_READ_FASTPATH")
        os.environ["CTPU_READ_FASTPATH"] = env_val
        c0, t0 = hist.count, hist.total_us
        lats, digests = [], []
        try:
            if batch_k:
                for i in range(0, len(seq), batch_k):
                    grp = seq[i:i + batch_k]
                    t = time.perf_counter()
                    res = cfs.read_partitions(grp, now=now)
                    lats.append((time.perf_counter() - t) * 1e6
                                / len(grp))
                    digests += [content_digest(b) for _, b in res]
            else:
                for pk in seq:
                    t = time.perf_counter()
                    b = cfs.read_partition(pk, now=now)
                    lats.append((time.perf_counter() - t) * 1e6)
                    digests.append(content_digest(b))
        finally:
            if prev is None:
                os.environ.pop("CTPU_READ_FASTPATH", None)
            else:
                os.environ["CTPU_READ_FASTPATH"] = prev
        arr = np.array(lats)
        dc = hist.count - c0
        stats = {"p50_us": round(float(np.percentile(arr, 50)), 1),
                 "p99_us": round(float(np.percentile(arr, 99)), 1),
                 "mean_sstables_consulted":
                 round((hist.total_us - t0) / dc, 2) if dc else None}
        return stats, digests

    naive, d_naive = leg("0")
    fast, d_fast = leg("1")
    batch_naive, db_naive = leg("0", batch_k=16)
    batch_fast, db_fast = leg("1", batch_k=16)
    # row-cache leg: attach a cache, warm it, measure repeat reads
    cfs.row_cache = RowCache(cfs.directory)
    _, d_warm = leg("1")
    cached, d_cached = leg("1")
    cfs.row_cache.clear()   # don't pin fixture merges in the shared
    cfs.row_cache = None    # service for the rest of the bench process
    identical = (d_naive == d_fast == d_warm == d_cached
                 and db_naive == db_fast)
    return {
        "fixture": {"partitions": READ_PARTITIONS,
                    "rows_per_partition": READ_ROWS,
                    "sstables": READ_ROUNDS, "reads": len(seq)},
        "single_partition_us": {"naive": naive, "fastpath": fast,
                                "row_cache": cached},
        "batch16_per_key_us": {"naive": batch_naive,
                               "fastpath": batch_fast},
        "identical_results": bool(identical),
        "fastpath_speedup_p50": round(
            naive["p50_us"] / max(fast["p50_us"], 0.1), 2),
    }


# ------------------------------------------------------------ scan bench --

SCAN_GENERATIONS = 4          # one flushed sstable per generation
SCAN_ROWS_PER_GEN = 3000
SCAN_QUERY_REPS = 5           # queries per paired_ab run


def run_scan_bench(base_dir: str) -> dict:
    """Analytical scan section (docs/read-path.md): the ALLOW FILTERING
    pushdown lane (zone-map pruning + fused device predicate kernels +
    candidate-only Phase B) paired_ab'd against the naive materializing
    Python scan on a selective predicate, plus the aggregation leg
    proving count/min/max/sum/avg fold on keys with ZERO rows
    materialized host-side. The fixture writes each flush generation
    into a disjoint score band, so zone maps prune the other
    generations' segments before decode — segments_skipped /
    segments_total is the observable prune rate. Row identity between
    the legs is asserted here and CI-pinned by scripts/check_scan_ab.py."""
    from cassandra_tpu.cql import Session
    from cassandra_tpu.ops import device_scan as ds
    from cassandra_tpu.schema import Schema
    from cassandra_tpu.service.metrics import GLOBAL as METRICS
    from cassandra_tpu.storage.engine import StorageEngine

    n_rows = SCAN_GENERATIONS * SCAN_ROWS_PER_GEN
    eng = StorageEngine(os.path.join(base_dir, "scan"), Schema(),
                        commitlog_sync="batch")
    try:
        s = Session(eng)
        s.execute("CREATE KEYSPACE bench WITH replication = "
                  "{'class': 'SimpleStrategy', 'replication_factor': 1}")
        s.execute("USE bench")
        s.execute("CREATE TABLE scanfix (id int PRIMARY KEY, "
                  "score int, pad text)")
        cfs = eng.store("bench", "scanfix")
        q = s.prepare("INSERT INTO scanfix (id, score, pad) "
                      "VALUES (?, ?, ?)")
        for g in range(SCAN_GENERATIONS):
            for i in range(SCAN_ROWS_PER_GEN):
                rid = g * SCAN_ROWS_PER_GEN + i
                s.execute_prepared(q, (rid, g * 1000 + i % 50,
                                       f"pad-{rid:08d}"))
            cfs.flush()
        # the selective predicate: 1/50th of ONE generation's band —
        # every other generation's segments are zone-pruned
        target = 1 * 1000 + 7
        query = (f"SELECT id, score FROM scanfix WHERE score = {target} "
                 "ALLOW FILTERING")
        expect = sorted((1 * SCAN_ROWS_PER_GEN + i, target)
                        for i in range(SCAN_ROWS_PER_GEN) if i % 50 == 7)

        def _run(shadow: bool) -> float:
            """Table rows scanned per second over SCAN_QUERY_REPS."""
            if shadow:     # instance attrs shadow the lane off: the
                cfs.scan_filtered = None          # executor's pushdown
                cfs.scan_filtered_aggregate = None  # attempt falls back
            try:
                t0 = time.perf_counter()
                for _ in range(SCAN_QUERY_REPS):
                    rows = s.execute(query).rows
                wall = time.perf_counter() - t0
                assert sorted(rows) == expect
                return n_rows * SCAN_QUERY_REPS / wall
            finally:
                cfs.__dict__.pop("scan_filtered", None)
                cfs.__dict__.pop("scan_filtered_aggregate", None)

        ab = paired_ab(lambda: _run(shadow=True),
                       lambda: _run(shadow=False), rounds=3)
        # prune accounting from one instrumented Phase A
        pred = ds.compile_predicate(
            cfs.table, [(cfs.table.columns["score"], "=", target)])
        _, info = cfs.scan_filtered(pred)
        # aggregation leg: the fold must answer from keys alone —
        # scan.rows_materialized unchanged proves no row dict was built
        m0 = METRICS.counter("scan.rows_materialized")
        a0 = METRICS.counter("scan.agg_pushdown")
        agg = s.execute(
            "SELECT count(score), min(score), max(score), sum(score), "
            f"avg(score) FROM scanfix WHERE score = {target} "
            "ALLOW FILTERING").rows
        n_match = len(expect)
        assert agg == [(n_match, target, target, n_match * target,
                        float(target))], agg
        agg_pushed = METRICS.counter("scan.agg_pushdown") - a0
        agg_materialized = METRICS.counter("scan.rows_materialized") - m0
        return {
            "fixture": {"rows": n_rows, "sstables": SCAN_GENERATIONS,
                        "match_rows": n_match,
                        "queries_per_leg": SCAN_QUERY_REPS},
            # headline: naive materializing scan vs the pushdown lane,
            # geomean of per-round ratios (target >= 2x)
            "rows_per_s": {"naive_geomean": ab["a_geomean"],
                           "pushdown_geomean": ab["b_geomean"]},
            "pushdown_speedup_geomean": ab["speedup_geomean"],
            "prune": {"segments_total": info["segments_total"],
                      "segments_skipped": info["segments_skipped"],
                      "sstables_skipped": info["sstables_skipped"],
                      "candidates": info["candidates"]},
            "aggregation": {"agg_pushdowns": agg_pushed,
                            "rows_materialized": agg_materialized,
                            "zero_materialization":
                            bool(agg_pushed >= 1
                                 and agg_materialized == 0)},
        }
    finally:
        eng.close()


# -------------------------------------------------------- dispatch bench --

DISPATCH_WRITES_PER_LEG = 300


def run_dispatch_bench(base_dir: str) -> dict:
    """Verb-dispatch pool scaling (cluster/messaging.py): the QUORUM
    write class against a 3-node RF=3 LocalCluster with every node's
    replica-side dispatch pool pinned at 1/2/4 workers
    (internode_dispatch_threads). verbs/s is the cluster-wide inbound
    message rate — each QUORUM write costs one MUTATION_REQ per
    replica plus the response legs — so it tracks replica-side handler
    throughput, the stage the pool widens. The 1-vs-4 headline goes
    through paired_ab because coordination rounds on this box drift
    with scheduling; byte/ack semantics are untouched (the pool only
    moves handlers off the distributor thread, and the worker-death
    blast-radius pin lives in tests/test_cluster.py)."""
    from cassandra_tpu.cluster.node import LocalCluster
    from cassandra_tpu.cluster.replication import ConsistencyLevel

    c = LocalCluster(3, os.path.join(base_dir, "cluster"), rf=3)
    try:
        for n in c.nodes:
            n.default_cl = ConsistencyLevel.QUORUM
        s = c.session(1)
        s.execute("CREATE KEYSPACE bench WITH replication = "
                  "{'class': 'SimpleStrategy', 'replication_factor': 3}")
        s.execute("USE bench")
        s.execute("CREATE TABLE kv (k int PRIMARY KEY, v text)")
        seq = [0]

        def leg(width: int) -> float:
            for n in c.nodes:
                n.messaging.set_dispatch_workers(width)
            recv0 = sum(n.messaging.metrics["received"]
                        for n in c.nodes)
            t0 = time.time()
            for _ in range(DISPATCH_WRITES_PER_LEG):
                k = seq[0] = seq[0] + 1
                s.execute(f"INSERT INTO kv (k, v) VALUES ({k}, 'v{k}')")
            dt = time.time() - t0
            recv = sum(n.messaging.metrics["received"]
                       for n in c.nodes) - recv0
            return recv / dt

        leg(1)   # warm-up: schema settled, pools spawned
        out = {f"workers_{w}": {"verbs_s": round(leg(w), 1)}
               for w in (1, 2, 4)}
        out["paired_1_vs_4"] = paired_ab(lambda: leg(1),
                                         lambda: leg(4))
        out["writes_per_leg"] = DISPATCH_WRITES_PER_LEG
        return out
    finally:
        c.shutdown()


# ------------------------------------------------------- frontdoor bench --

FRONTDOOR_KEYS = 4096
FRONTDOOR_OPS = 2048
# saturation matrix sizing: 9 legs + hints + chaos against a 3-node
# RF=3 cluster at QUORUM — per-op cost is a full coordination round, so
# legs stay in the hundreds of ops
SATURATION_CONNS = 6
SATURATION_OPS_PER_LEG = 240


def run_frontdoor_bench(base_dir: str) -> dict:
    """Front-door section: end-to-end native-protocol ops/s and tail
    latency through the event-loop server (docs/native-transport.md) at
    16/64/256 concurrent wire connections via scripts/stress.py, plus an
    overload run proving the admission gate SHEDS with OVERLOADED errors
    while in-flight requests never exceed the permit cap (no unbounded
    queueing, no collapse). The server-thread sampler pins the
    event-loop contract: thread count stays fixed while serving 256
    connections."""
    import threading

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts"))
    import stress as stress_mod

    from cassandra_tpu.client import Cluster
    from cassandra_tpu.schema import Schema
    from cassandra_tpu.storage.engine import StorageEngine
    from cassandra_tpu.transport import CQLServer

    engine = StorageEngine(os.path.join(base_dir, "fd"), Schema(),
                           commitlog_sync="periodic")
    # throughput legs must not shed: cap above the largest leg's
    # offered concurrency (the overload leg then pinches it)
    engine.settings.set("native_transport_max_concurrent_requests", 1024)
    srv = CQLServer(engine)
    host, port = "127.0.0.1", srv.port
    fixed = len(srv.event_loops) + len(srv.dispatcher.threads)
    server_threads = lambda: stress_mod._server_thread_count(port)  # noqa: E731

    try:
        # preload the key space (disjoint sequential ranges) so the
        # mixed legs' reads hit real rows
        stress_mod.run_stress(host, port, profile="write",
                              connections=8, ops=FRONTDOOR_KEYS,
                              dist="sequential", key_space=FRONTDOOR_KEYS,
                              seed=1)
        legs = {}
        samples: list[int] = []
        for conns in (16, 64, 256):
            stop = threading.Event()

            def sampler():
                while not stop.is_set():
                    samples.append(server_threads())
                    stop.wait(0.05)
            st = threading.Thread(target=sampler, daemon=True)
            st.start()
            r = stress_mod.run_stress(
                host, port, profile="mixed", connections=conns,
                ops=FRONTDOOR_OPS, dist="zipf",
                key_space=FRONTDOOR_KEYS, seed=conns, setup=False)
            stop.set()
            st.join()
            legs[f"{conns}_connections"] = {
                k: r[k] for k in ("ops_s", "p50_us", "p99_us", "ok",
                                  "errors")}
        threads_fixed = bool(samples) and \
            min(samples) == max(samples) == fixed
        # overload run: pinch the permit cap, hammer, prove shedding
        engine.settings.set("native_transport_max_concurrent_requests", 2)
        srv.permits.reset_high_water()
        o = stress_mod.run_stress(host, port, profile="write",
                                  connections=32, ops=1024,
                                  dist="uniform",
                                  key_space=FRONTDOOR_KEYS, seed=99,
                                  setup=False)
        hwm = srv.permits.high_water
        engine.settings.set("native_transport_max_concurrent_requests",
                            1024)
        s = Cluster(host, port).connect()
        responsive = bool(
            s.execute("SELECT v FROM stress.frontdoor WHERE key = 0")
            .rows)
        s.close()
        shed = o["errors"].get("overloaded", 0)
        return {
            "event_loop_threads": len(srv.event_loops),
            "dispatch_threads": len(srv.dispatcher.threads),
            "threads_fixed_while_serving_256_connections": threads_fixed,
            "legs": legs,
            "overload": {
                "permit_cap": 2,
                "ok": o["ok"],
                "overloaded_errors": shed,
                "max_in_flight": hwm,
                "within_cap": hwm <= 2,
                "responsive_after": responsive,
                "shed_not_collapsed": bool(
                    shed > 0 and o["ok"] > 0 and hwm <= 2
                    and responsive),
            },
        }
    finally:
        srv.close()
        engine.close()


def _dispatch_p99_before_after(base_dir: str) -> dict:
    """Matrix write-p99 before/after the verb-dispatch pool: the
    matrix's kv/zipf QUORUM write class with every node's replica-side
    pool pinned at 1 worker — the old single-inbound-worker replica
    path that produced PR 11's breach verdicts — against the auto
    width, through paired_ab on the leg's client-side write p99.
    `p99_ratio_auto_vs_1` < 1.0 is recovered headroom."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts"))
    import stress as stress_mod

    from cassandra_tpu.client import Cluster
    from cassandra_tpu.cluster.node import LocalCluster
    from cassandra_tpu.cluster.replication import ConsistencyLevel
    from cassandra_tpu.transport import CQLServer

    cluster = LocalCluster(3, os.path.join(base_dir, "ab"), rf=3)
    servers = [CQLServer(n) for n in cluster.nodes]
    ports = [srv.port for srv in servers]
    try:
        for nn in cluster.nodes:
            nn.default_cl = ConsistencyLevel.QUORUM
        s = Cluster("127.0.0.1", ports[0]).connect()
        for ddl in stress_mod.SAT_DDL:
            s.execute(ddl)
        s.close()
        seed = [100]

        def leg(width: int) -> float:
            for nn in cluster.nodes:
                nn.messaging.set_dispatch_workers(width)
            seed[0] += 1
            r = stress_mod.run_scenario(
                ports, "kv", connections=SATURATION_CONNS,
                ops=SATURATION_OPS_PER_LEG, dist="zipf",
                key_space=512, write_ratio=1.0, cl="QUORUM",
                seed=seed[0])
            return float(r["p99_us"])

        leg(0)   # warm-up: schema + pools settled
        auto_width = cluster.nodes[0].messaging.dispatch_workers
        pair = paired_ab(lambda: leg(1), lambda: leg(0))
        return {
            "scenario": "kv:zipf write-only (QUORUM)",
            "auto_width": auto_width,
            "write_p99_us": {"workers_1": pair["a_geomean"],
                             "auto": pair["b_geomean"]},
            "p99_ratio_auto_vs_1": pair["speedup_geomean"],
            "rounds": pair["rounds"],
        }
    finally:
        for srv in servers:
            try:
                srv.close()
            except Exception:
                pass
        cluster.shutdown()


def run_saturation_bench(base_dir: str) -> dict:
    """Saturation section (ROADMAP item 5): the scenario matrix from
    scripts/stress.py — zipf/sequential/uniform key streams crossed
    with the workload classes (wide partitions, TTL time series on
    TWCS, counters, LWT, logged batches, mixed RMW, kv baseline), every
    leg through the WIRE against a 3-node RF=3 LocalCluster with hints
    and speculative retry live and the SLO service polling. Each leg
    reports a verdict (p99 vs target, error budget remaining); the
    chaos leg (faultfs EIO on one replica's sstables mid-run, that
    node's disk policy `stop`) must end in a breach-triggered
    flight-recorder bundle carrying the `slo.breach` event and the
    scenario id."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts"))
    import stress as stress_mod

    out = stress_mod.run_matrix(
        os.path.join(base_dir, "sat"), connections=SATURATION_CONNS,
        ops_per_leg=SATURATION_OPS_PER_LEG, key_space=512, seed=3)
    ch = out.get("chaos", {})
    out["certified"] = bool(
        len(out.get("workload_classes", [])) >= 6
        # every leg must have actually SERVED operations and carry an
        # SLO verdict — a workload class whose workers all failed must
        # not certify on an empty (vacuously compliant) latency list
        and all(leg["ok"] > 0 and "slo" in leg
                for leg in out["legs"].values())
        and ch.get("breached") and ch.get("bundle_has_breach_event")
        and ch.get("scenario_id_in_bundle"))
    # write-p99 before/after the dispatch pool (the matrix's QUORUM
    # write class at pool width 1 vs auto) — the headroom record the
    # breach verdicts asked for
    out["dispatch_before_after"] = _dispatch_p99_before_after(base_dir)
    return out


def run_observatory_bench(base_dir: str) -> dict:
    """Observatory section (docs/observability.md layer 5): prove
    (a) the metrics-history sampler costs < 1 % of a real
    flush+compaction run even at a 4 Hz interval (40x the default
    rate) with the pipeline ledger armed — the sampler's cumulative
    capture seconds over the leg's wall, same clock both sides; and
    (b) the per-table WA/SA gauges reconcile EXACTLY against the
    run's actual flushed/compacted byte counters (same-source
    arithmetic, the contract scripts/check_observatory.py gates)."""
    from cassandra_tpu.config import Config, Settings
    from cassandra_tpu.schema import Schema, make_table
    from cassandra_tpu.storage.engine import StorageEngine
    from cassandra_tpu.storage.mutation import Mutation

    settings = Settings(Config.load({
        "metrics_history_enabled": True,
        "metrics_history_interval": "250ms",   # 40x the default rate
        "compaction_throughput": 0}))
    schema = Schema()
    schema.create_keyspace("obs")
    table = make_table("obs", "t", pk=["id"], ck=["c"],
                       cols={"id": "int", "c": "int", "v": "blob"})
    schema.add_table(table)
    d = os.path.join(base_dir, "eng")
    eng = StorageEngine(d, schema, commitlog_sync="periodic",
                        settings=settings)
    try:
        cfs = eng.store("obs", "t")
        vcol = table.columns["v"].column_id
        rng = np.random.default_rng(9)
        vals = rng.integers(0, 256, (4096, 256), dtype=np.uint8)
        t0 = time.perf_counter()
        for gen in range(4):
            muts = []
            for i in range(4096):
                m = Mutation(table.id,
                             table.serialize_partition_key([i % 512]))
                m.add(table.serialize_clustering([gen * 4096 + i]),
                      vcol, b"", vals[i].tobytes(), 1_000_000 + i)
                muts.append(m)
            eng.apply_batch(muts)
            cfs.flush()
        stats = eng.compactions.major_compaction(cfs)
        wall = time.perf_counter() - t0
        svc = eng.metrics_history
        overhead = svc.sample_seconds / max(wall, 1e-9)

        m = cfs.metrics
        amp = cfs.amplification()
        wa_recomputed = round(
            (m["bytes_flushed"] + m["bytes_compacted_out"])
            / max(m["bytes_ingested"], 1), 6)
        live = cfs.live_sstables()
        total_parts = sum(s.n_partitions for s in live)
        toks = np.concatenate([np.asarray(s.partition_tokens)
                               for s in live if s.n_partitions > 0])
        sa_recomputed = round(total_parts
                              / max(len(np.unique(toks)), 1), 6)
        return {
            "sampler": {
                "interval_s": svc.interval_s,
                "samples": svc.samples,
                "sample_seconds": round(svc.sample_seconds, 4),
                "wall_s": round(wall, 3),
                "overhead_pct": round(overhead * 100.0, 4),
                "overhead_ok": bool(overhead < 0.01),
            },
            "amplification": {
                "write_amplification": amp["write_amplification"],
                "space_amplification": amp["space_amplification"],
                "wa_recomputed": wa_recomputed,
                "sa_recomputed": sa_recomputed,
                "bytes_ingested": m["bytes_ingested"],
                "bytes_flushed": m["bytes_flushed"],
                "bytes_compacted_in": m["bytes_compacted_in"],
                "bytes_compacted_out": m["bytes_compacted_out"],
                "reconciled": bool(
                    amp["write_amplification"] == wa_recomputed
                    and amp["space_amplification"] == sa_recomputed),
            },
            "compaction": {"inputs": stats["inputs"],
                           "bytes_read": stats["bytes_read"],
                           "bytes_written": stats["bytes_written"]},
            "history_series": svc.stats()["series"],
        }
    finally:
        eng.close()


def run_profiler_bench(base_dir: str) -> dict:
    """Profiler section (docs/observability.md layer 6): (a) the
    always-on wall-clock sampler ring ON vs OFF over the same
    flush+compaction leg, paired+interleaved (paired_ab) because the
    box drifts — the ring must cost < 1 % of the compaction headline.
    The pass/fail bar is the sampler's own clock-measured capture
    seconds over the ON legs' wall (the observatory section's
    measurement: the only one that can RESOLVE 1 % under this box's
    2x run-to-run drift); the paired throughput ratio is reported
    beside it as the end-to-end sanity bound. (b) an attribution
    block from a profiled session over one leg: the hottest
    cpu/blocked frames, plus the per-thread tie-out against the
    pipeline ledger — for each ledger-instrumented worker thread, the
    sampler's on-CPU share of that thread's samples and the ledger's
    busy share of the same wall are two observers of the same
    question (scripts/check_profiler.py gates the mechanics, this
    proves them on a real run)."""
    from cassandra_tpu.config import Config, Settings
    from cassandra_tpu.schema import Schema, make_table
    from cassandra_tpu.service import sampler as wallprof
    from cassandra_tpu.storage.engine import StorageEngine
    from cassandra_tpu.storage.mutation import Mutation
    from cassandra_tpu.utils import pipeline_ledger

    def leg(tag: str, ring_on: bool, session: bool = False) -> dict:
        settings = Settings(Config.load({
            "profiler_enabled": ring_on,
            "profiler_interval": "10ms",   # 5x the default rate: the
            #                                < 1 % bar is held with
            #                                headroom to spare
            "compaction_throughput": 0}))
        schema = Schema()
        schema.create_keyspace("prof")
        table = make_table("prof", "t", pk=["id"], ck=["c"],
                           cols={"id": "int", "c": "int", "v": "blob"})
        schema.add_table(table)
        d = os.path.join(base_dir, tag)
        eng = StorageEngine(d, schema, commitlog_sync="periodic",
                            settings=settings)
        sid = None
        try:
            if session:
                sid = wallprof.GLOBAL.start_session(f"bench-{tag}")
            cfs = eng.store("prof", "t")
            vcol = table.columns["v"].column_id
            rng = np.random.default_rng(11)
            vals = rng.integers(0, 256, (4096, 256), dtype=np.uint8)
            t0 = time.perf_counter()
            for gen in range(4):
                muts = []
                for i in range(4096):
                    m = Mutation(table.id,
                                 table.serialize_partition_key(
                                     [i % 512]))
                    m.add(table.serialize_clustering(
                        [gen * 4096 + i]),
                        vcol, b"", vals[i].tobytes(), 1_000_000 + i)
                    muts.append(m)
                eng.apply_batch(muts)
                cfs.flush()
            stats = eng.compactions.major_compaction(cfs)
            wall = time.perf_counter() - t0
            out = {"wall_s": wall, "bytes_read": stats["bytes_read"],
                   "mib_s": stats["bytes_read"] / 2**20 / wall}
            if session:
                out["split"] = wallprof.GLOBAL.stop_session(sid)
                sid = None
                lines = wallprof.GLOBAL.collapsed(
                    out["split"]["target"])
                out["flamegraph_top"] = lines[:10]
                # per-thread state shares from the FULL dump (the
                # tie-out needs every sample, not the top 10 lines)
                per_thread: dict = {}
                for line in lines:
                    stack, _, n = line.rpartition(" ")
                    state, tname = stack.split(";")[:2]
                    t = per_thread.setdefault(
                        tname, {"cpu": 0, "blocked": 0})
                    t[state] += int(n)
                for t in per_thread.values():
                    t["cpu_share"] = round(
                        t["cpu"] / max(t["cpu"] + t["blocked"], 1), 4)
                out["per_thread"] = per_thread
                out["ledger_stages"] = {
                    f"{pname}.{sname}": {
                        "busy_s": s["busy_s"],
                        "stall_s": s["stall_s"],
                        "busy_share_of_wall": round(
                            s["busy_s"] / max(wall, 1e-9), 4)}
                    for pname, st in
                    pipeline_ledger.snapshot_all().items()
                    for sname, s in st.items()}
            return out
        finally:
            if sid is not None:
                wallprof.GLOBAL.stop_session(sid)
            eng.close()
            shutil.rmtree(d, ignore_errors=True)

    # ----- (a) ring overhead: paired interleaved OFF vs ON, MiB/s ----
    samples0 = wallprof.GLOBAL.samples
    seconds0 = wallprof.GLOBAL.sample_seconds
    on_walls: list = []

    def _on():
        r = leg("on", True)
        on_walls.append(r["wall_s"])
        return r["mib_s"]

    pair = paired_ab(lambda: leg("off", False)["mib_s"], _on,
                     rounds=3)
    ring_samples = wallprof.GLOBAL.samples - samples0
    # the bar: the sampler's own clock-measured capture seconds as a
    # share of the ON legs' wall — same-clock, so it resolves < 1 %
    # where the throughput ratio (reported beside it) is drowned by
    # the box's run-to-run drift
    capture_s = wallprof.GLOBAL.sample_seconds - seconds0
    overhead = capture_s / max(sum(on_walls), 1e-9)

    # ----- (b) attribution: profiled session over one leg -----------
    wallprof.GLOBAL.reset()
    pipeline_ledger.reset_all()   # ledger counts THIS leg only
    attributed = leg("attrib", True, session=True)

    # the tie-out: the compress-pool worker is sampled by thread name
    # AND ledger-instrumented as compress_pool.pack — two observers of
    # the same thread over the same wall must agree on whether it was
    # mostly parked or mostly busy
    recon = {}
    worker = next((v for k, v in attributed["per_thread"].items()
                   if k.startswith("sstable-compress")), None)
    pack = attributed["ledger_stages"].get("compress_pool.pack")
    if worker and pack:
        recon["compress_worker"] = {
            "sampler_cpu_share": worker["cpu_share"],
            "ledger_busy_share_of_wall": pack["busy_share_of_wall"],
            "agree": bool((worker["cpu_share"] > 0.5)
                          == (pack["busy_share_of_wall"] > 0.5)),
        }
    return {
        "ring_overhead": {
            "paired_throughput": pair,
            "ring_samples": ring_samples,
            "capture_seconds": round(capture_s, 4),
            "on_legs_wall_s": round(sum(on_walls), 3),
            "overhead_pct": round(overhead * 100.0, 4),
            "overhead_ok": bool(overhead < 0.01),
        },
        "attribution": {
            "wall_s": round(attributed["wall_s"], 3),
            "mib_s": round(attributed["mib_s"], 2),
            "sampler_split": attributed["split"],
            "flamegraph_top": attributed["flamegraph_top"],
            "per_thread": attributed["per_thread"],
            "ledger_stages": attributed["ledger_stages"],
            "reconciliation": recon,
        },
    }


# ------------------------------------------------------ adaptive bench --

ADAPT_PARTITIONS = 256
ADAPT_BURSTS = 8
ADAPT_VALUE_BYTES = 256
ADAPT_TOMB_FLUSHES = 8
ADAPT_TOMBS_PER_FLUSH = 2048
ADAPT_READ_PASSES = 3

ADAPT_STATICS = {
    "stcs": {"class": "SizeTieredCompactionStrategy"},
    "lcs": {"class": "LeveledCompactionStrategy",
            "sstable_size_in_mb": 160, "l0_threshold": 4},
    "twcs": {"class": "TimeWindowCompactionStrategy",
             "compaction_window_unit": "HOURS",
             "compaction_window_size": 1},
}


def _adaptive_leg(base_dir: str, compaction: dict | None,
                  adaptive: bool) -> dict:
    """One full 3-phase run: W (8 write bursts, each its own TWCS hour
    window, one new clustering row per partition per burst — so an
    unmerged layout spreads every partition over 8 sstables), T (8
    flushes of already-expired tombstones on a disjoint LOW-timestamp
    partition range: TWCS drops them rewrite-free, merge strategies pay
    the decode), R (point partition reads — cost tracks sstables per
    partition). Static legs pin `compaction`; the adaptive leg starts
    on default STCS with the controller ON (parked thread, explicit
    deterministic ticks between chunks). Returns per-phase walls + a
    workload-constant MiB/s score (higher = better)."""
    from cassandra_tpu.config import Config, Settings
    from cassandra_tpu.schema import Schema, TableParams, make_table
    from cassandra_tpu.storage.cellbatch import FLAG_TOMBSTONE
    from cassandra_tpu.storage.engine import StorageEngine
    from cassandra_tpu.storage.mutation import Mutation

    opts = {"compaction_throughput": 0}
    if adaptive:
        opts.update({"adaptive_compaction_enabled": True,
                     "adaptive_compaction_interval": "1h",
                     "adaptive_compaction_confirm_ticks": 1,
                     "adaptive_compaction_cooldown": "1ms"})
    settings = Settings(Config.load(opts))
    schema = Schema()
    schema.create_keyspace("ad")
    params = TableParams(gc_grace_seconds=0)
    if compaction is not None:
        params.compaction = dict(compaction)
    table = make_table("ad", "t", pk=["id"], ck=["c"],
                       cols={"id": "int", "c": "int", "v": "blob"},
                       params=params)
    schema.add_table(table)
    eng = StorageEngine(os.path.join(base_dir, "eng"), schema,
                        commitlog_sync="periodic", settings=settings)
    try:
        cfs = eng.store("ad", "t")
        mgr = eng.compactions
        vcol = table.columns["v"].column_id
        rng = np.random.default_rng(17)
        vals = rng.integers(0, 256, (ADAPT_PARTITIONS,
                                     ADAPT_VALUE_BYTES), dtype=np.uint8)

        def tick():
            if adaptive:
                eng.controller.tick()
                time.sleep(0.002)   # let the 1 ms cooldown lapse

        def drain():
            mgr.submit_background(cfs)
            while mgr.run_pending():
                mgr.submit_background(cfs)

        # --- phase W: hour-spread write bursts
        hour_us = 3600 * 1_000_000
        t0 = time.perf_counter()
        for burst in range(ADAPT_BURSTS):
            base_ts = (1_000 + burst) * hour_us
            muts = []
            for p in range(ADAPT_PARTITIONS):
                m = Mutation(table.id,
                             table.serialize_partition_key([p]))
                m.add(table.serialize_clustering([burst]), vcol, b"",
                      vals[p].tobytes(), base_ts + p)
                muts.append(m)
            eng.apply_batch(muts)
            cfs.flush()
            tick()
            drain()
        wall_w = time.perf_counter() - t0

        # --- phase T: expired-tombstone backfill purge
        now = int(time.time())
        t0 = time.perf_counter()
        for f in range(ADAPT_TOMB_FLUSHES):
            muts = []
            for j in range(ADAPT_TOMBS_PER_FLUSH):
                pid = 100_000 + f * ADAPT_TOMBS_PER_FLUSH + j
                m = Mutation(table.id,
                             table.serialize_partition_key([pid]))
                m.add(table.serialize_clustering([0]), vcol, b"", b"",
                      1 + f * ADAPT_TOMBS_PER_FLUSH + j,
                      ldt=now - 7200, flags=FLAG_TOMBSTONE)
                muts.append(m)
            eng.apply_batch(muts)
            cfs.flush()
            tick()
            drain()
        wall_t = time.perf_counter() - t0

        # --- phase R: point partition reads
        t0 = time.perf_counter()
        for _ in range(ADAPT_READ_PASSES):
            for p in range(ADAPT_PARTITIONS):
                cfs.read_partition(table.serialize_partition_key([p]))
            tick()
            drain()
        wall_r = time.perf_counter() - t0

        total = wall_w + wall_t + wall_r
        # workload-constant numerator: ingested payload + rows served
        work_mib = (ADAPT_BURSTS * ADAPT_PARTITIONS * ADAPT_VALUE_BYTES
                    + ADAPT_READ_PASSES * ADAPT_PARTITIONS
                    * ADAPT_BURSTS * ADAPT_VALUE_BYTES) / (1 << 20)
        amp = cfs.amplification()
        out = {
            "phase_s": {"write_burst": round(wall_w, 3),
                        "tombstone": round(wall_t, 3),
                        "read": round(wall_r, 3)},
            "total_s": round(total, 3),
            "score_mib_s": round(work_mib / max(total, 1e-9), 2),
            "write_amplification": amp["write_amplification"],
            "space_amplification": amp["space_amplification"],
            "sstables_end": len(cfs.live_sstables()),
            "final_strategy": cfs.table.params.compaction["class"],
        }
        if adaptive:
            out["decisions"] = [
                {k: e.get(k) for k in ("seq", "at_ms", "keyspace",
                                       "table", "regime", "action",
                                       "old", "new", "applied",
                                       "reason")}
                for e in eng.controller.decisions()]
        return out
    finally:
        eng.close()


def run_adaptive_bench(base_dir: str) -> dict:
    """Adaptive-compaction section (docs/adaptive-compaction.md): the
    controller-on leg vs each pinned static strategy on the same
    3-phase shifting workload, paired+interleaved (paired_ab) because
    this box drifts. Headline: the controller's score geomean ratio vs
    each static — the close-the-loop claim is that no single static
    strategy matches the controller across ALL phases."""
    details: dict = {}
    paired: dict = {}
    counters = {"n": 0}

    def leg(tag, compaction, adaptive):
        d = _adaptive_leg(
            os.path.join(base_dir, f"{tag}{counters['n']}"),
            compaction, adaptive)
        counters["n"] += 1
        details.setdefault(tag, d)
        return d["score_mib_s"]

    for name, params in ADAPT_STATICS.items():
        paired[name] = paired_ab(
            lambda name=name, params=params: leg(name, params, False),
            lambda: leg("adaptive", None, True))

    speedups = {n: p["speedup_geomean"] for n, p in paired.items()}
    best_static = max(paired, key=lambda n: paired[n]["a_geomean"])
    return {
        "workload": {"partitions": ADAPT_PARTITIONS,
                     "bursts": ADAPT_BURSTS,
                     "tombstone_flushes": ADAPT_TOMB_FLUSHES,
                     "tombstones_per_flush": ADAPT_TOMBS_PER_FLUSH,
                     "read_passes": ADAPT_READ_PASSES},
        "paired": paired,
        "legs": details,
        "decision_timeline": details.get("adaptive", {}).get(
            "decisions", []),
        "acceptance": {
            "speedup_vs": speedups,
            "best_static": best_static,
            "vs_best_static": speedups[best_static],
            "wins_gt_1": sum(1 for v in speedups.values() if v > 1.0),
            "pass": bool(speedups[best_static] >= 1.0
                         and sum(1 for v in speedups.values()
                                 if v > 1.0) >= 2),
        },
    }


def _kernel_probe(table):
    """Two tiny merge rounds through the device programs on the pinned
    CPU backend (host-engine benches only): the first pays jit
    compilation, the second is warm, so the kernel_profile section
    always reports a compile-vs-execute split. CPU numbers — they say
    nothing about a chip. A failure here fails the bench."""
    from cassandra_tpu.ops.device_write import merge_sorted_device
    from cassandra_tpu.storage import cellbatch as cb
    from cassandra_tpu.tools import bulk
    rng = np.random.default_rng(3)
    batches = []
    for _ in range(2):
        n = 2048
        pk = rng.integers(0, 64, n)
        ck = rng.integers(1, 100, n)
        vals = rng.integers(0, 256, (n, 8), dtype=np.uint8)
        ts = rng.integers(1, 1 << 40, n).astype(np.int64)
        batches.append(cb.merge_sorted(
            [bulk.build_int_batch(table, pk, ck, vals, ts)]))
    for _ in range(2):
        merge_sorted_device(batches)


def main():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import jax
    if os.environ.get("CTPU_BENCH_ENGINE", "native") != "device":
        # the host engines never touch the accelerator: pin the CPU
        # backend so a host-engine bench neither holds a chip nor
        # reports under its name
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "tpu":
        raise SystemExit(
            "CTPU_BENCH_ENGINE=device needs a TPU; jax's backend is "
            f"{jax.default_backend()!r}")
    from cassandra_tpu.utils import compile_cache
    compile_cache.configure()
    from cassandra_tpu.ops.codec import CompressionParams
    from cassandra_tpu.schema import TableParams, make_table

    cfg_name = os.environ.get("CTPU_BENCH_CONFIG", "stcs")
    cfg = CONFIGS[cfg_name]
    comp, chunk = cfg["compressor"]
    gc_grace = 0 if cfg.get("ttl") else 864000
    params = TableParams(
        compression=CompressionParams(comp, chunk_length=chunk),
        gc_grace_seconds=gc_grace)
    if cfg.get("compaction"):
        params.compaction = dict(cfg["compaction"])
    table = make_table(
        "bench", "stress", pk=["id"], ck=["c"],
        cols={"id": "int", "c": "int", "v": "blob"},
        params=params)

    engine = os.environ.get("CTPU_BENCH_ENGINE", "native")
    base = tempfile.mkdtemp(prefix="ctpu-bench-")
    try:
        from cassandra_tpu.service import profiling
        from cassandra_tpu.service.metrics import GLOBAL as METRICS
        from cassandra_tpu.service.metrics import prometheus_text
        warm = run_compaction(os.path.join(base, "warm"), table, 1, cfg)
        stats = run_compaction(os.path.join(base, "timed"), table, 2, cfg)
        # both rounds feed the decaying reservoir so the metrics section
        # carries a real windowed p50/p95/p99 snapshot
        METRICS.hist("compaction.task").update_us(warm["wall"] * 1e6)
        METRICS.hist("compaction.task").update_us(stats["wall"] * 1e6)
        if engine != "device":
            _kernel_probe(table)   # cold+warm device-program rounds on
            # the pinned CPU backend: kernel_profile always has the
            # compile-vs-execute split even for host-engine benches
        mib = stats["bytes_read"] / 2**20
        mib_s = mib / stats["wall"]
        prof_h = stats["profile"]
        # write-phase attribution for the headline: per-stage busy
        # seconds (stages overlap on different threads — they are
        # capacities, not additive wall shares) plus the two numbers
        # that ARE wall: the producer's genuine write-leg backpressure
        # (write_stall) and the terminal seal drain. Their share of
        # wall is the fraction of the compaction the write leg actually
        # gated — the "where did the wall go" answer ROADMAP item 1
        # asks for (an io_write-bound profile would show it again, as
        # io stalls).
        write_phase = {
            "serialize_s": prof_h.get("serialize", 0.0),
            "compress_s": prof_h.get("compress", 0.0),
            "io_write_s": prof_h.get("io_write", 0.0),
            "seal_s": prof_h.get("seal", 0.0),
            "producer_stall_s": prof_h.get("write_stall", 0.0),
            "blocked_share_of_wall": round(
                (prof_h.get("write_stall", 0.0)
                 + prof_h.get("seal", 0.0)) / max(stats["wall"], 1e-9),
                3),
        }
        result = {
            "metric": "compaction MiB/s (%s, %s engine)"
                      % (cfg["desc"], engine),
            "value": round(mib_s, 2),
            "unit": "MiB/s",
            "vs_baseline": round(mib_s / 64.0, 2),
            "detail": {
                "cells_read": stats["cells_read"],
                "cells_written": stats["cells_written"],
                "bytes_read": stats["bytes_read"],
                "bytes_written": stats["bytes_written"],
                "seconds": round(stats["wall"], 3),
                "phases": stats["profile"],
                # the write leg split out (serialize / compress /
                # io_write / seal + producer stall), replacing the old
                # aggregated `write` number — later records can attribute
                # the wall per stage
                "write_phase": write_phase,
                # per-stage capacity (input MiB over phase seconds);
                # stages run on different threads so these overlap —
                # the smallest one is the pipeline's current wall
                "phase_mib_s": stats["phase_mib_s"],
            },
            # parallel-compress worker sweep on one fixture: serial
            # compress vs pinned pools — scaling flattens where the
            # compress stage stops being the wall (docs/compaction-
            # executor.md; byte-identity across legs is CI-checked by
            # scripts/check_compaction_ab.py)
            "compressor_sweep": run_compressor_sweep(
                os.path.join(base, "sweep"), table, cfg),
            # compress_iov micro-benchmark: native FFI vs the generic
            # fallback — codec regressions are visible here
            "codec": run_codec_bench(),
            # unified pipeline ledger (docs/observability.md): per-stage
            # busy/stall/queue-occupancy for compaction, flush and mesh
            # lanes + reconciliation against the profile phase split
            "pipeline": run_pipeline_bench(
                os.path.join(base, "pipeline"), table, cfg),
            # decayed (windowed) latency snapshot + the Prometheus
            # exposition the exporter serves (nodetool exportmetrics)
            "metrics": {
                "compaction.task": METRICS.hist("compaction.task")
                .summary(),
                "window_s": METRICS.window_s,
                "prometheus": prometheus_text(),
            },
            # per-kernel compile/dispatch/execute split + recompile
            # counts by operand shape, plus aggregated phase timings
            "kernel_profile": profiling.GLOBAL.snapshot(),
            # mesh data-plane scaling curve (docs/multichip.md):
            # compaction MiB/s + batched-read rows/s at 1/2/4/8 host
            # lanes, serial-vs-mesh headline through the paired
            # interleaved A/B so box drift cancels; byte identity
            # across lane counts is CI-checked by the mesh legs of
            # scripts/check_compaction_ab.py
            "mesh": run_mesh_bench(os.path.join(base, "mesh"), table,
                                   cfg),
            # read-path fast lane A/B (docs/read-path.md): timestamp-
            # skip collation + batched partition reads vs the naive
            # every-sstable collation, bit-identical results required
            "read_path": run_read_bench(os.path.join(base, "read")),
            # analytical scan lane (docs/read-path.md): zone-map
            # pruning + fused predicate kernels + candidate-only
            # Phase B vs the naive materializing ALLOW FILTERING
            # scan through paired_ab (target >= 2x rows/s), plus the
            # aggregation leg folding on keys with zero rows
            # materialized; zero divergence across legs is CI-checked
            # by scripts/check_scan_ab.py
            "scan": run_scan_bench(os.path.join(base, "scan")),
            # write-path fast lane A/B (docs/write-path.md): group-commit
            # commitlog + sharded memtable + pipelined flush vs the
            # per-mutation-fsync serial path
            "write_path": run_write_bench(os.path.join(base, "write")),
            # native-protocol front door (docs/native-transport.md):
            # wire ops/s + p50/p99 through the event-loop server at
            # 16/64/256 connections, plus the overload run proving
            # OVERLOADED shedding with in-flight <= the permit cap
            "frontdoor": run_frontdoor_bench(
                os.path.join(base, "frontdoor")),
            # verb-dispatch pool scaling (docs/observability.md
            # messaging rows): cluster-wide verbs/s for the QUORUM
            # write class at 1/2/4 replica-side dispatch workers,
            # 1-vs-4 through paired_ab
            "dispatch": run_dispatch_bench(
                os.path.join(base, "dispatch")),
            # workload observatory (docs/observability.md layer 5):
            # metrics-history sampler overhead share of a real
            # flush+compaction run (< 1% required even at 40x the
            # default sampling rate) + exact same-source WA/SA gauge
            # reconciliation against the run's byte counters
            "observatory": run_observatory_bench(
                os.path.join(base, "observatory")),
            # continuous profiler (docs/observability.md layer 6):
            # always-on wall sampler ring ON vs OFF through paired_ab
            # (< 1% of the compaction headline, held at 5x the default
            # rate) + an attribution block tying a profiled session's
            # top frames and cpu share to the pipeline ledger's
            # busy/stall split on the same run
            "profiler": run_profiler_bench(
                os.path.join(base, "profiler")),
            # saturation matrix (docs/observability.md SLO layer,
            # ROADMAP item 5): workload classes x key streams through
            # the wire against a 3-node RF=3 cluster, per-leg SLO
            # verdicts, hints + speculative retry live, chaos leg with
            # a breach-triggered flight-recorder bundle
            "saturation": run_saturation_bench(
                os.path.join(base, "saturation")),
            # adaptive compaction controller
            # (docs/adaptive-compaction.md): controller-on vs each
            # pinned static strategy on a 3-phase shifting workload
            # (write burst -> tombstone purge -> read plateau),
            # paired_ab per pairing, per-phase walls + decision
            # timeline; acceptance = geomean >= 1.0 vs the best
            # static and > 1.0 vs at least 2 of 3
            "adaptive": run_adaptive_bench(
                os.path.join(base, "adaptive")),
        }
        print(json.dumps(result))
    finally:
        shutil.rmtree(base, ignore_errors=True)


if __name__ == "__main__":
    main()
