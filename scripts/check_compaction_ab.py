#!/usr/bin/env python
"""CI check: parallel-compression byte-identity A/B.

The parallel compress leg (storage/sstable/compress_pool.py + the
writer's ordered completion queue) promises BYTE-identical sstables for
any compressor pool size — including the serial path. That promise has
two load-bearing parts:

  - the ordered completion queue re-sequences out-of-order worker
    results before any sequential writer state (file offsets, index
    entries, digest folds) sees them;
  - the adaptive-compression-skip machine decides attempt flags from a
    FIXED-lag outcome stream (SSTableWriter.SKIP_DECISION_LAG), so the
    decision sequence cannot depend on completion timing or pool size.

This check exercises both with a workload built to CROSS skip-machine
transitions (alternating compressible text and incompressible random
partitions — the payload stream enters and leaves skip mode):

  1. the same input sstables major-compacted with the serial compress
     thread, a 1-worker pool and a 4-worker pool (+ decode-ahead),
     under the mesh execution mode (2 lanes, and 4 lanes combined with
     a 2-worker pool — docs/multichip.md: token-range shards drained in
     token order), under the DEVICE engine (device-resident rounds,
     ops/device_write.py — fused sort/reconcile/purge/serialize on the
     jax device incl. its per-round host fallbacks, plus the
     device+mesh-2 cross), and with DEVICE-SIDE BLOCK COMPRESSION
     (ops/device_compress.py — the policy-scan kernel compresses META +
     lanes on-device; alone, feeding a 2-worker pool's ordered
     completion queue, and crossed with mesh-2) must produce
     sha256-identical components AND equal merged-view content_digests;
  2. the same mutation set flushed with CTPU_WRITE_FASTPATH=0 (serial
     sort-and-write) and =1 over 1- and 4-worker shared pools must
     produce identical sstable bytes and read-back digests.

Run as a script (exit 1 on divergence) or through pytest
(tests/test_parallel_compress.py imports run_check).
"""
from __future__ import annotations

import hashlib
import os
import shutil
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

FIXED_NOW = 1_700_000_000
HASHED_COMPONENTS = ("Data.db", "Index.db", "Partitions.db",
                     "Filter.db", "Statistics.db", "Digest.crc32")


def _mk_table(name: str):
    from cassandra_tpu.ops.codec import CompressionParams
    from cassandra_tpu.schema import TableParams, make_table

    return make_table(
        "abks", name, pk=["id"], ck=["c"],
        cols={"id": "int", "c": "int", "v": "blob"},
        params=TableParams(compression=CompressionParams(
            "LZ4Compressor", chunk_length=16 * 1024)))


def _mixed_batch(table, seed: int, n: int):
    """Sorted batch whose payload compressibility ALTERNATES by
    partition: even partitions carry lowercase text (compresses well),
    odd ones uniform random bytes (stores raw) — segments flip between
    the two, driving the skip machine through engage/probe/disengage."""
    import numpy as np

    from cassandra_tpu.storage import cellbatch as cb
    from cassandra_tpu.tools import bulk

    rng = np.random.default_rng(seed)
    pk = rng.integers(0, 256, n)
    ck = rng.integers(0, 100_000, n)
    text = rng.integers(97, 122, (n, 48), dtype=np.uint8)
    blob = rng.integers(0, 256, (n, 48), dtype=np.uint8)
    vals = np.where((pk % 2 == 0)[:, None], text, blob)
    ts = rng.integers(1, 1 << 40, n).astype(np.int64)
    return cb.merge_sorted([bulk.build_int_batch(table, pk, ck, vals, ts)])


def _component_hashes(directory: str) -> dict:
    out = {}
    for fn in sorted(os.listdir(directory)):
        p = os.path.join(directory, fn)
        if not os.path.isfile(p):
            continue
        if not any(fn.endswith(c) for c in HASHED_COMPONENTS):
            continue
        with open(p, "rb") as f:
            out[fn] = hashlib.sha256(f.read()).hexdigest()
    return out


def _scan_digest(cfs) -> bytes:
    from cassandra_tpu.storage.cellbatch import content_digest

    return content_digest(cfs.scan_all(now=FIXED_NOW))


# ------------------------------------------------------------ compaction --

def _compaction_leg(base: str, pristine: str, table, tag: str,
                    **task_kw) -> tuple[dict, bytes]:
    from cassandra_tpu.compaction.task import CompactionTask
    from cassandra_tpu.storage.table import ColumnFamilyStore

    leg = os.path.join(base, tag)
    shutil.copytree(pristine, leg)
    cfs = ColumnFamilyStore(table, leg, commitlog=None)
    cfs.reload_sstables()
    task = CompactionTask(cfs, cfs.tracker.view(), **task_kw)
    task.execute()
    hashes = _component_hashes(cfs.directory)
    digest = _scan_digest(cfs)
    for r in cfs.live_sstables():
        r.close()
    return hashes, digest


def check_compaction(base: str) -> list[str]:
    from cassandra_tpu.storage.sstable import Descriptor, SSTableWriter
    from cassandra_tpu.storage.sstable.compress_pool import CompressorPool
    from cassandra_tpu.storage.table import ColumnFamilyStore

    table = _mk_table("compact")
    pristine = os.path.join(base, "pristine")
    cfs = ColumnFamilyStore(table, pristine, commitlog=None)
    for gen in range(1, 4):
        w = SSTableWriter(Descriptor(cfs.directory, gen), table,
                          estimated_partitions=256)
        w.append(_mixed_batch(table, seed=gen, n=200_000))
        w.finish()

    legs = {
        "serial": dict(pipelined_io=False, compress_pool=0,
                       decode_ahead=False),
        "threaded": dict(pipelined_io=True, compress_pool=0,
                         decode_ahead=False),
        "pool1": dict(pipelined_io=True, compress_pool=CompressorPool(1),
                      decode_ahead=True),
        "pool4": dict(pipelined_io=True, compress_pool=CompressorPool(4),
                      decode_ahead=True),
        # mesh execution mode (docs/multichip.md): token-range-sharded
        # decode->merge fanned across mesh lanes, drained in token
        # order — bytes must match serial for any lane count, including
        # combined with the parallel compress pool
        "mesh2": dict(pipelined_io=True, compress_pool=0,
                      decode_ahead=False, mesh_devices=2),
        "mesh4_pool2": dict(pipelined_io=True,
                            compress_pool=CompressorPool(2),
                            decode_ahead=False, mesh_devices=4),
        # device engine, device-resident rounds (ops/device_write.py):
        # merge + purge + segment-cut + META serialize run on the jax
        # device; the mixed fixture's equal-ts duplicates also push
        # rounds through the per-round host fallback — both sides of
        # the residency decision must land the same bytes
        "device": dict(pipelined_io=True, compress_pool=0,
                       decode_ahead=False, engine="device",
                       use_device=True, device_compress=False),
        # device engine crossed with the mesh execution mode: shards
        # fan across jax devices and drain host-side in token order
        "device_mesh2": dict(pipelined_io=True, compress_pool=0,
                             decode_ahead=False, engine="device",
                             use_device=True, mesh_devices=2),
        # device-side block compression (ops/device_compress.py): full
        # segments arrive at the writer ALREADY LZ4-compressed by the
        # fused policy-scan kernel; the mixed fixture crosses skip-
        # machine transitions, so attempted/raw decisions and the
        # compress-vs-raw boundary must land identically to the native
        # packer on every stream
        "device_compress": dict(pipelined_io=True, compress_pool=0,
                                decode_ahead=False, engine="device",
                                use_device=True, device_compress=True),
        # device compression feeding the ordered completion queue of a
        # live compressor pool: device-born jobs (ready pre-set) and
        # pool jobs (partial final segment, per-segment fallbacks)
        # interleave in submit order
        "device_compress_pool2": dict(pipelined_io=True,
                                      compress_pool=CompressorPool(2),
                                      decode_ahead=False,
                                      engine="device", use_device=True,
                                      device_compress=True),
        # the mesh cross: shards drain through the host writer (the
        # device-resident lane is a serial-round mode), so this pins
        # that device_compress=True stays inert — and byte-identical —
        # under the mesh execution mode
        "device_compress_mesh2": dict(pipelined_io=True, compress_pool=0,
                                      decode_ahead=False,
                                      engine="device", use_device=True,
                                      mesh_devices=2,
                                      device_compress=True),
    }
    results = {tag: _compaction_leg(base, pristine, table, tag, **kw)
               for tag, kw in legs.items()}
    for kw in legs.values():
        pool = kw["compress_pool"]
        if pool:
            pool.shutdown(timeout=5.0)

    diverged = []
    ref_tag = "serial"
    ref_hashes, ref_digest = results[ref_tag]
    if not ref_hashes:
        diverged.append("compaction produced no components to compare")
    for tag, (hashes, digest) in results.items():
        if tag == ref_tag:
            continue
        if hashes != ref_hashes:
            bad = sorted(set(hashes) ^ set(ref_hashes)) or sorted(
                k for k in hashes if hashes[k] != ref_hashes.get(k))
            diverged.append(
                f"compaction {tag} vs {ref_tag}: component bytes "
                f"differ: {bad}")
        if digest != ref_digest:
            diverged.append(
                f"compaction {tag} vs {ref_tag}: merged-view "
                f"content_digest differs")
    return diverged


# ----------------------------------------------------------------- flush --

def _flush_mutations(table):
    """Deterministic mutation set, compressibility alternating by
    partition like the compaction fixture; fixed timestamps so every
    leg writes identical cells."""
    from cassandra_tpu.schema import COL_ROW_LIVENESS
    from cassandra_tpu.storage.cellbatch import FLAG_ROW_LIVENESS
    from cassandra_tpu.storage.mutation import Mutation

    vcol = table.columns["v"].column_id
    muts = []
    text = b"abcdefghijklmnopqrstuvwx" * 2
    for k in range(160):
        pkb = table.serialize_partition_key([k])
        for c in range(450):
            m = Mutation(table.id, pkb)
            ck = table.serialize_clustering([c])
            ts = 1_000_000 + k * 1000 + c
            if k % 2 == 0:
                val = text
            else:   # deterministic pseudo-random bytes
                val = hashlib.sha256(b"%d-%d" % (k, c)).digest() + \
                    hashlib.sha256(b"x%d-%d" % (k, c)).digest()[:16]
            m.add(ck, COL_ROW_LIVENESS, b"", b"", ts,
                  flags=FLAG_ROW_LIVENESS)
            m.add(ck, vcol, b"", val, ts)
            muts.append(m)
    return muts


def _flush_leg(base: str, table, tag: str, fast: bool,
               pool_workers: int) -> tuple[dict, bytes]:
    from cassandra_tpu.storage.sstable import compress_pool
    from cassandra_tpu.storage.table import ColumnFamilyStore

    os.environ["CTPU_WRITE_FASTPATH"] = "1" if fast else "0"
    compress_pool.configure(pool_workers)
    try:
        cfs = ColumnFamilyStore(table, os.path.join(base, tag),
                                commitlog=None)
        muts = _flush_mutations(table)
        for i in range(0, len(muts), 512):
            cfs.apply_batch(muts[i:i + 512])
        cfs.flush()
        hashes = _component_hashes(cfs.directory)
        digest = _scan_digest(cfs)
        for r in cfs.live_sstables():
            r.close()
        return hashes, digest
    finally:
        os.environ.pop("CTPU_WRITE_FASTPATH", None)
        compress_pool.configure(0)   # back to auto


def check_flush(base: str) -> list[str]:
    table = _mk_table("flush")
    legs = {
        "serial": (False, 1),
        "fast_pool1": (True, 1),
        "fast_pool4": (True, 4),
    }
    results = {tag: _flush_leg(base, table, tag, fast, w)
               for tag, (fast, w) in legs.items()}
    diverged = []
    ref_hashes, ref_digest = results["serial"]
    if not ref_hashes:
        diverged.append("flush produced no components to compare")
    for tag, (hashes, digest) in results.items():
        if tag == "serial":
            continue
        if hashes != ref_hashes:
            bad = sorted(set(hashes) ^ set(ref_hashes)) or sorted(
                k for k in hashes if hashes[k] != ref_hashes.get(k))
            diverged.append(
                f"flush {tag} vs serial: component bytes differ: {bad}")
        if digest != ref_digest:
            diverged.append(
                f"flush {tag} vs serial: content_digest differs")
    return diverged


# ------------------------------------------------------------------ main --

def run_check(base_dir: str | None = None) -> list[str]:
    own = base_dir is None
    base = base_dir or tempfile.mkdtemp(prefix="ctpu-compab-")
    try:
        diverged = check_compaction(os.path.join(base, "compaction"))
        diverged += check_flush(os.path.join(base, "flush"))
        return diverged
    finally:
        if own:
            shutil.rmtree(base, ignore_errors=True)


def main() -> int:
    from cassandra_tpu.utils import compile_cache
    compile_cache.configure()
    diverged = run_check()
    if diverged:
        print("parallel-compression A/B DIVERGED:", file=sys.stderr)
        for d in diverged:
            print(f"  {d}", file=sys.stderr)
        return 1
    print("compaction/flush parallel-compression A/B: zero divergence "
          "(serial vs threaded vs pool-1 vs pool-4 vs mesh-2 vs "
          "mesh-4+pool-2 vs device-resident vs device+mesh-2 vs "
          "device-compress vs device-compress+pool-2 vs "
          "device-compress+mesh-2)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
