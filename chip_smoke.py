#!/usr/bin/env python3
"""chip_smoke.py — the store's device path, end to end, on one TPU.

The quickest proof that the system still starts on the chip. One process,
one chip, the entry points a user would call, data made from --seed:

  wire     a node started the way tools/noded.py starts one, spoken to over
           loopback with the repo's own wire client: DDL, a few thousand
           writes at the default consistency level, EVERY acknowledged
           write read back, flush.
  compact  a backlog of overlapping sstables (stress-shaped: int keys,
           64-byte random blobs, ordinary tombstones, TTLs that purge)
           loaded by the bulk path, compacted by the device engine, and a
           copy by the numpy engine (the executable spec): output
           components byte-identical, zero rounds or segments handed back
           to the host, then a sample of partitions read over the wire
           against a plain numpy merge of the seeded cells.
  scan     a scan table: one ALLOW FILTERING range predicate and one pure
           COUNT/MIN/MAX through ColumnFamilyStore.scan_filtered with the
           device gate on, against numpy over the seeded rows, and the
           same statements with the gate off.
  ann      a vector table and ORDER BY v ANN OF ? LIMIT 10 queries
           against a numpy brute force.

With --chips 4 it runs ONLY what exists across chips: the same backlog
compacted by the device engine serially and with mesh_devices=4
(byte-identical), the sharded merge (per-device dispatch and the
shard_map/psum program) against cellbatch.merge_sorted, and a check that
four distinct devices did the work.

Every earlier line of stdout is one JSON object per phase. The LAST line is
the contract line, {"ok": true, "device": {...}}, printed only when every
phase passed; any failure — no TPU, a phase that raised, a comparison that
differed, a device program that fell back to the host — exits non-zero
without it.

The served path has no switch for the device engine today:
compaction/strategies.py builds its tasks with the defaults, which pick the
native C++ engine. Adding one is a feature and not this script's business;
the compaction here is constructed exactly as scripts/check_compaction_ab.py
constructs its `device_compress` leg.
"""
from __future__ import annotations

import argparse
import dataclasses
import glob
import hashlib
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

HASHED_COMPONENTS = ("Data.db", "Index.db", "Partitions.db", "Filter.db",
                     "Statistics.db", "Digest.crc32", "ZoneMap.db")
FALLBACK_COUNTERS = ("compaction.device_compress_fallback",
                     "compaction.device_host_rounds",
                     "compaction.device_resident_fallback",
                     "scan.fallback", "scan.host_segments",
                     "profile.retraces")
GC_GRACE = 3600          # the backlog table's gc_grace_seconds
COLD_REQUEST_TIMEOUT_S = 600.0
VALUE_BYTES = 64         # cassandra-stress default blob column


@dataclasses.dataclass(frozen=True)
class Sizes:
    wire_rows: int
    runs: int               # overlapping input sstables
    run_cells: int
    partitions: int
    ck_space: int
    sample_partitions: int
    min_device_rounds: int
    min_full_segments: int
    scan_rows: int
    scan_partitions: int
    scan_sstables: int
    ann_rows: int
    ann_dim: int
    ann_queries: int
    shard_cells: int        # --chips 4: cells per input of the sharded merge


# what a user would call real: ~4x bench.py CONFIGS["stcs"], a 2^20-row scan
# table, 100,000 x 128 vectors. Cuts from these, if any, are in CHANGES.md.
FULL = Sizes(wire_rows=3000, runs=4, run_cells=1 << 20, partitions=4096,
             ck_space=50_000, sample_partitions=8, min_device_rounds=8,
             min_full_segments=60, scan_rows=1 << 20,
             scan_partitions=1 << 14, scan_sstables=4, ann_rows=100_000,
             ann_dim=128, ann_queries=5,
             shard_cells=(1 << 19) - (1 << 15))
# the same control flow in seconds on the CPU (tests/test_chip_smoke.py)
TINY = Sizes(wire_rows=40, runs=4, run_cells=1 << 15, partitions=64,
             ck_space=20_000, sample_partitions=3, min_device_rounds=1,
             min_full_segments=1, scan_rows=1 << 12, scan_partitions=1 << 8,
             scan_sstables=2, ann_rows=300, ann_dim=16, ann_queries=2,
             shard_cells=1 << 11)


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def require(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ------------------------------------------------------------- start-up --

def rebuild_native() -> dict:
    """Build the C++ library from what git commits (codec.cpp, merge.cpp),
    over whatever libcodec.so the checkout was copied with; a build that
    fails fails the smoke instead of turning `native` into `numpy`."""
    import subprocess
    t0 = time.perf_counter()
    from cassandra_tpu.ops import host_merge
    from cassandra_tpu.ops.native import build as native_build
    try:
        native_build.rebuild()
    except subprocess.CalledProcessError as e:
        raise SmokeFailure("g++ failed on ops/native: "
                           + e.stderr.decode("utf-8", "replace")[-2000:])
    native_build.load()
    require(host_merge.available(), "native merge engine did not load")
    return {"built": True, "seconds": round(time.perf_counter() - t0, 2)}


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes() -> list:
    import jax
    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


class Ledger:
    """Deltas of the repo's own accounting across one phase: the device
    program registry (service/profiling.py) and the fallback counters
    (service/metrics.py)."""

    def __init__(self):
        from cassandra_tpu.service.metrics import GLOBAL as metrics
        from cassandra_tpu.service.profiling import GLOBAL as registry
        self.metrics, self.registry = metrics, registry
        self.mark()
        self._start = (self._k0, dict(self._c0))   # the whole run's base

    def mark(self) -> None:
        self._k0 = self.registry.snapshot()["kernels"]
        self._c0 = {c: self.metrics.counter(c) for c in FALLBACK_COUNTERS}

    def counter(self, name: str) -> int:
        return self.metrics.counter(name)

    def fallbacks(self, whole_run: bool = False) -> dict:
        base = self._start[1] if whole_run else self._c0
        return {c: self.metrics.counter(c) - base[c]
                for c in FALLBACK_COUNTERS}

    def programs(self, whole_run: bool = False) -> dict:
        """{program: calls, compiles (= distinct shapes seen), compile_s,
        warm_dispatch_s, execute_s} since mark()."""
        out = {}
        k0 = self._start[0] if whole_run else self._k0
        for name, k in self.registry.snapshot()["kernels"].items():
            b = k0.get(name, {})
            calls = k["calls"] - b.get("calls", 0)
            if not calls:
                continue
            out[name] = {
                "calls": calls,
                "compiles": k["compiles"] - b.get("compiles", 0),
                "compile_s": round(k["compile_s"]
                                   - b.get("compile_s", 0.0), 3),
                "warm_dispatch_s": round(k["dispatch_s"]
                                         - b.get("dispatch_s", 0.0), 3),
                "execute_s": round(k["execute_s"]
                                   - b.get("execute_s", 0.0), 3)}
        return out

    def require_no_fallback(self, phase: str) -> dict:
        fb = self.fallbacks()
        bad = {k: v for k, v in fb.items()
               if v and k != "profile.retraces"}
        require(not bad, f"{phase}: device programs fell back to the "
                         f"host: {bad}")
        return fb


# ------------------------------------------------------------ the node --

class ServedNode:
    """A node in this process, started as tools/noded.py:main starts one
    (build_node + CQLServer; no "jax_platform" in the config, so jax keeps
    the platform it found), and a wire session to it.

    `local_session` speaks to a second CQLServer on the same node's
    StorageEngine (the single-node form tests/test_native_protocol.py
    serves). The scan phase needs it: a Node answers SELECTs through its
    coordinator facade (cluster/node.py _DistributedStore), which has no
    scan_filtered, so the analytical scan lane is not reachable through a
    noded node today. Routing it there is a feature, not this script's."""

    def __init__(self, data_dir: str):
        from cassandra_tpu.client import Cluster
        from cassandra_tpu.cluster.ring import even_tokens
        from cassandra_tpu.tools.noded import build_node
        from cassandra_tpu.transport.server import CQLServer
        cfg = {"name": "smoke", "host": "127.0.0.1", "port": 0,
               "tokens": even_tokens(1, vnodes=4)[0],
               "data_dir": data_dir, "peers": [], "seeds": [],
               "native_port": 0}
        self.node, self.transport = build_node(cfg)
        self.server = CQLServer(self.node, cfg["host"], cfg["native_port"])
        self.session = Cluster("127.0.0.1", self.server.port).connect()
        # the wire client gives every request 10 s. On an empty compile
        # cache the first ANN query compiles index.ann server-side for
        # longer than that (first chip run of PR 21: TimeoutError). The
        # smoke waits a cold compile out and reports first_query_s; it
        # does not fail on the client's clock.
        self.session._sock.settimeout(COLD_REQUEST_TIMEOUT_S)
        self.session.execute(
            "CREATE KEYSPACE smoke WITH replication = "
            "{'class': 'SimpleStrategy', 'replication_factor': 1}")
        self.session.execute("USE smoke")
        self.local_server = CQLServer(self.node.engine, cfg["host"], 0)
        self.local_session = Cluster(
            "127.0.0.1", self.local_server.port).connect()
        self.local_session.execute("USE smoke")

    def table(self, name: str):
        return self.node.schema.get_table("smoke", name)

    def store(self, name: str):
        return self.node.engine.store("smoke", name)

    def close(self) -> None:
        self.session.close()
        self.local_session.close()
        self.server.close()
        self.local_server.close()
        self.node.shutdown()      # engine, gossip, messaging + its socket


def bulk_load(cfs, batch) -> None:
    """One sorted CellBatch -> one sstable in the store's directory, the
    way bench.py:build_inputs lands its runs; reload_sstables() picks the
    set up afterwards."""
    from cassandra_tpu.storage.sstable import Descriptor, SSTableWriter
    w = SSTableWriter(Descriptor(cfs.directory, cfs.next_generation()),
                      cfs.table)
    w.append(batch)
    w.finish()


# ----------------------------------------------------------- phase: wire --

def phase_wire(served: ServedNode, rng, sz: Sizes) -> dict:
    from cassandra_tpu.client import serialize_params
    s = served.session
    s.execute("CREATE TABLE kv (k int PRIMARY KEY, v blob, n bigint)")
    table = served.table("kv")
    keys = rng.permutation(1 << 20)[:sz.wire_rows].astype(np.int64)
    blobs = rng.integers(0, 256, (sz.wire_rows, 32), dtype=np.uint8)
    ins = s.prepare("INSERT INTO kv (k, v, n) VALUES (?, ?, ?)")
    sel = s.prepare("SELECT v, n FROM kv WHERE k = ?")
    t0 = time.perf_counter()
    for i, k in enumerate(keys):
        # a Rows comes back only once the coordinator acknowledged
        s.execute_prepared(ins, serialize_params(
            table, ["k", "v", "n"], [int(k), blobs[i].tobytes(), i]))
    t_write = time.perf_counter() - t0

    def read_all(what: str) -> float:
        t1 = time.perf_counter()
        for i, k in enumerate(keys):
            rows = s.execute_prepared(
                sel, serialize_params(table, ["k"], [int(k)])).rows
            require(rows == [(blobs[i].tobytes(), i)],
                    f"wire: acknowledged write k={int(k)} not read back "
                    f"{what}")
        return time.perf_counter() - t1

    t_read = read_all("from the memtable")
    served.store("kv").flush()
    require(len(served.store("kv").live_sstables()) >= 1,
            "wire: flush left no sstable")
    t_read_flushed = read_all("after flush")
    return {"rows_written": sz.wire_rows, "rows_read_back": sz.wire_rows,
            "consistency": "ONE (the client's and the node's default)",
            "write_s": round(t_write, 3), "read_s": round(t_read, 3),
            "read_after_flush_s": round(t_read_flushed, 3)}


# -------------------------------------------------------- phase: compact --

LIVE, TOMB_OLD, TOMB_NEW, TTL_LIVE, TTL_DEAD = range(5)


def load_backlog(cfs, rng, sz: Sizes) -> dict:
    """`runs` overlapping sorted runs over one key space, each landed as
    one sstable in `cfs`. Per run: ~1% tombstones past gc grace (purged)
    and ~1% inside it (kept), ~1% TTL'd cells long expired (purged) and
    ~2% with TTL far in the future (kept live). No range tombstones,
    counters, or cells expired INSIDE gc grace (those are the rounds the
    device hands to the host by design). Returns the seeded columns for
    the plain reference: pk/ck/ts/kind over all runs, values per run."""
    from cassandra_tpu.storage import cellbatch as cb
    from cassandra_tpu.tools import bulk
    now = int(time.time())
    cols: dict = {"pk": [], "ck": [], "ts": [], "kind": [], "vals": []}
    for _ in range(sz.runs):
        n = sz.run_cells
        pk = rng.integers(0, sz.partitions, n)
        ck = rng.integers(1, sz.ck_space, n)
        vals = rng.integers(0, 256, (n, VALUE_BYTES), dtype=np.uint8)
        ts = rng.integers(1, 1 << 40, n).astype(np.int64)
        kind = rng.choice(5, n, p=[0.95, 0.01, 0.01, 0.02, 0.01]) \
            .astype(np.int8)
        b = bulk.build_int_batch(cfs.table, pk, ck, vals, ts)
        tomb = (kind == TOMB_OLD) | (kind == TOMB_NEW)
        b.flags[tomb] |= cb.FLAG_TOMBSTONE
        b.ldt[kind == TOMB_OLD] = now - 3 * GC_GRACE
        b.ldt[kind == TOMB_NEW] = now - 60
        ttl = (kind == TTL_LIVE) | (kind == TTL_DEAD)
        b.flags[ttl] |= cb.FLAG_EXPIRING
        b.ttl[ttl] = 86400
        b.ldt[kind == TTL_LIVE] = now + 30 * 86400
        b.ldt[kind == TTL_DEAD] = now - 3 * GC_GRACE
        bulk_load(cfs, cb.merge_sorted([b.drop_values(tomb)]))
        for name, col in zip(cols, (pk, ck, ts, kind, vals)):
            cols[name].append(col)
    return {k: v if k == "vals" else np.concatenate(v)
            for k, v in cols.items()}


def reference_partition(raw: dict, pk: int) -> list:
    """Plain numpy merge of one partition: newest timestamp wins per
    clustering key; the row shows iff the winner is a live value."""
    sel = np.flatnonzero(raw["pk"] == pk)
    ck, ts, kind = raw["ck"][sel], raw["ts"][sel], raw["kind"][sel]
    order = np.lexsort((ts, ck))            # by ck, then ts ascending
    last = np.ones(len(order), dtype=bool)
    last[:-1] = ck[order][1:] != ck[order][:-1]
    win = order[last]
    win = win[(kind[win] == LIVE) | (kind[win] == TTL_LIVE)]
    per_run = len(raw["vals"][0])
    return sorted(
        (int(ck[i]), raw["vals"][sel[i] // per_run][sel[i] % per_run]
         .tobytes()) for i in win)


def component_hashes(directory: str) -> dict:
    """{component suffix per output sstable, in generation order: sha256}
    — generation numbers may differ between two stores, bytes may not."""
    from cassandra_tpu.storage.sstable import Descriptor
    out = {}
    gens = sorted(d.generation for d in Descriptor.list_in(directory))
    for rank, gen in enumerate(gens):
        for comp in HASHED_COMPONENTS:
            for p in glob.glob(os.path.join(directory, f"*-{gen}-{comp}")):
                with open(p, "rb") as f:
                    out[f"{rank}:{comp}"] = hashlib.sha256(
                        f.read()).hexdigest()
    return out


def compact(cfs, **task_kw) -> tuple:
    """Major-compacts the store's sstables; (report, the task)."""
    from cassandra_tpu.compaction.task import CompactionTask
    task = CompactionTask(cfs, cfs.tracker.view(), **task_kw)
    t0 = time.perf_counter()
    stats = task.execute()
    wall = time.perf_counter() - t0
    return {"engine": task.engine, "wall_s": round(wall, 3),
            "cells_read": stats["cells_read"],
            "cells_written": stats["cells_written"],
            "bytes_read": stats["bytes_read"],
            "bytes_written": stats["bytes_written"],
            "phases_s": {k: round(v, 3)
                         for k, v in sorted(task.profile.items())}}, task


# the `device_compress` leg of scripts/check_compaction_ab.py, verbatim
DEVICE_LEG = dict(pipelined_io=True, compress_pool=0, decode_ahead=False,
                  engine="device", use_device=True, device_compress=True)
NUMPY_LEG = dict(pipelined_io=True, compress_pool=0, decode_ahead=False,
                 engine="numpy")


def standalone_store(table, base_dir: str, inputs_from: str | None = None):
    """A store outside any node (as bench.py and the A/B scripts open
    one), optionally over hard links to another store's sstables."""
    from cassandra_tpu.storage.table import ColumnFamilyStore
    cfs = ColumnFamilyStore(table, base_dir, commitlog=None)
    if inputs_from is not None:
        for fn in os.listdir(inputs_from):
            src = os.path.join(inputs_from, fn)
            if os.path.isfile(src):
                os.link(src, os.path.join(cfs.directory, fn))
    cfs.reload_sstables()
    return cfs


def close_stores(*stores) -> None:
    for cfs in stores:
        for reader in cfs.live_sstables():
            reader.close()


def phase_compact(served: ServedNode, rng, sz: Sizes, scratch: str,
                  led: Ledger) -> dict:
    from cassandra_tpu.client import serialize_params
    from cassandra_tpu.storage.sstable.format import SEGMENT_CELLS
    s = served.session
    s.execute(
        "CREATE TABLE backlog (id int, c int, v blob, PRIMARY KEY (id, c)) "
        "WITH compression = {'class': 'LZ4Compressor', "
        f"'chunk_length_in_kb': 16}} AND gc_grace_seconds = {GC_GRACE}")
    table = served.table("backlog")
    cfs = served.store("backlog")
    t0 = time.perf_counter()
    raw = load_backlog(cfs, rng, sz)
    ref_cfs = standalone_store(
        table, os.path.join(scratch, "backlog-numpy"), cfs.directory)
    warm_cfs = standalone_store(
        table, os.path.join(scratch, "backlog-device-warm"), cfs.directory)
    cfs.reload_sstables()
    require(len(cfs.live_sstables()) == sz.runs,
            "compact: the bulk path did not land every run")
    t_load = time.perf_counter() - t0

    # cold: the served node's own store, every program compiled here
    led.mark()
    cold, _ = compact(cfs, **DEVICE_LEG)
    cold_programs = led.programs()
    fb = led.require_no_fallback("compact (cold)")
    rounds = cold_programs.get("merge.resident", {}).get("calls", 0)
    seg_full = cold["cells_written"] // SEGMENT_CELLS
    require(cold["engine"] == "device", "compact: not the device engine")
    require(rounds >= sz.min_device_rounds,
            f"compact: {rounds} device rounds < {sz.min_device_rounds}")
    require(seg_full >= sz.min_full_segments,
            f"compact: {seg_full} full segments < {sz.min_full_segments}")
    if seg_full:
        for prog in ("write.serialize", "write.compress"):
            require(cold_programs.get(prog, {}).get("calls", 0) == seg_full,
                    f"compact: {prog} ran for "
                    f"{cold_programs.get(prog, {}).get('calls', 0)} of "
                    f"{seg_full} full segments")

    # the executable spec on a copy of the same inputs
    ref, _ = compact(ref_cfs, **NUMPY_LEG)
    require(ref["engine"] == "numpy", "compact: not the numpy engine")
    h_dev = component_hashes(cfs.directory)
    h_ref = component_hashes(ref_cfs.directory)
    differ = sorted(k for k in h_dev.keys() | h_ref.keys()
                    if h_dev.get(k) != h_ref.get(k))
    require(h_ref and not differ, "compact: device-engine output differs "
                                  f"from the numpy engine's: {differ}")

    # warm: the same work again, everything compiled
    led.mark()
    warm, _ = compact(warm_cfs, **DEVICE_LEG)
    warm_programs = led.programs()
    led.require_no_fallback("compact (warm)")
    require(component_hashes(warm_cfs.directory) == h_ref,
            "compact: the warm device run's output differs")
    close_stores(ref_cfs, warm_cfs)

    # the compacted table, over the wire, against the plain merge
    sel = s.prepare("SELECT c, v FROM backlog WHERE id = ?")
    sample = rng.choice(sz.partitions, sz.sample_partitions, replace=False)
    rows_checked = 0
    for pk in sample:
        got = sorted(s.execute_prepared(
            sel, serialize_params(table, ["id"], [int(pk)])).rows)
        want = reference_partition(raw, int(pk))
        require(got == want, f"compact: partition id={int(pk)} read over "
                             f"the wire differs from the plain merge "
                             f"({len(got)} rows vs {len(want)})")
        rows_checked += len(want)
    return {"input": {"runs": sz.runs, "cells_per_run": sz.run_cells,
                      "value_bytes": VALUE_BYTES,
                      "partitions": sz.partitions,
                      "bytes_on_disk": cold["bytes_read"]},
            "load_s": round(t_load, 3),
            "device_rounds": rounds, "host_rounds":
                fb["compaction.device_host_rounds"]
                + fb["compaction.device_resident_fallback"],
            "full_segments": int(seg_full), "fallbacks": fb,
            "byte_identical_components": len(h_ref),
            "device_cold": cold, "numpy": ref, "device_warm": warm,
            "programs_cold": cold_programs, "programs_warm": warm_programs,
            "wire_sample": {"partitions": int(sz.sample_partitions),
                            "rows": rows_checked}}


# ----------------------------------------------------------- phase: scan --

def phase_scan(served: ServedNode, rng, sz: Sizes, led: Ledger) -> dict:
    from cassandra_tpu.storage import cellbatch as cb
    from cassandra_tpu.tools import bulk
    served.session.execute("CREATE TABLE scan (id int, c int, v int, "
                           "PRIMARY KEY (id, c))")
    s = served.local_session      # see ServedNode: the lane's only door
    table = served.table("scan")
    cfs = served.store("scan")
    n, parts = sz.scan_rows, sz.scan_partitions
    i = np.arange(n, dtype=np.int64)
    pk, ck = i % parts, i // parts
    # v rises with the partition, so a range predicate picks few
    # partitions while every segment still holds candidates
    v = (pk * 1000 + rng.integers(0, 1000, n)).astype(np.int64)
    vbytes = np.ascontiguousarray(v.astype(">i4")).view(np.uint8) \
        .reshape(n, 4)
    ts = rng.integers(1, 1 << 40, n).astype(np.int64)
    per = n // sz.scan_sstables
    t0 = time.perf_counter()
    for g in range(sz.scan_sstables):
        sl = slice(g * per, n if g == sz.scan_sstables - 1
                   else (g + 1) * per)
        bulk_load(cfs, cb.merge_sorted([bulk.build_int_batch(
            table, pk[sl], ck[sl], vbytes[sl], ts[sl])]))
    cfs.reload_sstables()
    t_load = time.perf_counter() - t0

    bound = int(max(parts // 100, 2)) * 1000
    hit = v < bound
    want_rows = sorted(zip(pk[hit].tolist(), ck[hit].tolist(),
                           v[hit].tolist()))
    want_agg = [(int(hit.sum()), int(v[hit].min()), int(v[hit].max()))]
    q_rows = f"SELECT id, c, v FROM scan WHERE v < {bound} ALLOW FILTERING"
    q_agg = ("SELECT count(v), min(v), max(v) FROM scan "
             f"WHERE v < {bound} ALLOW FILTERING")

    def leg(gate: bool) -> dict:
        served.node.engine.settings.set("scan_device_filter", gate)
        led.mark()
        c0 = {k: led.counter(k) for k in
              ("scan.device_segments", "scan.host_segments",
               "scan.pushdown", "scan.agg_pushdown", "scan.segments_total")}
        walls = []
        for _ in range(2):           # first pays the compiles
            t1 = time.perf_counter()
            got_rows = sorted(s.execute(q_rows).rows)
            got_agg = s.execute(q_agg).rows
            walls.append(round(time.perf_counter() - t1, 3))
            require(got_rows == want_rows,
                    f"scan: filtered rows differ from numpy (gate={gate}: "
                    f"{len(got_rows)} vs {len(want_rows)})")
            require(got_agg == want_agg,
                    f"scan: aggregate {got_agg} != numpy {want_agg} "
                    f"(gate={gate})")
        d = {k.split(".", 1)[1]: led.counter(k) - c0[k] for k in c0}
        d.update(first_s=walls[0], warm_s=walls[1],
                 programs=led.programs(), fallbacks=led.fallbacks())
        return d

    on = leg(True)
    require(on["device_segments"] > 0 and on["host_segments"] == 0,
            f"scan: segments device={on['device_segments']} "
            f"host={on['host_segments']} with the device gate on")
    require(on["pushdown"] == 4 and on["agg_pushdown"] == 2,
            "scan: a statement did not take the pushdown lane")
    require(on["fallbacks"]["scan.fallback"] == 0, "scan: lane refused")
    off = leg(False)
    require(off["device_segments"] == 0 and off["host_segments"] > 0,
            "scan: the gate off still ran device segments")
    served.node.engine.settings.set("scan_device_filter", True)
    return {"rows": n, "sstables": sz.scan_sstables,
            "matching_rows": len(want_rows), "load_s": round(t_load, 3),
            "device_gate_on": on, "device_gate_off": off}


# ------------------------------------------------------------ phase: ann --

def phase_ann(served: ServedNode, rng, sz: Sizes, led: Ledger) -> dict:
    """Loaded by the fastest path the repo has: tools/bulk.build_int_batch
    (vectorised CellBatch assembly) -> SSTableWriter -> reload_sstables;
    the index component is built from the sstable on the first query."""
    from cassandra_tpu.client import serialize_params
    from cassandra_tpu.storage import cellbatch as cb
    from cassandra_tpu.tools import bulk
    s = served.session
    s.execute(f"CREATE TABLE vec (id int, c int, v vector<float, "
              f"{sz.ann_dim}>, PRIMARY KEY (id, c))")
    s.execute("CREATE CUSTOM INDEX ON vec (v) USING 'SAI'")
    table = served.table("vec")
    cfs = served.store("vec")
    n = sz.ann_rows
    mat = rng.standard_normal((n, sz.ann_dim)).astype(np.float32)
    ids = np.arange(n, dtype=np.int64)
    vbytes = np.ascontiguousarray(mat.astype(">f4")).view(np.uint8) \
        .reshape(n, 4 * sz.ann_dim)
    t0 = time.perf_counter()
    bulk_load(cfs, cb.merge_sorted([bulk.build_int_batch(
        table, ids, np.zeros(n, dtype=np.int64), vbytes,
        np.full(n, 1000, dtype=np.int64))]))
    cfs.reload_sstables()
    t_load = time.perf_counter() - t0

    unit = mat.astype(np.float64)
    unit /= np.linalg.norm(unit, axis=1, keepdims=True)
    ann = s.prepare("SELECT id FROM vec ORDER BY v ANN OF ? LIMIT 10")
    led.mark()
    walls, gaps = [], []
    for _ in range(sz.ann_queries):
        q = rng.standard_normal(sz.ann_dim).astype(np.float32)
        scores = unit @ (q.astype(np.float64) / np.linalg.norm(q))
        order = np.argsort(-scores)[:11]
        want = order[:10].tolist()
        gaps.append(float(np.min(-np.diff(scores[order]))))
        t1 = time.perf_counter()
        got = [r[0] for r in s.execute_prepared(
            ann, serialize_params(table, ["v"], [q.tolist()])).rows]
        walls.append(round(time.perf_counter() - t1, 3))
        require(got == want, f"ann: top-10 {got} != brute force {want}")
    return {"rows": n, "dim": sz.ann_dim, "queries": sz.ann_queries,
            "load_s": round(t_load, 3), "first_query_s": walls[0],
            "warm_query_s": walls[1:],
            "smallest_score_gap_in_top11": min(gaps),
            "programs": led.programs()}


# ------------------------------------------------- --chips 4: the mesh --

def phase_mesh_compact(rng, sz: Sizes, scratch: str, led: Ledger,
                       chips: int) -> dict:
    """The one-chip backlog again, compacted by the device engine serially
    and across `chips` mesh lanes: same bytes, and every lane on its own
    device."""
    import jax

    from cassandra_tpu.ops.codec import CompressionParams
    from cassandra_tpu.schema import TableParams, make_table
    table = make_table(
        "smoke", "backlog", pk=["id"], ck=["c"],
        cols={"id": "int", "c": "int", "v": "blob"},
        params=TableParams(compression=CompressionParams(
            "LZ4Compressor", chunk_length=16 * 1024),
            gc_grace_seconds=GC_GRACE))
    serial_cfs = standalone_store(table, os.path.join(scratch, "serial"))
    load_backlog(serial_cfs, rng, sz)
    mesh_cfs = standalone_store(table, os.path.join(scratch, "mesh"),
                                serial_cfs.directory)
    serial_cfs.reload_sstables()

    # device-resident rounds on both sides; block compression stays on the
    # host here. The compress lane is inert under the mesh anyway, the
    # one-chip run covers it, and its host-side LZ4 emitter (3.5 s per
    # segment, PERF.md) would be paid at four chips' price.
    leg = dict(DEVICE_LEG, device_compress=False)
    led.mark()
    serial, _ = compact(serial_cfs, mesh_devices=0, **leg)
    serial_programs = led.programs()
    led.require_no_fallback("mesh (serial leg)")

    led.mark()
    mesh, task = compact(mesh_cfs, mesh_devices=chips, **leg)
    mesh_programs = led.programs()
    led.require_no_fallback("mesh (mesh leg)")
    lanes = [d.id for d in task.mesh_lane_devices]
    shards = [c for c in task.mesh_shard_cells if c]
    require(len(set(lanes)) == chips,
            f"mesh: lanes were placed on devices {lanes}, not {chips} "
            "distinct ones")
    require(len(shards) >= chips, f"mesh: only {len(shards)} shards ran")
    h_serial = component_hashes(serial_cfs.directory)
    require(h_serial and h_serial == component_hashes(mesh_cfs.directory),
            f"mesh: mesh-{chips} output differs from the serial device run")
    peaks = peak_bytes()
    if jax.default_backend() == "tpu":
        require(all(p for p in peaks[:chips]),
                f"mesh: a device held no memory at its peak: {peaks}")
    close_stores(serial_cfs, mesh_cfs)
    return {"lanes_on_devices": lanes, "shards": len(shards),
            "shard_cells": shards,
            "byte_identical_components": len(h_serial),
            "serial": serial, "mesh": mesh,
            "programs_serial": serial_programs,
            "programs_mesh": mesh_programs,
            "peak_bytes_in_use": peaks}


def phase_sharded_merge(rng, sz: Sizes, led: Ledger, chips: int) -> dict:
    """parallel/mesh.py against cellbatch.merge_sorted: the per-device
    dispatch path (materialize_sharded_merge / run_sharded_merge) and the
    one-program shard_map step whose stats are psum'd across the mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from cassandra_tpu.parallel.mesh import (make_mesh,
                                             materialize_sharded_merge,
                                             shard_batch,
                                             sharded_merge_step)
    from cassandra_tpu.schema import make_table
    from cassandra_tpu.storage import cellbatch as cb
    from cassandra_tpu.tools import bulk
    table = make_table("smoke", "shard", pk=["id"], ck=["c"],
                       cols={"id": "int", "c": "int", "v": "blob"})
    runs = []
    for _ in range(2):
        n = sz.shard_cells
        runs.append(cb.merge_sorted([bulk.build_int_batch(
            table, rng.integers(0, sz.partitions, n),
            rng.integers(1, sz.ck_space, n),
            rng.integers(0, 256, (n, 16), dtype=np.uint8),
            rng.integers(1, 1 << 40, n).astype(np.int64))]))
    cat = cb.CellBatch.concat(runs)
    ref = cb.merge_sorted(runs)
    mesh = make_mesh(chips)
    led.mark()

    t0 = time.perf_counter()
    walls: list = []
    shards = materialize_sharded_merge(cat, mesh, walls_out=walls)
    t_dispatch = time.perf_counter() - t0
    merged = cb.CellBatch.concat([x for x in shards if len(x)])
    for col in ("lanes", "ts", "flags", "off", "payload"):
        require(np.array_equal(getattr(merged, col), getattr(ref, col)),
                f"sharded merge: {col} differs from merge_sorted")
    require(sum(1 for w in walls if w > 0) == chips,
            f"sharded merge: device walls {walls}: not every device ran")

    # the shard_map program: keep masks per shard + psum'd (kept, dropped)
    operands, _shard_of, _pos, members = shard_batch(cat, chips)
    arr = NamedSharding(mesh, P("shard"))
    rep = NamedSharding(mesh, P())
    placed = {k: jax.device_put(v, rep if k in ("gc_before", "now")
                                else arr) for k, v in operands.items()}
    t1 = time.perf_counter()
    perm, packed, stats = sharded_merge_step(mesh)(placed)
    stats = np.asarray(stats)
    t_step = time.perf_counter() - t1
    require(len(packed.sharding.device_set) == chips,
            "sharded step: output not spread over every device")
    require(int(stats[0]) == len(ref)
            and int(stats[1]) == len(cat) - len(ref),
            f"sharded step: psum'd stats {stats.tolist()} != "
            f"({len(ref)}, {len(cat) - len(ref)})")
    keep = (np.asarray(packed) & 1).astype(bool)
    perm = np.asarray(perm)
    picked = np.concatenate([members[s][perm[s][keep[s]]]
                             for s in range(chips)])
    got = cat.apply_permutation(picked)
    for col in ("lanes", "ts", "payload"):
        require(np.array_equal(getattr(got, col), getattr(ref, col)),
                f"sharded step: {col} differs from merge_sorted")
    return {"cells_in": len(cat), "cells_kept": len(ref),
            "per_device_dispatch_s": round(t_dispatch, 3),
            "device_walls_s": [round(w, 3) for w in walls],
            "shard_map_step_s": round(t_step, 3),
            "psum_stats": stats.tolist(), "programs": led.programs()}


# ------------------------------------------------------------------ run --

def run(seed: int = 0, chips: int = 1, sizes: Sizes = FULL,
        check_platform: bool = True) -> int:
    """Runs the phases; returns the process exit code. check_platform=False
    is the tier-1 entry (tests/test_chip_smoke.py): the same control flow
    on whatever backend jax has, and nothing else skipped."""
    t_start = time.perf_counter()
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    phase = "startup"
    scratch = served = count_cache_event = None
    try:
        native = rebuild_native()
        import jax

        from cassandra_tpu.utils import compile_cache
        cache_dir = compile_cache.configure()
        cache_events = {"/jax/compilation_cache/cache_hits": 0,
                        "/jax/compilation_cache/cache_misses": 0}

        def count_cache_event(event, **_kw):
            if event in cache_events:
                cache_events[event] += 1
        jax.monitoring.register_event_listener(count_cache_event)
        device = device_info()
        if check_platform:
            require(device["platform"] == "tpu",
                    f"jax's platform is {device['platform']!r}, not 'tpu': "
                    "the smoke does not run on a CPU")
        require(device["count"] >= chips,
                f"--chips {chips} but jax sees {device['count']} device(s)")
        cached_before = len(os.listdir(cache_dir)) \
            if os.path.isdir(cache_dir) else 0
        emit({"phase": phase, "ok": True, "device": device, "seed": seed,
              "chips": chips, "native_library": native,
              "compile_cache_dir": cache_dir,
              "compile_cache_entries_at_start": cached_before,
              "x64": bool(jax.config.jax_enable_x64),
              "sizes": dataclasses.asdict(sizes)})
        rng = np.random.default_rng(seed)
        led = Ledger()
        scratch = tempfile.mkdtemp(prefix="ctpu-chip-smoke-")
        if chips == 1:
            served = ServedNode(os.path.join(scratch, "node"))
            phases = (
                ("wire", lambda: phase_wire(served, rng, sizes)),
                ("compact", lambda: phase_compact(served, rng, sizes,
                                                  scratch, led)),
                ("scan", lambda: phase_scan(served, rng, sizes, led)),
                ("ann", lambda: phase_ann(served, rng, sizes, led)))
        else:
            phases = (
                ("mesh_compact", lambda: phase_mesh_compact(
                    rng, sizes, scratch, led, chips)),
                ("sharded_merge", lambda: phase_sharded_merge(
                    rng, sizes, led, chips)))
        for phase, fn in phases:
            t0 = time.perf_counter()
            out = fn()
            emit({"phase": phase, "ok": True,
                  "wall_s": round(time.perf_counter() - t0, 3), **out})
        phase = "summary"
        programs = led.programs(whole_run=True)
        counters = led.fallbacks(whole_run=True)
        bad = {k: v for k, v in counters.items()
               if v and k not in ("profile.retraces", "scan.host_segments")}
        require(not bad, f"fallback counters at exit: {bad}")
        cached_after = len(os.listdir(cache_dir)) \
            if os.path.isdir(cache_dir) else 0
        emit({"phase": phase, "ok": True,
              "wall_s": round(time.perf_counter() - t_start, 3),
              "compile_s_total": round(sum(
                  k["compile_s"] for k in programs.values()), 3),
              "compiles": {n: k["compiles"] for n, k in programs.items()},
              "fallback_counters": counters,
              "compile_cache": {
                  "dir": cache_dir, "entries_at_start": cached_before,
                  "entries_at_end": cached_after,
                  "hits": cache_events[
                      "/jax/compilation_cache/cache_hits"],
                  "misses": cache_events[
                      "/jax/compilation_cache/cache_misses"]},
              "peak_bytes_in_use": peak_bytes()})
    except Exception as e:
        traceback.print_exc()
        emit({"phase": phase, "ok": False,
              "error": f"{type(e).__name__}: {e}"})
        return 1
    finally:
        if count_cache_event is not None:
            jax.monitoring.unregister_event_listener(count_cache_event)
        if served is not None:
            try:
                served.close()
            except Exception:
                traceback.print_exc()
        if scratch is not None:
            shutil.rmtree(scratch, ignore_errors=True)
    emit({"ok": True, "device": device})
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh path and what it is "
                         "compared with (needs four chips)")
    args = ap.parse_args(argv)
    return run(seed=args.seed, chips=args.chips)


if __name__ == "__main__":
    sys.exit(main())
