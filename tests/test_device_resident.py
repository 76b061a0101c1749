"""Device-resident compaction rounds (ops/device_write.py): byte
identity with the serial host path, the fused META serialize kernel
pinned against the host builder, adversarial completion-order /
knob-flip / EIO-unwind behavior of the device→host handshake, and the
hot-reloadable `compaction_decode_ahead` knob."""
from __future__ import annotations

import hashlib
import os
import time

import numpy as np
import pytest

from cassandra_tpu.compaction.task import CompactionTask
from cassandra_tpu.ops import device_write as dwrite
from cassandra_tpu.schema import TableParams, make_table
from cassandra_tpu.storage import cellbatch as cb
from cassandra_tpu.storage.cellbatch import CellBatchBuilder
from cassandra_tpu.storage.sstable import Descriptor, SSTableWriter
from cassandra_tpu.storage.sstable.writer import build_meta_block
from cassandra_tpu.storage.table import ColumnFamilyStore
from cassandra_tpu.tools import bulk
from cassandra_tpu.utils import faultfs
from cassandra_tpu.ops.codec import CompressionParams

N_CELLS = 60_000


def _table(name: str):
    return make_table(
        "devres", name, pk=["id"], ck=["c"],
        cols={"id": "int", "c": "int", "v": "blob"},
        params=TableParams(compression=CompressionParams(
            "LZ4Compressor", chunk_length=16 * 1024)))


def _build_inputs(cfs, table, n_ssts=3, n=N_CELLS, deletions=True):
    now = 1_700_000_000   # fixed: legs built at different wall times
    #                       must produce identical fixtures
    rng = np.random.default_rng(7)
    vcol = table.columns["v"].column_id
    for gen in range(1, n_ssts + 1):
        b = CellBatchBuilder(table)
        for p in range(200):
            pk = table.serialize_partition_key([p])
            if deletions and gen == 2 and p % 9 == 0:
                b.add_partition_deletion(pk, 5_000_000, ldt=now - 100)
            for c in range(n // 200 // n_ssts):
                ck = table.serialize_clustering([c])
                ts = 1_000_000 * gen + c
                if deletions and (p + c) % 13 == 0:
                    b.add_tombstone(pk, ck, vcol, ts, ldt=now - 50)
                elif deletions and (p + c) % 17 == 0:
                    # equal-ts duplicates across inputs: the device
                    # flags them ambiguous -> per-round host fallback
                    b.add_cell(pk, ck, vcol,
                               rng.integers(0, 256, 24,
                                            dtype=np.uint8).tobytes(),
                               999_999)
                else:
                    b.add_cell(pk, ck, vcol,
                               rng.integers(0, 256, 24,
                                            dtype=np.uint8).tobytes(),
                               ts)
        w = SSTableWriter(Descriptor(cfs.directory, gen), table,
                          estimated_partitions=200)
        w.append(cb.merge_sorted([b.seal()], now=0))
        w.finish()


def _build_big(cfs, table, n_ssts=3, n_per=140_000, seed=5):
    """Multi-segment inputs (vectorized build): each sstable spans 3
    Data.db segments, so rolls and decode-ahead fetches really happen."""
    rng = np.random.default_rng(seed)
    for gen in range(1, n_ssts + 1):
        pk = rng.integers(0, 500, n_per)
        ck = rng.integers(0, 100_000, n_per)
        vals = rng.integers(0, 256, (n_per, 24), dtype=np.uint8)
        ts = rng.integers(1, 1 << 40, n_per).astype(np.int64)
        w = SSTableWriter(Descriptor(cfs.directory, gen), table,
                          estimated_partitions=500)
        w.append(cb.merge_sorted([bulk.build_int_batch(table, pk, ck,
                                                       vals, ts)]))
        w.finish()


def _hashes(directory: str) -> dict:
    comps = ("Data.db", "Index.db", "Partitions.db", "Filter.db",
             "Statistics.db", "Digest.crc32")
    out = {}
    for fn in sorted(os.listdir(directory)):
        p = os.path.join(directory, fn)
        if os.path.isfile(p) and any(fn.endswith(c) for c in comps):
            with open(p, "rb") as f:
                out[fn] = hashlib.sha256(f.read()).hexdigest()
    return out


def _compact(tmp_path, tag: str, table, **task_kw) -> dict:
    d = str(tmp_path / tag)
    cfs = ColumnFamilyStore(table, d, commitlog=None)
    _build_inputs(cfs, table)
    cfs.reload_sstables()
    task = CompactionTask(cfs, cfs.tracker.view(), **task_kw)
    task.execute()
    h = _hashes(cfs.directory)
    for r in cfs.live_sstables():
        r.close()
    return h


# ------------------------------------------------------- serialize kernel --

def test_meta_kernel_matches_host_builder():
    """The fused device META kernel and the host build_meta_block must
    emit identical bytes — including wraparound ts deltas at extreme
    timestamps — and identical stats reductions."""
    rng = np.random.default_rng(3)
    n = 4096
    ts = rng.integers(-(1 << 62), 1 << 62, n).astype(np.int64)
    ts[:4] = [np.iinfo(np.int64).min, np.iinfo(np.int64).max, -1, 0]
    ldt = rng.integers(-(1 << 31), 1 << 31, n).astype(np.int32)
    ttl = rng.integers(0, 1 << 20, n).astype(np.int32)
    flags = rng.integers(0, 256, n).astype(np.uint8)
    fl = rng.integers(0, 1 << 16, n).astype(np.uint32)
    vr = rng.integers(0, 1 << 12, n).astype(np.uint32)
    host = build_meta_block(ts, ldt, ttl, flags,
                            fl.astype("<u4"), vr.astype("<u4"))
    import jax.numpy as jnp
    with np.errstate(over="ignore"):
        uts = ts.astype(np.uint64) ^ np.uint64(1 << 63)
    meta_d, st = dwrite._meta_block_kernel(
        jnp.asarray((uts >> np.uint64(32)).astype(np.uint32)),
        jnp.asarray((uts & np.uint64(0xFFFFFFFF)).astype(np.uint32)),
        jnp.asarray(ldt), jnp.asarray(ttl), jnp.asarray(flags),
        jnp.asarray(fl), jnp.asarray(vr))
    assert bytes(np.asarray(meta_d)) == bytes(host)
    assert dwrite._uts_pair_to_i64(st[0], st[1]) == int(ts.min())
    assert dwrite._uts_pair_to_i64(st[2], st[3]) == int(ts.max())
    assert int(st[4]) == int(ldt.min())
    assert int(st[5]) == int(ldt.max())
    from cassandra_tpu.storage.cellbatch import DEATH_FLAGS
    assert int(st[6]) == int(((flags & DEATH_FLAGS) != 0).sum())


# ----------------------------------------------------------- byte identity --

def test_device_resident_identical_to_serial(tmp_path):
    table = _table("ident")
    serial = _compact(tmp_path, "serial", table, pipelined_io=False,
                      compress_pool=0, decode_ahead=False)
    device = _compact(tmp_path, "device", table, engine="device",
                      use_device=True, pipelined_io=True,
                      compress_pool=0, decode_ahead=False)
    assert serial and device == serial


def test_device_resident_roll_identical(tmp_path):
    """Output rolling (max_output_bytes) flushes the device lane's
    pending partial into the finishing writer — the exact cells the
    host path's finish() would cut. Both legs run the synchronous
    write path (pipelined_io=False) so the published offset the roll
    check reads is timing-independent and the roll points — and
    therefore every component byte — must match exactly."""
    table = _table("roll")

    def leg(tag, **kw):
        d = str(tmp_path / tag)
        cfs = ColumnFamilyStore(table, d, commitlog=None)
        _build_big(cfs, table)
        cfs.reload_sstables()
        CompactionTask(cfs, cfs.tracker.view(), pipelined_io=False,
                       compress_pool=0, decode_ahead=False,
                       round_cells=150_000, max_output_bytes=1,
                       **kw).execute()
        h = _hashes(cfs.directory)
        for r in cfs.live_sstables():
            r.close()
        return h

    serial = leg("serial")
    device = leg("device", engine="device", use_device=True)
    assert len(serial) > 6   # really rolled (> 1 output sstable)
    assert device == serial


def test_reverse_completion_order_drains_in_order(tmp_path):
    """Round 0's collect is delayed until rounds 1-2's device programs
    completed — the in-flight rounds finish in REVERSE order, and the
    submit-order drain must still produce identical bytes."""
    table = _table("revorder")
    serial = _compact(tmp_path, "serial", table, pipelined_io=False,
                      compress_pool=0, decode_ahead=False,
                      round_cells=30_000)
    dwrite._collect_seq = 0
    dwrite._TEST_COLLECT_DELAY = {0: 0.3, 1: 0.1}
    try:
        device = _compact(tmp_path, "device", table, engine="device",
                          use_device=True, pipelined_io=True,
                          compress_pool=0, decode_ahead=False,
                          round_cells=30_000)
    finally:
        dwrite._TEST_COLLECT_DELAY = None
    assert device == serial


# ------------------------------------------- one program, host rounds counted --

def _counter_table():
    return make_table("devres", "cnt", pk=["id"],
                      cols={"id": "int", "hits": "counter"})


def _counter_inputs(cfs, table):
    cid = table.columns["hits"].column_id
    for gen in range(1, 4):
        b = CellBatchBuilder(table)
        for p in range(300):
            b.append_raw(table.serialize_partition_key([p]), b"", cid, b"",
                         (gen * 1000 + p).to_bytes(8, "big"),
                         ts=100 * gen + p % 7, flags=cb.FLAG_COUNTER)
        # small segments: the round loop advances a segment at a time
        w = SSTableWriter(Descriptor(cfs.directory, gen), table,
                          estimated_partitions=300, segment_cells=100)
        w.append(cb.merge_sorted([b.seal()]))
        w.finish()


def _range_bound_inputs(cfs, table):
    from cassandra_tpu.storage.rangetomb import Slice
    vcol = table.columns["v"].column_id
    ck = lambda c: table.serialize_clustering([c])
    for gen in range(1, 4):
        b = CellBatchBuilder(table)
        for p in range(100):
            pk = table.serialize_partition_key([p])
            for c in range(6):
                b.add_cell(pk, ck(c), vcol, bytes([gen, c]) * 8,
                           1000 * gen + c)
            if gen == 2:   # every partition, so every round, holds one
                b.add_range_tombstone(
                    pk, Slice(ck(1), True, ck(3), False, 1500, 0))
        w = SSTableWriter(Descriptor(cfs.directory, gen), table,
                          estimated_partitions=100, segment_cells=100)
        w.append(cb.merge_sorted([b.seal()]))
        w.finish()


@pytest.mark.parametrize("mk_table,build", [
    (_counter_table, _counter_inputs),
    (lambda: _table("rt"), _range_bound_inputs),
], ids=["counter", "range_bound"])
def test_named_device_task_counts_rounds_it_cannot_encode(
        tmp_path, mk_table, build, monkeypatch):
    """choose_engine keeps counter and range-bound inputs off the device
    engine; a caller that names engine="device" anyway gets numpy's
    bytes, and each such round is counted twice: it left the resident
    lane, and the numpy spec merged it."""
    from cassandra_tpu.compaction import task as task_mod
    from cassandra_tpu.service.metrics import GLOBAL as METRICS
    names = ("compaction.device_host_rounds",
             "compaction.device_resident_fallback")
    rounds = []
    submit = task_mod.submit_merge_resident
    monkeypatch.setattr(
        task_mod, "submit_merge_resident",
        lambda slices, **kw: rounds.append(1) or submit(slices, **kw))
    out, rose = {}, {}
    table = mk_table()
    for engine in ("numpy", "device"):
        cfs = ColumnFamilyStore(table, str(tmp_path / engine),
                                commitlog=None)
        build(cfs, table)
        cfs.reload_sstables()
        before = [METRICS.counter(n) for n in names]
        CompactionTask(cfs, cfs.tracker.view(), engine=engine,
                       round_cells=300).execute()
        rose[engine] = [METRICS.counter(n) - b
                        for n, b in zip(names, before)]
        out[engine] = _hashes(cfs.directory)
        for r in cfs.live_sstables():
            r.close()
    assert out["numpy"] and out["device"] == out["numpy"]
    assert rose["numpy"] == [0, 0]
    assert len(rounds) >= 2 and rose["device"] == [len(rounds)] * 2


def test_compaction_dispatches_one_merge_program(tmp_path):
    """Whoever asks — the serial round loop, a mesh lane on its own
    device, merge_sorted_device — a device-engine round is merged by
    `merge.resident` and nothing else."""
    from cassandra_tpu.service import profiling
    profiling.GLOBAL.reset()
    table = _table("oneprog")
    kw = dict(engine="device", compress_pool=0)
    serial = _compact(tmp_path, "serial", table, **kw)
    mesh = _compact(tmp_path, "mesh2", table, mesh_devices=2, **kw)
    assert serial and mesh == serial
    b = CellBatchBuilder(table)
    b.add_cell(table.serialize_partition_key([1]),
               table.serialize_clustering([1]),
               table.columns["v"].column_id, b"v", 5)
    assert len(dwrite.merge_sorted_device([b.seal()])) == 1
    kernels = profiling.GLOBAL.snapshot()["kernels"]
    assert kernels["merge.resident"]["calls"] >= 3
    assert set(kernels) <= {"merge.resident", "write.serialize"}


# ------------------------------------------------------- decode-ahead knob --

def test_decode_ahead_knob_flip_mid_compaction(tmp_path):
    """The task re-reads the engine-scoped knob every round: flipping
    it off mid-compaction retires the prefetch thread at the next
    round boundary, and the output bytes are identical regardless of
    when (or how often) it flips."""
    table = _table("knobflip")
    # multi-segment inputs: merge rounds advance one segment span at a
    # time, so the task makes >= 4 rounds (= 4 knob reads)
    dp = str(tmp_path / "pinned")
    pcfs = ColumnFamilyStore(table, dp, commitlog=None)
    _build_big(pcfs, table, n_per=220_000, seed=11)
    pcfs.reload_sstables()
    CompactionTask(pcfs, pcfs.tracker.view(), pipelined_io=True,
                   compress_pool=0, decode_ahead=False,
                   round_cells=10_000).execute()
    pinned = _hashes(pcfs.directory)
    for r in pcfs.live_sstables():
        r.close()

    d = str(tmp_path / "flip")
    cfs = ColumnFamilyStore(table, d, commitlog=None)
    _build_big(cfs, table, n_per=220_000, seed=11)
    cfs.reload_sstables()
    calls = [0]

    def knob():
        calls[0] += 1
        return calls[0] <= 2    # on for two rounds, then OFF

    cfs.decode_ahead_fn = knob
    task = CompactionTask(cfs, cfs.tracker.view(), pipelined_io=True,
                          compress_pool=0, round_cells=10_000)
    assert task._decode_ahead_enabled() in (True, False)
    task.execute()
    # the knob was re-read every round (hot-reload contract) and bytes
    # match the pinned-off leg
    assert calls[0] >= 4
    assert _hashes(cfs.directory) == pinned
    for r in cfs.live_sstables():
        r.close()


def test_decode_ahead_eio_unwinds_with_inputs_live(tmp_path):
    """An EIO surfacing from a decode-ahead prefetched segment read
    must fail the task through the normal unwind: lifecycle txn rolled
    back, tmp components gone, input sstables still live and readable."""
    table = _table("eio")
    d = str(tmp_path / "store")
    cfs = ColumnFamilyStore(table, d, commitlog=None)
    _build_big(cfs, table)
    cfs.reload_sstables()
    inputs_before = list(cfs.tracker.view())
    # fire on the SECOND read of input 1's data — a later segment,
    # fetched by the decode-ahead helper (or, under unlucky
    # scheduling, a merge-thread extend): either path must unwind
    # identically
    faultfs.GLOBAL.arm("sstable.read", mode="error", after=1,
                       path_substr="-1-Data.db")
    try:
        task = CompactionTask(cfs, inputs_before, pipelined_io=True,
                              compress_pool=0, decode_ahead=True,
                              round_cells=100_000)
        with pytest.raises(OSError):
            task.execute()
    finally:
        faultfs.GLOBAL.disarm()
    # rollback left the inputs live and the directory clean
    assert list(cfs.tracker.view()) == inputs_before
    assert not [f for f in os.listdir(cfs.directory)
                if f.startswith("tmp-")]
    # the store still serves every partition from the untouched inputs
    from cassandra_tpu.storage.chunk_cache import GLOBAL as chunk_cache
    chunk_cache.clear()
    pk = table.serialize_partition_key([5])
    assert len(cfs.read_partition(pk, now=int(time.time()))) > 0
    for r in cfs.live_sstables():
        r.close()
