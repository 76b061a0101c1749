"""Write-path fast lane: group-commit commitlog, sharded memtable,
pipelined flush (docs/write-path.md; CTPU_WRITE_FASTPATH A/B).

Covers the ISSUE-4 satellite matrix: commitlog replay edge cases (torn
final record, compressed records around a segment rotation, group-commit
durability under simulated crash), sync-failure accounting (the loop
must survive and count, not die silently), sharded-memtable identity
(concurrent apply == serial apply bit-for-bit; reads across shard
boundaries), batched apply identity, pipelined-flush identity, and the
full A/B harness (scripts/check_writepath_ab.py)."""
import os
import shutil
import struct
import threading
import uuid

import numpy as np
import pytest

from cassandra_tpu.schema import Schema, make_table
from cassandra_tpu.storage import commitlog as cl_mod
from cassandra_tpu.storage.cellbatch import content_digest
from cassandra_tpu.storage.commitlog import CommitLog
from cassandra_tpu.storage.memtable import Memtable
from cassandra_tpu.storage.mutation import Mutation


@pytest.fixture(autouse=True)
def _fastpath_env():
    prev = os.environ.get("CTPU_WRITE_FASTPATH")
    yield
    if prev is None:
        os.environ.pop("CTPU_WRITE_FASTPATH", None)
    else:
        os.environ["CTPU_WRITE_FASTPATH"] = prev


TID = uuid.UUID("00000000-0000-0000-0000-00000000a51e")


def _mut(i: int, payload: bytes = b"v") -> Mutation:
    m = Mutation(TID, b"pk-%05d" % i)
    m.add(b"", 8, b"", payload, 1_000 + i)
    return m


def _table():
    return make_table("ks", "t", pk=["id"], ck=["c"],
                      cols={"id": "int", "c": "int", "v": "blob"})


# ------------------------------------------------------------ commitlog --


def test_group_commit_durability_survives_crash(tmp_path):
    """A mutation acked under sync_mode='group' must be on disk the
    moment add() returns: a directory copy taken right after the acks
    (what a crash leaves) replays every acked record."""
    os.environ["CTPU_WRITE_FASTPATH"] = "1"
    d = str(tmp_path / "cl")
    cl = CommitLog(d, sync_mode="group", group_window_ms=2.0)
    n = 24
    ts = [threading.Thread(target=cl.add, args=(_mut(i),))
          for i in range(n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    crash = str(tmp_path / "crash")
    shutil.copytree(d, crash)     # simulated crash: no close()
    cl.close()
    replayed = CommitLog(crash, sync_mode="periodic")
    got = sorted(m.pk for _pos, m in replayed.replay())
    replayed.close()
    assert got == sorted(b"pk-%05d" % i for i in range(n))


def test_batch_leader_coalesces_fsyncs(tmp_path):
    """Concurrent writers under sync_mode='batch' + fast lane must pay
    FEWER fsyncs than mutations (the group-commit win itself)."""
    os.environ["CTPU_WRITE_FASTPATH"] = "1"
    cl = CommitLog(str(tmp_path / "cl"), sync_mode="batch")
    before = cl._sync_hist.count
    n = 64
    ts = [threading.Thread(
        target=lambda k: [cl.add(_mut(k * 8 + j)) for j in range(8)],
        args=(i,)) for i in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    syncs = cl._sync_hist.count - before
    cl.close()
    assert syncs < n, f"no coalescing: {syncs} fsyncs for {n} mutations"
    assert sum(1 for _ in cl.replay()) == n


def test_torn_final_record_stops_replay(tmp_path):
    """A torn tail (crash mid-append) terminates replay of that segment
    without losing the intact prefix."""
    cl = CommitLog(str(tmp_path / "cl"), sync_mode="batch")
    for i in range(5):
        cl.add(_mut(i))
    cl.close()
    seg = cl._seg_path(cl.segment_ids()[-1])
    with open(seg, "ab") as f:
        # frame header promising 1000 bytes, then a short payload
        f.write(struct.pack("<II", 1000, 0xDEADBEEF) + b"short")
    got = list(CommitLog(str(tmp_path / "cl"),
                         sync_mode="periodic").replay())
    assert len(got) == 5
    assert [m.pk for _p, m in got] == [b"pk-%05d" % i for i in range(5)]


def test_corrupt_crc_tail_stops_replay(tmp_path):
    cl = CommitLog(str(tmp_path / "cl"), sync_mode="batch")
    for i in range(4):
        cl.add(_mut(i))
    cl.close()
    seg = cl._seg_path(cl.segment_ids()[-1])
    payload = b"x" * 10
    with open(seg, "ab") as f:
        f.write(struct.pack("<II", len(payload), 0x12345678) + payload)
    got = list(CommitLog(str(tmp_path / "cl"),
                         sync_mode="periodic").replay())
    assert len(got) == 4


def test_compressed_records_across_segment_rotation(tmp_path):
    """Compressed frames written right up against (and across) segment
    rotations replay bit-identically — rotation is now asynchronous
    (the retiring segment syncs off the write path), and the tail of
    segment k must be intact when k+1 opens."""
    os.environ["CTPU_WRITE_FASTPATH"] = "1"
    d = str(tmp_path / "cl")
    payload = b"abcdefgh" * 64            # compressible
    cl = CommitLog(d, sync_mode="batch", segment_size=2048,
                   compression="LZ4Compressor")
    n = 120
    for i in range(n):
        cl.add(_mut(i, payload))
    assert len(cl.segment_ids()) > 2      # really rotated
    cl.close()
    got = list(CommitLog(d, sync_mode="periodic",
                         compression="LZ4Compressor").replay())
    assert [m.pk for _p, m in got] == [b"pk-%05d" % i for i in range(n)]
    assert all(m.ops[0][3] == payload for _p, m in got)


def test_compressed_encrypted_rotation_replay(tmp_path):
    """Compress-then-encrypt segments across rotations (the reference's
    EncryptedSegment composition)."""
    pytest.importorskip("cryptography")
    from cassandra_tpu.storage import encryption as enc_mod
    os.environ["CTPU_WRITE_FASTPATH"] = "1"
    prev_ctx = enc_mod.get_context()
    enc_mod.set_context(enc_mod.EncryptionContext(str(tmp_path / "keys")))
    try:
        d = str(tmp_path / "cl")
        payload = b"secret--" * 64            # compressible: sized like
        cl = CommitLog(d, sync_mode="batch", segment_size=2048,
                       compression="LZ4Compressor", encrypt=True)
        n = 120                               # the test above, so the
        for i in range(n):                    # frames really rotate
            cl.add(_mut(i, payload))
        assert len(cl.segment_ids()) > 2
        cl.close()
        got = list(CommitLog(d, sync_mode="periodic",
                             compression="LZ4Compressor",
                             encrypt=True).replay())
        assert [m.pk for _p, m in got] == [b"pk-%05d" % i
                                           for i in range(n)]
        assert all(m.ops[0][3] == payload for _p, m in got)
    finally:
        enc_mod.set_context(prev_ctx)


def test_sync_failure_counted_not_silent(tmp_path, monkeypatch):
    """Satellite fix: a failing fsync increments commitlog.sync_failures
    and the syncer loop SURVIVES — before, it swallowed the error and
    exited, silently disabling periodic sync forever."""
    cl = CommitLog(str(tmp_path / "cl"), sync_mode="periodic",
                   sync_period_ms=20)
    cl.add(_mut(0))
    real_fsync = os.fsync
    fails = {"n": 0}

    def flaky(fd):
        if fails["n"] < 2:
            fails["n"] += 1
            raise OSError(5, "injected EIO")
        return real_fsync(fd)

    monkeypatch.setattr(cl_mod.os, "fsync", flaky)
    import time
    deadline = time.time() + 5
    while cl._sync_failures < 2 and time.time() < deadline:
        time.sleep(0.02)
    assert cl._sync_failures >= 2
    assert cl._syncer.is_alive()          # the loop did NOT die
    # next sync succeeds and clears the error
    deadline = time.time() + 5
    while cl._sync_error is not None and time.time() < deadline:
        time.sleep(0.02)
    assert cl._sync_error is None
    monkeypatch.setattr(cl_mod.os, "fsync", real_fsync)
    cl.close()
    assert cl.stats()["sync_failures"] >= 2


def test_retired_segment_requeued_on_sync_failure(tmp_path, monkeypatch):
    """A retired (rotated) segment whose fsync fails must go BACK on the
    retiring queue: the next successful cycle advancing the synced
    watermark past its positions would otherwise ack writers whose
    bytes were never fsynced."""
    cl = CommitLog(str(tmp_path / "cl"), sync_mode="batch")
    cl.add(_mut(0))
    # hand-retire a real segment file (the rotation path's state)
    side = open(str(tmp_path / "cl" / "commitlog-99.log"), "ab")
    side.write(b"x")
    with cl._lock:
        cl._retiring.append((99, side))
    real_fsync = os.fsync
    state = {"fail": 1}

    def flaky(fd):
        if state["fail"] and fd == side.fileno():
            state["fail"] -= 1
            raise OSError(5, "injected EIO")
        return real_fsync(fd)

    monkeypatch.setattr(cl_mod.os, "fsync", flaky)
    with pytest.raises(OSError):
        cl.sync()
    with cl._lock:
        assert cl._retiring == [(99, side)]     # re-queued, not lost
    cl.sync()                                   # retries and completes
    with cl._lock:
        assert cl._retiring == []
    assert side.closed
    monkeypatch.setattr(cl_mod.os, "fsync", real_fsync)
    cl.close()


def test_commitlogstats_and_vtable(tmp_path):
    os.environ["CTPU_WRITE_FASTPATH"] = "1"
    from cassandra_tpu.storage.engine import StorageEngine
    from cassandra_tpu.tools import nodetool
    schema = Schema()
    schema.create_keyspace("ks")
    t = _table()
    schema.add_table(t)
    eng = StorageEngine(str(tmp_path / "d"), schema,
                        commitlog_sync="batch")
    vcol = t.columns["v"].column_id
    for i in range(8):
        m = Mutation(t.id, t.serialize_partition_key([i]))
        m.add(t.serialize_clustering([0]), vcol, b"", b"x", 100 + i)
        eng.apply(m)
    st = nodetool.commitlogstats(eng)
    assert st["enabled"] and st["segments"] >= 1
    assert st["sync_mode"] == "batch"
    assert st["oldest_dirty"] == 1
    assert st["waiting_on_commit_us"]["count"] > 0
    assert st["sync_latency_us"]["count"] > 0
    rows = eng.virtual_tables.get("system_views", "commitlog").rows()
    status = [r for r in rows if r["name"] == "<status>"]
    assert len(status) == 1 and status[0]["segments"] >= 1
    assert any(r["name"].startswith("commitlog-") for r in rows)
    eng.close()


# ------------------------------------------------------- sharded memtable --


def _fill_serial(t, muts):
    mem = Memtable(t, shards=1)
    for m in muts:
        mem.apply(m)
    return mem


def _mutations(t, n=400, seed=3):
    rng = np.random.default_rng(seed)
    vcol = t.columns["v"].column_id
    out = []
    for i in range(n):
        pk = t.serialize_partition_key([int(rng.integers(0, 37))])
        m = Mutation(t.id, pk)
        m.add(t.serialize_clustering([i]), vcol, b"",
              rng.integers(0, 256, 16, dtype=np.uint8).tobytes(),
              1_000_000 + i)
        out.append(m)
    return out


def test_concurrent_sharded_apply_bit_identical_to_serial():
    os.environ["CTPU_WRITE_FASTPATH"] = "1"
    t = _table()
    muts = _mutations(t)
    serial = _fill_serial(t, muts)
    sharded = Memtable(t, shards=8)
    ts = [threading.Thread(
        target=lambda sl: [sharded.apply(m) for m in sl],
        args=(muts[k::6],)) for k in range(6)]
    for th in ts:
        th.start()
    for th in ts:
        th.join()
    assert sharded.shard_count == 8
    assert len(sharded) == len(serial)
    assert sharded.ops == serial.ops
    assert sharded.live_bytes == serial.live_bytes
    assert content_digest(sharded.flush_batch()) == \
        content_digest(serial.flush_batch())


def test_apply_batch_identical_and_reads_cross_shards():
    t = _table()
    muts = _mutations(t, n=300, seed=11)
    one_by_one = Memtable(t, shards=8)
    for m in muts:
        one_by_one.apply(m)
    batched = Memtable(t, shards=8)
    for i in range(0, len(muts), 64):
        batched.apply_batch(muts[i:i + 64])
    assert content_digest(batched.flush_batch()) == \
        content_digest(one_by_one.flush_batch())
    # point reads / contains across every shard boundary
    serial = _fill_serial(t, muts)
    for k in range(37):
        pk = t.serialize_partition_key([k])
        a = batched.read_partition(pk)
        b = serial.read_partition(pk)
        assert batched.contains(pk) == serial.contains(pk)
        if a is None or b is None:
            assert a is None and b is None
        else:
            assert content_digest(a) == content_digest(b)
    # absent partition
    pk = t.serialize_partition_key([999])
    assert not batched.contains(pk)
    assert batched.read_partition(pk) is None


def test_shard_runs_concatenate_in_token_order():
    """flush_shards yields ascending-identity runs: the pipelined flush
    feeds them straight to the writer's ordering guard."""
    t = _table()
    mem = Memtable(t, shards=8)
    for m in _mutations(t, n=200, seed=7):
        mem.apply(m)
    runs = list(mem.flush_shards())
    assert sum(len(r) for r in runs) == len(mem)
    last = None
    for r in runs:
        first = r.lanes[0].astype(">u4").tobytes()
        if last is not None:
            assert first > last
        last = r.lanes[-1].astype(">u4").tobytes()


def test_flush_pipelined_identical_to_serial(tmp_path):
    from cassandra_tpu.storage.table import ColumnFamilyStore
    t = _table()
    muts = _mutations(t, n=500, seed=23)
    digs = {}
    for fp in ("0", "1"):
        os.environ["CTPU_WRITE_FASTPATH"] = fp
        cfs = ColumnFamilyStore(t, str(tmp_path / ("fp" + fp)),
                                commitlog=None)
        cfs.apply_batch(muts)
        reader = cfs.flush()
        assert reader is not None
        digs[fp] = content_digest(cfs.scan_all(now=0))
        segs = list(reader.scanner())
        assert sum(len(s) for s in segs) == reader.n_cells
        for s in cfs.live_sstables():
            s.close()
    assert digs["0"] == digs["1"]


def test_fastpath_off_single_shard():
    os.environ["CTPU_WRITE_FASTPATH"] = "0"
    t = _table()
    assert Memtable(t).shard_count == 1
    os.environ["CTPU_WRITE_FASTPATH"] = "1"
    assert Memtable(t).shard_count == 8


# ------------------------------------------------------------ A/B harness --


def test_writepath_ab_harness(tmp_path):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "check_writepath_ab",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts",
            "check_writepath_ab.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    diverged = mod.run_check(str(tmp_path))
    assert diverged == [], "\n".join(diverged)
