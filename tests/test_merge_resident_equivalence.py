"""The one device merge program (ops/device_write.py `merge.resident`)
must be bit-identical to the numpy spec on the rounds that once had a
layout of their own: sorted live runs whose timestamps cluster or collide
exactly (the host resolves equal-(identity, ts) runs with full values),
wide timestamps, TTL expiry under a purge guard, and unsorted or
tombstone rounds."""
import random

import numpy as np
import pytest

from cassandra_tpu.ops.device_write import merge_sorted_device
from cassandra_tpu.schema import COL_REGULAR_BASE, make_table
from cassandra_tpu.storage import cellbatch as cb

T = make_table("ks", "t", pk=["id"], ck=["c"],
               cols={"id": "int", "c": "int", "v": "text", "w": "text"})
IDT = T.columns["id"].cql_type


def pk(i):
    return IDT.serialize(i)


def ck(i):
    return T.serialize_clustering([i])


def assert_equal_batches(a, b):
    assert len(a) == len(b)
    np.testing.assert_array_equal(a.lanes, b.lanes)
    np.testing.assert_array_equal(a.ts, b.ts)
    np.testing.assert_array_equal(a.ldt, b.ldt)
    np.testing.assert_array_equal(a.flags, b.flags)
    np.testing.assert_array_equal(a.payload, b.payload)
    np.testing.assert_array_equal(a.off, b.off)


def sorted_live_batches(seed, n_batches=4, n_cells=400, n_parts=16,
                        n_cks=8, collide=True, ttl_frac=0.0):
    """Batches of live (optionally expiring) cells, individually sorted
    and deduped (each run goes through the spec merge, as sstable-backed
    runs are). With collide=True timestamps cluster so many distinct ts
    fall in the same ts>>24 bucket AND some are exactly equal."""
    rng = random.Random(seed)
    out = []
    base = 1 << 30
    for _ in range(n_batches):
        b = cb.CellBatchBuilder(T)
        for _ in range(n_cells):
            p = pk(rng.randrange(n_parts))
            c = ck(rng.randrange(n_cks))
            col = COL_REGULAR_BASE + rng.randrange(2)
            if collide:
                # low 24 bits only (always same bucket) or exact dup ts
                ts = base + rng.choice(
                    [rng.randrange(1 << 24), rng.randrange(4)])
            else:
                ts = rng.randrange(1, 1 << 40)
            val = rng.choice([b"a", b"zz", b"abcd1", b"abcd2", b"x" * 9])
            if rng.random() < ttl_frac:
                b.add_cell(p, c, col, val, ts, ttl=rng.randrange(1, 30),
                           now=rng.randrange(0, 40))
            else:
                b.add_cell(p, c, col, val, ts)
        out.append(cb.merge_sorted([b.seal()]))
    return out


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_collision_equivalence(seed):
    batches = sorted_live_batches(seed)
    ref = cb.merge_sorted(batches)
    dev = merge_sorted_device(batches)
    assert_equal_batches(ref, dev)


@pytest.mark.parametrize("seed", [5, 6])
def test_wide_ts_equivalence(seed):
    batches = sorted_live_batches(seed, collide=False)
    ref = cb.merge_sorted(batches)
    dev = merge_sorted_device(batches)
    assert_equal_batches(ref, dev)


@pytest.mark.parametrize("seed", [7, 8])
def test_ttl_expiry_and_purge(seed):
    batches = sorted_live_batches(seed, ttl_frac=0.3)
    ref = cb.merge_sorted(batches, gc_before=35, now=30)
    dev = merge_sorted_device(batches, gc_before=35, now=30)
    assert_equal_batches(ref, dev)
    guard = lambda s: (s.ts % 7) * (1 << 28)
    ref = cb.merge_sorted(batches, gc_before=35, now=30,
                          purgeable_ts_fn=guard)
    dev = merge_sorted_device(batches, gc_before=35, now=30,
                                     purgeable_ts_fn=guard)
    assert_equal_batches(ref, dev)


def test_equal_ts_value_tiebreak():
    """Equal (identity, ts): larger value wins, beyond the 4-byte prefix."""
    outs = []
    for vals in ((b"abcdA", b"abcdZ"), (b"abcdZ", b"abcdA")):
        batches = []
        for v in vals:
            b = cb.CellBatchBuilder(T)
            b.add_cell(pk(1), ck(1), COL_REGULAR_BASE, v, 100)
            batches.append(cb.merge_sorted([b.seal()]))
        ref = cb.merge_sorted(batches)
        dev = merge_sorted_device(batches)
        assert_equal_batches(ref, dev)
        outs.append(dev.cell_value(0))
    assert outs == [b"abcdZ", b"abcdZ"]


def test_unsorted_or_deleting_rounds():
    b = cb.CellBatchBuilder(T)
    b.add_cell(pk(2), ck(1), COL_REGULAR_BASE, b"v", 5)
    b.add_cell(pk(1), ck(1), COL_REGULAR_BASE, b"v", 5)
    unsorted = b.seal()
    b2 = cb.CellBatchBuilder(T)
    b2.add_tombstone(pk(1), ck(1), COL_REGULAR_BASE, 10, 100)
    tomb = cb.merge_sorted([b2.seal()])
    for batches in ([unsorted], [tomb]):
        assert_equal_batches(cb.merge_sorted(batches),
                             merge_sorted_device(batches))


def test_pipelined_task_matches_numpy(tmp_path):
    """CompactionTask engine=device (pipelined submit/collect) produces the
    same output sstable content as engine=numpy."""
    from cassandra_tpu.compaction.task import CompactionTask
    from cassandra_tpu.storage.sstable import Descriptor, SSTableWriter
    from cassandra_tpu.storage.table import ColumnFamilyStore

    rng = random.Random(99)
    results = {}
    for engine in ("numpy", "device"):
        base = tmp_path / engine
        base.mkdir()
        cfs = ColumnFamilyStore(T, str(base), commitlog=None)
        d = cfs.directory
        rng = random.Random(99)
        for gen in range(1, 4):
            b = cb.CellBatchBuilder(T)
            for _ in range(600):
                b.add_cell(pk(rng.randrange(40)), ck(rng.randrange(6)),
                           COL_REGULAR_BASE + rng.randrange(2),
                           bytes([65 + rng.randrange(26)]) * rng.randrange(1, 9),
                           (1 << 30) + rng.randrange(1 << 24))
            w = SSTableWriter(Descriptor(str(d), gen), T,
                              estimated_partitions=64)
            w.append(cb.merge_sorted([b.seal()]))
            w.finish()
        cfs.reload_sstables()
        task = CompactionTask(cfs, cfs.tracker.view(), engine=engine,
                              round_cells=1500)
        task.execute()
        [out] = cfs.live_sstables()
        scan = cb.CellBatch.concat(list(out.scanner()))
        results[engine] = scan
        cfs.close() if hasattr(cfs, "close") else None
    a, b = results["numpy"], results["device"]
    np.testing.assert_array_equal(a.lanes, b.lanes)
    np.testing.assert_array_equal(a.ts, b.ts)
    np.testing.assert_array_equal(a.payload, b.payload)
