"""Device (JAX) merge kernel must produce results identical to the numpy
reference reconcile (storage/cellbatch.py) — same kept cells, same order,
same payloads. Runs on the 8-device virtual CPU mesh (conftest)."""
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


def random_batches(seed, n_batches=4, n_cells=300, n_parts=12, n_cks=6):
    rng = random.Random(seed)
    out = []
    for _ in range(n_batches):
        b = cb.CellBatchBuilder(T)
        for _ in range(n_cells):
            p = pk(rng.randrange(n_parts))
            c = ck(rng.randrange(n_cks))
            col = COL_REGULAR_BASE + rng.randrange(2)
            ts = rng.randrange(1, 50)
            kind = rng.random()
            if kind < 0.55:
                val = rng.choice([b"a", b"zz", b"abcd1", b"abcd2", b"x" * 10])
                if rng.random() < 0.2:  # expiring
                    b.add_cell(p, c, col, val, ts, ttl=rng.randrange(1, 30),
                               now=rng.randrange(0, 40))
                else:
                    b.add_cell(p, c, col, val, ts)
            elif kind < 0.75:
                b.add_tombstone(p, c, col, ts, rng.randrange(0, 100))
            elif kind < 0.85:
                b.add_row_liveness(p, c, ts)
            elif kind < 0.95:
                b.add_row_deletion(p, c, ts, rng.randrange(0, 100))
            else:
                b.add_partition_deletion(p, ts, rng.randrange(0, 100))
        out.append(b.seal())
    return out


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_random_equivalence(seed):
    batches = random_batches(seed)
    ref = cb.merge_sorted(batches)
    dev = merge_sorted_device(batches)
    assert_equal_batches(ref, dev)


@pytest.mark.parametrize("seed", [7, 8])
def test_random_equivalence_with_gc(seed):
    batches = random_batches(seed)
    ref = cb.merge_sorted(batches, gc_before=50, now=60)
    dev = merge_sorted_device(batches, gc_before=50, now=60)
    assert_equal_batches(ref, dev)


def test_equivalence_with_purge_guard(seed=11):
    batches = random_batches(seed)
    guard = lambda s: (s.ts % 7) * 5  # arbitrary per-cell guard
    ref = cb.merge_sorted(batches, gc_before=80, now=60, purgeable_ts_fn=guard)
    dev = merge_sorted_device(batches, gc_before=80, now=60,
                                     purgeable_ts_fn=guard)
    assert_equal_batches(ref, dev)


def test_directed_cases_on_device():
    b = cb.CellBatchBuilder(T)
    V = COL_REGULAR_BASE
    b.add_cell(pk(1), ck(1), V, b"old", 100)
    b.add_cell(pk(1), ck(1), V, b"new", 200)
    b.add_tombstone(pk(1), ck(2), V, 100, 1000)
    b.add_cell(pk(1), ck(2), V, b"dead", 100)      # equal ts: tombstone wins
    b.add_cell(pk(2), ck(1), V, b"abcdA", 100)
    b.add_cell(pk(2), ck(1), V, b"abcdZ", 100)     # tie beyond prefix
    b.add_partition_deletion(pk(3), 500, 1000)
    b.add_cell(pk(3), ck(1), V, b"shadowed", 400)
    batch = b.seal()
    ref = cb.merge_sorted([batch])
    dev = merge_sorted_device([batch])
    assert_equal_batches(ref, dev)
    # sanity on content
    vals = {dev.cell_value(i) for i in range(len(dev))}
    assert b"new" in vals and b"abcdZ" in vals
    assert b"old" not in vals and b"abcdA" not in vals and b"shadowed" not in vals


def test_empty_and_single():
    assert len(merge_sorted_device([cb.CellBatchBuilder(T).seal()])) == 0
    b = cb.CellBatchBuilder(T)
    b.add_cell(pk(1), ck(1), COL_REGULAR_BASE, b"v", 1)
    ref = cb.merge_sorted([b.seal()])
    dev = merge_sorted_device([b.seal()])
    assert_equal_batches(ref, dev)


def test_counter_sum_both_paths():
    Tc = make_table("ks", "cnt", pk=["id"], cols={"id": "int",
                                                  "hits": "counter"})
    cid = Tc.columns["hits"].column_id
    idt = Tc.columns["id"].cql_type
    batches = []
    for gen, deltas in enumerate([(3, 4), (5,), (-2,)]):
        b = cb.CellBatchBuilder(Tc)
        for j, d in enumerate(deltas):
            b.append_raw(idt.serialize(1), b"", cid, b"",
                         (d & 0xFFFFFFFFFFFFFFFF).to_bytes(8, "big"),
                         ts=100 * gen + j, flags=cb.FLAG_COUNTER)
        batches.append(b.seal())
    ref = cb.merge_sorted(batches)
    dev = merge_sorted_device(batches)
    assert len(ref) == 1 and len(dev) == 1
    for m in (ref, dev):
        v = int.from_bytes(m.cell_value(0), "big", signed=True)
        assert v == 10, v
    # replica duplicates (same deltas, same timestamps) must count once
    dup = cb.merge_sorted([batches[0], batches[0]])
    assert int.from_bytes(dup.cell_value(0), "big", signed=True) == 7
    # merging the compacted result with NEW deltas must add up
    b = cb.CellBatchBuilder(Tc)
    b.append_raw(idt.serialize(1), b"", cid, b"",
                 (7).to_bytes(8, "big"), ts=1000, flags=cb.FLAG_COUNTER)
    m3 = cb.merge_sorted([ref, b.seal()])
    assert int.from_bytes(m3.cell_value(0), "big", signed=True) == 17
