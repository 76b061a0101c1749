"""SSTable write/read round-trips (reference test model:
io/sstable/SSTableReaderTest, CompressedSequentialWriterTest)."""
import os
import random

import numpy as np
import pytest

from cassandra_tpu.ops.codec import CompressionParams
from cassandra_tpu.schema import COL_REGULAR_BASE, TableParams, make_table
from cassandra_tpu.storage import cellbatch as cb
from cassandra_tpu.storage.sstable import (Component, Descriptor,
                                           SSTableReader, SSTableWriter)


def make_t(compressor="LZ4Compressor"):
    return make_table("ks", "t", pk=["id"], ck=["c"],
                      cols={"id": "int", "c": "int", "v": "text"},
                      params=TableParams(
                          compression=CompressionParams(compressor)))


def sorted_batch(table, n_parts=50, n_cks=20, seed=3):
    rng = random.Random(seed)
    b = cb.CellBatchBuilder(table)
    idt = table.columns["id"].cql_type
    for p in range(n_parts):
        for c in range(n_cks):
            b.add_cell(idt.serialize(p), table.serialize_clustering([c]),
                       COL_REGULAR_BASE,
                       f"value-{p}-{c}-{rng.random()}".encode(), 1000 + c)
    return cb.merge_sorted([b.seal()])


@pytest.mark.parametrize("compressor", ["LZ4Compressor", "SnappyCompressor",
                                        "ZstdCompressor", "DeflateCompressor",
                                        "NoopCompressor"])
def test_roundtrip(tmp_path, compressor):
    t = make_t(compressor)
    batch = sorted_batch(t)
    desc = Descriptor(str(tmp_path), 1)
    w = SSTableWriter(desc, t, segment_cells=256)  # force many segments
    w.append(batch)
    stats = w.finish()
    assert stats["n_cells"] == len(batch)
    assert stats["n_partitions"] == 50

    r = SSTableReader(desc)
    assert r.n_cells == len(batch)
    assert r.verify_digest()
    # full scan == original batch
    got = cb.CellBatch.concat(list(r.scanner()))
    np.testing.assert_array_equal(got.lanes, batch.lanes)
    np.testing.assert_array_equal(got.ts, batch.ts)
    np.testing.assert_array_equal(got.payload, batch.payload)
    r.close()


def test_point_reads(tmp_path):
    t = make_t()
    batch = sorted_batch(t, n_parts=100, n_cks=10)
    desc = Descriptor(str(tmp_path), 1)
    w = SSTableWriter(desc, t, segment_cells=128)
    w.append(batch)
    w.finish()
    r = SSTableReader(desc)
    idt = t.columns["id"].cql_type
    for p in (0, 7, 50, 99):
        part = r.read_partition(idt.serialize(p))
        assert part is not None and len(part) == 10
        for i in range(len(part)):
            assert part.partition_key(i) == idt.serialize(p)
            assert part.cell_value(i).startswith(f"value-{p}-".encode())
    assert r.read_partition(idt.serialize(100000)) is None
    r.close()


def test_partition_spanning_segments(tmp_path):
    t = make_t()
    # one huge partition crossing many segments
    b = cb.CellBatchBuilder(t)
    idt = t.columns["id"].cql_type
    for c in range(1000):
        b.add_cell(idt.serialize(1), t.serialize_clustering([c]),
                   COL_REGULAR_BASE, f"v{c}".encode(), 1)
    batch = cb.merge_sorted([b.seal()])
    desc = Descriptor(str(tmp_path), 1)
    w = SSTableWriter(desc, t, segment_cells=64)
    w.append(batch)
    stats = w.finish()
    assert stats["n_partitions"] == 1
    r = SSTableReader(desc)
    part = r.read_partition(idt.serialize(1))
    assert len(part) == 1000
    vals = {part.cell_value(i) for i in range(1000)}
    assert vals == {f"v{c}".encode() for c in range(1000)}
    r.close()


def test_multiple_appends_and_order_guard(tmp_path):
    t = make_t()
    batch = sorted_batch(t, n_parts=20, n_cks=5)
    half = len(batch) // 2
    first = batch.apply_permutation(np.arange(half))
    first.pk_map = batch.pk_map
    second = batch.apply_permutation(np.arange(half, len(batch)))
    second.pk_map = batch.pk_map
    desc = Descriptor(str(tmp_path), 1)
    w = SSTableWriter(desc, t, segment_cells=32)
    w.append(first)
    w.append(second)
    w.finish()
    r = SSTableReader(desc)
    got = cb.CellBatch.concat(list(r.scanner()))
    np.testing.assert_array_equal(got.lanes, batch.lanes)
    r.close()
    # out-of-order append must raise
    desc2 = Descriptor(str(tmp_path), 2)
    w2 = SSTableWriter(desc2, t, segment_cells=32)
    w2.append(second)
    with pytest.raises(ValueError):
        w2.append(first)
        w2.finish()
    w2.abort()


def test_corruption_detected(tmp_path):
    t = make_t()
    desc = Descriptor(str(tmp_path), 1)
    w = SSTableWriter(desc, t, segment_cells=256)
    w.append(sorted_batch(t))
    w.finish()
    # flip a byte in Data.db
    p = desc.path(Component.DATA)
    data = bytearray(open(p, "rb").read())
    data[len(data) // 2] ^= 0xFF
    open(p, "wb").write(bytes(data))
    r = SSTableReader(desc)
    assert not r.verify_digest()
    from cassandra_tpu.storage.sstable.reader import CorruptSSTableError
    with pytest.raises((CorruptSSTableError, ValueError)):
        list(r.scanner())
    r.close()


def test_discovery_and_generations(tmp_path):
    t = make_t()
    assert Descriptor.next_generation(str(tmp_path)) == 1
    for gen in (1, 2):
        w = SSTableWriter(Descriptor(str(tmp_path), gen), t)
        w.append(sorted_batch(t, n_parts=5, n_cks=2, seed=gen))
        w.finish()
    descs = Descriptor.list_in(str(tmp_path))
    assert [d.generation for d in descs] == [1, 2]
    assert Descriptor.next_generation(str(tmp_path)) == 3
    # aborted writer leaves no trace
    w = SSTableWriter(Descriptor(str(tmp_path), 3), t)
    w.append(sorted_batch(t, n_parts=3, n_cks=2))
    w.abort()
    assert [d.generation for d in Descriptor.list_in(str(tmp_path))] == [1, 2]


def test_tombstones_and_stats(tmp_path):
    t = make_t()
    b = cb.CellBatchBuilder(t)
    idt = t.columns["id"].cql_type
    b.add_cell(idt.serialize(1), t.serialize_clustering([1]),
               COL_REGULAR_BASE, b"x", 100)
    b.add_tombstone(idt.serialize(1), t.serialize_clustering([2]),
                    COL_REGULAR_BASE, 200, 5000)
    batch = cb.merge_sorted([b.seal()])
    desc = Descriptor(str(tmp_path), 1)
    w = SSTableWriter(desc, t)
    w.append(batch)
    stats = w.finish()
    assert stats["tombstones"] == 1
    assert stats["min_ts"] == 100 and stats["max_ts"] == 200
    r = SSTableReader(desc)
    part = r.read_partition(idt.serialize(1))
    assert len(part) == 2
    assert bool(part.flags[1] & cb.FLAG_TOMBSTONE)
    r.close()


# ------------------------------------------- partition directory + bloom --
# Partitions.db and Filter.db held byte for byte against a plain
# per-partition reference that knows nothing of the writer. The benchmark
# cells cannot catch a directory fault: both engines they compare write
# through the same SSTableWriter._index_segment.

def make_t2():
    """Composite, variable-length partition key."""
    return make_table("ks", "t2", pk=["a", "b"], ck=["c"],
                      cols={"a": "text", "b": "int", "c": "int", "v": "text"})


def batch_of(table, parts):
    """parts: [(pk bytes, cells)] -> one sorted batch, `cells` rows each."""
    b = cb.CellBatchBuilder(table)
    for pk, cells in parts:
        for c in range(cells):
            b.add_cell(pk, table.serialize_clustering([c]),
                       COL_REGULAR_BASE, b"v%d" % c, 1000 + c)
    return cb.merge_sorted([b.seal()])


def int_keys(table, n):
    idt = table.columns["id"].cql_type
    return [idt.serialize(p) for p in range(n)]


def in_token_order(table, pks):
    """The keys as the store orders them (token, then key hash)."""
    probe = batch_of(table, [(pk, 1) for pk in pks])
    return [probe.partition_key(i) for i in range(len(probe))]


def _one_row(table):
    return [(pk, 1) for pk in int_keys(table, 300)], 64


def _edge_and_span3(table):
    # in token order, 8 cells a segment: the 2nd partition ends exactly at
    # the edge (the 3rd STARTS a segment), the 4th runs over cells 12..31
    # = all of three segments' worth, the 7th starts a segment again
    sizes = [3, 5, 4, 20, 1, 7, 8, 2, 9, 5]
    return list(zip(in_token_order(table, int_keys(table, len(sizes))),
                    sizes)), 8


def _single_partition(table):
    return [(int_keys(table, 1)[0], 50)], 16


def _variable_composite(table):
    rng = random.Random(11)
    pks = [table.serialize_partition_key(
        ["k" * rng.choice((0, 1, 7, 9, 15, 16, 17, 31, 40)) + str(i), i])
        for i in range(120)]
    return [(pk, rng.choice((1, 2, 5))) for pk in pks], 32


def _hundred(table):
    return [(pk, 1) for pk in int_keys(table, 100)], 1 << 16


def _ten_thousand(table):
    return [(pk, 1) for pk in int_keys(table, 10_000)], 1 << 16


DIRECTORY_CASES = {
    "one_row_partitions": (make_t, _one_row),
    "edge_and_span3": (make_t, _edge_and_span3),
    "single_partition": (make_t, _single_partition),
    "variable_composite_keys": (make_t2, _variable_composite),
    "partitions_100": (make_t, _hundred),
    "partitions_10000": (make_t, _ten_thousand),
}


def reference_directory(batch, estimated_partitions):
    """(Partitions.db, Filter.db, [(pk, cells)]) the slow way: one Python
    iteration per cell, the scalar murmur3, Python-int bit sets."""
    import struct

    from cassandra_tpu.utils import bloom, murmur3
    keys, first, pks, sizes = [], [], [], []
    prev = None
    for i in range(len(batch)):
        l4 = b"".join(int(x).to_bytes(4, "big") for x in batch.lanes[i, :4])
        if l4 != prev:
            keys.append(l4)
            first.append(i)
            pks.append(batch.pk_map[l4])
            sizes.append(0)
            prev = l4
        sizes[-1] += 1
    part = struct.pack("<I", len(keys)) + b"".join(keys)
    part += b"".join(struct.pack("<q", c) for c in first)
    off = 0
    part += struct.pack("<q", 0)
    for pk in pks:
        off += len(pk)
        part += struct.pack("<q", off)
    part += b"".join(pks)
    bits, k = bloom.optimal_params(max(estimated_partitions, 16), 0.01)
    field = 0
    for pk in pks:
        h1, h2 = murmur3.hash128(pk)
        for j in range(k):
            field |= 1 << (((h1 + j * h2) & (2**64 - 1)) % bits)
    filt = struct.pack("<QII", bits, k, 0) + field.to_bytes(bits // 8,
                                                             "little")
    return part, filt, list(zip(pks, sizes))


def write_by_append(w, batch, segment_cells):
    # three appends cut at odd places, so _take splits and joins batches
    cuts = [0, len(batch) // 3 + 1, 2 * len(batch) // 3 + 2, len(batch)]
    for lo, hi in zip(cuts, cuts[1:]):
        if hi > lo:
            w.append(batch.slice_range(lo, hi))


def write_by_emit_segment(w, batch, segment_cells):
    """As ops/device_write.DeviceWriteLane hands segments over: finished
    blocks, the lane's whole merged pk_map, no append()."""
    from cassandra_tpu.storage.sstable.writer import build_meta_block
    w.K = batch.lanes.shape[1]
    for lo in range(0, len(batch), segment_cells):
        seg = batch.slice_range(lo, min(lo + segment_cells, len(batch)))
        n = len(seg)
        meta = build_meta_block(
            seg.ts.astype(np.int64), seg.ldt, seg.ttl, seg.flags,
            (seg.off[1:] - seg.off[:-1]).astype("<u4"),
            (seg.val_start - seg.off[:-1]).astype("<u4"))
        stats = (int(seg.ts.min()), int(seg.ts.max()), int(seg.ldt.min()),
                 int(seg.ldt.max()),
                 int(((seg.flags & cb.DEATH_FLAGS) != 0).sum()))
        w._emit_segment(n, meta, np.ascontiguousarray(seg.lanes),
                        np.ascontiguousarray(seg.payload),
                        dict(batch.pk_map), stats)


WRITE_PATHS = {"append": write_by_append,
               "emit_segment": write_by_emit_segment}


@pytest.mark.parametrize("path", list(WRITE_PATHS))
@pytest.mark.parametrize("case", list(DIRECTORY_CASES))
def test_directory_and_filter_bytes(tmp_path, case, path):
    make, build = DIRECTORY_CASES[case]
    table = make()
    parts, segment_cells = build(table)
    batch = batch_of(table, parts)
    est = len(parts)
    want_part, want_filter, want_rows = reference_directory(batch, est)
    assert sorted(want_rows) == sorted(parts)
    if case == "edge_and_span3":
        starts = np.cumsum([0] + [c for _, c in want_rows])
        assert starts[2] == segment_cells              # cut at the edge
        assert starts[4] - starts[3] >= 2 * segment_cells + 2   # 3 segments

    desc = Descriptor(str(tmp_path), 1)
    w = SSTableWriter(desc, table, estimated_partitions=est,
                      segment_cells=segment_cells)
    WRITE_PATHS[path](w, batch, segment_cells)
    stats = w.finish()
    assert stats["n_partitions"] == len(parts)
    assert stats["n_cells"] == len(batch)
    with open(desc.path(Component.PARTITIONS), "rb") as f:
        assert f.read() == want_part
    with open(desc.path(Component.FILTER), "rb") as f:
        assert f.read() == want_filter

    r = SSTableReader(desc)
    assert r.verify_digest()
    for pk, cells in parts:
        got = r.read_partition(pk)
        assert got is not None and len(got) == cells
        assert {got.partition_key(i) for i in range(cells)} == {pk}
    r.close()


def _writer_lines(fn):
    """`line` events of frames of sstable/writer.py while fn() runs, on
    this thread (the appender: the one the directory runs on)."""
    import sys
    from cassandra_tpu.storage.sstable import writer as wmod
    count = [0]

    def tracer(frame, event, arg):
        if frame.f_code.co_filename != wmod.__file__:
            return None
        if event == "line":
            count[0] += 1
        return tracer
    old = sys.gettrace()
    sys.settrace(tracer)
    try:
        fn()
    finally:
        sys.settrace(old)
    return count[0]


@pytest.mark.parametrize("path", list(WRITE_PATHS))
def test_directory_runs_no_python_per_partition(tmp_path, path):
    """The mechanism itself: one segment of 10,000 partitions executes
    exactly as many lines of writer.py as one of 100."""
    table = make_t()
    lines = {}
    for gen, n in enumerate((100, 10_000), start=1):
        batch = batch_of(table, [(pk, 1) for pk in int_keys(table, n)])
        w = SSTableWriter(Descriptor(str(tmp_path), gen), table,
                          estimated_partitions=n, segment_cells=1 << 16)

        def run():
            WRITE_PATHS[path](w, batch, 1 << 16)
            w.finish()
        lines[n] = _writer_lines(run)
    assert lines[100] > 50          # the tracer saw the writer at all
    assert lines[10_000] == lines[100]


@pytest.mark.parametrize("fault", ["pk_map_missing_key", "order_across_segments",
                                   "order_inside_a_segment"])
def test_writer_error_paths_keep_type_and_words(tmp_path, fault):
    table = make_t()
    batch = batch_of(table, [(pk, 4) for pk in int_keys(table, 30)])
    w = SSTableWriter(Descriptor(str(tmp_path), 1), table, segment_cells=16)
    if fault == "pk_map_missing_key":
        gone = dict(batch.pk_map)
        del gone[sorted(gone)[len(gone) // 2]]
        bad, words = batch.slice_range(0, len(batch)), "pk_map missing partition key"
        bad.pk_map = gone
    else:
        perm = np.arange(len(batch))
        if fault == "order_across_segments":
            perm[:32] = np.roll(perm[:32], 16)      # segments 0 and 1 swapped
        else:
            perm[[17, 22]] = perm[[22, 17]]         # inside segment 1
        bad, words = batch.apply_permutation(perm), "appended cells out of order"
        bad.pk_map = batch.pk_map
    with pytest.raises(ValueError, match=words):
        w.append(bad)
        w.finish()
    w.abort()


def test_directory_of_an_empty_sstable(tmp_path):
    """No segment, no chunk: count 0 and the one pk offset."""
    desc = Descriptor(str(tmp_path), 1)
    stats = SSTableWriter(desc, make_t()).finish()
    assert stats["n_partitions"] == 0
    with open(desc.path(Component.PARTITIONS), "rb") as f:
        assert f.read() == bytes(4) + bytes(8)
