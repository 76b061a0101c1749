"""A point read costs what its partition costs: SSTableReader.read_partition
and read_partitions_batch return the partition's own key and nothing of the
segment's, decode no segment-wide key map, and share the chunk cache with
the scans that need one (reference test model: io/sstable/
SSTableReaderTest, cache/ChunkCacheTest)."""
import os
import sys
import threading
import time

import numpy as np
import pytest

from cassandra_tpu.compaction.task import CompactionTask
from cassandra_tpu.schema import COL_REGULAR_BASE, make_table
from cassandra_tpu.storage import cellbatch as cb
from cassandra_tpu.storage.chunk_cache import GLOBAL as chunk_cache
from cassandra_tpu.storage.key_cache import GLOBAL as key_cache
from cassandra_tpu.storage.sstable import (Descriptor, SSTableReader,
                                           SSTableWriter)
from cassandra_tpu.storage.table import ColumnFamilyStore
from cassandra_tpu.utils import partitioners

SEG = 64
ARRAYS = ("lanes", "ts", "ldt", "ttl", "flags", "off", "val_start",
          "payload")
# cells per partition -> partitions: one cell never straddles a segment
# edge, five and 23 do (64 is a multiple of neither), 150 spans three
# segments and 64 IS one segment
SHAPES = {"1_cell": (1, 300), "5_cells": (5, 120), "23_cells": (23, 40),
          "150_cells": (150, 7), "whole_segment": (SEG, 6)}


def _table(name="t", key="int"):
    return make_table("ks", name, pk=["id"], ck=["c"],
                      cols={"id": key, "c": "int", "v": "blob"})


def _batch(table, cells, parts, ts=1000, ids=None):
    b = cb.CellBatchBuilder(table)
    for p in ids if ids is not None else range(parts):
        pk = table.serialize_partition_key([p])
        for c in range(cells):
            b.add_cell(pk, table.serialize_clustering([c]), COL_REGULAR_BASE,
                       b"%r/%d/%d" % (p, c, ts), ts + c)
    return cb.merge_sorted([b.seal()])


def _write(directory, table, batch, gen=1):
    w = SSTableWriter(Descriptor(str(directory), gen), table,
                      segment_cells=SEG)
    w.append(batch)
    w.finish()
    return SSTableReader(Descriptor(str(directory), gen), table)


def _same_cells(got: cb.CellBatch, want: cb.CellBatch) -> None:
    for name in ARRAYS:
        a, b = getattr(got, name), getattr(want, name)
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), name


def _slice_of_scan(full: cb.CellBatch, key16: bytes) -> cb.CellBatch:
    mine = np.flatnonzero((full.lanes[:, :4] == np.frombuffer(
        key16, dtype=">u4")).all(axis=1))
    assert len(mine) and mine[-1] - mine[0] + 1 == len(mine)
    return full.slice_range(int(mine[0]), int(mine[-1]) + 1)


def _fresh_caches():
    chunk_cache.clear()
    key_cache.clear()


@pytest.fixture
def reader(tmp_path, request):
    cells, parts = SHAPES[request.param]
    table = _table()
    _fresh_caches()
    r = _write(tmp_path, table, _batch(table, cells, parts))
    r.pks = [table.serialize_partition_key([p]) for p in range(parts)]
    r.absent = [table.serialize_partition_key([p])
                for p in range(parts, parts + 20)]
    yield r
    r.close()


def _point_reads(r, how):
    if how == "read_partition":
        return {pk: b for pk in r.pks + r.absent
                if (b := r.read_partition(pk)) is not None}
    got, passed = r.read_partitions_batch(r.pks + r.absent)
    assert set(r.pks) <= set(passed)
    return got


@pytest.mark.parametrize("cache", ["cold", "warmed_by_a_scan"])
@pytest.mark.parametrize("how", ["read_partition", "read_partitions_batch"])
@pytest.mark.parametrize("reader", list(SHAPES), indirect=True)
def test_point_read_is_a_slice_of_the_scan_with_its_own_key(reader, how,
                                                            cache):
    if cache == "cold":
        got = _point_reads(reader, how)
        # nothing a point read decoded carries a key map
        assert chunk_cache.stats()["entries"] > 0
        assert not any(s.pk_map for s in chunk_cache._lru.values())
    full = cb.CellBatch.concat(list(reader.scanner()))
    if cache != "cold":
        got = _point_reads(reader, how)
    assert set(got) == set(reader.pks)
    for pk in reader.pks:
        key16 = cb.pk_lane_key(pk)
        part = got[pk]
        assert part.pk_map == {key16: pk}
        assert part.sorted
        _same_cells(part, _slice_of_scan(full, key16))
        assert part.partition_key(0) == pk
    # every read has a dict of its own
    assert len({id(b.pk_map) for b in got.values()}) == len(got)


def _store(directory, table):
    """Two overlapping sstables of SEG-cell segments in a store of its
    own; the same bytes whatever the directory."""
    os.makedirs(directory)
    cfs = ColumnFamilyStore(table, str(directory), commitlog=None)
    for ts, ids in ((1000, range(0, 90)), (2000, range(60, 150))):
        _write(cfs.directory, table, _batch(table, 5, 0, ts, ids),
               cfs.next_generation()).close()
    cfs.reload_sstables()
    pks = [table.serialize_partition_key([p]) for p in range(150)]
    return cfs, pks


def _compact(cfs) -> dict:
    CompactionTask(cfs, cfs.tracker.view(), engine="numpy").execute()
    (out,) = cfs.live_sstables()
    comps = {}
    for path in out.desc.all_paths():
        if os.path.exists(path):
            with open(path, "rb") as f:
                comps[os.path.basename(path).split("-", 2)[-1]] = f.read()
    return comps


@pytest.mark.parametrize("order", ["point_reads_then_scans",
                                   "scans_then_point_reads"])
def test_scan_and_compaction_after_point_reads_share_the_cache(tmp_path,
                                                               order):
    _fresh_caches()
    table = _table("pr_" + order)
    cold_cfs, _ = _store(tmp_path / "cold", table)
    cold_scan = [cb.CellBatch.concat(list(r.scanner()))
                 for r in cold_cfs.live_sstables()]
    _fresh_caches()
    cold = _compact(cold_cfs)
    assert len(cold) >= 7

    _fresh_caches()
    cfs, pks = _store(tmp_path / "warm", table)

    def scans():
        for r, want in zip(cfs.live_sstables(), cold_scan):
            segs = list(r.scanner())
            # every cell's key is in its segment's map: what the
            # writer's directory pass asks of it
            for s in segs:
                assert {s.lanes[i, :4].astype(">u4").tobytes()
                        for i in range(len(s))} == set(s.pk_map)
            got = cb.CellBatch.concat(segs)
            _same_cells(got, want)
            assert got.pk_map == want.pk_map

    def point_reads():
        for pk in pks:
            merged = cfs.read_partition(pk, now=10)
            assert merged.pk_map == {cb.pk_lane_key(pk): pk}
            assert len(merged) == 5

    first, second = (point_reads, scans) \
        if order == "point_reads_then_scans" else (scans, point_reads)
    first()
    hits = chunk_cache.hits
    second()
    assert chunk_cache.hits > hits
    assert _compact(cfs) == cold


def test_threads_mixing_point_reads_and_scans_on_one_reader(tmp_path):
    table = _table()
    _fresh_caches()
    r = _write(tmp_path, table, _batch(table, 5, 200))
    pks = [table.serialize_partition_key([p]) for p in range(200)]
    full = cb.CellBatch.concat(list(r.scanner()))
    want = {pk: _slice_of_scan(full, cb.pk_lane_key(pk)) for pk in pks}
    _fresh_caches()
    errors, rounds = [], [0] * 8
    deadline = time.monotonic() + 1.0

    def work(i):
        rng = np.random.default_rng(i)
        try:
            while time.monotonic() < deadline:
                if i % 2:
                    got = cb.CellBatch.concat(list(r.scanner()))
                    _same_cells(got, full)
                    assert got.pk_map == full.pk_map
                else:
                    mine = [pks[j] for j in rng.integers(0, 200, 8)]
                    if rounds[i] % 2:
                        got, _ = r.read_partitions_batch(mine)
                    else:
                        got = {pk: r.read_partition(pk) for pk in mine}
                    for pk in mine:
                        _same_cells(got[pk], want[pk])
                        assert got[pk].pk_map == {cb.pk_lane_key(pk): pk}
                # the cache is what the threads contend for: drop it now
                # and then so keyed and unkeyed decodes keep alternating
                if rounds[i] % 3 == 2:
                    chunk_cache.clear()
                rounds[i] += 1
        except BaseException as e:     # noqa: BLE001 - reported below
            errors.append(e)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)        # switch threads mid-read, often
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    r.close()
    assert not errors, errors
    assert all(n > 0 for n in rounds)


@pytest.fixture
def byte_ordered():
    before = partitioners.current()
    partitioners.set_current("ByteOrderedPartitioner")
    yield
    partitioners.set_current(before)


def _lookups_agree(r, pks):
    key_cache.clear()
    batch = r._partition_indexes_batch(pks)
    single = [r._partition_index(pk) for pk in pks]
    assert single == batch
    # the single lookup fed the key cache; a hit answers the same
    assert [r._partition_index(pk) for pk in pks] == batch
    for pk, p in zip(pks, batch):
        assert (key_cache.get(r._key_cache_key(pk)) == (p,)) \
            if p is not None else \
            (key_cache.get(r._key_cache_key(pk)) is None)
    return batch


@pytest.mark.parametrize("keys", ["present", "absent"])
def test_partition_index_matches_the_batched_lookup(tmp_path, keys):
    table = _table()
    r = _write(tmp_path, table, _batch(table, 2, 500))
    lo = 0 if keys == "present" else 500
    pks = [table.serialize_partition_key([p]) for p in range(lo, lo + 500)]
    got = _lookups_agree(r, pks)
    if keys == "present":
        assert sorted(got) == list(range(500))
        assert all(r.partition_key_at(p) == pk for pk, p in zip(pks, got))
    else:
        assert got == [None] * 500
    r.close()


def test_partition_index_matches_the_batched_lookup_on_colliding_tokens(
        tmp_path, byte_ordered):
    # ByteOrdered tokens are the first eight key bytes: these 40 keys
    # share three tokens, and only the pk-hash lanes tell them apart
    table = _table("coll", key="text")
    ids = [f"{stem}-{i:03d}" for stem in ("prefix-A", "prefix-B",
                                          "other-px")
           for i in range(0, 40, 3)]
    r = _write(tmp_path, table, _batch(table, 3, 0, ids=ids))
    assert len(set(r.partition_tokens.tolist())) == 3
    present = [table.serialize_partition_key([i]) for i in ids]
    # same tokens, keys the sstable does not hold
    absent = [table.serialize_partition_key([f"prefix-A-{i:03d}"])
              for i in range(1, 40, 3)]
    got = _lookups_agree(r, present + absent)
    assert sorted(got[:len(ids)]) == list(range(len(ids)))
    assert got[len(ids):] == [None] * len(absent)
    for pk in present:
        part = r.read_partition(pk)
        assert part.pk_map == {cb.pk_lane_key(pk): pk} and len(part) == 3
    r.close()


@pytest.mark.parametrize("cached_as", ["decoded_by_the_point_read",
                                       "decoded_by_a_scan"])
def test_whole_segment_partition_leaves_the_cached_segment_alone(tmp_path,
                                                                 cached_as):
    table = _table()
    _fresh_caches()
    r = _write(tmp_path, table, _batch(table, SEG, 5))
    assert r.n_segments == 5 and r.n_partitions == 5
    if cached_as == "decoded_by_a_scan":
        list(r.scanner())
    for p in range(5):
        pk = r.partition_key_at(p)
        part = r.read_partition(pk)
        key = (r.desc.directory, r.desc.generation, p)
        cached = chunk_cache._lru[key]
        want_map = {} if cached_as == "decoded_by_the_point_read" \
            else {cb.pk_lane_key(pk): pk}
        assert cached.pk_map == want_map
        # the read's batch is the segment's cells under a map of its own
        assert part is not cached and part.pk_map is not cached.pk_map
        assert part.pk_map == {cb.pk_lane_key(pk): pk}
        assert part.lanes is cached.lanes
        again, _ = r.read_partitions_batch([pk])
        assert again[pk] is not cached
        assert chunk_cache._lru[key] is cached
        assert cached.pk_map == want_map
    r.close()


def test_a_cache_miss_is_one_span_with_its_keys_and_bytes(tmp_path):
    from cassandra_tpu.utils import pipeline_ledger as pl
    table = _table()
    _fresh_caches()
    r = _write(tmp_path, table, _batch(table, 5, 100))

    def decodes(fn):
        last = pl.RING[-1][5] if pl.RING else 0
        with pl.span("test.reads") as root:
            fn()
        return [d for d in pl.ring_records()
                if d["id"] > last and d["name"] == "sstable.read.segment"
                and d["parent"] == root._id]

    pk = r.partition_key_at(0)
    (one,) = decodes(lambda: r.read_partition(pk))
    assert one["items"] == 0 and one["kind"] == "busy"
    assert one["bytes"] == int(r._blk[0, :, 1].sum()) > 0
    assert decodes(lambda: r.read_partition(pk)) == []       # a hit
    # the scan finds segment 0 cached without keys (no decode, no span)
    # and decodes the rest with theirs
    scanned = decodes(lambda: list(r.scanner()))
    assert len(scanned) == r.n_segments - 1
    assert sum(d["items"] for d in scanned) >= r.n_partitions - SEG // 5 - 1
    assert all(d["items"] > 0 for d in scanned)
    assert sum(len(s.pk_map) for s in r.scanner()) >= r.n_partitions
    r.close()
