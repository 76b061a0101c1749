"""The purge guard (CompactionController.purgeable_ts_fn) probes only the
partition runs that hold a cell a purge can apply to — a death flag or a
TTL — and leaves every other run at +inf. Same answer as the full walk it
replaced (kept here as the oracle) wherever an engine reads it, same
output bytes on numpy, native and resident device; a tombstone the
memtable or an outside sstable still covers is kept; and the cost follows
the purgeable partitions, not the batch."""
from __future__ import annotations

import numpy as np
import pytest

from cassandra_tpu.compaction.task import CompactionController, CompactionTask
from cassandra_tpu.ops import host_merge
from cassandra_tpu.schema import TableParams, make_table
from cassandra_tpu.storage import cellbatch as cb
from cassandra_tpu.storage.cellbatch import CellBatchBuilder
from cassandra_tpu.storage.memtable import Memtable
from cassandra_tpu.storage.mutation import Mutation
from cassandra_tpu.storage.sstable import Descriptor, SSTableWriter
from cassandra_tpu.storage.table import ColumnFamilyStore
from cassandra_tpu.service.metrics import GLOBAL as METRICS
from cassandra_tpu.utils import bloom, murmur3, pipeline_ledger, timeutil

from test_device_resident import _hashes

NOW = 1_700_000_000
INT64_MAX = np.iinfo(np.int64).max
PURGE_FLAGS = cb.DEATH_FLAGS | cb.FLAG_EXPIRING
SPAN = "compaction.purge.probe"
# gc_before = NOW - gc_grace: after every tombstone's ldt, and before
GC_GRACES = {"gc_after_ldt": 0, "gc_before_ldt": 864_000}
ENGINES = ["numpy",
           pytest.param("native", marks=pytest.mark.skipif(
               not host_merge.available(),
               reason="the native merge library does not load")),
           "device"]
ENGINE_KW = {"numpy": dict(engine="numpy"),
             "native": dict(engine="native"),
             "device": dict(engine="device", use_device=True,
                            mesh_devices=0, device_compress=False)}


@pytest.fixture(autouse=True)
def frozen_clock(monkeypatch):
    monkeypatch.setattr(timeutil, "CLOCK", lambda: NOW)


def full_walk(self, batch):
    """The controller's purge guard as it was before: every partition
    run of the batch is probed. The oracle."""
    n = len(batch)
    out = np.full(n, INT64_MAX, dtype=np.int64)
    overlapping = self._overlapping()
    mems = {id(m): m for m in (self.memtable_at_start,
                               self.cfs.memtable)}.values()
    mems = [m for m in mems if not m.is_empty]
    if not overlapping and not mems:
        return out
    lane4 = batch.lanes[:, :4]
    part_new = np.ones(n, dtype=bool)
    part_new[1:] = (lane4[1:] != lane4[:-1]).any(axis=1)
    part_id = np.cumsum(part_new) - 1
    starts = np.flatnonzero(part_new)
    per_part = np.full(len(starts), INT64_MAX, dtype=np.int64)
    for j, s in enumerate(starts):
        pk = batch.partition_key(int(s))
        lo = INT64_MAX
        for src in overlapping:
            if src.might_contain(pk) and src.min_ts is not None:
                lo = min(lo, src.min_ts)
        if any(m.contains(pk) for m in mems):
            lo = min(lo, 0)
        per_part[j] = lo
    return per_part[part_id]


def _table(name: str, gc_grace: int):
    return make_table(
        "pg", name, pk=["id"], ck=["c"],
        cols={"id": "int", "c": "int", "v": "blob", "m": "map<int,int>"},
        params=TableParams(gc_grace_seconds=gc_grace))


def _write(cfs, table, gen: int, batch) -> None:
    w = SSTableWriter(Descriptor(cfs.directory, gen), table,
                      estimated_partitions=256)
    w.append(cb.merge_sorted([batch], now=0))
    w.finish()


N_PARTS = 240
OVERLAP_MIN_TS = 5_000     # the outside sstable's oldest cell


def _mixed_batch(table, rng) -> "cb.CellBatch":
    """One input: live cells, cell tombstones, row / partition / complex
    deletions, TTL cells expired and not — over N_PARTS partitions, with
    timestamps on both sides of OVERLAP_MIN_TS. A third of the
    partitions hold live cells only."""
    v, m = table.columns["v"].column_id, table.columns["m"].column_id
    b = CellBatchBuilder(table)
    for p in range(N_PARTS):
        pk = table.serialize_partition_key([p])
        plain = p % 3 == 0
        if not plain and rng.random() < 0.1:
            b.add_partition_deletion(pk, int(rng.integers(1, 10_000)),
                                     ldt=NOW - int(rng.integers(50, 150)))
        for c in range(int(rng.integers(1, 6))):
            ck = table.serialize_clustering([int(rng.integers(0, 8))])
            ts = int(rng.integers(1, 10_000))
            ldt = NOW - int(rng.integers(50, 150))
            kind = 0.0 if plain else rng.random()
            if kind < 0.4:
                b.add_cell(pk, ck, v, rng.bytes(12), ts)
            elif kind < 0.55:
                b.add_tombstone(pk, ck, v, ts, ldt)
            elif kind < 0.65:
                b.add_row_deletion(pk, ck, ts, ldt)
            elif kind < 0.75:
                b.add_complex_deletion(pk, ck, m, ts, ldt)
                b.add_cell(pk, ck, m, rng.bytes(4), ts + 1,
                           path=rng.bytes(4))
            elif kind < 0.9:     # expired long ago (written NOW-1000)
                b.add_cell(pk, ck, v, rng.bytes(12), ts,
                           ttl=int(rng.integers(100, 900)), now=NOW - 1000)
            else:                # not expired yet
                b.add_cell(pk, ck, v, rng.bytes(12), ts,
                           ttl=5_000, now=NOW - 10)
    return b.seal()


def _store(tmp_path, tag: str, table, seed: int, n_inputs: int = 3):
    """n_inputs inputs (gen 1..n_inputs), one sstable OUTSIDE the
    compaction (the next gen) holding every fifth partition, and a
    memtable holding every seventh."""
    cfs = ColumnFamilyStore(table, str(tmp_path / tag), commitlog=None)
    rng = np.random.default_rng(seed)
    batches = [_mixed_batch(table, rng) for _ in range(n_inputs)]
    for gen, batch in enumerate(batches, 1):
        _write(cfs, table, gen, batch)
    v = table.columns["v"].column_id
    b = CellBatchBuilder(table)
    for p in range(0, N_PARTS, 5):
        b.add_cell(table.serialize_partition_key([p]),
                   table.serialize_clustering([0]), v, b"outside",
                   OVERLAP_MIN_TS + p)
    _write(cfs, table, n_inputs + 1, b.seal())
    cfs.reload_sstables()
    _memtable_rows(cfs, table, range(0, N_PARTS, 7))
    inputs = [r for r in cfs.live_sstables()
              if r.desc.generation <= n_inputs]
    assert len(inputs) == n_inputs
    assert len(cfs.live_sstables()) == n_inputs + 1
    return cfs, inputs, batches


def _memtable_rows(cfs, table, parts) -> None:
    v = table.columns["v"].column_id
    for p in parts:
        mu = Mutation(table.id, table.serialize_partition_key([p]))
        mu.add(table.serialize_clustering([0]), v, b"", b"mem", 1)
        cfs.apply(mu)


def _close(cfs) -> None:
    for r in cfs.live_sstables():
        r.close()


# ------------------------------------------------------------ same answer --

@pytest.mark.parametrize("gc", list(GC_GRACES))
@pytest.mark.parametrize("seed", [11, 12])
def test_timestamps_agree_where_a_purge_reads_them(tmp_path, seed, gc):
    """Per cell the guard's answer equals the full walk's wherever an
    engine reads it (a death flag or a TTL); everywhere else it is
    +inf, which no engine reads. On a sorted batch and on the unsorted
    concatenation the device and native engines hand in."""
    cfs, inputs, batches = _store(tmp_path, "s",
                                  _table("mixed", GC_GRACES[gc]), seed)
    ctl = CompactionController(cfs, inputs)
    cat = cb.CellBatch.concat(batches)
    for batch in (cat, cat.apply_permutation(cat.sort_permutation())):
        new, old = ctl.purgeable_ts_fn(batch), full_walk(ctl, batch)
        read = (batch.flags & PURGE_FLAGS) != 0
        assert read.any() and not read.all()
        np.testing.assert_array_equal(new[read], old[read])
        # the walk did protect something, at both of its levels
        assert (old[read] == 0).any() and (old[read] == INT64_MAX).any()
        assert ((old[read] > 0) & (old[read] < INT64_MAX)).any()
        # partitions without such a cell are left at +inf
        lane4 = batch.lanes[:, :4]
        part_new = np.ones(len(batch), dtype=bool)
        part_new[1:] = (lane4[1:] != lane4[:-1]).any(axis=1)
        part_id = np.cumsum(part_new) - 1
        holds = np.bincount(part_id[read], minlength=part_id[-1] + 1) > 0
        assert (new[~holds[part_id]] == INT64_MAX).all()
        assert (old[~holds[part_id]] != INT64_MAX).any()
    _close(cfs)


def _probed_walk(ctl, batch) -> np.ndarray:
    """The oracle on the runs the guard probes (those holding a cell a
    purge reads), +inf on the others: what the guard must return cell
    for cell."""
    want = full_walk(ctl, batch)
    if not len(batch):
        return want
    read = (batch.flags & PURGE_FLAGS) != 0
    starts = np.flatnonzero(_part_new(batch))
    holds = np.logical_or.reduceat(read, starts)
    return np.where(np.repeat(holds, np.diff(starts, append=len(batch))),
                    want, INT64_MAX)


def _part_new(batch) -> np.ndarray:
    lane4 = batch.lanes[:, :4]
    part_new = np.ones(len(batch), dtype=bool)
    part_new[1:] = (lane4[1:] != lane4[:-1]).any(axis=1)
    return part_new


def _probed_keys(batch) -> set:
    read = (batch.flags & PURGE_FLAGS) != 0
    return {batch.partition_key(int(i)) for i in np.flatnonzero(read)}


GUARD_CASES = ["cursors2", "cursors4", "bloom_false_positives",
               "min_ts_none", "two_memtables", "probed_and_unprobed",
               "empty"]


@pytest.mark.parametrize("case", GUARD_CASES)
def test_batched_guard_equals_the_walk(tmp_path, case):
    """Hashing each probed key once and asking each outside filter one
    vectorised question returns, cell for cell, what the full walk
    returns on the probed runs: the same partition in runs of 2 and 4
    concatenated cursors, an outside filter so small it answers yes to
    keys it never saw, a source without a min_ts (whose yes counts for
    nothing), a flush's new memtable beside the one the task started
    with, runs probed and not side by side, no cell at all."""
    table = _table("cases", 0)
    n_inputs = {"cursors2": 2, "cursors4": 4}.get(case, 3)
    cfs, inputs, batches = _store(tmp_path, case, table, 31, n_inputs)
    pk = table.serialize_partition_key
    if case == "min_ts_none":
        b = CellBatchBuilder(table)
        b.add_cell(pk([N_PARTS + 1]), table.serialize_clustering([0]),
                   table.columns["v"].column_id, b"x", 1)
        _write(cfs, table, n_inputs + 2, b.seal())
        cfs.reload_sstables()
        inputs = [r for r in cfs.live_sstables()
                  if r.desc.generation <= n_inputs]
    ctl = CompactionController(cfs, inputs)
    outside = ctl._overlapping()
    cat = cb.CellBatch.concat(batches)
    if case == "empty":
        cat = cat.apply_permutation(np.zeros(0, dtype=np.int64))
    got = ctl.purgeable_ts_fn(cat)
    if case == "bloom_false_positives":
        src, = outside
        held = set(src.partition_keys())
        src.bloom = bloom.BloomFilter(64, 1)
        src.bloom.add_batch(sorted(held))
        assert any(src.might_contain(k) for k in _probed_keys(cat) - held)
        got = ctl.purgeable_ts_fn(cat)
    elif case == "min_ts_none":
        extra, = [r for r in outside if r.desc.generation > n_inputs + 1]
        extra.bloom.words[:] = np.iinfo(np.uint64).max   # yes to all
        extra.stats["min_ts"] = None
        got = ctl.purgeable_ts_fn(cat)
    elif case == "two_memtables":
        cfs.memtable = Memtable(table)    # a flush switched it
        _memtable_rows(cfs, table, range(3, N_PARTS, 11))
        got = ctl.purgeable_ts_fn(cat)
    want = _probed_walk(ctl, cat)
    assert got.dtype == np.int64 and got.shape == (len(cat),)
    np.testing.assert_array_equal(got, want)
    if case == "empty":
        assert len(cat) == 0
        _close(cfs)
        return
    read = (cat.flags & PURGE_FLAGS) != 0
    assert (want[read] < INT64_MAX).any() and (want[~read] == INT64_MAX).any()
    runs = np.flatnonzero(_part_new(cat))
    probed = np.logical_or.reduceat(read, runs)
    assert probed.any() and not probed.all()
    if case.startswith("cursors"):
        # probed runs of one partition from several cursors share a key
        assert probed.sum() > len(_probed_keys(cat))
    elif case == "two_memtables":
        # partitions only the new memtable holds read 0
        only_new = {pk([p]) for p in range(3, N_PARTS, 11) if p % 7}
        cells = [i for i in np.flatnonzero(read)
                 if cat.partition_key(int(i)) in only_new]
        assert cells and (got[cells] == 0).all()
    _close(cfs)


@pytest.mark.parametrize("gc", list(GC_GRACES))
@pytest.mark.parametrize("engine", ENGINES)
def test_compaction_bytes_equal_the_full_walk(tmp_path, monkeypatch,
                                              engine, gc):
    """The sstable a compaction writes under the guard is the one it
    wrote under the full walk, component for component."""
    table = _table("mixed", GC_GRACES[gc])   # one table id for both legs

    def leg(tag):
        cfs, inputs, batches = _store(tmp_path, tag, table, 21)
        task = CompactionTask(cfs, inputs, pipelined_io=False,
                              compress_pool=0, decode_ahead=False,
                              **ENGINE_KW[engine])
        task.execute()
        assert task.engine == engine
        out = [r for r in cfs.live_sstables() if r.desc.generation > 4]
        deaths = sum(r.n_tombstones for r in out)
        unpurged = cb.merge_sorted(batches, gc_before=0, now=NOW)
        h = _hashes(cfs.directory)
        _close(cfs)
        return h, deaths, int(((unpurged.flags & cb.DEATH_FLAGS) != 0).sum())

    new = leg("guard")
    with monkeypatch.context() as mp:
        mp.setattr(CompactionController, "purgeable_ts_fn", full_walk)
        old = leg("walk")
    assert new[0] and new == old
    # gc_before after the ldts purges what nothing outside covers and
    # keeps what the memtable or gen 4 covers; before them, nothing goes
    _, deaths, unpurged = new
    if gc == "gc_after_ldt":
        assert 0 < deaths < unpurged
    else:
        assert deaths == unpurged


# ----------------------------------------------------------------- safety --

@pytest.mark.parametrize("engine", ENGINES)
def test_covered_tombstone_and_expired_cell_survive(tmp_path, engine):
    """Past gc_grace, a tombstone and an expired TTL cell whose partition
    the memtable holds (with an OLDER timestamp) survive the compaction;
    the same cells in a partition the memtable does not hold are purged."""
    table = _table("safety", 0)
    cfs = ColumnFamilyStore(table, str(tmp_path / "d"), commitlog=None)
    v = table.columns["v"].column_id
    pk = table.serialize_partition_key
    ck0, ck1 = (table.serialize_clustering([i]) for i in (0, 1))
    COVERED, BARE, LIVE = 1, 2, 3
    for gen in (1, 2):
        b = CellBatchBuilder(table)
        for p in (COVERED, BARE):
            if gen == 1:
                b.add_tombstone(pk([p]), ck0, v, 200, NOW - 100)
                b.add_cell(pk([p]), ck1, v, b"ttl", 200, ttl=100,
                           now=NOW - 1000)
            else:
                b.add_cell(pk([p]), ck0, v, b"shadowed", 100)
        b.add_cell(pk([LIVE]), ck0, v, b"live%d" % gen, 100 + gen)
        _write(cfs, table, gen, b.seal())
    cfs.reload_sstables()
    mu = Mutation(table.id, pk([COVERED]))
    mu.add(ck0, v, b"", b"older", 50)
    cfs.apply(mu)
    CompactionTask(cfs, cfs.live_sstables(), pipelined_io=False,
                   compress_pool=0, decode_ahead=False,
                   **ENGINE_KW[engine]).execute()
    out, = cfs.live_sstables()
    kept = out.read_partition(pk([COVERED]))
    assert kept is not None and len(kept) == 2
    assert ((kept.flags & cb.FLAG_TOMBSTONE) != 0).all()
    assert out.read_partition(pk([BARE])) is None
    live = out.read_partition(pk([LIVE]))
    assert [live.cell_value(i) for i in range(len(live))] == [b"live2"]
    _close(cfs)


def test_tombstone_covered_by_an_outside_sstable_survives(tmp_path):
    """The other half of the guard: an sstable outside the compaction
    whose bloom filter admits the key and whose oldest cell is older
    than the tombstone keeps it; a tombstone older than that cell goes."""
    table = _table("outside", 0)
    cfs = ColumnFamilyStore(table, str(tmp_path / "d"), commitlog=None)
    v = table.columns["v"].column_id
    pk = table.serialize_partition_key
    ck0 = table.serialize_clustering([0])
    b = CellBatchBuilder(table)
    b.add_row_deletion(pk([1]), ck0, 900, NOW - 100)     # newer: kept
    b.add_row_deletion(pk([2]), ck0, 300, NOW - 100)     # older: purged
    b.add_row_deletion(pk([3]), ck0, 900, NOW - 100)     # not outside
    _write(cfs, table, 1, b.seal())
    b = CellBatchBuilder(table)
    for p in (1, 2):
        b.add_cell(pk([p]), ck0, v, b"outside", 500)
    _write(cfs, table, 2, b.seal())
    cfs.reload_sstables()
    tomb, = [r for r in cfs.live_sstables() if r.desc.generation == 1]
    CompactionTask(cfs, [tomb], engine="numpy").execute()
    out, = [r for r in cfs.live_sstables() if r.desc.generation > 2]
    kept = out.read_partition(pk([1]))
    assert kept is not None and len(kept) == 1 \
        and kept.flags[0] & cb.FLAG_ROW_DEL
    assert out.read_partition(pk([2])) is None
    assert out.read_partition(pk([3])) is None
    _close(cfs)


# ------------------------------------------------------------------- cost --

def _wide_batch(table, n_parts: int, tombstoned=()) -> "cb.CellBatch":
    """n_parts single-cell partitions, the cell a tombstone in the given
    ones."""
    v = table.columns["v"].column_id
    ck = table.serialize_clustering([0])
    b = CellBatchBuilder(table)
    for p in range(n_parts):
        pk = table.serialize_partition_key([p])
        if p in tombstoned:
            b.add_tombstone(pk, ck, v, 100, NOW - 100)
        else:
            b.add_cell(pk, ck, v, b"live", 100)
    return cb.merge_sorted([b.seal()])


def _probe_spans(since: int) -> list:
    return [r for r in pipeline_ledger.ring_records()
            if r["name"] == SPAN and r["id"] > since]


@pytest.fixture
def wide(tmp_path):
    table = make_table("pg", "wide", pk=["id"], ck=["c"],
                       cols={"id": "int", "c": "int", "v": "blob"},
                       params=TableParams(gc_grace_seconds=0))
    cfs = ColumnFamilyStore(table, str(tmp_path / "w"), commitlog=None)
    yield table, cfs
    _close(cfs)


class _CountingMemtable(Memtable):
    calls = 0

    def contains(self, pk):
        type(self).calls += 1
        return super().contains(pk)


def _counting_store(table, cfs, monkeypatch):
    monkeypatch.setattr(_CountingMemtable, "calls", 0)
    cfs.memtable = _CountingMemtable(table)
    mu = Mutation(table.id, table.serialize_partition_key([10**6]))
    mu.add(table.serialize_clustering([0]),
           table.columns["v"].column_id, b"", b"m", 1)
    cfs.apply(mu)
    return CompactionController(cfs, [])


def test_probes_only_the_tombstoned_partitions(wide, monkeypatch):
    table, cfs = wide
    ctl = _counting_store(table, cfs, monkeypatch)
    batch = _wide_batch(table, 10_000, tombstoned=(17, 4_242, 9_999))
    since = pipeline_ledger.new_task_id()
    pts = ctl.purgeable_ts_fn(batch)
    sp, = _probe_spans(since)
    assert (sp["cells"], sp["items"], sp["kind"]) == (10_000, 3, "busy")
    assert _CountingMemtable.calls == 3
    np.testing.assert_array_equal(pts, full_walk(ctl, batch))


def test_probes_nothing_without_a_purgeable_cell(wide, monkeypatch):
    table, cfs = wide
    ctl = _counting_store(table, cfs, monkeypatch)
    batch = _wide_batch(table, 10_000)
    since = pipeline_ledger.new_task_id()
    pts = ctl.purgeable_ts_fn(batch)
    sp, = _probe_spans(since)
    assert sp["items"] == 0 and _CountingMemtable.calls == 0
    assert (pts == INT64_MAX).all()


def test_no_span_without_memtable_or_overlap(wide):
    table, cfs = wide
    ctl = CompactionController(cfs, [])
    assert cfs.memtable.is_empty and not ctl._overlapping()
    batch = _wide_batch(table, 1_000, tombstoned=(5,))
    since = pipeline_ledger.new_task_id()
    assert (ctl.purgeable_ts_fn(batch) == INT64_MAX).all()
    assert _probe_spans(since) == []


def test_each_key_hashed_once_and_each_filter_asked_once(tmp_path,
                                                        monkeypatch):
    """One call over four cursors' runs hashes each distinct probed key
    once, in ONE batch, and asks each outside sstable's filter ONE
    vectorised question; the scalar bloom probe never runs, and the
    counters `compaction.purge.keys_hashed` / `.filter_probes` say so."""
    table = _table("cost", 0)
    cfs, inputs, batches = _store(tmp_path, "c", table, 41, n_inputs=4)
    b = CellBatchBuilder(table)
    for p in range(1, N_PARTS, 4):
        b.add_cell(table.serialize_partition_key([p]),
                   table.serialize_clustering([1]),
                   table.columns["v"].column_id, b"second", 7_000)
    _write(cfs, table, 6, b.seal())
    cfs.reload_sstables()
    inputs = [r for r in cfs.live_sstables() if r.desc.generation <= 4]
    ctl = CompactionController(cfs, inputs)
    assert len(ctl._overlapping()) == 2
    cat = cb.CellBatch.concat(batches)
    keys = _probed_keys(cat)
    hashed = []
    real = murmur3.hash128_batch

    def counting(batch_keys, *a, **kw):
        hashed.append(sorted(batch_keys))
        return real(batch_keys, *a, **kw)

    def scalar(self, key):
        raise AssertionError("the scalar bloom probe ran")

    with monkeypatch.context() as mp:
        mp.setattr(murmur3, "hash128_batch", counting)
        mp.setattr(bloom.BloomFilter, "might_contain", scalar)
        n_hashed = METRICS.counter("compaction.purge.keys_hashed")
        n_probes = METRICS.counter("compaction.purge.filter_probes")
        since = pipeline_ledger.new_task_id()
        got = ctl.purgeable_ts_fn(cat)
        assert hashed == [sorted(keys)]
        assert METRICS.counter("compaction.purge.keys_hashed") \
            - n_hashed == len(keys)
        assert METRICS.counter("compaction.purge.filter_probes") \
            - n_probes == 2
    sp, = _probe_spans(since)
    # the runs probed outnumber the keys hashed: the dedupe engaged
    assert sp["items"] > len(keys) > 0
    want = _probed_walk(ctl, cat)
    np.testing.assert_array_equal(got, want)
    assert (want == 7_000).any()       # the second filter's own answer
    _close(cfs)
