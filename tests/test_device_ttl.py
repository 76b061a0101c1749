"""TTL'd rounds on the device engine (PR 31): the resident program turns
a kept expired cell into a tombstone itself, so such a round stays
resident, and every component is what the numpy engine writes. Plus
TimeWindowCompactionStrategy through the manager over a TTL'd table: the
expired window dropped whole, then the repaired window, then the closed
one, each merge on the engine the task chose itself."""
from __future__ import annotations

import hashlib
import os
import time

import numpy as np
import pytest

from cassandra_tpu.compaction import task as task_mod
from cassandra_tpu.compaction.task import CompactionTask
from cassandra_tpu.ops.codec import CompressionParams
from cassandra_tpu.schema import Schema, TableParams, make_table
from cassandra_tpu.service.metrics import GLOBAL as METRICS
from cassandra_tpu.storage import cellbatch as cb
from cassandra_tpu.storage.engine import StorageEngine
from cassandra_tpu.storage.sstable import Descriptor, SSTableWriter
from cassandra_tpu.storage.table import ColumnFamilyStore
from cassandra_tpu.tools import bulk

DAY = 86400
TTL = 30 * DAY
FALLBACKS = ("compaction.device_resident_fallback",
             "compaction.device_host_rounds")
CONVERTED = "compaction.device_expired_converted"
COMPONENTS = ("Data.db", "Index.db", "Partitions.db", "Filter.db",
              "Statistics.db", "Digest.crc32", "ZoneMap.db")


def _table(name: str, **params):
    return make_table(
        "ttl", name, pk=["id"], ck=["c"],
        cols={"id": "int", "c": "int", "v": "blob"},
        params=TableParams(compression=CompressionParams(
            "LZ4Compressor", chunk_length=16 * 1024), **params))


def _cells(table, pk, ck, ts, ldt=None, seed=0):
    """One value cell per (pk, ck), written at `ts`; with `ldt` given
    they carry a TTL and run out then."""
    n = len(pk)
    vals = np.random.default_rng(seed).integers(0, 256, (n, 16),
                                                dtype=np.uint8)
    b = bulk.build_int_batch(table, np.asarray(pk), np.asarray(ck), vals,
                             np.asarray(ts, dtype=np.int64))
    if ldt is not None:
        b.flags[:] |= cb.FLAG_EXPIRING
        b.ttl[:] = TTL
        b.ldt[:] = ldt
    return b


def _grid(parts, rows, p0=0):
    pk = np.repeat(np.arange(p0, p0 + parts), rows)
    ck = np.tile(np.arange(rows), parts)
    return pk, ck


def _row_deletions(table, pk, ck, ts, ldt):
    b = cb.CellBatchBuilder(table)
    for p, c, t in zip(pk, ck, ts):
        b.add_row_deletion(table.serialize_partition_key([int(p)]),
                           table.serialize_clustering([int(c)]),
                           int(t), ldt)
    return b.seal()


# ------------------------------------------------------------ the cases --
# each returns (the compaction's inputs, sstables left outside it): lists
# of unsorted CellBatches, one sstable each

def all_live(table, now):
    pk, ck = _grid(40, 60)
    return [_cells(table, pk, ck, 1_000_000 * (r + 1) + ck,
                   now + 5 * DAY, seed=r) for r in range(3)], []


def expired_inside_grace(table, now):
    pk, ck = _grid(40, 60)
    return [_cells(table, pk, ck, 1_000_000 * (r + 1) + ck,
                   now - 4 * DAY, seed=r) for r in range(3)], []


def past_grace_guard_lets_go(table, now):
    """Half the partitions ran out 14 days ago (past the 10 days of
    grace, nothing outside the compaction: purged), half are live."""
    pk, ck = _grid(40, 60)
    ldt = np.where(pk % 2 == 0, now - 14 * DAY, now + 5 * DAY)
    return [_cells(table, pk, ck, 1_000_000 * (r + 1) + ck, ldt, seed=r)
            for r in range(3)], []


def past_grace_guard_holds_back(table, now):
    """Everything ran out past grace, but an sstable outside the
    compaction holds older data of the same partitions: the purge guard
    keeps the cells, as tombstones."""
    pk, ck = _grid(40, 60)
    inputs = [_cells(table, pk, ck, 5_000_000 * (r + 1) + ck,
                     now - 14 * DAY, seed=r) for r in range(2)]
    return inputs, [_cells(table, pk, ck, 1_000 + ck, seed=9)]


def wide_partition(table, now):
    """One partition of 150,000 rows over two inputs, a third of it
    expired inside grace: it straddles the inputs' 65,536-cell segments
    and the output's, and stretches a round to its end."""
    rows = 150_000
    pk = np.concatenate([np.full(rows, 7), _grid(6, 50, p0=100)[0]])
    ck = np.concatenate([np.arange(rows), _grid(6, 50)[1]])
    ldt = np.where(ck % 3 == 0, now - 4 * DAY, now + 5 * DAY)
    half = ck % 2 == 0
    return [_cells(table, pk[half], ck[half], 1_000_000 + ck[half],
                   ldt[half], seed=1),
            _cells(table, pk[~half], ck[~half], 2_000_000 + ck[~half],
                   ldt[~half], seed=2),
            _cells(table, pk[:5000], ck[:5000], 3_000_000 + ck[:5000],
                   ldt[:5000], seed=3)], []


def row_delete_shadows_older_readings(table, now):
    """An explicit row delete (inside grace: kept) over older TTL'd
    readings, some of them already expired."""
    pk, ck = _grid(40, 60)
    ldt = np.where(ck % 4 == 0, now - 4 * DAY, now + 5 * DAY)
    gone = (pk + ck) % 5 == 0
    dels = _row_deletions(table, pk[gone], ck[gone],
                          9_000_000 + ck[gone], now - 100)
    return [_cells(table, pk, ck, 1_000_000 + ck, ldt, seed=1),
            cb.CellBatch.concat([
                _cells(table, pk[~gone], ck[~gone],
                       2_000_000 + ck[~gone], ldt[~gone], seed=2), dels])], []


CASES = [all_live, expired_inside_grace, past_grace_guard_lets_go,
         past_grace_guard_holds_back, wide_partition,
         row_delete_shadows_older_readings]


def _land(cfs, batches, **writer_kw):
    for b in batches:
        w = SSTableWriter(Descriptor(cfs.directory, cfs.next_generation()),
                          cfs.table, **writer_kw)
        w.append(cb.merge_sorted([b]))
        w.finish()
    cfs.reload_sstables()


def _hashes(cfs, generations) -> dict:
    out = {}
    for rank, gen in enumerate(sorted(generations)):
        for fn in sorted(os.listdir(cfs.directory)):
            if f"-{gen}-" in fn and fn.endswith(COMPONENTS):
                with open(os.path.join(cfs.directory, fn), "rb") as f:
                    out[rank, fn.split("-")[-1]] = hashlib.sha256(
                        f.read()).hexdigest()
    return out


def _leg(tmp_path, engine, table, inputs, outside, round_cells):
    """One engine's compaction of `inputs` beside `outside`: (component
    hashes of its outputs, its cells as columns, counters that rose)."""
    cfs = ColumnFamilyStore(table, str(tmp_path / engine), commitlog=None)
    # small inputs in many segments; the wide partition in the store's own
    small = max(len(b) for b in inputs) < 65_536
    _land(cfs, inputs + outside,
          **({"segment_cells": 2048} if small else {}))
    live = cfs.live_sstables()
    by_gen = sorted(live, key=lambda r: r.desc.generation)
    before = {n: METRICS.counter(n) for n in FALLBACKS + (CONVERTED,)}
    task = CompactionTask(cfs, by_gen[:len(inputs)], engine=engine,
                          round_cells=round_cells, compress_pool=0)
    stats = task.execute()
    rose = {n: METRICS.counter(n) - v for n, v in before.items()}
    kept = {r.desc.generation for r in by_gen[len(inputs):]}
    outs = [r for r in cfs.live_sstables()
            if r.desc.generation not in kept]
    segs = [s for r in outs for s in r.scanner()]
    cols = {"flags": np.concatenate([s.flags for s in segs] or [[]]),
            "vlen": np.concatenate(
                [np.asarray(s.off[1:]) - np.asarray(s.val_start)
                 for s in segs] or [[]])}
    hashes = _hashes(cfs, [r.desc.generation for r in outs])
    for r in cfs.live_sstables():
        r.close()
    return hashes, cols, rose, stats


@pytest.mark.parametrize("round_cells", [3000, None],
                         ids=["small_rounds", "default_rounds"])
@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__)
def test_device_engine_writes_the_numpy_engines_bytes_on_ttl_rounds(
        tmp_path, case, round_cells):
    now = int(time.time())
    table = _table(case.__name__[:20], default_ttl=TTL)
    inputs, outside = case(table, now)
    want_h, want, rose_np, _ = _leg(tmp_path, "numpy", table, inputs,
                                    outside, round_cells)
    got_h, got, rose, stats = _leg(tmp_path, "device", table, inputs,
                                   outside, round_cells)
    assert got_h == want_h
    assert rose_np == {n: 0 for n in rose_np}
    # every round stayed resident, and the program converted exactly
    # the cells the numpy engine did
    converted = (want["flags"] & (cb.FLAG_EXPIRING | cb.FLAG_TOMBSTONE)) \
        == (cb.FLAG_EXPIRING | cb.FLAG_TOMBSTONE)
    assert [rose[n] for n in FALLBACKS] == [0, 0]
    assert rose[CONVERTED] == int(converted.sum())
    assert (got["vlen"][converted] == 0).all()
    assert stats["cells_written"] == len(want["flags"])
    if case is all_live:
        assert rose[CONVERTED] == 0 and want_h
        assert stats["cells_written"] == 40 * 60
    elif case is expired_inside_grace:
        assert converted.all() and len(converted) == 40 * 60
    elif case is past_grace_guard_lets_go:
        assert rose[CONVERTED] == 0 and len(converted) == 20 * 60
    elif case is past_grace_guard_holds_back:
        assert converted.all() and len(converted) == 40 * 60
    elif case is wide_partition:
        assert len(want["flags"]) == 150_300 and len(want_h) == 7
        assert rose[CONVERTED] == 50_000 + 6 * 17
    else:
        dead = (want["flags"] & cb.FLAG_ROW_DEL) != 0
        assert dead.sum() == 480 and 0 < rose[CONVERTED] < 40 * 15


def test_a_kept_expired_cell_is_gathered_without_its_value():
    """The host's payload gather follows the program's converted frame
    lengths: header kept, value gone, cell for cell as finalize_merged
    (the materialize path's spec) leaves them."""
    from cassandra_tpu.ops import device_write as dw
    now = int(time.time())
    table = _table("gather", default_ttl=TTL)
    pk, ck = _grid(5, 40)
    ldt = np.where(ck % 2 == 0, now - DAY, now + DAY)
    runs = [cb.merge_sorted([_cells(table, pk, ck, 1_000 * (r + 1) + ck,
                                    ldt, seed=r)]) for r in range(2)]
    want = cb.merge_sorted(runs, gc_before=now - 10 * DAY, now=now)
    got = dw.collect_merge_resident(dw.submit_merge_resident(
        runs, gc_before=now - 10 * DAY, now=now))
    assert isinstance(got, dw.DeviceRound) and got.n == len(want) == 200
    for name in ("payload", "off", "val_start"):
        np.testing.assert_array_equal(getattr(got, name),
                                      getattr(want, name), err_msg=name)
    np.testing.assert_array_equal(np.asarray(got.cols["flags8"])[:200],
                                  want.flags)
    np.testing.assert_array_equal(np.asarray(got.cols["ldt"])[:200],
                                  want.ldt)
    np.testing.assert_array_equal(np.asarray(got.cols["ttl"])[:200],
                                  want.ttl)
    # what the mesh lanes and merge_sorted_device take is the same batch
    host = dw.merge_sorted_device(runs, gc_before=now - 10 * DAY, now=now)
    np.testing.assert_array_equal(host.payload, want.payload)
    np.testing.assert_array_equal(host.flags, want.flags)


# ------------------------------------------- TWCS through the manager --

@pytest.fixture
def twcs(tmp_path, monkeypatch):
    """A TTL'd TWCS table (1-day windows, 30-day TTL, 10 days of grace)
    on an engine whose tasks see a TPU."""
    monkeypatch.setattr(task_mod, "tpu_backend", lambda: True)
    monkeypatch.setattr(CompactionTask, "DEVICE_MIN_CELLS", 1)
    schema = Schema()
    schema.create_keyspace("ttl")
    table = _table("series", default_ttl=TTL, compaction={
        "class": "TimeWindowCompactionStrategy",
        "compaction_window_unit": "DAYS", "compaction_window_size": 1})
    schema.add_table(table)
    eng = StorageEngine(str(tmp_path / "data"), schema,
                        commitlog_sync="batch")
    yield eng, table, eng.store("ttl", "series")
    eng.close()


def _window(table, day, slices, seed):
    """Day `day`'s readings (seconds since the epoch at its midnight),
    cut into `slices` sstables by time, each cell written when read."""
    pk, ck = _grid(12, 96)
    at = day * DAY + ck * 900                       # a reading per 15 min
    out = []
    for s in range(slices):
        m = ck * slices // 96 == s
        out.append(_cells(table, pk[m], ck[m], at[m] * 1_000_000 + 7,
                          at[m] + TTL, seed=seed + s))
    return out


def test_twcs_drops_the_expired_window_then_compacts_the_other_two(twcs):
    eng, table, cfs = twcs
    today = int(time.time()) // DAY
    old, mid, new = today - 45, today - 35, today - 1
    _land(cfs, _window(table, old, 1, 10) + _window(table, mid, 2, 20)
          + _window(table, new, 4, 30))
    assert len(cfs.live_sstables()) == 7
    before = {n: METRICS.counter(n) for n in FALLBACKS + (CONVERTED,)}
    cm = eng.compactions
    cm.submit_background(cfs)
    assert cm.run_pending() == 3
    done = cm.completed[-3:]
    # the drop first, then the older window, then the closed one
    assert [bool(s.get("dropped")) for s in done] == [True, False, False]
    assert [s["inputs"] for s in done] == [1, 2, 4]
    assert done[0]["bytes_read"] == 0 and done[0]["outputs"] == 0
    assert [(s["engine"], s["engine_chosen"]) for s in done[1:]] \
        == [("device", True)] * 2
    rose = {n: METRICS.counter(n) - v for n, v in before.items()}
    assert [rose[n] for n in FALLBACKS] == [0, 0]
    assert rose[CONVERTED] == 12 * 96          # all of the repaired window
    live = sorted(cfs.live_sstables(), key=lambda r: r.max_ts)
    assert len(live) == 2 and [r.n_cells for r in live] == [12 * 96] * 2
    assert live[0].n_tombstones == 12 * 96 and live[1].n_tombstones == 0
    assert min(r.min_ts for r in live) // 1_000_000 // DAY == mid
    # nothing is left to do, and a read sees the closed window only
    cm.submit_background(cfs)
    assert cm.run_pending() == 0
