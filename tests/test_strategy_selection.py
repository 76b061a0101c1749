"""Strategy-selection pins: LeveledCompactionStrategy level overflow,
TimeWindowCompactionStrategy window grouping + fully-expired drop —
the `next_background_task` behaviors ROADMAP item 3's adaptive layer
will build on (reference models: LeveledCompactionStrategyTest,
TimeWindowCompactionStrategyTest.testDropExpiredSSTables)."""
import time

import pytest

from cassandra_tpu.compaction.strategies import (
    LeveledCompactionStrategy, TimeWindowCompactionStrategy,
    UnifiedCompactionStrategy, get_strategy)
from cassandra_tpu.schema import (COL_ROW_LIVENESS, Schema, TableParams,
                                  make_table)
from cassandra_tpu.storage.engine import StorageEngine
from cassandra_tpu.storage.mutation import Mutation
from cassandra_tpu.utils import timeutil


def new_engine(tmp_path, compaction=None, gc_grace=864000):
    schema = Schema()
    schema.create_keyspace("ks")
    params = TableParams(gc_grace_seconds=gc_grace)
    if compaction:
        params.compaction = compaction
    t = make_table("ks", "t", pk=["id"], ck=["c"],
                   cols={"id": "int", "c": "int", "v": "text"},
                   params=params)
    schema.add_table(t)
    eng = StorageEngine(str(tmp_path / "data"), schema,
                        commitlog_sync="batch")
    return eng, t, eng.store("ks", "t")


def put(eng, t, p, c, v, ts=None):
    m = Mutation(t.id, t.columns["id"].cql_type.serialize(p))
    ck = t.serialize_clustering([c])
    ts = ts or timeutil.now_micros()
    m.add(ck, COL_ROW_LIVENESS, b"", b"", ts)
    m.add(ck, t.columns["v"].column_id, b"",
          t.columns["v"].cql_type.serialize(v), ts)
    eng.apply(m)


def put_dead(eng, t, p, c, ts, ldt):
    """A cell tombstone (the shape a TTL'd cell takes once a merge past
    its expiry converted it)."""
    from cassandra_tpu.storage.cellbatch import FLAG_TOMBSTONE
    m = Mutation(t.id, t.columns["id"].cql_type.serialize(p))
    ck = t.serialize_clustering([c])
    m.add(ck, t.columns["v"].column_id, b"", b"", ts, ldt=ldt,
          flags=FLAG_TOMBSTONE)
    eng.apply(m)


def test_lcs_level_overflow_promotes_one_victim(tmp_path):
    """A level above its byte target pushes its LARGEST sstable into
    the next level, merged with every overlapping run there — the
    LeveledManifest overflow rule (no L0 backlog involved)."""
    eng, t, cfs = new_engine(
        tmp_path,
        compaction={"class": "LeveledCompactionStrategy",
                    # tiny level target so two small flushes overflow L1
                    "sstable_size_in_mb": 0.001, "fanout_size": 2,
                    "l0_threshold": 4})
    for gen in range(3):
        for p in range(40):
            put(eng, t, p + gen * 40, 0, "x" * 120)
        cfs.flush()
    # pin the flushed sstables to L1/L2 by rewriting their level stats
    ssts = sorted(cfs.live_sstables(), key=lambda s: s.desc.generation)
    for s, lvl in zip(ssts, (1, 1, 2)):
        s.stats["level"] = lvl
    strat = LeveledCompactionStrategy(
        cfs, {"sstable_size_in_mb": 0.001, "fanout_size": 2,
              "l0_threshold": 4})
    task = strat.next_background_task()
    assert task is not None
    # the victim is the LARGEST L1 sstable; every overlapping L2 run
    # rides along; the output lands one level down
    victim = max((s for s in ssts if s.level == 1),
                 key=lambda s: s.data_size)
    assert victim in task.inputs
    assert task.level == 2
    assert all(s.level in (1, 2) for s in task.inputs)
    l2 = [s for s in ssts if s.level == 2]
    overlapping = [s for s in l2
                   if s.min_token() <= victim.max_token()
                   and victim.min_token() <= s.max_token()]
    assert set(overlapping) <= set(task.inputs)
    # output-size cap carries the strategy's shard target
    assert task.max_output_bytes == strat.max_sstable_bytes
    eng.close()


def test_lcs_no_task_when_levels_fit(tmp_path):
    eng, t, cfs = new_engine(
        tmp_path,
        compaction={"class": "LeveledCompactionStrategy",
                    "sstable_size_in_mb": 160, "l0_threshold": 4})
    for p in range(20):
        put(eng, t, p, 0, "v")
    cfs.flush()
    assert get_strategy(cfs).next_background_task() is None
    eng.close()


def test_twcs_window_grouping_current_vs_old(tmp_path):
    """One sstable per OLD window is the goal: any old window holding
    more than one sstable is compacted first; the CURRENT window runs
    STCS and only compacts at min_threshold."""
    eng, t, cfs = new_engine(
        tmp_path,
        compaction={"class": "TimeWindowCompactionStrategy",
                    "compaction_window_unit": "HOURS",
                    "compaction_window_size": 1})
    now_us = timeutil.now_micros()
    hour = 3600 * 1_000_000
    # current window: 3 sstables (below min_threshold=4 -> untouched)
    for i in range(3):
        put(eng, t, i, 0, f"cur{i}", ts=now_us + i)
        cfs.flush()
    strat = get_strategy(cfs)
    assert strat.next_background_task() is None
    # an old window accumulates 2 sstables -> grouped into one task
    for i in range(2):
        put(eng, t, 10 + i, 0, f"old{i}", ts=now_us - 7 * hour + i)
        cfs.flush()
    task = get_strategy(cfs).next_background_task()
    assert task is not None and len(task.inputs) == 2
    wins = {strat._window_of(s) for s in task.inputs}
    assert len(wins) == 1
    assert wins.pop() != strat._window_of(
        max(cfs.live_sstables(), key=lambda s: s.max_ts or 0))
    # current window reaches min_threshold -> STCS inside the window
    task.execute()
    put(eng, t, 3, 0, "cur3", ts=now_us + 3)
    cfs.flush()
    task2 = get_strategy(cfs).next_background_task()
    assert task2 is not None
    assert {strat._window_of(s) for s in task2.inputs} == {
        strat._window_of(max(cfs.live_sstables(),
                             key=lambda s: s.max_ts or 0))}
    assert len(task2.inputs) >= 4
    eng.close()


def test_twcs_fully_expired_drop(tmp_path):
    """SSTables whose every cell is an expired tombstone past gc grace,
    with no overlapping older data and an empty memtable, are selected
    for a rewrite-free DROP — before any window compaction
    (TimeWindowCompactionStrategy.java:128)."""
    eng, t, cfs = new_engine(
        tmp_path,
        compaction={"class": "TimeWindowCompactionStrategy",
                    "compaction_window_unit": "HOURS",
                    "compaction_window_size": 1},
        gc_grace=0)
    now = int(time.time())
    # disjoint partition ranges so the expired sstable has no
    # overlapping-older-data concern with the live one; every cell is
    # a tombstone whose ldt is long past (gc_grace=0)
    for p in range(5):
        put_dead(eng, t, p, 0, ts=1_000_000 + p, ldt=now - 7200)
    cfs.flush()
    for p in range(100, 105):
        put(eng, t, p, 0, "live", ts=2_000_000 + p)
    cfs.flush()
    strat = get_strategy(cfs)
    expired = strat._fully_expired()
    assert len(expired) == 1
    task = strat.next_background_task()
    assert task is not None and list(task.inputs) == expired
    before = len(cfs.live_sstables())
    stats = task.execute()
    # everything purged: the expired sstable vanishes, no output lands
    assert stats["outputs"] == 0
    assert len(cfs.live_sstables()) == before - 1
    # a hot memtable blocks the drop (purge guard consults it)
    put_dead(eng, t, 200, 0, ts=1, ldt=now - 7200)
    cfs.flush()
    put(eng, t, 3, 0, "resurrect", ts=1)
    assert strat._fully_expired() == []
    eng.close()


def put_ttl(eng, t, p, c, ts, ldt, ttl=3600, converted=False):
    """A TTL'd row as an INSERT leaves it (row liveness + value, both
    expiring at `ldt`), or as a merge past its expiry rewrote it."""
    from cassandra_tpu.storage.cellbatch import (FLAG_EXPIRING,
                                                 FLAG_ROW_LIVENESS,
                                                 FLAG_TOMBSTONE)
    dead = FLAG_TOMBSTONE if converted else 0
    m = Mutation(t.id, t.columns["id"].cql_type.serialize(p))
    ck = t.serialize_clustering([c])
    m.add(ck, COL_ROW_LIVENESS, b"", b"", ts, ldt=ldt, ttl=ttl,
          flags=FLAG_ROW_LIVENESS | FLAG_EXPIRING | dead)
    m.add(ck, t.columns["v"].column_id, b"",
          b"" if converted else t.columns["v"].cql_type.serialize("x"),
          ts, ldt=ldt, ttl=ttl, flags=FLAG_EXPIRING | dead)
    eng.apply(m)


TWCS_HOURLY = {"class": "TimeWindowCompactionStrategy",
               "compaction_window_unit": "HOURS",
               "compaction_window_size": 1}


@pytest.mark.parametrize("converted", [False, True],
                         ids=["never_rewritten", "rewritten_as_tombstones"])
def test_twcs_drops_a_window_of_ttl_cells_past_gc_grace_whole(
        tmp_path, converted):
    """Upstream's rule (CompactionController.getFullyExpiredSSTables):
    max local deletion time before gc_before. Whether a compaction ever
    rewrote the expired cells as tombstones does not matter: the sstable
    goes without being decoded."""
    eng, t, cfs = new_engine(tmp_path, compaction=TWCS_HOURLY)
    now = int(time.time())
    for p in range(5):
        put_ttl(eng, t, p, 0, ts=1_000_000 + p, ldt=now - 20 * 86400,
                converted=converted)
    cfs.flush()
    for p in range(100, 105):
        put_ttl(eng, t, p, 0, ts=9_000_000_000 + p, ldt=now + 86400)
    cfs.flush()
    old = min(cfs.live_sstables(), key=lambda s: s.max_ts)
    assert old.n_tombstones == (old.n_cells if converted else 0)
    strat = get_strategy(cfs)
    assert strat._fully_expired() == [old]
    task = strat.next_background_task()
    assert task.drop_only and list(task.inputs) == [old]
    scanned = []
    old.scanner = lambda *a, **kw: scanned.append(1)
    stats = task.execute()
    assert stats["dropped"] and stats["outputs"] == 0 and not scanned
    assert [s.max_ts for s in cfs.live_sstables()] == [9_000_000_104]
    eng.close()


@pytest.mark.parametrize("survivor", ["a_live_cell_without_ttl",
                                      "a_ttl_cell_inside_gc_grace"])
def test_one_cell_that_is_not_past_grace_keeps_the_sstable(tmp_path,
                                                           survivor):
    """A live cell without TTL carries NO_DELETION_TIME, so max_ldt says
    it is there; so does a TTL'd cell that ran out inside gc grace."""
    eng, t, cfs = new_engine(tmp_path, compaction=TWCS_HOURLY)
    now = int(time.time())
    for p in range(5):
        put_ttl(eng, t, p, 0, ts=1_000_000 + p, ldt=now - 20 * 86400)
    if survivor == "a_live_cell_without_ttl":
        put(eng, t, 7, 0, "forever", ts=1_000_007)
    else:
        put_ttl(eng, t, 7, 0, ts=1_000_007, ldt=now - 86400)
    cfs.flush()
    strat = get_strategy(cfs)
    assert strat._fully_expired() == []
    assert strat.next_background_task() is None
    eng.close()


def test_expired_sstables_do_not_block_one_another(tmp_path):
    """Two expired sstables of one window overlap in tokens and in time:
    neither holds anything the other's cells shadow that would stay, so
    both go in one drop (upstream lets expired candidates not block one
    another). An sstable that stays, with older data in their span,
    blocks both."""
    eng, t, cfs = new_engine(tmp_path, compaction=TWCS_HOURLY)
    now = int(time.time())
    for half in range(2):
        for p in range(20):
            put_ttl(eng, t, p, half, ts=1_000_000 + 2 * p + half,
                    ldt=now - 20 * 86400)
        cfs.flush()
    a, b = cfs.live_sstables()
    assert a.min_ts <= b.max_ts and b.min_ts <= a.max_ts
    strat = get_strategy(cfs)
    assert sorted(strat._fully_expired(), key=id) == sorted([a, b], key=id)
    task = strat.next_background_task()
    assert task.drop_only and len(task.inputs) == 2
    # older live data of the same partitions lands before the task runs:
    # the re-check refuses the drop and the task merges instead
    for p in range(20):
        put(eng, t, p, 5, "old and live", ts=500 + p)
    cfs.flush()
    assert strat._fully_expired() == []
    assert not task._drop_safe()
    eng.close()


def _component_hashes(cfs, gens):
    """{(generation, component): sha256} for the given generations —
    the check_compaction_ab.py byte-identity contract."""
    import hashlib
    import os
    out = {}
    for s in cfs.live_sstables():
        if s.desc.generation not in gens:
            continue
        d = os.path.dirname(s.desc.path("Data.db"))
        prefix = os.path.basename(s.desc.path("Data.db"))[:-len("Data.db")]
        for fn in sorted(os.listdir(d)):
            if not fn.startswith(prefix):
                continue
            with open(os.path.join(d, fn), "rb") as f:
                out[(s.desc.generation, fn[len(prefix):])] = \
                    hashlib.sha256(f.read()).hexdigest()
    return out


def _burst_fixture(tmp_path, table):
    """Four identical fixed-timestamp flushes — an STCS bucket one
    selection away from compacting. Takes a SHARED TableMetadata so
    the two legs' sstables are byte-comparable (Statistics.db embeds
    the table id, which make_table mints randomly)."""
    schema = Schema()
    schema.create_keyspace("ks")
    schema.add_table(table)
    eng = StorageEngine(str(tmp_path / "data"), schema,
                        commitlog_sync="batch")
    cfs = eng.store("ks", "t")
    ts = 1_000_000
    for gen in range(4):
        for p in range(32):
            put(eng, table, p + gen * 32, 0, "v" * 64, ts=ts)
            ts += 1
        cfs.flush()
    return eng, cfs


def test_mid_flight_strategy_flip_no_orphan_bytes_identical(tmp_path):
    """A hot STCS->LCS flip while a compaction task is in flight (the
    adaptive controller's actuation seam,
    ColumnFamilyStore.set_compaction_params) must never orphan or
    re-select the task's inputs: the manager's claim registry refuses
    the new strategy's overlapping selection, the in-flight task
    finishes under its OLD plan, and the resulting sstables are
    byte-identical to a no-flip run."""
    stcs = {"class": "SizeTieredCompactionStrategy", "min_threshold": 4}
    table = make_table("ks", "t", pk=["id"], ck=["c"],
                       cols={"id": "int", "c": "int", "v": "text"},
                       params=TableParams(compaction=dict(stcs)))

    # --- leg B FIRST: identical fixture, no flip (the flip leg
    # mutates the SHARED table params, so it must run second)
    eng_b, cfs_b = _burst_fixture(tmp_path / "b", table)
    task_b = get_strategy(cfs_b).next_background_task()
    assert task_b is not None
    assert eng_b.compactions._claim(cfs_b, task_b.inputs)
    task_b.execute()
    eng_b.compactions._release(cfs_b, task_b.inputs)
    live_b = {s.desc.generation for s in cfs_b.live_sstables()}
    hashes_b = _component_hashes(cfs_b, live_b)
    eng_b.close()

    # --- leg A: flip mid-flight
    eng_a, cfs_a = _burst_fixture(tmp_path / "a", table)
    mgr = eng_a.compactions
    task = get_strategy(cfs_a).next_background_task()
    assert task is not None and len(task.inputs) == 4
    assert mgr._claim(cfs_a, task.inputs)   # in flight now
    inputs_a = {s.desc.generation for s in task.inputs}
    old = cfs_a.set_compaction_params(
        {"class": "LeveledCompactionStrategy",
         "sstable_size_in_mb": 160, "l0_threshold": 4})
    assert old["class"] == "SizeTieredCompactionStrategy"
    # the NEW strategy sees the same four L0 sstables and wants them —
    # but the claim registry holds: the manager would DROP this
    # selection (_execute_task returns None), never double-compact
    resel = get_strategy(cfs_a).next_background_task()
    assert resel is not None
    assert not mgr._claim(cfs_a, resel.inputs)
    # the in-flight task completes under the OLD (STCS) plan
    stats = task.execute()
    mgr._release(cfs_a, task.inputs)
    assert stats["inputs"] == 4
    live_a = {s.desc.generation for s in cfs_a.live_sstables()}
    assert not (inputs_a & live_a)   # inputs replaced, none orphaned
    out_gens_a = live_a - inputs_a
    hashes_a = _component_hashes(cfs_a, out_gens_a)
    eng_a.close()

    assert out_gens_a == live_b
    assert hashes_a == hashes_b
    assert len(hashes_a) > 0


def test_strategy_registry_covers_all_four(tmp_path):
    """get_strategy resolves every shipped class (the ROADMAP item 3
    note that 'only STCS exists' is stale — pin the roster)."""
    for cls_name, cls in (
            ("LeveledCompactionStrategy", LeveledCompactionStrategy),
            ("TimeWindowCompactionStrategy",
             TimeWindowCompactionStrategy),
            ("UnifiedCompactionStrategy", UnifiedCompactionStrategy)):
        eng, t, cfs = new_engine(tmp_path / cls_name,
                                 compaction={"class": cls_name})
        assert isinstance(get_strategy(cfs).unrepaired, cls)
        eng.close()
