"""A served compaction chooses its engine itself (compaction/task.py
choose_engine, PR 27): `device` on a TPU backend for inputs above the size
floor whose statistics show nothing the resident program sends to the
host, the host engine otherwise; explicit arguments win; on the CPU
nothing changed. Plus what makes the choice visible (counters, events,
compactionstats, the vtable) and the spans PR 27 put under `cql.execute`.
"""
import collections

import pytest

from cassandra_tpu.compaction import task as task_mod
from cassandra_tpu.compaction.task import CompactionTask, choose_engine
from cassandra_tpu.cql import Session
from cassandra_tpu.schema import Schema
from cassandra_tpu.service.metrics import GLOBAL as METRICS
from cassandra_tpu.storage import cellbatch as cb
from cassandra_tpu.storage.engine import StorageEngine
from cassandra_tpu.utils import pipeline_ledger as pl

HOST = task_mod.host_engine()       # native where g++ built it, else numpy


class FakeInput:
    def __init__(self, n_cells, cell_flags=0):
        self.n_cells, self.cell_flags = n_cells, cell_flags


def _probe(answer, calls):
    def probe():
        calls.append(answer)
        return answer
    return probe


# ------------------------------------------------------- choose_engine --

def test_device_on_a_tpu_probe_above_the_floor():
    calls = []
    inputs = [FakeInput(CompactionTask.DEVICE_MIN_CELLS // 2 + 1)] * 2
    engine, why = choose_engine(inputs, _probe(True, calls))
    assert engine == "device" and calls == [True] and "TPU" in why


def test_host_engine_below_the_floor_and_the_probe_is_never_asked():
    calls = []
    inputs = [FakeInput(CompactionTask.DEVICE_MIN_CELLS // 4)] * 3
    engine, why = choose_engine(inputs, _probe(True, calls))
    assert engine == HOST and calls == [] and "floor" in why


def test_the_floor_is_one_full_device_round():
    assert CompactionTask.DEVICE_MIN_CELLS \
        == CompactionTask.ROUND_CELLS_DEVICE
    big = [FakeInput(CompactionTask.DEVICE_MIN_CELLS)]
    assert choose_engine(big, lambda: True)[0] == "device"
    assert choose_engine([FakeInput(CompactionTask.DEVICE_MIN_CELLS - 1)],
                         lambda: True)[0] == HOST


@pytest.mark.parametrize("flag", [cb.FLAG_RANGE_BOUND, cb.FLAG_COUNTER,
                                  cb.FLAG_RANGE_BOUND | cb.FLAG_EXPIRING])
def test_host_engine_for_what_the_resident_program_cannot_encode(flag):
    calls = []
    n = CompactionTask.DEVICE_MIN_CELLS
    inputs = [FakeInput(n), FakeInput(n, cb.FLAG_TOMBSTONE | flag)]
    engine, why = choose_engine(inputs, _probe(True, calls))
    assert engine == HOST and calls == []
    assert "range tombstones or counters" in why


def test_plain_tombstones_and_row_markers_stay_on_the_device():
    n = CompactionTask.DEVICE_MIN_CELLS
    flags = cb.FLAG_TOMBSTONE | cb.FLAG_ROW_DEL | cb.FLAG_PARTITION_DEL \
        | cb.FLAG_ROW_LIVENESS | cb.FLAG_COMPLEX_DEL
    assert choose_engine([FakeInput(n, flags)], lambda: True)[0] == "device"


@pytest.mark.parametrize("flags", [
    cb.FLAG_EXPIRING, cb.FLAG_EXPIRING | cb.FLAG_ROW_LIVENESS,
    cb.FLAG_EXPIRING | cb.FLAG_TOMBSTONE | cb.FLAG_ROW_DEL])
def test_ttls_stay_on_the_device(flags):
    """The resident program converts a kept expired cell itself (PR 31):
    a table with default_time_to_live reaches the device."""
    calls = []
    n = CompactionTask.DEVICE_MIN_CELLS
    engine, why = choose_engine([FakeInput(n), FakeInput(n, flags)],
                                _probe(True, calls))
    assert engine == "device" and calls == [True] and "TPU" in why


def test_host_engine_when_an_input_does_not_say_what_it_holds():
    n = CompactionTask.DEVICE_MIN_CELLS
    engine, why = choose_engine([FakeInput(n), FakeInput(n, None)],
                                lambda: True)
    assert engine == HOST and "do not record" in why


def test_no_tpu_means_the_host_engine():
    n = CompactionTask.DEVICE_MIN_CELLS
    assert choose_engine([FakeInput(n)], lambda: False) \
        == (HOST, "no TPU backend")


def test_the_default_probe_reads_jax_and_says_cpu_here():
    assert task_mod.tpu_backend() is False
    n = CompactionTask.DEVICE_MIN_CELLS
    assert choose_engine([FakeInput(n)])[0] == HOST


def test_without_the_native_library_the_host_engine_is_numpy(monkeypatch):
    from cassandra_tpu.ops import host_merge
    monkeypatch.setattr(host_merge, "available", lambda: False)
    assert task_mod.host_engine() == "numpy"
    assert choose_engine([FakeInput(5)], lambda: True)[0] == "numpy"


# ------------------------------------------------------ real sstables --

@pytest.fixture
def engine(tmp_path):
    eng = StorageEngine(str(tmp_path / "data"), Schema(),
                        commitlog_sync="batch")
    yield eng
    eng.close()


@pytest.fixture
def session(engine):
    s = Session(engine)
    s.execute("CREATE KEYSPACE ks WITH replication = "
              "{'class': 'SimpleStrategy', 'replication_factor': 1}")
    s.execute("USE ks")
    return s


def _flushed(engine, session, table, statements, flushes=2):
    """`flushes` sstables of table ks.<table>, the statements run before
    each flush."""
    cfs = engine.store("ks", table)
    for i in range(flushes):
        for stmt in statements:
            session.execute(stmt.format(i=i))
        cfs.flush()
    return cfs


PLAIN = ["INSERT INTO {t} (k, c, v) VALUES ({{i}}, 1, 'a')",
         "INSERT INTO {t} (k, c, v) VALUES ({{i}}, 2, 'b')",
         "DELETE v FROM {t} WHERE k = {{i}} AND c = 2"]
KINDS = {
    "plain": (PLAIN, 0, "device"),
    "ttl": (PLAIN + ["INSERT INTO {t} (k, c, v) VALUES ({{i}}, 3, 'x') "
                     "USING TTL 100000"], cb.FLAG_EXPIRING, "device"),
    "range_tombstone": (PLAIN + ["DELETE FROM {t} WHERE k = {{i}} "
                                 "AND c > 5 AND c < 9"],
                        cb.FLAG_RANGE_BOUND, HOST),
}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_writer_records_cell_kinds_and_the_task_reads_them(
        engine, session, monkeypatch, kind):
    stmts, flag, want = KINDS[kind]
    session.execute(f"CREATE TABLE t_{kind} (k int, c int, v text, "
                    "PRIMARY KEY (k, c))")
    cfs = _flushed(engine, session, f"t_{kind}",
                   [s.format(t=f"t_{kind}") for s in stmts])
    ssts = cfs.live_sstables()
    assert len(ssts) == 2
    for s in ssts:
        assert s.cell_flags is not None
        assert s.cell_flags & cb.FLAG_TOMBSTONE
        assert bool(s.cell_flags & flag) == bool(flag)
    monkeypatch.setattr(CompactionTask, "DEVICE_MIN_CELLS", 1)
    task = CompactionTask(cfs, ssts, backend_probe=lambda: True)
    assert task.engine_chosen and task.engine == want


def test_counter_inputs_go_to_the_host_engine(engine, session, monkeypatch):
    session.execute("CREATE TABLE cnt (k int PRIMARY KEY, n counter)")
    cfs = _flushed(engine, session, "cnt",
                   ["UPDATE cnt SET n = n + 1 WHERE k = {i}"])
    assert all(s.cell_flags & cb.FLAG_COUNTER for s in cfs.live_sstables())
    monkeypatch.setattr(CompactionTask, "DEVICE_MIN_CELLS", 1)
    task = CompactionTask(cfs, cfs.live_sstables(),
                          backend_probe=lambda: True)
    assert task.engine == HOST and task.engine_chosen


def test_an_sstable_without_the_stat_reads_none(engine, session):
    session.execute("CREATE TABLE old (k int PRIMARY KEY, v text)")
    cfs = _flushed(engine, session, "old",
                   ["INSERT INTO old (k, v) VALUES ({i}, 'x')"], flushes=1)
    sst = cfs.live_sstables()[0]
    assert sst.cell_flags == cb.FLAG_ROW_LIVENESS
    del sst.stats["cell_flags"]     # as written before PR 27
    assert sst.cell_flags is None


@pytest.fixture
def plain_store(engine, session, monkeypatch):
    session.execute("CREATE TABLE p (k int, c int, v text, "
                    "PRIMARY KEY (k, c))")
    monkeypatch.setattr(CompactionTask, "DEVICE_MIN_CELLS", 1)
    return _flushed(engine, session, "p", [s.format(t="p") for s in PLAIN],
                    flushes=4)


@pytest.mark.parametrize("kw,want", [
    ({"engine": "numpy"}, "numpy"),
    ({"engine": "native"}, "native"),
    ({"engine": "device"}, "device"),
    ({"use_device": True}, "device"),
    ({"use_device": False}, "numpy"),
    ({"engine": "numpy", "use_device": True}, "numpy"),
])
def test_explicit_arguments_win_over_the_choice(plain_store, kw, want):
    calls = []
    task = CompactionTask(plain_store, plain_store.live_sstables(),
                          backend_probe=_probe(True, calls), **kw)
    assert task.engine == want and not task.engine_chosen and calls == []
    assert task.engine_why == "named by the caller"


def test_on_the_cpu_a_task_resolves_as_it_always_did(plain_store):
    task = CompactionTask(plain_store, plain_store.live_sstables())
    assert task.engine == HOST and task.engine_chosen
    assert task.round_cells == CompactionTask.ROUND_CELLS_HOST


def test_the_module_probe_is_what_an_unnamed_task_asks(plain_store,
                                                       monkeypatch):
    monkeypatch.setattr(task_mod, "tpu_backend", lambda: True)
    task = CompactionTask(plain_store, plain_store.live_sstables())
    assert task.engine == "device" and task.engine_chosen
    assert task.round_cells == CompactionTask.ROUND_CELLS_DEVICE


def _ring():
    ring = collections.deque(maxlen=pl.RING_CAP)
    saved, pl.RING = pl.RING, ring
    return ring, saved


@pytest.mark.parametrize("tpu,want", [(True, "device"), (False, HOST)])
def test_the_managers_compaction_chooses_and_says_so(
        engine, plain_store, monkeypatch, tpu, want):
    """STCS picks the four flushed sstables; the task it builds chooses;
    the counter, the stats, the history, the events and the select span
    say what it chose; the rows survive; device and host engines write
    the same cell kinds."""
    from cassandra_tpu.service import diagnostics
    from cassandra_tpu.storage.rows import rows_from_batch
    monkeypatch.setattr(task_mod, "tpu_backend", lambda: tpu)
    cm = engine.compactions
    before = METRICS.counter(f"compaction.engine_chosen.{want}")
    seen = []
    owner = object()
    diagnostics.GLOBAL.set_demand(owner, True)
    diagnostics.GLOBAL.subscribe(seen.append)
    ring, saved = _ring()
    try:
        cm.submit_background(plain_store)
        assert cm.run_pending() == 1
    finally:
        pl.RING = saved
        diagnostics.GLOBAL.unsubscribe(seen.append)
        diagnostics.GLOBAL.set_demand(owner, False)
    assert METRICS.counter(f"compaction.engine_chosen.{want}") == before + 1
    stats = cm.completed[-1]
    assert stats["engine"] == want and stats["engine_chosen"] is True
    assert plain_store.compaction_history[-1]["engine"] == want
    events = {e.type: e.fields for e in seen
              if e.type.startswith("compaction.")}
    assert events["compaction.start"]["engine"] == want
    assert events["compaction.start"]["engine_chosen"] is True
    assert events["compaction.start"]["engine_why"] == (
        "TPU backend, resident-encodable inputs" if tpu
        else "no TPU backend")
    assert events["compaction.finish"]["engine"] == want
    select = [dict(zip(pl.RECORD_FIELDS, r)) for r in ring
              if r[0] == "compaction.select"]
    assert select and select[0]["items"] == 4 and select[0]["cells"] > 0
    (out,) = plain_store.live_sstables()
    assert out.cell_flags == cb.FLAG_TOMBSTONE | cb.FLAG_ROW_LIVENESS
    assert len(list(rows_from_batch(plain_store.table,
                                    plain_store.scan_all()))) == 8


def test_a_running_task_shows_its_engine(engine, plain_store):
    """nodetool compactionstats and system_views.compactions_in_progress
    carry the engine of each running task."""
    from cassandra_tpu.compaction.executor import CompactionProgress
    from cassandra_tpu.tools import nodetool
    info = CompactionProgress(keyspace="ks", table="p", total_bytes=10)
    info.engine = "device"
    engine.compactions.active.begin(info)
    try:
        (row,) = nodetool.compactionstats(engine)["active_compactions"]
        assert row["engine"] == "device"
        rows = Session(engine).execute(
            "SELECT table_name, engine FROM "
            "system_views.compactions_in_progress").rows
        assert rows == [("p", "device")]
    finally:
        engine.compactions.active.finish(info)


def test_the_task_stamps_its_progress_handle(plain_store):
    from cassandra_tpu.compaction.executor import CompactionProgress
    info = CompactionProgress(keyspace="ks", table="p")
    assert info.snapshot()["engine"] == ""
    task = CompactionTask(plain_store, plain_store.live_sstables())
    task.progress = info
    task.execute()
    assert info.snapshot()["engine"] == HOST


# ----------------------------------------------------------- settings --

def test_device_compress_is_off_by_default_and_still_a_knob(engine):
    from cassandra_tpu.config import Config
    assert Config().compaction_device_compress is False
    assert engine.settings.get("compaction_device_compress") is False
    engine.settings.set("compaction_device_compress", True)
    assert engine._device_compress() is True


def test_a_device_task_inherits_the_default_off_gate(plain_store):
    task = CompactionTask(plain_store, plain_store.live_sstables(),
                          engine="device")
    gate = task._device_compress_gate()
    assert (gate() if callable(gate) else gate) is False
    pinned = CompactionTask(plain_store, plain_store.live_sstables(),
                            engine="device", device_compress=True)
    assert pinned._device_compress_gate() is True


@pytest.mark.parametrize("block,want", [
    ({}, None),
    ({"commitlog_sync": "periodic", "commitlog_sync_period": "10s"},
     ("periodic", 10000)),
    ({"commitlog_sync": "group"}, ("group", 10000)),
])
def test_a_node_syncs_every_write_unless_its_config_names_a_mode(block,
                                                                 want):
    from cassandra_tpu.tools.noded import _engine_opts
    opts = _engine_opts({"config": block})
    if want is None:
        assert "commitlog_sync" not in opts
    else:
        assert (opts["commitlog_sync"],
                opts["commitlog_sync_period_ms"]) == want


def test_the_engine_hands_the_period_to_its_commitlog(tmp_path):
    eng = StorageEngine(str(tmp_path / "d"), Schema(),
                        commitlog_sync="periodic",
                        commitlog_sync_period_ms=10000)
    try:
        assert eng.commitlog.sync_mode == "periodic"
        assert eng.commitlog.sync_period_ms == 10000
    finally:
        eng.close()


# -------------------------------------------------------------- spans --

def test_a_read_and_an_update_are_no_longer_dark(engine, session):
    session.execute("CREATE TABLE s (k text PRIMARY KEY, a text, b text)")
    session.execute("INSERT INTO s (k, a, b) VALUES ('k1', 'x', 'y')")
    engine.store("ks", "s").flush()
    ring, saved = _ring()
    try:
        session.execute("UPDATE s SET a = 'z' WHERE k = 'k1'")
        assert session.execute("SELECT * FROM s WHERE k = 'k1'").rows \
            == [("k1", "z", "y")]
    finally:
        pl.RING = saved
    recs = [dict(zip(pl.RECORD_FIELDS, r)) for r in ring]
    by_name = {r["name"]: r for r in recs}
    write = by_name["engine.write"]
    for child, kind in (("commitlog.append", "busy"),
                        ("memtable.apply", "busy"),
                        ("commitlog.wait", "stall")):
        assert by_name[child]["parent"] == write["id"]
        assert by_name[child]["kind"] == kind
    assert write["bytes"] > 0
    read = by_name["engine.read"]
    assert read["items"] == 1           # one sstable consulted
    # the order inside an apply: append, memtable, then the wait
    assert by_name["commitlog.append"]["end"] \
        <= by_name["memtable.apply"]["start"] \
        <= by_name["commitlog.wait"]["start"]
