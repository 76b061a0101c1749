"""The one span primitive (utils/pipeline_ledger.py): parent/child and
self-time arithmetic, one write to ledger stage + profile key + ring,
the ring's bound, task ids carried across threads, the span set of a
device-engine compaction and of a wire request, and the granularity
bound (spans per task counted, never timed)."""
from __future__ import annotations

import collections
import json
import subprocess
import sys
import threading

import numpy as np
import pytest

from cassandra_tpu.compaction.task import CompactionTask
from cassandra_tpu.ops.codec import CompressionParams
from cassandra_tpu.schema import TableParams, make_table
from cassandra_tpu.storage import cellbatch as cb
from cassandra_tpu.storage.sstable import Descriptor, SSTableWriter
from cassandra_tpu.storage.sstable.compress_pool import CompressorPool
from cassandra_tpu.storage.table import ColumnFamilyStore
from cassandra_tpu.tools import bulk
from cassandra_tpu.utils import pipeline_ledger as pl


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


@pytest.fixture
def clock(monkeypatch):
    c = FakeClock()
    monkeypatch.setattr(pl, "CLOCK", c)
    # the thread's CPU reading is reused for CPU_REUSE_S of CLOCK: one
    # an earlier test took on another clock must not be reused here
    vars(pl._TLS).pop("cpu_at", None)
    return c


@pytest.fixture
def ring(monkeypatch):
    """A ring of this test's own: the process-global one is shared with
    whatever else the worker runs."""
    r = collections.deque(maxlen=pl.RING_CAP)
    monkeypatch.setattr(pl, "RING", r)
    return r


def _records(ring_) -> list:
    return [dict(zip(pl.RECORD_FIELDS, r)) for r in ring_]


def _self_seconds(recs: list) -> dict:
    by_id = {r["id"]: r for r in recs}
    own = {r["id"]: r["end"] - r["start"] for r in recs}
    for r in recs:
        p = by_id.get(r["parent"])
        if p is not None and r["start"] >= p["start"]:
            own[p["id"]] -= r["end"] - r["start"]
    return own


# ------------------------------------------------------------ primitive --

@pytest.mark.parametrize("module", ["cassandra_tpu.utils.pipeline_ledger",
                                    "cassandra_tpu.client"])
def test_importing_the_primitive_does_not_import_jax(module):
    code = (f"import sys, {module}; "
            "assert 'jax' not in sys.modules, 'jax was imported'")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True)
    assert p.returncode == 0, p.stderr[-2000:]


def test_parent_child_and_self_time_arithmetic(clock, ring):
    tid = pl.new_task_id()
    with pl.span("t.root", task=tid, cells=7, nbytes=9, items=11):
        clock.t += 1.0
        with pl.span("t.a"):
            clock.t += 3.0
            with pl.span("t.a.inner", kind="stall"):
                clock.t += 0.5
        clock.t += 2.0
        with pl.span("t.b", kind="idle"):
            clock.t += 4.0
    recs = {r["name"]: r for r in _records(ring)}
    root = recs["t.root"]
    assert root["parent"] == 0 and root["task"] == tid
    assert (root["cells"], root["bytes"], root["items"]) == (7, 9, 11)
    assert recs["t.a"]["parent"] == root["id"] == recs["t.b"]["parent"]
    assert recs["t.a.inner"]["parent"] == recs["t.a"]["id"]
    # children inherit the task; kinds are kept; closing order is
    # innermost first
    assert {r["task"] for r in recs.values()} == {tid}
    assert recs["t.a.inner"]["kind"] == "stall"
    assert recs["t.b"]["kind"] == "idle"
    assert [r["name"] for r in _records(ring)] == [
        "t.a.inner", "t.a", "t.b", "t.root"]
    own = _self_seconds(list(recs.values()))
    assert own[root["id"]] == pytest.approx(3.0)        # 10.5 - 3.5 - 4
    assert own[recs["t.a"]["id"]] == pytest.approx(3.0)  # 3.5 - 0.5
    assert root["end"] - root["start"] == pytest.approx(10.5)
    assert root["thread"] == threading.current_thread().name


def test_stage_profile_and_ring_carry_the_same_seconds(clock, ring):
    st = pl.ledger("spantest").stage("one_write")
    before = (st.busy_s, st.stall_s, st.idle_s)
    prof: dict = {"phase": 1.0}
    with st.busy("spantest.one_write.part", prof=prof, key="phase") as sp:
        clock.t += 0.375
    with st.stall(prof=prof, key="blocked"):
        clock.t += 0.25
    with st.idle():
        clock.t += 0.125
    busy, stall, idle = _records(ring)
    assert sp.seconds == 0.375 == busy["end"] - busy["start"]
    assert st.busy_s - before[0] == 0.375 == prof["phase"] - 1.0
    assert st.stall_s - before[1] == 0.25 == prof["blocked"]
    assert st.idle_s - before[2] == 0.125
    assert busy["name"] == "spantest.one_write.part"
    # a stage's unnamed span is named after the stage
    assert stall["name"] == idle["name"] == "spantest.one_write"
    assert (busy["kind"], stall["kind"], idle["kind"]) == (
        "busy", "stall", "idle")


def test_ring_bound_holds_and_the_oldest_goes_first(clock, monkeypatch):
    small = collections.deque(maxlen=8)
    monkeypatch.setattr(pl, "RING", small)
    for i in range(20):
        with pl.span("t.n", items=i):
            clock.t += 1.0
    assert len(small) == 8
    assert [r["items"] for r in _records(small)] == list(range(12, 20))
    assert pl.ring_records(tail=3)[0]["items"] == 17
    assert pl.RING_CAP == 32768


def test_back_dated_span_is_a_record_not_thread_time(clock, ring):
    st = pl.ledger("spantest").stage("queue")
    before = st.stall_s
    stamp = clock.t
    clock.t += 2.0                       # waited in a queue
    with pl.span("t.request", task=5):
        with st.stall("t.queue_wait", since=stamp):
            pass
        clock.t += 1.0
    wait, req = _records(ring)
    assert wait["parent"] == req["id"] and wait["task"] == 5
    assert wait["end"] - wait["start"] == 2.0 == st.stall_s - before
    assert wait["start"] < req["start"]
    # it takes nothing from its parent's self time
    assert _self_seconds([wait, req])[req["id"]] == 1.0


def test_task_scope_hands_the_id_to_another_thread(ring):
    tid = pl.new_task_id()
    seen = []

    def worker():
        with pl.task_scope(tid):
            seen.append(pl.current_task())
            with pl.span("t.on_worker"):
                pass
        seen.append(pl.current_task())
        with pl.span("t.outside"):
            pass
    t = threading.Thread(target=worker, name="span-test-worker")
    t.start()
    t.join()
    recs = {r["name"]: r for r in _records(ring)}
    assert seen == [tid, 0]
    assert recs["t.on_worker"]["task"] == tid
    assert recs["t.on_worker"]["thread"] == "span-test-worker"
    assert recs["t.outside"]["task"] == 0


def test_spans_are_trace_annotations_with_thread_and_task(tmp_path, ring):
    """While a profiler session runs a span lies in the .xplane.pb under
    `ctpu.<name>` with the Python thread's name and the task id as
    stats (the profiler names host lines after the process)."""
    import glob

    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    tid = pl.new_task_id()

    def worker():
        with pl.span("t.traced", task=tid):
            with pl.span("t.traced.child"):
                pass
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        t = threading.Thread(target=worker, name="span-trace-w")
        t.start()
        t.join()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(pl.TRACE_PREFIX + "t.traced"):
                    found[e.name] = dict(e.stats)
    assert set(found) == {"ctpu.t.traced", "ctpu.t.traced.child"}
    for stats in found.values():
        assert stats["thread"] == "span-trace-w" and stats["task"] == tid


# ----------------------------------------------------------- compaction --

def _table(name: str):
    return make_table(
        "spans", name, pk=["id"], ck=["c"],
        cols={"id": "int", "c": "int", "v": "blob"},
        params=TableParams(compression=CompressionParams(
            "LZ4Compressor", chunk_length=16 * 1024)))


def _store(tmp_path, name: str, n_ssts=3, n_per=90_000):
    table = _table(name)
    cfs = ColumnFamilyStore(table, str(tmp_path / name), commitlog=None)
    rng = np.random.default_rng(11)
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
    cfs.reload_sstables()
    return cfs


def _task_records(ring_) -> tuple:
    recs = _records(ring_)
    roots = [r for r in recs if r["name"] == "compaction.task"]
    assert len(roots) == 1
    return roots[0], [r for r in recs if r["task"] == roots[0]["task"]]


DEVICE_SPANS = {
    "compaction.task", "compaction.decode.fetch", "compaction.round.cut",
    "merge.resident.concat", "merge.resident.pack",
    "merge.resident.dispatch", "merge.resident.wait",
    "merge.resident.gather", "compaction.writeq.put_wait",
    "compaction.writeq.drain", "compaction.writeq.get_wait",
    "compaction.seal", "compaction.commit", "write.lane.append",
    "write.lane.keys", "write.lane.cut", "write.lane.cut.slice", "write.lane.cut.kernel_pull",
    "write.lane.cut.host_meta", "write.lane.cut.payload", "write.emit",
    "write.emit.directory", "write.emit.submit", "write.compress",
    "compress_pool.pack", "write.io"}
PROBE_SPAN = "runtime.gil.handoff"
CUT_PARTS = {"write.lane.cut.slice", "write.lane.cut.kernel_pull",
             "write.lane.cut.host_meta", "write.lane.cut.payload"}
# what CompactionTask.profile held on this path before the primitive,
# and what the primitive's spans added
OLD_KEYS = {"io_decode", "pack", "device", "gather", "serialize",
            "compress", "io_write", "seal"}
NEW_KEYS = {"directory", "commit", "writeq_put_wait", "writeq_get_wait"}


@pytest.fixture(scope="module")
def device_compaction(tmp_path_factory):
    """One tiny device-engine compaction (3 x 90,000 cells, rounds cut
    at 2^16: more than one round, several full segments and a partial
    one) under a ring of its own."""
    ring_ = collections.deque(maxlen=pl.RING_CAP)
    saved, pl.RING = pl.RING, ring_
    pool = CompressorPool(2, name="spans-pool")
    try:
        cfs = _store(tmp_path_factory.mktemp("dev"), "dev")
        task = CompactionTask(cfs, cfs.tracker.view(), engine="device",
                              use_device=True, mesh_devices=0,
                              device_compress=False, round_cells=1 << 16,
                              compress_pool=pool)
        stats = task.execute()
        for r in cfs.live_sstables():
            r.close()
    finally:
        pl.RING = saved
        pool.shutdown()
    root, recs = _task_records(ring_)
    return {"task": task, "stats": stats, "root": root, "recs": recs,
            "all": _records(ring_)}


def test_device_compaction_yields_every_span_of_the_catalogue(
        device_compaction):
    names = {r["name"] for r in device_compaction["recs"]}
    assert DEVICE_SPANS <= names, sorted(DEVICE_SPANS - names)
    root = device_compaction["root"]
    assert root["cells"] == device_compaction["stats"]["cells_read"]
    assert root["bytes"] == device_compaction["stats"]["bytes_read"]
    # every span of the compaction carries the task: none is left on
    # id 0 between the root's start and end on the task's own threads
    stray = [r for r in device_compaction["all"]
             if r["task"] == 0 and r["thread"] in ("compact-w", "sstable-io")
             and root["start"] <= r["start"] <= root["end"]]
    assert not stray, stray[:3]
    # the catalogue's one span that belongs to no task: the GIL probe
    # beats for the length of the task, on its own thread (PR 35)
    beats = [r for r in device_compaction["all"] if r["name"] == PROBE_SPAN]
    assert beats and all(
        r["kind"] == "stall" and r["thread"] == "gil-probe"
        and r["task"] == 0 and r["cpu"] is not None
        and root["start"] <= r["start"] <= root["end"] for r in beats)


def test_task_id_reaches_the_write_lane_the_pool_and_the_io_thread(
        device_compaction):
    by_thread = collections.defaultdict(set)
    for r in device_compaction["recs"]:
        by_thread[r["thread"]].add(r["name"])
    assert "write.lane.cut" in by_thread["compact-w"]
    assert "compaction.writeq.get_wait" in by_thread["compact-w"]
    assert "write.io" in by_thread["sstable-io"]
    # a pack job runs on a pool worker or on the thread that stole it;
    # the workers carry an index in their names
    packers = {r["thread"] for r in device_compaction["recs"]
               if r["name"] == "write.compress"}
    assert packers
    for t in packers - {"compact-w", "sstable-io",
                        threading.current_thread().name}:
        assert t in ("spans-pool-w0", "spans-pool-w1"), t
    me = threading.current_thread().name
    assert {"merge.resident.dispatch", "compaction.seal"} <= by_thread[me]


def test_profile_keys_and_serialize_is_the_sum_of_its_children(
        device_compaction):
    prof = device_compaction["task"].profile
    assert set(prof) - {"write_stall"} == OLD_KEYS | NEW_KEYS
    recs = device_compaction["recs"]

    def total(names):
        return sum(r["end"] - r["start"] for r in recs
                   if r["name"] in names)
    # one write: the profile key IS the sum of the spans that bill it
    assert prof["serialize"] == pytest.approx(
        total({"write.lane.append", "write.lane.keys", "write.lane.cut"}),
        abs=1e-9)
    assert prof["directory"] == pytest.approx(
        total({"write.emit.directory"}), abs=1e-9)
    assert prof["compress"] == pytest.approx(
        total({"write.compress"}), abs=1e-9)
    assert prof["io_decode"] == pytest.approx(
        total({"compaction.decode.fetch"}), abs=1e-9)
    assert prof["pack"] == pytest.approx(
        total({"merge.resident.pack"}), abs=1e-9)
    assert prof["device"] == pytest.approx(
        total({"merge.resident.wait"}), abs=1e-9)
    # and the finer spans of a cut cover it (within 5%), never more
    parts = total(CUT_PARTS)
    cut = total({"write.lane.cut"})
    assert 0.95 * cut <= parts <= cut
    assert prof["serialize"] == pytest.approx(
        parts + total({"write.lane.append", "write.lane.keys"}), rel=0.05)


def test_granularity_is_bounded_by_count_not_by_time(device_compaction):
    """Spans open per round, per segment, per pool job: their number is
    a fixed multiple of those, whatever the cells and partitions."""
    recs = device_compaction["recs"]
    n = collections.Counter(r["name"] for r in recs)
    rounds = n["compaction.round.cut"]
    segments = n["write.emit"]
    jobs = n["write.compress"]
    fetches = n["compaction.decode.fetch"]
    assert rounds >= 2 and segments >= 4 and jobs == segments
    assert device_compaction["stats"]["cells_read"] > 250_000
    assert len(recs) <= 8 * rounds + 12 * segments + 2 * jobs \
        + fetches + 8
    # the probe is bounded by time, ten a second, whatever the work
    root = device_compaction["root"]
    beats = [r for r in device_compaction["all"] if r["name"] == PROBE_SPAN]
    assert len(beats) <= 1 + 10 * (root["end"] - root["start"])
    # the attributes that feed the benchmark's readers
    pack = [r for r in recs if r["name"] == "merge.resident.pack"]
    assert all(r["items"] >= r["cells"] > 0 and r["bytes"] > 0
               for r in pack)
    directory = [r for r in recs if r["name"] == "write.emit.directory"]
    assert sum(r["cells"] for r in directory) == \
        device_compaction["stats"]["cells_written"]
    assert all(r["items"] > 0 for r in directory)     # partitions


def test_prefetch_thread_carries_the_task_and_the_merge_waits(tmp_path,
                                                              ring):
    """Host engines prefetch the next round's segments on
    `compact-prefetch`: its fetches bill `decode_ahead`, the merge
    thread's wait for it is a stall span."""
    cfs = _store(tmp_path, "host", n_ssts=2, n_per=140_000)
    task = CompactionTask(cfs, cfs.tracker.view(), engine="numpy",
                          mesh_devices=0, decode_ahead=True,
                          round_cells=1 << 16)
    task.execute()
    for r in cfs.live_sstables():
        r.close()
    _root, recs = _task_records(ring)
    on_prefetch = {r["name"] for r in recs
                   if r["thread"] == "compact-prefetch"}
    assert {"compaction.decode.fetch",
            "compaction.decode.park"} <= on_prefetch
    assert any(r["name"] == "compaction.decode.wait"
               and r["kind"] == "stall" for r in recs)
    assert "write.serialize" in {r["name"] for r in recs
                                 if r["thread"] == "compact-w"}
    assert task.profile["decode_ahead"] == pytest.approx(sum(
        r["end"] - r["start"] for r in recs
        if r["name"] == "compaction.decode.fetch"
        and r["thread"] == "compact-prefetch"), abs=1e-9)


# ------------------------------------------------------------ transport --

def test_wire_request_spans_and_queue_wait(tmp_path, ring):
    import socket
    import struct

    from cassandra_tpu.storage.engine import StorageEngine
    from cassandra_tpu.transport.frame import encode_envelope
    from cassandra_tpu.transport.server import CQLServer
    eng = StorageEngine(str(tmp_path / "e"))
    srv = CQLServer(eng)
    try:
        s = socket.create_connection(("127.0.0.1", srv.port), timeout=5)
        body = struct.pack(">H", 1) + \
            struct.pack(">H", len("CQL_VERSION")) + b"CQL_VERSION" + \
            struct.pack(">H", len("3.4.5")) + b"3.4.5"
        s.sendall(encode_envelope(0x04, 0, 0x01, body))   # STARTUP
        s.recv(4096)
        q = b"SELECT * FROM system.local"
        qbody = struct.pack(">i", len(q)) + q + \
            struct.pack(">H", 1) + b"\x00"
        s.sendall(encode_envelope(0x04, 7, 0x07, qbody))  # QUERY
        s.recv(65536)
        s.close()
    finally:
        srv.close()
        eng.close()
    recs = _records(ring)
    execs = [r for r in recs if r["name"] == "cql.execute"]
    assert len(execs) == 1
    by_id = {r["id"]: r for r in recs}
    req = by_id[execs[0]["parent"]]
    assert req["name"] == "transport.request"
    assert req["thread"].startswith(f"cql-exec-{srv.port}-")
    assert req["task"] & 0xFFFF == 7 and req["task"] >> 16 >= 1
    assert req["bytes"] == len(qbody) == execs[0]["bytes"]
    waits = [r for r in recs if r["name"] == "transport.queue_wait"
             and r["parent"] == req["id"]]
    assert len(waits) == 1 and waits[0]["kind"] == "stall"
    assert waits[0]["start"] <= req["start"] == pytest.approx(
        waits[0]["end"], abs=0.05)
    assert any(r["name"] == "transport.dispatch.idle"
               and r["kind"] == "idle" for r in recs)


def test_flight_bundle_carries_the_ring_tail(tmp_path):
    from cassandra_tpu.service import diagnostics
    from cassandra_tpu.storage.engine import StorageEngine
    eng = StorageEngine(str(tmp_path / "e"))
    try:
        with pl.span("t.before_the_dump", items=41):
            pass
        with open(eng.flight_recorder.dump("test")) as fh:
            bundle = json.load(fh)
        tail = bundle["pipeline_spans"]
        assert 0 < len(tail) <= diagnostics.BUNDLE_SPAN_TAIL
        assert set(tail[-1]) == set(pl.RECORD_FIELDS)
        assert any(r["name"] == "t.before_the_dump" and r["items"] == 41
                   for r in tail)
        assert "pipeline_ledger" in bundle
    finally:
        eng.close()
