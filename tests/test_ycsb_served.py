"""YCSB core workload A through a node's wire sessions while the
manager-driven compaction of the loaded sstables runs underneath, on the
engine the task chooses itself (PR 27): every read agrees with the dict
model (tests/ycsb_model.py) under its staleness rule, the table ends as
one sstable whose seven components are the numpy engine's bytes, and no
fallback counter moves. Once with the choice falling on `device` (the
probe faked, jax's CPU backend doing the device's work) and once on the
host engine: same answers."""
import glob
import hashlib
import os
import threading
import time

import numpy as np
import pytest

import ycsb_model as ycsb
from cassandra_tpu.compaction import task as task_mod
from cassandra_tpu.compaction.task import CompactionTask
from cassandra_tpu.service.metrics import GLOBAL as METRICS
from cassandra_tpu.utils import pipeline_ledger

RECORDS, SSTABLES, FIELDS, LENGTH = 2000, 4, 10, 100
THREADS, MIN_OPS, MAX_OPS = 4, 150, 1500
COMPONENTS = ("Data.db", "Index.db", "Partitions.db", "Filter.db",
              "Statistics.db", "Digest.crc32", "ZoneMap.db")
FALLBACKS = ("compaction.device_compress_fallback",
             "compaction.device_host_rounds",
             "compaction.device_resident_fallback")
HOST = task_mod.host_engine()
PROBE_SPAN = "compaction.purge.probe"


class Served:
    """A node as tools/noded.py builds one, with its CQL front door (the
    shape of benchmarks/wire.ServedNode)."""

    def __init__(self, data_dir: str):
        from cassandra_tpu.client import Cluster
        from cassandra_tpu.cluster.ring import even_tokens
        from cassandra_tpu.tools.noded import build_node
        from cassandra_tpu.transport.server import CQLServer
        cfg = {"name": "ycsb", "host": "127.0.0.1", "port": 0,
               "tokens": even_tokens(1, vnodes=4)[0], "data_dir": data_dir,
               "peers": [], "seeds": [], "native_port": 0,
               "config": {"commitlog_sync": "periodic"}}
        self.node, self.transport = build_node(cfg)
        self.server = CQLServer(self.node, cfg["host"], cfg["native_port"])
        self.cluster = Cluster("127.0.0.1", self.server.port)
        self.session = self.connect(use=False)
        self.session.execute(
            "CREATE KEYSPACE ycsb WITH replication = "
            "{'class': 'SimpleStrategy', 'replication_factor': 1}")
        self.session.execute("USE ycsb")

    def connect(self, use=True):
        s = self.cluster.connect()
        s._sock.settimeout(120.0)
        if use:
            s.execute("USE ycsb")
        return s

    def close(self):
        self.session.close()
        self.server.close()
        self.node.shutdown()


def _hashes(directory: str) -> dict:
    out = {}
    for comp in COMPONENTS:
        (path,) = glob.glob(os.path.join(directory, f"*-{comp}"))
        with open(path, "rb") as f:
            out[comp] = hashlib.sha256(f.read()).hexdigest()
    return out


def _client(served, stream, names, columns, start, stop, out):
    """One closed-loop client thread: its seeded stream until the
    compaction is over (at least MIN_OPS, at most MAX_OPS operations)."""
    s = served.connect()
    read = s.prepare("SELECT * FROM usertable WHERE y_id = ?")
    update = [s.prepare(f"UPDATE usertable SET field{f} = ? WHERE y_id = ?")
              for f in range(FIELDS)]
    start.wait()
    for i in range(MAX_OPS):
        if i >= MIN_OPS and stop.is_set():
            break
        keynum = int(stream["keynum"][i])
        op = {"keynum": keynum, "sent": time.monotonic(), "ok": True}
        if stream["is_read"][i]:
            rows = s.execute_prepared(read, [names[keynum]]).rows
            op.update(kind="read", row=[rows[0][c].encode()
                                        for c in columns] if rows else None)
        else:
            field, value = int(stream["field"][i]), \
                stream["value"][i].tobytes()
            s.execute_prepared(update[field], [value, names[keynum]])
            op.update(kind="update", field=field, value=value)
        op["done"] = time.monotonic()
        out.append(op)
    s.close()


@pytest.fixture(scope="module", params=["device", "host"])
def run(request, tmp_path_factory):
    """The whole scenario once per engine the choice can fall on."""
    device = request.param == "device"
    mp = pytest.MonkeyPatch()
    mp.setattr(task_mod, "tpu_backend", lambda: device)
    mp.setattr(CompactionTask, "DEVICE_MIN_CELLS", 1000)
    base = tmp_path_factory.mktemp(request.param)
    served = Served(str(base / "node"))
    try:
        cm = served.node.engine.compactions
        cm.paused = True                    # disableautocompaction
        fields = ", ".join(f"field{f} varchar" for f in range(FIELDS))
        served.session.execute(
            f"CREATE TABLE usertable (y_id varchar PRIMARY KEY, {fields})")
        cfs = served.node.engine.store("ycsb", "usertable")
        loaded = ycsb.loaded_values(7, RECORDS, FIELDS, LENGTH)
        names = ycsb.key_names(np.arange(RECORDS))
        insert = served.session.prepare(
            "INSERT INTO usertable (y_id, "
            + ", ".join(f"field{f}" for f in range(FIELDS))
            + ") VALUES (" + ", ".join("?" * (FIELDS + 1)) + ")")
        per = RECORDS // SSTABLES
        for r in range(SSTABLES):           # `ycsb load`, four flushes
            for k in range(r * per, (r + 1) * per):
                served.session.execute_prepared(
                    insert, [names[k]] + [loaded[k, f].tobytes()
                                          for f in range(FIELDS)])
            cfs.flush()
        inputs = cfs.live_sstables()
        assert len(inputs) == SSTABLES
        copies = str(base / "copies" / "data")
        os.makedirs(copies)
        for fn in os.listdir(cfs.directory):
            if os.path.isfile(os.path.join(cfs.directory, fn)):
                os.link(os.path.join(cfs.directory, fn),
                        os.path.join(copies, fn))
        got = served.session.execute(
            "SELECT * FROM usertable WHERE y_id = ?", [names[0]])
        columns = [list(got.column_names).index(f"field{f}")
                   for f in range(FIELDS)]
        fallbacks0 = {c: METRICS.counter(c) for c in FALLBACKS}
        chosen0 = {e: METRICS.counter(f"compaction.engine_chosen.{e}")
                   for e in ("device", "native", "numpy")}
        streams = [ycsb.op_stream(7, c, MAX_OPS, RECORDS, FIELDS, LENGTH,
                                  0.5) for c in range(THREADS)]
        start, stop = threading.Event(), threading.Event()
        outs = [[] for _ in range(THREADS)]
        threads = [threading.Thread(
            target=_client, args=(served, streams[c], names, columns,
                                  start, stop, outs[c]))
            for c in range(THREADS)]
        for t in threads:
            t.start()
        start.set()
        end = time.monotonic() + 240
        while time.monotonic() < end and cfs.memtable.is_empty:
            time.sleep(0.002)               # the purge guard's switch
        first_span = pipeline_ledger.new_task_id()
        probes = {}

        def drain_probes():                 # before the ring can wrap
            for r in list(pipeline_ledger.RING):
                if r[0] == PROBE_SPAN and r[5] > first_span:
                    probes[r[5]] = dict(zip(pipeline_ledger.RECORD_FIELDS,
                                            r))
        cm.paused = False                   # enableautocompaction
        cm.submit_background(cfs)
        polls = 0
        while time.monotonic() < end and not (
                len(cfs.live_sstables()) == 1 and len(cm.active) == 0
                and cm.pending_tasks() == 0):
            time.sleep(0.02)
            polls += 1
            if polls % 10 == 0:
                drain_probes()
        drain_probes()
        stop.set()
        for t in threads:
            t.join()
        ops = [o for out in outs for o in out]
        history = ycsb.History(loaded, ops)
        final = {}
        for k in history.updated_keys() + list(range(0, RECORDS, 40)):
            rows = served.session.execute(
                "SELECT * FROM usertable WHERE y_id = ?", [names[k]]).rows
            final[k] = [rows[0][c].encode() for c in columns] \
                if rows else None
        result = {
            "device": device, "ops": ops, "history": history,
            "final": final, "cfs_dir": cfs.directory,
            "live": len(cfs.live_sstables()),
            "compactions": [dict(h) for h in cfs.compaction_history],
            "fallbacks": {c: METRICS.counter(c) - v
                          for c, v in fallbacks0.items()},
            "chosen": {e: METRICS.counter(f"compaction.engine_chosen.{e}")
                       - v for e, v in chosen0.items()},
            "served_hashes": _hashes(cfs.directory),
            "probes": list(probes.values()),
            "copies": copies, "table": cfs.table}
    finally:
        served.close()
        mp.undo()
    return result


def test_the_traffic_ran_while_the_compaction_ran(run):
    assert len(run["ops"]) >= THREADS * MIN_OPS
    kinds = {o["kind"] for o in run["ops"]}
    assert kinds == {"read", "update"}
    (comp,) = run["compactions"]
    assert comp["inputs"] == SSTABLES
    assert comp["cells_read"] >= RECORDS * FIELDS


def test_every_read_agrees_with_the_model(run):
    assert run["history"].judge_reads() == {"reads_stale": 0,
                                            "reads_unknown_value": 0}


def test_every_final_row_is_a_candidate_nothing_follows(run):
    assert len(run["final"]) > 50
    assert run["history"].final_rows_wrong(run["final"]) == 0


def test_the_task_chose_its_engine_and_nothing_fell_back(run):
    want = "device" if run["device"] else HOST
    (comp,) = run["compactions"]
    assert comp["engine"] == want and comp["engine_chosen"] is True
    assert run["chosen"][want] >= 1
    assert all(v == 0 for v in run["fallbacks"].values()), run["fallbacks"]


def test_the_purge_guard_probed_no_partition(run):
    """usertable holds no tombstone and no TTL: with the memtable
    non-empty from before the compaction's first round, every call of
    the guard opened its span and probed nothing."""
    assert run["probes"]
    assert all(p["items"] == 0 for p in run["probes"]), run["probes"]


def test_one_sstable_with_the_numpy_engines_bytes(run, tmp_path):
    from cassandra_tpu.storage.table import ColumnFamilyStore
    assert run["live"] == 1
    host = ColumnFamilyStore(run["table"], str(tmp_path / "host"),
                             commitlog=None)
    for fn in os.listdir(run["copies"]):
        os.link(os.path.join(run["copies"], fn),
                os.path.join(host.directory, fn))
    host.reload_sstables()
    assert len(host.live_sstables()) == SSTABLES
    CompactionTask(host, host.tracker.view(), engine="numpy",
                   use_device=False).execute()
    want = _hashes(host.directory)
    for r in host.live_sstables():
        r.close()
    assert set(want) == set(COMPONENTS)
    assert run["served_hashes"] == want


# ------------------------------------------------ the model's own rules --

LOADED = ycsb.loaded_values(1, 4, 2, 8)
V = [bytes([65 + i]) * 8 for i in range(6)]


def _w(keynum, field, value, sent, done, ok=True):
    return {"kind": "update", "keynum": keynum, "field": field,
            "value": value, "sent": sent, "done": done, "ok": ok}


def _r(keynum, row, sent, done):
    return {"kind": "read", "keynum": keynum, "row": row, "sent": sent,
            "done": done, "ok": True}


def _row(keynum, **over):
    return [over.get(f"f{f}", LOADED[keynum, f].tobytes())
            for f in range(2)]


HISTORIES = {
    "loaded_value_with_no_write": ([_r(0, _row(0), 1, 2)], 0, 0),
    "fresh_value": ([_w(0, 0, V[0], 1, 2), _r(0, _row(0, f0=V[0]), 3, 4)],
                    0, 0),
    "loaded_value_after_an_acked_write": (
        [_w(0, 0, V[0], 1, 2), _r(0, _row(0), 3, 4)], 1, 0),
    "old_write_after_a_later_acked_write": (
        [_w(0, 0, V[0], 1, 2), _w(0, 0, V[1], 3, 4),
         _r(0, _row(0, f0=V[0]), 5, 6)], 1, 0),
    "racing_writes_either_is_right": (
        [_w(0, 0, V[0], 1, 4), _w(0, 0, V[1], 2, 3),
         _r(0, _row(0, f0=V[0]), 5, 6), _r(0, _row(0, f0=V[1]), 5, 6)],
        0, 0),
    "write_in_flight_while_read": (
        [_w(0, 0, V[0], 1, 6), _r(0, _row(0), 2, 3),
         _r(0, _row(0, f0=V[0]), 4, 5)], 0, 0),
    "write_acked_after_the_read_was_sent": (
        [_w(0, 0, V[0], 1, 4), _r(0, _row(0), 3, 5)], 0, 0),
    "value_nobody_wrote": ([_r(0, _row(0, f1=V[5]), 1, 2)], 0, 1),
    "value_from_the_future": (
        [_r(0, _row(0, f0=V[0]), 1, 2), _w(0, 0, V[0], 3, 4)], 0, 1),
    "missing_row": ([_r(0, None, 1, 2)], 0, 1),
    "missing_field": ([_r(0, _row(0)[:1], 1, 2)], 0, 1),
    "other_keys_write_is_not_mine": (
        [_w(1, 0, V[0], 1, 2), _r(0, _row(0), 3, 4)], 0, 0),
    "unacked_write_may_show_or_not": (
        [_w(0, 0, V[0], 1, 2, ok=False), _r(0, _row(0), 3, 4),
         _r(0, _row(0, f0=V[0]), 3, 4)], 0, 0),
    "unacked_write_hides_nothing": (
        [_w(0, 0, V[0], 1, 2), _w(0, 0, V[1], 3, 4, ok=False),
         _r(0, _row(0, f0=V[0]), 5, 6), _r(0, _row(0), 5, 6)], 1, 0),
}


@pytest.mark.parametrize("name", sorted(HISTORIES))
def test_the_staleness_rule_on_hand_made_histories(name):
    ops, stale, unknown = HISTORIES[name]
    assert ycsb.History(LOADED, ops).judge_reads() == {
        "reads_stale": stale, "reads_unknown_value": unknown}


FINALS = {
    "untouched": ([], {0: _row(0)}, 0),
    "last_write": ([_w(0, 0, V[0], 1, 2), _w(0, 0, V[1], 3, 4)],
                   {0: _row(0, f0=V[1])}, 0),
    "lost_update": ([_w(0, 0, V[0], 1, 2), _w(0, 0, V[1], 3, 4)],
                    {0: _row(0, f0=V[0])}, 1),
    "racing_either": ([_w(0, 0, V[0], 1, 4), _w(0, 0, V[1], 2, 3)],
                      {0: _row(0, f0=V[0])}, 0),
    "loaded_value_back": ([_w(0, 1, V[0], 1, 2)], {0: _row(0)}, 1),
    "row_gone": ([_w(0, 0, V[0], 1, 2)], {0: None}, 1),
    "truncated": ([], {0: [v[:7] for v in _row(0)]}, 1),
}


@pytest.mark.parametrize("name", sorted(FINALS))
def test_the_final_row_rule_on_hand_made_histories(name):
    ops, rows, wrong = FINALS[name]
    assert ycsb.History(LOADED, ops).final_rows_wrong(rows) == wrong


def test_ycsb_key_names_are_ycsbs():
    # YCSB's first two keys under insertorder=hashed
    assert ycsb.key_names([0, 1]) == [b"user6284781860667377211",
                                      b"user8517097267634966620"]


def test_the_dict_model_and_its_controls():
    loaded = ycsb.loaded_values(3, 500, 10, 100)
    streams = [ycsb.op_stream(3, c, 400, 500, 10, 100, 0.5)
               for c in range(4)]
    for kw, clean in (({}, True), ({"drop_every": 100}, False),
                      ({"truncate_to": 99}, False)):
        model = ycsb.Model(loaded, **kw)
        history = ycsb.History(loaded, ycsb.serial_history(model, streams,
                                                           400))
        judged = history.judge_reads()
        final = history.final_rows_wrong(
            {k: model.read(k) for k in history.updated_keys()})
        assert (sum(judged.values()) + final == 0) is clean, (kw, judged)
