"""YCSB core workload A at QUORUM through three coordinators of a
three-node RF 3 ring (tests/rf3_cluster.py: noded-built nodes, TCP between
them, the real CQL wire) while each node's manager-chosen compaction of the
loaded sstables runs underneath (PR 33): every read agrees with the dict
model's history rule whichever coordinator it and the writes went through,
the three replicas converge (benchmarks/reference/ycsb_quorum.py's rule,
and that file's replica-set model run on the same seeded streams), every
node ends as one sstable with the numpy engine's bytes. Once with each
task's choice falling on `device` (the probe faked, jax's CPU backend doing
the device's work) and once on the host engine. Then a replica that is sent
nothing for a while: digest mismatches and read repairs rise, reads stay
right, the hint brings the replica back."""
import importlib.util
import os
import threading
import time

import numpy as np
import pytest

import rf3_cluster
import ycsb_model as ycsb
from cassandra_tpu.cluster.messaging import Verb
from cassandra_tpu.compaction import task as task_mod
from cassandra_tpu.compaction.task import CompactionTask
from cassandra_tpu.service.metrics import GLOBAL as METRICS
from cassandra_tpu.storage.rows import rows_from_batch
from cassandra_tpu.utils import pipeline_ledger
from test_ycsb_served import COMPONENTS, FALLBACKS, HOST, _hashes

RECORDS, SSTABLES, FIELDS, LENGTH = 2000, 4, 10, 100
THREADS, MIN_OPS, MAX_OPS, NODES = 6, 100, 1000, 3
LEVEL = "QUORUM"
COLUMNS = "y_id varchar PRIMARY KEY, " + ", ".join(
    f"field{f} varchar" for f in range(FIELDS))
TABLES = [rf3_cluster.table_ddl("usertable", COLUMNS)]
NEW_SPANS = ("coordinator.write", "coordinator.write.await",
             "coordinator.read", "coordinator.read.await",
             "messaging.encode", "messaging.decode",
             "messaging.handle.mutation_req", "messaging.handle.read_req")


def _reference():
    """benchmarks/reference/ycsb_quorum.py, by its file: the plain
    replica-set model the benchmark's cell is judged by."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "reference",
        "ycsb_quorum.py")
    spec = importlib.util.spec_from_file_location("ycsb_quorum", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


QUORUM_REF = _reference()


def _client(ring, conn, stream, names, columns, start, stop, out):
    """One closed-loop client thread on coordinator conn mod 3: its seeded
    stream until the compactions are over."""
    s = ring.connect(conn % NODES, timeout=120.0)
    read = s.prepare("SELECT * FROM usertable WHERE y_id = ?")
    update = [s.prepare(f"UPDATE usertable SET field{f} = ? WHERE y_id = ?")
              for f in range(FIELDS)]
    start.wait()
    for i in range(MAX_OPS):
        if i >= MIN_OPS and stop.is_set():
            break
        keynum = int(stream["keynum"][i])
        op = {"keynum": keynum, "sent": time.monotonic(), "ok": True,
              "via": conn % NODES}
        if stream["is_read"][i]:
            rows = s.execute_prepared(read, [names[keynum]],
                                      consistency=LEVEL).rows
            op.update(kind="read", row=[rows[0][c].encode()
                                        for c in columns] if rows else None)
        else:
            field, value = int(stream["field"][i]), \
                stream["value"][i].tobytes()
            s.execute_prepared(update[field], [value, names[keynum]],
                               consistency=LEVEL)
            op.update(kind="update", field=field, value=value)
        op["done"] = time.monotonic()
        out.append(op)
    s.close()


def _local_row(node, table, field_ids, name: bytes):
    pk = table.serialize_partition_key([name.decode()])
    batch = node.engine.store(rf3_cluster.KEYSPACE,
                              "usertable").read_partition(pk)
    rows = list(rows_from_batch(table, batch))
    return [rows[0].cells.get(c) for c in field_ids] if len(rows) == 1 \
        else None


def _idle(ring) -> bool:
    return all(len(n.engine.store(rf3_cluster.KEYSPACE, "usertable")
                   .live_sstables()) == 1
               and len(n.engine.compactions.active) == 0
               and n.engine.compactions.pending_tasks() == 0
               for n in ring.nodes)


@pytest.fixture(scope="module", params=["device", "host"])
def run(request, tmp_path_factory):
    """The whole scenario once per engine the choice can fall on."""
    device = request.param == "device"
    mp = pytest.MonkeyPatch()
    mp.setattr(task_mod, "tpu_backend", lambda: device)
    mp.setattr(CompactionTask, "DEVICE_MIN_CELLS", 1000)
    base = tmp_path_factory.mktemp(request.param)
    ring = rf3_cluster.Ring3(base, TABLES)
    try:
        for n in ring.nodes:
            n.engine.compactions.paused = True      # disableautocompaction
        stores = [n.engine.store(rf3_cluster.KEYSPACE, "usertable")
                  for n in ring.nodes]
        table = stores[0].table
        field_ids = [c.column_id for c in sorted(
            table.regular_columns, key=lambda c: int(c.name[5:]))]
        loaded = ycsb.loaded_values(11, RECORDS, FIELDS, LENGTH)
        names = ycsb.key_names(np.arange(RECORDS))
        session = ring.connect(0, timeout=120.0)
        insert = session.prepare(
            "INSERT INTO usertable (y_id, "
            + ", ".join(f"field{f}" for f in range(FIELDS))
            + ") VALUES (" + ", ".join("?" * (FIELDS + 1)) + ")")
        per = RECORDS // SSTABLES
        for r in range(SSTABLES):       # `ycsb load` at ALL, four flushes
            for k in range(r * per, (r + 1) * per):
                session.execute_prepared(
                    insert, [names[k]] + [loaded[k, f].tobytes()
                                          for f in range(FIELDS)],
                    consistency="ALL")
            for cfs in stores:
                cfs.flush()
        assert all(len(cfs.live_sstables()) == SSTABLES for cfs in stores)
        copies = []
        for i, cfs in enumerate(stores):
            d = str(base / "copies" / str(i))
            os.makedirs(d)
            for fn in os.listdir(cfs.directory):
                if os.path.isfile(os.path.join(cfs.directory, fn)):
                    os.link(os.path.join(cfs.directory, fn),
                            os.path.join(d, fn))
            copies.append(d)
        got = session.execute("SELECT * FROM usertable WHERE y_id = ?",
                              [names[0]], consistency=LEVEL)
        columns = [list(got.column_names).index(f"field{f}")
                   for f in range(FIELDS)]
        fallbacks0 = {c: METRICS.counter(c) for c in FALLBACKS}
        counted0 = {v: METRICS.counter(
            f"coordinator.requests.{v}.{LEVEL.lower()}")
            for v in ("read", "write")}
        streams = [ycsb.op_stream(11, c, MAX_OPS, RECORDS, FIELDS, LENGTH,
                                  0.5) for c in range(THREADS)]
        start, stop = threading.Event(), threading.Event()
        outs = [[] for _ in range(THREADS)]
        threads = [threading.Thread(
            target=_client, args=(ring, c, streams[c], names, columns,
                                  start, stop, outs[c]))
            for c in range(THREADS)]
        for t in threads:
            t.start()
        mark = pipeline_ledger.new_task_id()
        start.set()
        time.sleep(0.2)
        seen = set()
        for n, cfs in zip(ring.nodes, stores):
            n.engine.compactions.paused = False     # enableautocompaction
            n.engine.compactions.submit_background(cfs)
        end = time.monotonic() + 300
        while time.monotonic() < end and not _idle(ring):
            time.sleep(0.05)
            seen |= {r[0] for r in list(pipeline_ledger.RING)
                     if r[5] > mark}
        stop.set()
        for t in threads:
            t.join()
        seen |= {r[0] for r in list(pipeline_ledger.RING) if r[5] > mark}
        ops = [o for out in outs for o in out]
        history = ycsb.History(loaded, ops)
        final = {}
        for j, k in enumerate(dict.fromkeys(
                history.updated_keys() + list(range(0, RECORDS, 40)))):
            s = ring.connect(j % NODES) if j < NODES else None
            rows = (s or session).execute(
                "SELECT * FROM usertable WHERE y_id = ?", [names[k]],
                consistency=LEVEL).rows
            final[k] = [rows[0][c].encode() for c in columns] \
                if rows else None
            if s is not None:
                s.close()
        time.sleep(0.3)                 # the third replica's write lands
        local = {k: [_local_row(n, table, field_ids, names[k])
                     for n in ring.nodes] for k in history.updated_keys()}
        result = {
            "device": device, "ops": ops, "history": history,
            "final": final, "local": local, "loaded": loaded,
            "streams": streams, "spans": seen,
            "counted": {v: METRICS.counter(
                f"coordinator.requests.{v}.{LEVEL.lower()}") - c
                for v, c in counted0.items()},
            "live": [len(cfs.live_sstables()) for cfs in stores],
            "compactions": [[dict(h) for h in cfs.compaction_history]
                            for cfs in stores],
            "fallbacks": {c: METRICS.counter(c) - v
                          for c, v in fallbacks0.items()},
            "served_hashes": [_hashes(cfs.directory) for cfs in stores],
            "copies": copies, "table": table}
        session.close()
    finally:
        ring.close()
        mp.undo()
    return result


def test_the_traffic_ran_through_three_coordinators(run):
    assert len(run["ops"]) >= THREADS * MIN_OPS
    assert {o["kind"] for o in run["ops"]} == {"read", "update"}
    assert {o["via"] for o in run["ops"]} == {0, 1, 2}
    for comps in run["compactions"]:
        (comp,) = comps
        assert comp["inputs"] == SSTABLES
        assert comp["cells_read"] >= RECORDS * FIELDS


def test_every_request_was_coordinated_at_quorum(run):
    reads = sum(1 for o in run["ops"] if o["kind"] == "read")
    updates = len(run["ops"]) - reads
    # the final read-back is made of QUORUM reads too
    assert run["counted"]["read"] == reads + len(run["final"])
    assert run["counted"]["write"] == updates


def test_every_read_agrees_with_the_history_rule(run):
    """A read through coordinator B sees a write acknowledged through
    coordinator A before the read was sent: R + W > N."""
    assert run["history"].judge_reads() == {"reads_stale": 0,
                                            "reads_unknown_value": 0}


def test_every_final_row_is_a_candidate_nothing_follows(run):
    assert len(run["final"]) > 50
    assert run["history"].final_rows_wrong(run["final"]) == 0


def test_the_three_replicas_converge(run):
    assert len(run["local"]) > 50
    assert QUORUM_REF.replicas_diverging(run["history"], run["local"]) == 0


def test_the_replica_set_reference_agrees_on_the_same_streams(run):
    """The plain model in the cluster's place, on the same seeded data:
    clean by the same rules; read and written at ONE it is not."""
    n = min(len(s["keynum"]) for s in run["streams"])
    for kw, clean in (({"w": 2, "r": 2}, True), ({"w": 1, "r": 1}, False)):
        model = QUORUM_REF.ReplicaSet(run["loaded"], NODES, **kw)
        ops = QUORUM_REF.serial_history(model, run["streams"], n, NODES)
        history = ycsb.History(run["loaded"], ops)
        model.settle()
        local = {k: [model.local_row(i, k) for i in range(NODES)]
                 for k in history.updated_keys()}
        judged = history.judge_reads()
        assert (judged["reads_stale"] == 0) is clean, (kw, judged)
        assert judged["reads_unknown_value"] == 0
        assert QUORUM_REF.replicas_diverging(history, local) == 0


def test_each_task_chose_its_engine_and_nothing_fell_back(run):
    want = "device" if run["device"] else HOST
    for comps in run["compactions"]:
        (comp,) = comps
        assert comp["engine"] == want and comp["engine_chosen"] is True
    assert all(v == 0 for v in run["fallbacks"].values()), run["fallbacks"]


def test_the_new_spans_are_in_the_ring_under_their_names(run):
    assert set(NEW_SPANS) <= run["spans"], set(NEW_SPANS) - run["spans"]
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "docs", "observability.md")) as f:
        doc = f.read()
    for name in ("coordinator.write", "coordinator.read",
                 "coordinator.write.await", "coordinator.read.await",
                 "coordinator.read.repair", "messaging.encode",
                 "messaging.decode", "messaging.handle.<verb>"):
        assert f"`{name}`" in doc, name
    for counter in ("reads.digest_mismatches", "reads.read_repairs",
                    "writes.hints_stored",
                    "coordinator.requests.<read|write>.<level>"):
        assert f"`{counter}`" in doc, counter


@pytest.mark.parametrize("node", range(NODES))
def test_one_sstable_with_the_numpy_engines_bytes(run, tmp_path, node):
    from cassandra_tpu.storage.table import ColumnFamilyStore
    assert run["live"][node] == 1
    host = ColumnFamilyStore(run["table"], str(tmp_path / "host"),
                             commitlog=None)
    for fn in os.listdir(run["copies"][node]):
        os.link(os.path.join(run["copies"][node], fn),
                os.path.join(host.directory, fn))
    host.reload_sstables()
    assert len(host.live_sstables()) == SSTABLES
    CompactionTask(host, host.tracker.view(), engine="numpy",
                   use_device=False).execute()
    want = _hashes(host.directory)
    for r in host.live_sstables():
        r.close()
    assert set(want) == set(COMPONENTS)
    assert run["served_hashes"][node] == want


# ------------------------------------- a replica that is sent nothing --

def test_a_lagging_replica_is_repaired_by_reads_and_then_by_its_hint(
        tmp_path):
    ring = rf3_cluster.Ring3(
        tmp_path, [rf3_cluster.table_ddl("kv", "k int PRIMARY KEY, v text")])
    s1, s3 = ring.connect(0), ring.connect(2)
    third = ring.nodes[2].endpoint
    try:
        for n in ring.nodes:
            n.proxy.write_timeout = 0.4
        s1.execute("INSERT INTO kv (k, v) VALUES (1, 'old')",
                   consistency="ALL")
        rules = [n.messaging.transport.filters.drop(
            verb=Verb.MUTATION_REQ, to=third) for n in ring.nodes[:2]]
        before = {c: METRICS.counter(c) for c in (
            "reads.digest_mismatches", "reads.read_repairs",
            "writes.hints_stored")}
        # acknowledged by nodes 1 and 2; node 3 never hears of it
        s1.execute("INSERT INTO kv (k, v) VALUES (1, 'new')",
                   consistency=LEVEL)
        table = ring.nodes[0].schema.get_table(rf3_cluster.KEYSPACE, "kv")
        pk = table.serialize_partition_key([1])
        store3 = ring.nodes[2].engine.store(rf3_cluster.KEYSPACE, "kv")

        def third_holds():
            rows = list(rows_from_batch(table, store3.read_partition(pk)))
            return rows[0].cells[table.regular_columns[0].column_id]
        assert third_holds() == b"old"
        # a QUORUM read through node 3 itself: its own copy and another's
        # digest disagree, the full round wins, its copy is repaired
        assert s3.execute("SELECT v FROM kv WHERE k = 1",
                          consistency=LEVEL).rows == [("new",)]
        assert METRICS.counter("reads.digest_mismatches") \
            - before["reads.digest_mismatches"] == 1
        assert METRICS.counter("reads.read_repairs") \
            - before["reads.read_repairs"] == 1
        assert third_holds() == b"new"
        # a second key, never read: only the hint can bring it
        s1.execute("INSERT INTO kv (k, v) VALUES (2, 'hinted')",
                   consistency=LEVEL)
        pk2 = table.serialize_partition_key([2])
        assert len(store3.read_partition(pk2)) == 0
        for rule in rules:
            rule["remaining"] = 0
        end = time.monotonic() + 15
        while time.monotonic() < end and not len(store3.read_partition(pk2)):
            time.sleep(0.05)
        assert len(store3.read_partition(pk2)) > 0
        assert METRICS.counter("writes.hints_stored") \
            - before["writes.hints_stored"] >= 2
    finally:
        s1.close()
        s3.close()
        ring.close()
