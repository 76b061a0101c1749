"""The consistency level a client sends is the level its request is
coordinated at: over the real CQL wire on a three-node RF 3 ring
(tests/rf3_cluster.py), a QUERY, a prepared EXECUTE and a BATCH statement
at each level are answered or refused exactly as `block_for` against the
live replicas says, with the protocol's error code and fields; a level no
live replica set can serve in time answers WRITE_TIMEOUT / READ_TIMEOUT;
an in-process Session still gets `Node.default_cl`; a single node at ONE
coordinates as before."""
import struct
import time

import pytest

import rf3_cluster
from cassandra_tpu import client
from cassandra_tpu.cluster.messaging import Verb
from cassandra_tpu.cluster.replication import ConsistencyLevel
from cassandra_tpu.service.metrics import GLOBAL as METRICS
from cassandra_tpu.transport import frame

LEVELS = ["ONE", "TWO", "QUORUM", "ALL", "LOCAL_QUORUM"]
BLOCK_FOR = {"ONE": 1, "TWO": 2, "QUORUM": 2, "ALL": 3, "LOCAL_QUORUM": 2}
OPS = ["read", "write", "prepared_read", "prepared_write", "batch"]
TABLES = [rf3_cluster.table_ddl("kv", "k int PRIMARY KEY, v text")]


def _int(i: int) -> bytes:
    return struct.pack(">i", i)


def _requests(verb: str, level: str) -> int:
    return METRICS.counter(f"coordinator.requests.{verb}.{level.lower()}")


@pytest.fixture(scope="module")
def ring(tmp_path_factory):
    r = rf3_cluster.Ring3(tmp_path_factory.mktemp("levels"), TABLES)
    r.session = r.connect(0)
    r.session.execute("INSERT INTO kv (k, v) VALUES (1, 'loaded')",
                      consistency="ALL")
    yield r
    r.session.close()
    r.close()


@pytest.fixture(scope="module", params=[0, 1, 2])
def down(request, ring):
    """The ring with that many nodes down: node 3 goes first, then
    node 2; node 1 coordinates throughout."""
    while len(ring.down) < request.param:
        ring.stop(2 - len(ring.down))
    ring.await_liveness()
    return request.param


def _send(session, op: str, level: str, key: int):
    if op == "read":
        return session.execute("SELECT v FROM kv WHERE k = 1",
                               consistency=level)
    if op == "write":
        return session.execute(
            f"INSERT INTO kv (k, v) VALUES ({key}, 'w')", consistency=level)
    if op == "prepared_read":
        qid = session.prepare("SELECT v FROM kv WHERE k = ?")
        return session.execute_prepared(qid, [_int(1)], consistency=level)
    if op == "prepared_write":
        qid = session.prepare("INSERT INTO kv (k, v) VALUES (?, ?)")
        return session.execute_prepared(qid, [_int(key), b"p"],
                                        consistency=level)
    return session.execute(
        f"BEGIN UNLOGGED BATCH INSERT INTO kv (k, v) VALUES ({key}, 'a'); "
        f"INSERT INTO kv (k, v) VALUES ({key + 1}, 'b'); APPLY BATCH",
        consistency=level)


@pytest.mark.parametrize("op", OPS)
@pytest.mark.parametrize("level", LEVELS)
def test_answered_or_refused_as_block_for_says(ring, down, level, op):
    alive, need = 3 - down, BLOCK_FOR[level]
    verb = "read" if op.endswith("read") else "write"
    statements = 2 if op == "batch" else 1
    key = 1000 + 100 * LEVELS.index(level) + 10 * OPS.index(op) + down
    before = _requests(verb, level)
    if alive >= need:
        rows = _send(ring.session, op, level, key).rows
        if verb == "read":
            assert rows == [("loaded",)]
    else:
        with pytest.raises(client.Unavailable) as err:
            _send(ring.session, op, level, key)
        e = err.value
        assert str(e).startswith(f"[{frame.ERR_UNAVAILABLE:#06x}]")
        assert (e.consistency, e.required, e.alive) == (level, need, alive)
        statements = 1                  # the first refusal ends a batch
    # the proxy was called with the level the frame declared
    assert _requests(verb, level) - before == statements


@pytest.mark.parametrize("level", LEVELS)
def test_an_answered_write_is_on_block_for_replicas(ring, down, level):
    """Nodes down or not, a write acknowledged at a level is in at least
    that many live replicas' LOCAL stores when the answer arrives."""
    alive, need = 3 - down, BLOCK_FOR[level]
    key = 5000 + 10 * LEVELS.index(level) + down
    insert = f"INSERT INTO kv (k, v) VALUES ({key}, 'x')"
    if alive < need:
        with pytest.raises(client.Unavailable):
            ring.session.execute(insert, consistency=level)
        need = 0                        # refused: nothing is promised
    else:
        ring.session.execute(insert, consistency=level)
    table = ring.nodes[0].schema.get_table(rf3_cluster.KEYSPACE, "kv")
    pk = table.serialize_partition_key([key])
    holding = sum(
        len(ring.nodes[i].engine.store(rf3_cluster.KEYSPACE, "kv")
            .read_partition(pk)) > 0
        for i in range(3) if i not in ring.down)
    assert holding >= need


# ------------------------------------------- a ring with every node up --

@pytest.fixture(scope="module")
def ring_up(tmp_path_factory):
    r = rf3_cluster.Ring3(tmp_path_factory.mktemp("up"), TABLES)
    r.session = r.connect(0)
    yield r
    for n in r.nodes:
        n.messaging.transport.filters.clear()
    r.session.close()
    r.close()


def test_a_write_no_replica_acknowledges_answers_write_timeout(ring_up):
    node = ring_up.nodes[0]
    node.proxy.write_timeout, keep = 0.3, node.proxy.write_timeout
    rule = node.messaging.transport.filters.drop(verb=Verb.MUTATION_REQ)
    hints = METRICS.counter("writes.hints_stored")
    try:
        with pytest.raises(client.WriteTimeout) as err:
            ring_up.session.execute(
                "INSERT INTO kv (k, v) VALUES (7, 't')", consistency="ALL")
    finally:
        rule["remaining"] = 0
        node.proxy.write_timeout = keep
    e = err.value
    assert str(e).startswith(f"[{frame.ERR_WRITE_TIMEOUT:#06x}]")
    assert (e.consistency, e.received, e.block_for, e.write_type) \
        == ("ALL", 1, 3, "SIMPLE")
    # each replica that did not acknowledge leaves a hint, when the
    # messaging reaper expires its callback
    end = time.monotonic() + 5.0
    while METRICS.counter("writes.hints_stored") - hints < 2 \
            and time.monotonic() < end:
        time.sleep(0.05)
    assert METRICS.counter("writes.hints_stored") - hints == 2


def test_a_read_no_replica_answers_is_read_timeout(ring_up):
    node = ring_up.nodes[0]
    node.proxy.read_timeout, keep = 0.3, node.proxy.read_timeout
    rule = node.messaging.transport.filters.drop(verb=Verb.READ_REQ)
    try:
        with pytest.raises(client.ReadTimeout) as err:
            ring_up.session.execute("SELECT v FROM kv WHERE k = 1",
                                    consistency="ALL")
    finally:
        rule["remaining"] = 0
        node.proxy.read_timeout = keep
    e = err.value
    assert str(e).startswith(f"[{frame.ERR_READ_TIMEOUT:#06x}]")
    assert (e.consistency, e.received, e.block_for, e.data_present) \
        == ("ALL", 1, 3, True)


def test_an_unknown_level_code_is_a_protocol_error(ring_up):
    with pytest.raises(client.DriverError) as err:
        ring_up.session.execute("SELECT v FROM kv WHERE k = 1",
                                consistency=0x0042)
    assert str(err.value).startswith(f"[{frame.ERR_PROTOCOL:#06x}]")
    assert "unknown consistency level" in str(err.value)
    # the connection goes on serving
    assert ring_up.session.execute("SELECT v FROM kv WHERE k = 1").rows \
        is not None


def test_each_quorum_on_a_read_is_invalid(ring_up):
    with pytest.raises(client.DriverError) as err:
        ring_up.session.execute("SELECT v FROM kv WHERE k = 1",
                                consistency="EACH_QUORUM")
    assert str(err.value).startswith(f"[{frame.ERR_INVALID:#06x}]")
    ring_up.session.execute("INSERT INTO kv (k, v) VALUES (8, 'e')",
                            consistency="EACH_QUORUM")


def test_a_scan_is_coordinated_at_the_declared_level(ring_up):
    ring_up.session.execute("INSERT INTO kv (k, v) VALUES (9, 's')",
                            consistency="ALL")
    calls = []
    proxy = ring_up.nodes[0].proxy
    real = proxy.scan_window
    proxy.scan_window = lambda ks, t, lo, hi, cl="ONE", **kw: (
        calls.append(cl), real(ks, t, lo, hi, cl, **kw))[1]
    try:
        rows = ring_up.session.execute("SELECT k FROM kv",
                                       consistency="QUORUM").rows
    finally:
        del proxy.scan_window
    assert (9,) in rows
    assert calls and set(calls) == {"QUORUM"}


def test_an_in_process_session_still_uses_default_cl(ring_up):
    """A caller that declares no level gets the node's; a wire request
    gets its own whatever the node's default is."""
    node = ring_up.nodes[0]
    s = node.session()
    s.keyspace = rf3_cluster.KEYSPACE
    node.default_cl = ConsistencyLevel.ALL
    try:
        w_all, r_all = _requests("write", "ALL"), _requests("read", "ALL")
        w_one = _requests("write", "ONE")
        s.execute("INSERT INTO kv (k, v) VALUES (11, 'd')")
        assert s.execute("SELECT v FROM kv WHERE k = 11").rows == [("d",)]
        assert _requests("write", "ALL") - w_all == 1
        assert _requests("read", "ALL") - r_all == 1
        ring_up.session.execute("INSERT INTO kv (k, v) VALUES (12, 'o')",
                                consistency="ONE")
        assert _requests("write", "ONE") - w_one == 1
        assert _requests("write", "ALL") - w_all == 1
    finally:
        node.default_cl = ConsistencyLevel.ONE


def test_the_spans_of_a_quorum_write_and_read_are_in_the_ring(ring_up):
    from cassandra_tpu.utils import pipeline_ledger
    mark = pipeline_ledger.new_task_id()
    ring_up.session.execute("INSERT INTO kv (k, v) VALUES (13, 'r')",
                            consistency="QUORUM")
    ring_up.session.execute("SELECT v FROM kv WHERE k = 13",
                            consistency="QUORUM")
    recs = [r for r in pipeline_ledger.ring_records() if r["id"] > mark]
    by_name = {}
    for r in recs:
        by_name.setdefault(r["name"], []).append(r)
    quorum = frame.CONSISTENCY_CODES["QUORUM"]
    (w,), (r,) = by_name["coordinator.write"], by_name["coordinator.read"]
    assert (w["cells"], w["items"], w["bytes"]) == (2, 3, quorum)
    assert (r["cells"], r["items"], r["bytes"]) == (2, 2, quorum)
    assert by_name["coordinator.write.await"][0]["parent"] == w["id"]
    assert by_name["coordinator.read.await"][0]["parent"] == r["id"]
    assert by_name["coordinator.write.await"][0]["kind"] == "stall"
    for name in ("messaging.encode", "messaging.decode",
                 "messaging.handle.mutation_req",
                 "messaging.handle.read_req"):
        assert by_name.get(name), name
    assert all(x["bytes"] > 0 for x in by_name["messaging.encode"]
               + by_name["messaging.decode"])


# ------------------------------------------------------- single nodes --

def test_one_node_at_one_coordinates_as_before(tmp_path):
    """ycsb_a.wire's deployment: RF 1, one noded-built node, CL ONE."""
    r = rf3_cluster.Ring3(tmp_path, TABLES, rf=1, n=1)
    s = r.connect(0)
    try:
        w, rd = _requests("write", "ONE"), _requests("read", "ONE")
        s.execute("INSERT INTO kv (k, v) VALUES (1, 'one')")
        assert s.execute("SELECT v FROM kv WHERE k = 1").rows == [("one",)]
        assert _requests("write", "ONE") - w == 1
        assert _requests("read", "ONE") - rd == 1
        # one replica is a quorum of one
        s.execute("INSERT INTO kv (k, v) VALUES (2, 'q')",
                  consistency="QUORUM")
        with pytest.raises(client.Unavailable) as err:
            s.execute("INSERT INTO kv (k, v) VALUES (3, 't')",
                      consistency="TWO")
        assert (err.value.required, err.value.alive) == (2, 1)
    finally:
        s.close()
        r.close()


def test_a_storage_engine_backend_accepts_and_ignores_the_level(tmp_path):
    from cassandra_tpu.config import Config, Settings
    from cassandra_tpu.schema import Schema
    from cassandra_tpu.storage.engine import StorageEngine
    from cassandra_tpu.transport.server import CQLServer
    eng = StorageEngine(str(tmp_path / "d"), Schema(), settings=Settings(
        Config.load({"commitlog_segment_size": "1MiB"})))
    srv = CQLServer(eng)
    s = client.Cluster("127.0.0.1", srv.port).connect()
    try:
        s.execute("CREATE KEYSPACE e WITH replication = "
                  "{'class': 'SimpleStrategy', 'replication_factor': 1}")
        s.execute("CREATE TABLE e.kv (k int PRIMARY KEY, v text)")
        for level in ("ONE", "QUORUM", "ALL"):
            s.execute(f"INSERT INTO e.kv (k, v) VALUES (1, '{level}')",
                      consistency=level)
            assert s.execute("SELECT v FROM e.kv WHERE k = 1",
                             consistency=level).rows == [(level,)]
    finally:
        s.close()
        srv.close()
        eng.close()
