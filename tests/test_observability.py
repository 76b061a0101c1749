"""Observability: end-to-end tracing, decaying metrics, device profiling.

Covers the ISSUE 2 acceptance surface: a traced multi-node read shows
coordinator AND replica events merged in one timeline (including a
dropped-message case), settraceprobability actually samples, the
decaying reservoir forgets old spikes, the exporter renders exposition
format, and the device profiler splits compile from execute.
"""
import time

import pytest

from cassandra_tpu.cluster.messaging import Verb
from cassandra_tpu.cluster.node import LocalCluster
from cassandra_tpu.cluster.replication import ConsistencyLevel
from cassandra_tpu.cql import Session
from cassandra_tpu.schema import Schema
from cassandra_tpu.service import profiling, tracing
from cassandra_tpu.service.metrics import (LatencyHistogram,
                                           MetricsRegistry,
                                           prometheus_text)
from cassandra_tpu.storage.engine import StorageEngine
from cassandra_tpu.tools import nodetool


@pytest.fixture
def eng(tmp_path):
    e = StorageEngine(str(tmp_path / "d"), Schema(),
                      commitlog_sync="batch")
    yield e
    e.close()


@pytest.fixture
def cluster(tmp_path):
    c = LocalCluster(3, str(tmp_path), rf=3)
    for n in c.nodes:
        n.proxy.timeout = 1.0
    s = c.session(1)
    s.execute("CREATE KEYSPACE ks WITH replication = "
              "{'class': 'SimpleStrategy', 'replication_factor': 3}")
    s.execute("USE ks")
    s.execute("CREATE TABLE kv (k int PRIMARY KEY, v text)")
    yield c
    c.shutdown()


# ------------------------------------------------------------- tracing --


def test_traced_read_merges_replica_events(cluster):
    """Coordinator + replica events land in ONE timeline: the session id
    propagates on READ_REQ, replicas record under their endpoint name,
    events ship back on the response and merge."""
    n1 = cluster.node(1)
    n1.default_cl = ConsistencyLevel.ALL
    s = cluster.session(1)
    s.keyspace = "ks"
    s.execute("INSERT INTO kv (k, v) VALUES (1, 'x')")
    rs = s.execute("SELECT v FROM kv WHERE k = 1", trace=True)
    assert rs.rows == [("x",)]
    sources = {src for _us, src, _a in rs.trace.events}
    # local coordinator events plus at least one replica's
    assert "local" in sources
    assert sources & {"node2", "node3"}, sources
    acts = [a for _us, _src, a in rs.trace.events]
    assert any("Sending READ_REQ" in a for a in acts)
    assert any("READ_REQ received from node1" in a for a in acts)
    # the session persisted to the coordinator's system_traces store
    assert cluster.node(1).trace_store.get(rs.trace.session_id)


def test_traced_write_replica_events(cluster):
    n1 = cluster.node(1)
    n1.default_cl = ConsistencyLevel.ALL
    s = cluster.session(1)
    s.keyspace = "ks"
    rs = s.execute("INSERT INTO kv (k, v) VALUES (9, 'w')", trace=True)
    acts = [a for _us, _src, a in rs.trace.events]
    assert any("Sending MUTATION_REQ" in a for a in acts)
    assert any("MUTATION_REQ received" in a for a in acts)
    # replica-side engine events recorded under the replica's name
    assert any(src in ("node2", "node3") and "commitlog" in a
               for _us, src, a in rs.trace.events)


def test_trace_drop_renders_failure_event(cluster):
    """MessageFilters.drop + replica timeout: the coordinator timeline
    still renders — local events intact plus the failure event — and
    nothing hangs."""
    n1 = cluster.node(1)
    n1.default_cl = ConsistencyLevel.ALL
    n1.proxy.timeout = 0.4
    s = cluster.session(1)
    s.keyspace = "ks"
    s.execute("INSERT INTO kv (k, v) VALUES (2, 'y')")
    victim = cluster.node(2).endpoint
    cluster.filters.drop(verb=Verb.READ_REQ, to=victim)
    try:
        with pytest.raises(Exception) as ei:
            s.execute("SELECT v FROM kv WHERE k = 2", trace=True)
        assert "Timeout" in type(ei.value).__name__ or \
            "timeout" in str(ei.value).lower()
    finally:
        cluster.filters.clear()
    # the failed request's timeline persisted anyway
    sessions = n1.trace_store.sessions()
    assert sessions, "trace of the failed read was lost"
    st = sessions[-1]
    acts = [a for _us, _src, a in st.events]
    assert any("Sending READ_REQ to node2" in a for a in acts)
    # the timeout event fires from the reaper shortly after the raise;
    # it merges into the session via the recent-tail registry
    deadline = time.time() + 5
    while time.time() < deadline:
        acts = [a for _us, _src, a in list(st.events)]
        if any("Failure/timeout" in a and "node2" in a for a in acts):
            break
        time.sleep(0.05)
    assert any("Failure/timeout" in a and "node2" in a for a in acts), acts


def test_settraceprobability_sampling(eng):
    s = Session(eng)
    s.execute("CREATE KEYSPACE ks WITH replication = "
              "{'class': 'SimpleStrategy', 'replication_factor': 1}")
    s.execute("USE ks")
    s.execute("CREATE TABLE kv (k int PRIMARY KEY, v text)")
    # p=0.0 (default): nothing sampled
    assert nodetool.gettraceprobability(eng) == {"trace_probability": 0.0}
    before = len(eng.trace_store.sessions())
    for i in range(5):
        s.execute(f"INSERT INTO kv (k, v) VALUES ({i}, 'a')")
    assert len(eng.trace_store.sessions()) == before
    # p=1.0: every statement samples into the store; the result set
    # stays untouched (no .trace attribute on background samples)
    nodetool.settraceprobability(eng, 1.0)
    rs = s.execute("SELECT * FROM kv WHERE k = 1")
    assert not hasattr(rs, "trace")
    got = len(eng.trace_store.sessions()) - before
    assert got >= 1
    stored = eng.trace_store.sessions()[-1]
    assert "SELECT" in stored.request
    # back to 0: sampling stops
    nodetool.settraceprobability(eng, 0.0)
    n = len(eng.trace_store.sessions())
    s.execute("SELECT * FROM kv WHERE k = 2")
    assert len(eng.trace_store.sessions()) == n
    with pytest.raises(ValueError):
        nodetool.settraceprobability(eng, 1.5)


def test_trace_vtables_and_gettraces(eng):
    s = Session(eng)
    s.execute("CREATE KEYSPACE ks WITH replication = "
              "{'class': 'SimpleStrategy', 'replication_factor': 1}")
    s.execute("USE ks")
    s.execute("CREATE TABLE kv (k int PRIMARY KEY, v text)")
    rs = s.execute("INSERT INTO kv (k, v) VALUES (1, 'x')", trace=True)
    sid = rs.trace.session_id
    rows = s.execute("SELECT * FROM system_traces.sessions").dicts()
    assert any(r["session_id"] == sid for r in rows)
    evs = s.execute("SELECT * FROM system_traces.events "
                    f"WHERE session_id = '{sid}'").dicts()
    assert evs and all(e["session_id"] == sid for e in evs)
    assert any("commitlog" in e["activity"] for e in evs)
    out = nodetool.gettraces(eng)
    assert any(t["session_id"] == sid and t["events"] for t in out)


def test_slow_query_links_trace_session(eng):
    s = Session(eng)
    s.execute("CREATE KEYSPACE ks WITH replication = "
              "{'class': 'SimpleStrategy', 'replication_factor': 1}")
    s.execute("USE ks")
    s.execute("CREATE TABLE kv (k int PRIMARY KEY, v text)")
    eng.monitor.threshold_ms = 0.0   # everything is "slow"
    rs = s.execute("SELECT * FROM kv WHERE k = 1", trace=True)
    entries = eng.monitor.entries()
    linked = [e for e in entries if e.get("trace_session")]
    assert linked and linked[-1]["trace_session"] == rs.trace.session_id
    rows = s.execute("SELECT * FROM system_views.slow_queries").dicts()
    assert any(r["trace_session"] == rs.trace.session_id for r in rows)
    # untraced statements carry no link
    eng.monitor.threshold_ms = 0.0
    s.execute("SELECT * FROM kv WHERE k = 2")
    assert eng.monitor.entries()[-1]["trace_session"] is None


# ------------------------------------------------------------- metrics --


def test_decaying_histogram_forgets_old_spikes():
    clk = [0.0]
    h = LatencyHistogram(window_s=10.0, clock=lambda: clk[0])
    for _ in range(100):
        h.update_us(100)          # bucket 2^6
    h.update_us(1_000_000)        # the spike: bucket 2^19
    assert h.percentile(0.5) == 64.0
    assert h.max_us == 1_000_000
    assert h.summary()["p99_us"] >= 64.0
    # an hour later (way past 2 windows) the spike no longer pollutes
    clk[0] = 3600.0
    for _ in range(50):
        h.update_us(100)
    s = h.summary()
    assert s["p99_us"] == 64.0
    assert s["max_us"] == 100
    # lifetime count/mean are immortal
    assert s["count"] == 151
    assert h.count == 151


def test_snapshot_exports_all_percentiles_consistently():
    reg = MetricsRegistry()
    reg.incr("cql.select", 3)
    h = reg.hist("request.read")
    for us in (100, 200, 400, 800):
        h.update_us(us)
    snap = reg.snapshot()
    assert snap["cql.select"] == 3
    for suffix in ("count", "mean_us", "p50_us", "p95_us", "p99_us",
                   "max_us"):
        assert f"request.read.{suffix}" in snap
    assert snap["request.read.count"] == 4
    assert snap["request.read.max_us"] == 800


def test_metric_groups_and_gauges():
    reg = MetricsRegistry()
    g = reg.group("table.ks.kv")
    g.incr("writes", 2)
    with g.timer("write_latency"):
        pass
    assert reg.counter("table.ks.kv.writes") == 2
    assert reg.hist("table.ks.kv.write_latency").count == 1
    reg.register_gauge("cache.chunks.entries", lambda: 7)
    assert reg.snapshot()["cache.chunks.entries"] == 7
    reg.register_gauge("cache.bad.gauge", lambda: 1 / 0)
    assert "cache.bad.gauge" not in reg.snapshot()   # dead gauge skipped


def test_prometheus_exporter_format():
    reg = MetricsRegistry()
    reg.incr("cql.select", 5)
    reg.hist("request.read").update_us(512)
    reg.register_gauge("compaction.pending", lambda: 3)
    text = prometheus_text(reg, extra_gauges={"compaction.slots": 2})
    assert "# TYPE ctpu_cql_select counter" in text
    assert "ctpu_cql_select 5" in text
    assert 'ctpu_request_read_us{quantile="0.99"}' in text
    assert "ctpu_request_read_us_count 1" in text
    assert "# TYPE ctpu_compaction_pending gauge" in text
    assert "ctpu_compaction_slots 2" in text


def test_nodetool_exportmetrics(eng):
    from cassandra_tpu.service.metrics import GLOBAL
    GLOBAL.incr("storage.writes", 0)   # ensure at least one counter
    text = nodetool.exportmetrics(eng)
    assert "# TYPE ctpu_" in text
    assert text.endswith("\n")


def test_coordinator_request_latency_groups(cluster):
    from cassandra_tpu.service.metrics import GLOBAL
    s = cluster.session(1)
    s.keyspace = "ks"
    base_w = GLOBAL.hist("request.write").count
    base_r = GLOBAL.hist("request.read").count
    s.execute("INSERT INTO kv (k, v) VALUES (5, 'm')")
    s.execute("SELECT v FROM kv WHERE k = 5")
    assert GLOBAL.hist("request.write").count > base_w
    assert GLOBAL.hist("request.read").count > base_r
    # per-verb internode counters
    assert GLOBAL.counter("verb.read_req.received") >= 0


def test_metric_name_check_script():
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "check_metric_names",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts",
            "check_metric_names.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.scan() == []            # the repo itself is clean
    assert mod.check_name("incr", "cql.request")
    assert mod.check_name("incr", "table.{ks}.{t}.writes")
    assert mod.check_name("hist", "read_latency")      # group member
    assert not mod.check_name("incr", "NoDots")
    assert not mod.check_name("incr", "Bad.Name")
    assert not mod.check_name("incr", "bad..name")


# ----------------------------------------------------------- profiling --


def test_kernel_profiler_splits_compile_from_execute():
    import numpy as np

    from cassandra_tpu.ops.device_write import merge_sorted_device
    from cassandra_tpu.schema import make_table
    from cassandra_tpu.storage import cellbatch as cb
    from cassandra_tpu.tools import bulk
    profiling.GLOBAL.reset()
    table = make_table("ks", "kp", pk=["id"], ck=["c"],
                       cols={"id": "int", "c": "int", "v": "blob"})
    rng = np.random.default_rng(7)
    batches = []
    for _ in range(2):
        n = 512
        b = bulk.build_int_batch(
            table, rng.integers(0, 16, n), rng.integers(1, 50, n),
            rng.integers(0, 256, (n, 8), dtype=np.uint8),
            rng.integers(1, 1 << 40, n).astype(np.int64))
        batches.append(cb.merge_sorted([b]))
    a = merge_sorted_device(batches)
    b2 = merge_sorted_device(batches)
    assert len(a) == len(b2)
    snap = profiling.GLOBAL.snapshot()
    kernels = snap["kernels"]
    assert kernels, "no kernel recorded"
    name, k = next(iter(kernels.items()))
    assert name.startswith("merge.")
    assert k["calls"] == 2
    assert k["compiles"] == 1          # same shape: one compile only
    assert k["shape_count"] == 1
    assert k["compile_s"] > 0
    assert k["execute_s"] > 0


def test_device_profile_vtable_and_phases(eng):
    profiling.GLOBAL.reset()
    s = Session(eng)
    s.execute("CREATE KEYSPACE ks WITH replication = "
              "{'class': 'SimpleStrategy', 'replication_factor': 1}")
    s.execute("USE ks")
    s.execute("CREATE TABLE kv (k int PRIMARY KEY, v text)")
    for gen in range(2):
        for i in range(20):
            s.execute(f"INSERT INTO kv (k, v) VALUES ({i}, 'g{gen}')")
        nodetool.flush(eng, "ks", "kv")
    res = nodetool.compact(eng, "ks", "kv")
    assert res
    rows = s.execute("SELECT * FROM system_views.device_profile").dicts()
    phases = {r["name"]: r for r in rows if r["kind"] == "phase"}
    # the pipelined writer's split phases from PR 1 feed the vtable
    assert "phase.compress" in phases
    assert "phase.io_write" in phases
    assert "phase.seal" in phases
    assert all(p["execute_seconds"] >= 0 for p in phases.values())
