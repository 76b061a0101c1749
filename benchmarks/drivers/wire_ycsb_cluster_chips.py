"""wire_ycsb_cluster_chips: `ycsb_a.wire_rf3` with a chip per node --
YCSB core workload A as its own Cassandra binding deploys it, three nodes,
RF 3, reads and updates at QUORUM, over the wire -- where node i's engine
is pinned to jax device i through its node config (`device_ordinal`, as a
tools/noded.py config carries it): every device program of node i, its
compactions' `merge.resident` rounds and write-lane programs above all,
runs on chip i. The configuration's `cluster.devices` lists the ordinals.

Everything else is `wire_ycsb_cluster`'s, loaded from
drivers/wire_ycsb_cluster.py and drivers/wire_ycsb.py and called as they
are: the data, the statements, the generator children, the window, the
history judge, the cluster's checks and the controls. What differs:

Set-up. (a) A program whose node config takes no `device_ordinal` cannot
run the cell: refused in the first lines, before any data is made.
(b) The cluster, each node built with its own ordinal; right after it is
up every engine has to report the device its config named, or set-up is
refused at once. (c) The sstables land once and are hard-linked into each
node, as in `wire_ycsb_cluster`. (d) Every statement warmed through every
coordinator. (e) The pending compaction runs through the manager on ALL
THREE nodes side by side, so that each chip compiles (or reads from the
compile cache) its own programs; then every node's table is truncated
and its inputs put back from hard links.

`correct` adds one check to `wire_ycsb_cluster`'s (limit 0):
`rounds_off_home_device`, from each node's compactions (their
`rounds_by_device` and `lane_dispatches_by_device`) the rounds and
write-lane programs of node i that ran on a chip other than its own, plus
every node whose served compactions ran no round on its own chip, plus,
from the program's per-device counters and the program registry since
set-up began, every round, write-lane dispatch and call of `merge.resident`,
`write.append` or `write.serialize` on a chip that is no node's. And,
since each node ran a warm-up compaction, `compactions_off_device` also
counts nodes 2 and 3 when their served compaction did not run.

The window's result is `wire_ycsb_cluster`'s plus `device_counters`: the
rise of `merge.resident.rounds.dev<i>` and `write.lane.dispatches.dev<i>`
from the release until the served compactions finished.
"""
from __future__ import annotations

import os
import time
import uuid
from concurrent.futures import ThreadPoolExecutor

import numpy as np

DEVICE_COUNTERS = ("merge.resident.rounds.dev", "write.lane.dispatches.dev")
DEVICE_PROGRAMS = ("merge.resident", "write.append", "write.serialize")


class State:
    pass


def _device_counters() -> dict:
    from cassandra_tpu.service.metrics import GLOBAL as METRICS
    return {n: v for n, v in METRICS.snapshot().items()
            if n.startswith(DEVICE_COUNTERS)}


def _program_calls() -> dict:
    """{(program, device id): calls} of the compaction's programs."""
    from cassandra_tpu.service.profiling import GLOBAL as registry
    kernels = registry.snapshot()["kernels"]
    return {(name, d): c["calls"] for name in DEVICE_PROGRAMS
            for d, c in kernels.get(name, {}).get("devices", {}).items()}


def _refuse_without_device_ordinal() -> None:
    from cassandra_tpu.config import Config, ConfigError
    try:
        Config.load({"device_ordinal": 0})
    except ConfigError as e:
        raise RuntimeError(
            f"this program's node config takes no device_ordinal ({e}): "
            "a node cannot be pinned to a chip, and the cell "
            "ycsb_a.wire_rf3_chips4 cannot run on it") from e


def _pinned_cluster(data_dir: str, cfg: dict):
    """wire_cluster.ServedCluster with node i's config carrying
    `device_ordinal` = cluster.devices[i]."""
    import wire
    import wire_cluster
    from cassandra_tpu.client import Cluster
    from cassandra_tpu.cluster.ring import even_tokens
    from cassandra_tpu.tools.noded import build_node
    from cassandra_tpu.transport.server import CQLServer

    s, c = cfg["schema"], cfg["cluster"]
    n, devices = int(c["nodes"]), [int(d) for d in c["devices"]]
    assert len(devices) == n
    keyspace = s["keyspace"]
    internode = wire_cluster.free_ports(n)
    tokens = even_tokens(n, vnodes=int(c["vnodes"]))
    names = [f"node{i + 1}" for i in range(n)]
    ddl = [f"CREATE KEYSPACE {keyspace} WITH replication = "
           f"{{'class': '{c['strategy']}', 'replication_factor': "
           f"{int(c['replication_factor'])}}}", f"USE {keyspace}"]
    for stmt in s["ddl"]:
        tid = uuid.uuid5(uuid.NAMESPACE_DNS,
                         f"ctpu.bench.{keyspace}.{len(ddl)}")
        ddl.append(f"{stmt} AND id = {tid}")

    def peer(i: int) -> dict:
        return {"name": names[i], "host": "127.0.0.1",
                "port": internode[i], "tokens": tokens[i]}
    cl = wire_cluster.ServedCluster.__new__(wire_cluster.ServedCluster)
    cl.keyspace = keyspace
    cl.nodes, cl.servers, cl.sessions = [], [], []
    try:
        for i in range(n):
            node, _transport = build_node(dict(
                peer(i), data_dir=os.path.join(data_dir, names[i]),
                peers=[peer(j) for j in range(n) if j != i],
                seeds=[names[0]], ddl=ddl,
                gossip_interval=float(c["gossip_interval"]),
                config=dict(cfg.get("node_config") or {},
                            device_ordinal=devices[i])))
            cl.nodes.append(node)
            cl.servers.append(CQLServer(node, "127.0.0.1", 0))
            cl.sessions.append(None)
        cl.ports = [srv.port for srv in cl.servers]
        cl.await_all_alive(float(c["liveness_wait_s"]))
        for i, port in enumerate(cl.ports):
            sess = Cluster("127.0.0.1", port).connect()
            sess._sock.settimeout(wire.COLD_REQUEST_TIMEOUT_S)
            sess.execute(f"USE {keyspace}")
            cl.sessions[i] = sess
    except BaseException:
        for i, node in enumerate(cl.nodes):
            if cl.sessions[i] is not None:
                cl.sessions[i].close()
            cl.servers[i].close()
            node.shutdown()
        raise
    return cl


def _check_bound(st: State) -> None:
    """Every engine reports the device its config named: refused at
    once otherwise."""
    import jax
    want = [jax.devices()[d] for d in st.devices]
    got = [getattr(node.engine, "device", None)
           for node in st.cluster.nodes]
    if got != want:
        raise RuntimeError(f"the engines report devices {got}, their "
                           f"configs named {want}")


# -------------------------------------------------------------- set-up --

def setup(ctx) -> State:
    import wire_cluster
    _refuse_without_device_ordinal()
    st = State()
    base = st.base = ctx.load("drivers", "wire_ycsb_cluster")
    cfg, mix = ctx.config, ctx.traffic
    st.cfg, st.mix, st.scratch = cfg, mix, ctx.scratch
    st.level = str(mix["consistency"])
    assert cfg["consistency"] == {"read": st.level, "write": st.level}
    st.ref = ctx.load("reference", "ycsb")
    st.quorum = ctx.load("reference", "ycsb_quorum")
    st.wy = ctx.load("drivers", "wire_ycsb")
    st.rows = ctx.load("drivers", "major_loop")
    s, w, d, c = cfg["schema"], cfg["workload"], cfg["data"], cfg["cluster"]
    st.records = int(w["recordcount"])
    st.fields, st.length = int(w["fieldcount"]), int(w["fieldlength"])
    st.n = int(c["nodes"])
    st.devices = [int(x) for x in c["devices"]]
    assert st.records == int(d["sstables"]) * int(d["records_per_sstable"])
    assert st.fields == int(s["fields"])
    st.counters0, st.programs0 = _device_counters(), _program_calls()
    t0 = time.perf_counter()
    st.loaded = st.ref.loaded_values(ctx.seed, st.records, st.fields,
                                     st.length)
    st.names = st.ref.key_names(np.arange(st.records))
    st.cluster = _pinned_cluster(os.path.join(ctx.scratch, "cluster"), cfg)
    _check_bound(st)
    st.homes = [node.engine.device.id for node in st.cluster.nodes]
    ctx.note("devices", st.homes)
    ctx.note("cluster_up_s", time.perf_counter() - t0)
    for node in st.cluster.nodes:
        node.engine.compactions.paused = True   # disableautocompaction
    st.table = st.cluster.table(s["table"])
    st.stores = [st.cluster.store(i, s["table"]) for i in range(st.n)]
    st.cfs = st.stores[0]                # wire_ycsb's builder lands here
    st.field_ids = st.wy._field_ids(st.table, cfg)
    st.wy._selfcheck(st)
    st.wy._land(st)
    st.copies = os.path.join(ctx.scratch, "inputs")
    st.wy._link_all(st.cfs.directory, st.copies)
    for cfs in st.stores[1:]:            # the same keys, the same bytes
        st.wy._link_all(st.copies, cfs.directory)
        cfs.reload_sstables()
    st.lanes = int(st.cfs.live_sstables()[0].K)
    st.input_bytes = sum(r.data_size for r in st.cfs.live_sstables())
    ctx.note("load_s", time.perf_counter() - t0)
    ctx.note("input_mib_per_node", st.input_bytes / 2.0 ** 20)

    # the statements, warmed through every coordinator at the level
    fmt = {"table": s["table"], "key": s["key"]}
    st.statements = {"read": mix["read"].format(**fmt)}
    for f in range(st.fields):
        st.statements[f"update{f}"] = mix["update"].format(field=f, **fmt)
    rng = np.random.default_rng([ctx.seed, 2])
    t0 = time.perf_counter()
    for session in st.cluster.sessions:
        qid = {n: session.prepare(c_) for n, c_ in st.statements.items()}
        for i, k in enumerate(rng.integers(0, st.records,
                                           int(mix["warm_operations"]))):
            got = session.execute_prepared(qid["read"], [st.names[k]],
                                           consistency=st.level)
            session.execute_prepared(
                qid[f"update{i % st.fields}"],
                [b"w" * st.length, st.wy.WARM_KEY], consistency=st.level)
    names = list(got.column_names)
    st.columns = [names.index(f"field{f}") for f in range(st.fields)]
    ctx.note("warm_statements_s", time.perf_counter() - t0)

    # every (program, shape, chip) the served compactions will use: the
    # pending compaction on EVERY node at once, each through its manager
    st.fallbacks0 = st.rows._counters()
    wait_s = float(cfg["correct"]["warm_compaction_wait_s"])
    with ThreadPoolExecutor(st.n) as pool:
        warm = list(pool.map(
            lambda i: st.wy._run_pending_compaction(base._view(st, i),
                                                    wait_s),
            range(st.n)))
    for node in st.cluster.nodes:
        node.engine.compactions.paused = True
    if not all(x["done"] for x in warm):
        raise RuntimeError("a warm-up compaction did not finish")
    st.warm = [st.wy._compactions(base._view(st, i)) for i in range(st.n)]
    st.warm_compactions = st.warm[0]     # wire_ycsb_cluster's node 1
    ctx.note("warm_compaction_s", [x["seconds"] for x in warm])
    ctx.note("warm_compaction_engine",
             [[c_.get("engine") for c_ in mine] for mine in st.warm])
    for cfs in st.stores:                # the warm-up row goes with it
        cfs.truncate()
        st.wy._link_all(st.copies, cfs.directory)
        cfs.reload_sstables()            # nodetool refresh
        assert len(cfs.live_sstables()) == int(d["sstables"])
    os.sync()

    st.streams = [st.ref.op_stream(ctx.seed, c_,
                                   int(mix["ops_per_connection"]),
                                   st.records, st.fields, st.length,
                                   float(w["readproportion"]))
                  for c_ in range(int(mix["connections"]))]
    st.children = wire_cluster.LevelChildren(
        os.path.join(ctx.scratch, "gen"), ctx.root)
    st.children.start([{
        "host": "127.0.0.1", "port": st.cluster.ports[c_ % st.n],
        "keyspace": s["keyspace"], "statements": st.statements,
        "consistency": st.level, "seconds": ctx.seconds,
        "timeout_s": float(mix["timeout_s"]),
        "ops": st.wy._job_ops(st, stream)}
        for c_, stream in enumerate(st.streams)])
    return st


# ---------------------------------------------------------- the window --

def _served(st: State) -> list:
    """Per node, what its compactions since the warm-up say of
    themselves."""
    return [st.wy._compactions(st.base._view(st, i))[len(st.warm[i]):]
            for i in range(st.n)]


def window(st: State, ctx) -> dict:
    counters0 = _device_counters()
    result = st.base.window(st, ctx)     # waits for the compactions too
    result["device_counters"] = {
        n: v - counters0.get(n, 0) for n, v in _device_counters().items()
        if v != counters0.get(n, 0)}
    result["detail"]["device_counters"] = result["device_counters"]
    result["detail"]["compactions"] = [
        [{k: c.get(k) for k in ("engine", "engine_chosen", "seconds",
                                "inputs", "cells_read", "bytes_read",
                                "rounds_by_device",
                                "lane_dispatches_by_device")}
         for c in mine] for mine in _served(st)]
    return result


# ------------------------------------------------------------ `correct` --

def rounds_off_home_device(homes: list, compactions: list, served: list,
                           counters: dict, calls: dict) -> int:
    """The check's count. `compactions[i]`: every compaction of node i
    since set-up (warm-up and served); `served[i]`: those of the window;
    `counters` / `calls`: the rise since set-up of the per-device
    counters and of the registry's {(program, device): calls}."""
    off = 0
    for home, mine, window in zip(homes, compactions, served):
        for c in mine:
            for key in ("rounds_by_device", "lane_dispatches_by_device"):
                off += sum(v for d, v in (c.get(key) or {}).items()
                           if d != home)
        off += not any((c.get("rounds_by_device") or {}).get(home)
                       for c in window)
    for name, v in counters.items():
        off += v * (int(name.rsplit(".dev", 1)[1]) not in homes)
    off += sum(v for (_p, d), v in calls.items() if d not in homes)
    return off


def check(st: State, ctx, result: dict) -> list:
    served = _served(st)
    compactions = [st.warm[i] + served[i] for i in range(st.n)]
    counters = {n: v - st.counters0.get(n, 0)
                for n, v in _device_counters().items()}
    calls = {k: v - st.programs0.get(k, 0)
             for k, v in _program_calls().items()}
    ctx.note("device_counters_since_setup",
             {n: v for n, v in counters.items() if v})
    ctx.note("program_calls_since_setup",
             {f"{p}.dev{d}": v for (p, d), v in sorted(calls.items()) if v})
    off_home = rounds_off_home_device(st.homes, compactions, served,
                                      counters, calls)
    # wire_ycsb_cluster counts a node without a served compaction for
    # node 1 only (its warm-up ran there alone)
    missing = sum(1 for mine in served[1:] if not mine)
    checks = st.base.check(st, ctx, result)
    for c in checks:
        if c["name"] == "compactions_off_device":
            c["value"] += missing
    return checks + [{"name": "rounds_off_home_device", "value": off_home,
                      "limit": 0, "of": sum(
                          sum((c.get("rounds_by_device") or {}).values())
                          for mine in compactions for c in mine)}]


def control(ctx) -> list:
    """wire_ycsb_cluster's controls: the layout changes no answer."""
    return ctx.load("drivers", "wire_ycsb_cluster").control(ctx)


def close(st: State) -> None:
    if hasattr(st, "children"):
        st.children.kill()
    if getattr(st, "cluster", None) is not None:
        st.cluster.close()
