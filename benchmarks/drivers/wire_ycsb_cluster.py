"""wire_ycsb_cluster: YCSB core workload A as its own Cassandra binding
deploys it -- three nodes, RF 3, reads and updates at QUORUM -- over the
wire against a cluster in this process (wire_cluster.ServedCluster: every
node built as tools/noded.py builds one, TcpTransport between them), each
generator child speaking to ONE coordinator and declaring the level, while
the compaction that `ycsb load` left pending runs on ALL THREE nodes,
selected by each node's CompactionManager and on the engine each task
chooses itself.

The data, the key chooser, the operation streams, the history judge and
the sstable builder are `ycsb_a.wire`'s: this driver loads
drivers/wire_ycsb.py and reference/ycsb.py for them, as wire_ycsb loads
major_loop.

Set-up. (a) A program whose executor takes no consistency level cannot
run the cell: refused in the first lines. (b) The cluster; every node sees
both peers alive. (c) The configuration's sstables are landed ONCE, on
node 1, and hard-linked into the other nodes' table directories (RF 3 on
three nodes: the same keys, the same bytes). (d) Every statement is warmed
through every coordinator at the mix's level. (e) The pending compaction
runs through the manager on ONE node (three replicas, the same shapes, one
process: that warms every program for all three); then every node's table
is truncated and its inputs put back from hard links.

How `correct` is decided (limits 0, exact; reference/ycsb.py and
reference/ycsb_quorum.py hold the rules): wire_ycsb's `ops_unanswered`,
`reads_stale`, `reads_unknown_value`, `final_rows_wrong` over all the
connections, whichever coordinator an operation went through (the final
read-back goes round the coordinators too); `replicas_diverging`: after
the window, the compactions, a drain of the messaging queues and the
replay of the hints, every updated key read from each node's LOCAL store
holds the same row on all three, right by the final-row rule (read
BEFORE the final read-back, whose read repair would mend a replica);
`levels_not_coordinated`: operations answered in the window less what the
coordinators counted at the mix's level; `quorum_not_enforced`: last, with
nodes 2 and 3 shut down, an update and a read at the mix's level through
node 1 must each be refused and a ONE read answered; summed over the nodes
`sstables_beyond_one`, `compactions_off_device` and
`components_differing_from_host_engine` (ONE numpy-engine compaction of
the shared inputs against all three outputs). `control(ctx)` runs
reference/ycsb_quorum.py's replica set in the cluster's place: as it is,
read and written at ONE, one replica dropping one mutation in a thousand,
values cut to 99 bytes.

The window's result carries what wire_ycsb's does (`release_perf`,
`spans`, `ops`, `lanes`) plus `counters`, the window's rise of the
program's counters the per-layer readers and the checks need.
"""
from __future__ import annotations

import inspect
import os
import time
from types import SimpleNamespace

import numpy as np

COUNTERS = ("reads.digest_mismatches", "reads.read_repairs",
            "writes.hints_stored")
QUIET_S = 1.2


class State:
    pass


def _view(st: State, i: int):
    """Node i as wire_ycsb's helpers see a served node: `.served.node`
    and `.cfs`."""
    return SimpleNamespace(served=SimpleNamespace(node=st.cluster.nodes[i]),
                           cfs=st.stores[i])


def _level_counters(level: str) -> dict:
    from cassandra_tpu.service.metrics import GLOBAL as METRICS
    names = list(COUNTERS) + [
        f"coordinator.requests.{verb}.{level.lower()}"
        for verb in ("read", "write")]
    return {n: METRICS.counter(n) for n in names}


# -------------------------------------------------------------- set-up --

def setup(ctx) -> State:
    import wire
    import wire_cluster
    from cassandra_tpu.cql.execution import Executor
    if "consistency" not in inspect.signature(Executor.execute).parameters:
        # before PR 33 the level a frame declared was parsed and dropped:
        # every request was coordinated at the node's default, ONE.
        raise RuntimeError(
            "this program's Executor.execute takes no consistency level "
            "(cql/execution.py): a request's level never reaches the "
            "coordinator, and the cell ycsb_a.wire_rf3 cannot run on it")
    st = State()
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
    assert st.records == int(d["sstables"]) * int(d["records_per_sstable"])
    assert st.fields == int(s["fields"])
    t0 = time.perf_counter()
    st.loaded = st.ref.loaded_values(ctx.seed, st.records, st.fields,
                                     st.length)
    st.names = st.ref.key_names(np.arange(st.records))
    st.cluster = wire_cluster.ServedCluster(
        os.path.join(ctx.scratch, "cluster"), s["keyspace"],
        {"class": c["strategy"],
         "replication_factor": int(c["replication_factor"])},
        s["ddl"], c, cfg.get("node_config"))
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

    # every (program, shape) the served compactions will use: ONE
    # compaction on ONE node, through its manager
    st.fallbacks0 = st.rows._counters()
    warm = st.wy._run_pending_compaction(
        _view(st, 0), float(cfg["correct"]["warm_compaction_wait_s"]))
    st.cluster.nodes[0].engine.compactions.paused = True
    if not warm["done"]:
        raise RuntimeError("the warm-up compaction did not finish")
    st.warm_compactions = st.wy._compactions(_view(st, 0))
    ctx.note("warm_compaction_s", warm["seconds"])
    ctx.note("warm_compaction_engine",
             [c_.get("engine") for c_ in st.warm_compactions])
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

def _await_compactions(st: State, wait_s: float) -> bool:
    end = time.monotonic() + wait_s
    return all(st.wy._await_compaction(
        _view(st, i), max(end - time.monotonic(), 0.1))
        for i in range(st.n))


def window(st: State, ctx) -> dict:
    import wire
    mix = st.mix
    drain = st.wy.RingDrain(float(mix["ring_drain_s"]))
    drain.start()
    counters0 = _level_counters(st.level)
    t0 = st.children.release()
    release_perf = t0 + (time.perf_counter() - time.monotonic())
    time.sleep(max(t0 - time.monotonic(), 0))
    for i, node in enumerate(st.cluster.nodes):
        cm = node.engine.compactions
        cm.paused = False                # nodetool enableautocompaction
        cm.submit_background(st.stores[i])   # a flush's notification
    th = wire.trace_slice(ctx, t0, mix.get("trace", {}))
    with ctx.annotate("bench.window.wait_generators"):
        per_child = st.children.collect(
            ctx.seconds + float(mix["timeout_s"]) + 60.0)
    counters = {n: v - counters0[n]
                for n, v in _level_counters(st.level).items()}
    if th is not None:
        th.join()
    t_wait = time.perf_counter()
    finished = _await_compactions(
        st, float(st.cfg["correct"]["compaction_wait_s"]))
    waited = time.perf_counter() - t_wait
    spans = drain.finish()

    ops, lost, exhausted = [], 0, 0
    for c, sent in enumerate(per_child):
        if sent is None:
            lost += 1
            continue
        stream = st.streams[c]
        exhausted += len(sent) == len(stream["keynum"])
        for index, t_sent, t_done, ok, rows, err in sent:
            op = {"conn": c, "index": index, "via": c % st.n,
                  "keynum": int(stream["keynum"][index]),
                  "sent": t_sent, "done": t_done, "ok": ok, "err": err}
            if stream["is_read"][index]:
                row = None
                if ok and rows:
                    row = [st.wy._as_bytes(rows[0][i]) for i in st.columns]
                op.update(kind="read", row=row)
            else:
                op.update(kind="update", field=int(stream["field"][index]),
                          value=stream["value"][index].tobytes())
            ops.append(op)
    good = [o for o in ops if o["ok"]]
    elapsed = max([o["done"] for o in ops] + [ctx.seconds])
    lat = {k: sorted((o["done"] - o["sent"]) * 1000.0 for o in good
                     if o["kind"] == k) for k in ("read", "update")}
    served = [[{k: c.get(k) for k in ("engine", "engine_chosen", "seconds",
                                      "inputs", "cells_read", "bytes_read")}
               for c in st.wy._compactions(_view(st, i))[
                   len(st.warm_compactions) if i == 0 else 0:]]
              for i in range(st.n)]
    return {"attempted": len(ops) + lost,
            "failed": len(ops) - len(good) + lost, "ops": ops,
            "elapsed_s": elapsed, "release_perf": release_perf,
            "spans": spans, "lanes": st.lanes, "counters": counters,
            "end_to_end": {"ops_s": len(good) / elapsed},
            "detail": {
                "operations": len(ops), "children_lost": lost,
                "streams_exhausted": exhausted,
                "reads": len(lat["read"]), "updates": len(lat["update"]),
                "read_p50_ms": st.wy._mid(lat["read"]),
                "update_p50_ms": st.wy._mid(lat["update"]),
                "counters": counters,
                "compactions_finished": finished,
                "compactions_waited_after_window_s": waited,
                "compactions": served,
                "spans_drained": None if spans is None else len(spans),
                "errors": sorted({o["err"] for o in ops if o["err"]})[:3]}}


# ------------------------------------------------------------ `correct` --

def cluster_checks(base: list, diverging: int, updated: int,
                   not_coordinated: int, not_enforced: int) -> list:
    """wire_ycsb's numbers and the cluster's own, from a run or from a
    control alike."""
    return base + [
        {"name": "replicas_diverging", "value": diverging, "limit": 0,
         "of": updated},
        {"name": "levels_not_coordinated", "value": not_coordinated,
         "limit": 0},
        {"name": "quorum_not_enforced", "value": not_enforced,
         "limit": 0, "of": 3}]


def _local_row(st: State, i: int, keynum: int) -> list | None:
    """Key `keynum` as node i's LOCAL store holds it: memtable and
    sstables, no coordinator and no other replica."""
    from cassandra_tpu.storage.rows import rows_from_batch
    pk = st.table.serialize_partition_key(
        [st.names[keynum].decode("ascii")])
    rows = list(rows_from_batch(st.table,
                                st.stores[i].read_partition(pk)))
    if len(rows) != 1:
        return None
    return [rows[0].cells.get(cid) for cid in st.field_ids]


def _by_token(st: State, keynums: list) -> list:
    """The keys in the order the sstables hold them (by token): the reads
    of `check` then walk each node's one sstable segment by segment, and a
    segment is decoded once, not once per key that the 128 MiB chunk cache
    has forgotten since (a first run on an empty compile cache has to
    fit the run's limit, PERF.md)."""
    ring = st.cluster.nodes[0].ring
    return sorted(keynums, key=lambda k: ring.token_of(
        st.table.serialize_partition_key([st.names[k].decode("ascii")])))


def _settle(st: State, wait_s: float) -> dict:
    """The messaging queues drained and every stored hint replayed (each
    node's hint loop runs twice a second): no message queued and no hint
    file left on any node for `QUIET_S` on end. A replica write still in
    flight when the window closed has been answered, or has timed out
    into a hint, long before (write_request_timeout: 2 s)."""
    end, quiet_since = time.monotonic() + wait_s, None
    while time.monotonic() < end:
        busy = 0
        for node in st.cluster.nodes:
            ms = node.messaging
            busy += ms._queue.qsize() + ms._dispatch_q.qsize()
            busy += sum(1 for ep in node.ring.endpoints
                        if node.hints.has_hints(ep))
        now = time.monotonic()
        quiet_since = None if busy else (quiet_since or now)
        if quiet_since is not None and now - quiet_since >= QUIET_S:
            return {"settled": True, "waited_s": wait_s - (end - now)}
        time.sleep(0.1)
    return {"settled": False, "waited_s": wait_s}


def _refusals(st: State, wait_s: float) -> int:
    """With nodes 2 and 3 shut down: an update and a read at the level
    through node 1 must each be refused in the protocol's terms, a ONE
    read answered. The count of wrong outcomes (of 3)."""
    from cassandra_tpu import client
    for i in range(1, st.n):
        st.cluster.stop(i)
    session = st.cluster.sessions[0]
    session._sock.settimeout(wait_s)
    qid = {n: session.prepare(st.statements[n])
           for n in ("read", "update0")}
    key = st.names[0]
    wrong = 0
    for name, params in (("update0", [b"q" * st.length, st.wy.WARM_KEY]),
                         ("read", [key])):
        try:
            session.execute_prepared(qid[name], params,
                                     consistency=st.level)
            wrong += 1                  # answered by one replica of three
        except (client.Unavailable, client.RequestTimeout):
            pass
    try:
        wrong += not session.execute_prepared(qid["read"], [key],
                                              consistency="ONE").rows
    except client.DriverError:
        wrong += 1
    return wrong


def check(st: State, ctx, result: dict) -> list:
    from cassandra_tpu.compaction.task import CompactionTask
    history = st.ref.History(st.loaded, result["ops"])
    ctx.note("settle", _settle(st, float(st.cfg["correct"]["settle_wait_s"])))
    # the replicas first: the read-back below goes through coordinators,
    # whose blocking read repair would mend what is being looked for
    t0 = time.perf_counter()
    updated = _by_token(st, history.updated_keys())
    local = {k: [_local_row(st, i, k) for i in range(st.n)]
             for k in updated}
    diverging = st.quorum.replicas_diverging(history, local)
    ctx.note("local_read_s", time.perf_counter() - t0)
    t0 = time.perf_counter()
    qids = [s.prepare(st.statements["read"]) for s in st.cluster.sessions]
    final = {}
    for j, k in enumerate(_by_token(st, st.wy._final_keys(
            history, st.records, st.cfg["correct"]["final_sample_keys"],
            ctx.seed))):
        via = j % st.n                   # round the coordinators
        rows = st.cluster.sessions[via].execute_prepared(
            qids[via], [st.names[k]], consistency=st.level).rows
        final[k] = [st.wy._as_bytes(rows[0][i]) for i in st.columns] \
            if rows else None
    ctx.note("final_read_s", time.perf_counter() - t0)

    answered = sum(1 for o in result["ops"] if o["ok"])
    counted = sum(v for n, v in result["counters"].items()
                  if n.startswith("coordinator.requests."))
    comps = [st.wy._compactions(_view(st, i)) for i in range(st.n)]
    fallbacks = {c: v - st.fallbacks0[c]
                 for c, v in st.rows._counters().items()}
    ctx.note("fallbacks", fallbacks)
    off = sum(1 for v in fallbacks.values() if v)
    for i, mine in enumerate(comps):
        in_window = mine[len(st.warm_compactions) if i == 0 else 0:]
        off += sum(1 for c in mine if st.wy._off_device(c)) \
            + (0 if in_window else 1)
    sstables = sum(len(cfs.live_sstables()) - 1 for cfs in st.stores) + 1
    served_hashes = [st.rows.component_hashes(cfs.directory)
                     for cfs in st.stores]
    not_enforced = _refusals(
        st, float(st.cfg["correct"]["refusal_wait_s"]))
    st.cluster.close()                   # the program's state goes first
    st.cluster = None
    # the stated guarantee: the bytes the host engines write, ONE
    # numpy-engine compaction of the shared inputs against all three
    t0 = time.perf_counter()
    host = st.rows.standalone_store(
        st.table, os.path.join(st.scratch, "host_engine"), st.copies)
    CompactionTask(host, host.tracker.view(), engine="numpy",
                   use_device=False).execute()
    want = st.rows.component_hashes(host.directory)
    st.rows.close_store(host)
    ctx.note("host_engine_s", time.perf_counter() - t0)
    differing = sum(1 for got in served_hashes
                    for k in want.keys() | got.keys()
                    if want.get(k) != got.get(k))
    base = st.wy.answer_checks(history, result["ops"], result["attempted"],
                               final, sstables, off, differing)
    return cluster_checks(base, diverging, len(updated),
                          answered - counted, not_enforced)


def control(ctx) -> list:
    """(name, checks) per control, at the cell's own size, no node and no
    chip: reference/ycsb_quorum.py's replica set in the cluster's place as
    it is (has to read correct), then written and read at ONE, then with
    one replica acknowledging one mutation in a thousand without applying
    it, then answering every value one byte short (each has to read not
    correct), through the comparison `check` makes."""
    ref = ctx.load("reference", "ycsb")
    quorum = ctx.load("reference", "ycsb_quorum")
    wy = ctx.load("drivers", "wire_ycsb")
    cfg, mix = ctx.config, ctx.traffic
    w, nodes = cfg["workload"], int(cfg["cluster"]["nodes"])
    records, fields = int(w["recordcount"]), int(w["fieldcount"])
    length, n = int(w["fieldlength"]), int(mix["control_ops_per_connection"])
    need = nodes // 2 + 1
    loaded = ref.loaded_values(ctx.seed, records, fields, length)
    streams = [ref.op_stream(ctx.seed, c, n, records, fields, length,
                             float(w["readproportion"]))
               for c in range(int(mix["connections"]))]
    out = []
    for name, kw in (
            ("reference_in_place", {}),
            ("read_and_written_at_one", {"w": 1, "r": 1}),
            ("replica_drops_per_1000", {"drop_every": 1000}),
            ("values_truncated_to_99", {"truncate_to": length - 1})):
        model = quorum.ReplicaSet(loaded, nodes, **{"w": need, "r": need,
                                                    **kw})
        ops = quorum.serial_history(model, streams, n, nodes)
        history = ref.History(loaded, ops)
        model.settle()
        updated = history.updated_keys()
        local = {k: [model.local_row(i, k) for i in range(nodes)]
                 for k in updated}    # before the read-back repairs them
        final = {k: model.read(k, j % nodes) for j, k in enumerate(
            wy._final_keys(history, records,
                           cfg["correct"]["final_sample_keys"], ctx.seed))}
        base = wy.answer_checks(history, ops, len(ops), final, 1, 0, 0)
        # a store read at r and written at w refuses at two nodes down
        # only if neither is 1
        out.append((name, cluster_checks(
            base, quorum.replicas_diverging(history, local), len(updated),
            0, 2 * (model.w + model.r <= model.n))))
    return out


def close(st: State) -> None:
    st.children.kill()
    if st.cluster is not None:
        st.cluster.close()
