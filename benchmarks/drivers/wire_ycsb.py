"""wire_ycsb: YCSB core workload A over the wire against one node in this
process, built as tools/noded.py builds one, while the compaction that
`ycsb load` left pending runs underneath, selected by the node's
CompactionManager and on the engine the task chooses itself.

Set-up lands the loaded records as the configuration's sstables (each key
in exactly one) with automatic compaction paused (`nodetool
disableautocompaction`), warms every prepared statement, lets the manager
run the pending compaction once to warm every shape, and puts the inputs
back from hard-linked copies (TRUNCATE's store call, then `nodetool
refresh`'s). At the release instant the generator children start their
closed loops and the driver re-enables automatic compaction and submits
the store; nothing of the benchmark's names an engine, a bucket or a task.
The warm-up compaction has a wait of its own (`warm_compaction_wait_s`):
with an empty compile cache it compiles every program and takes three
times as long as any compaction after it.

How `correct` is decided (reference/ycsb.py holds the rules; limits 0,
exact): every answered read against the history of what was sent to its
key (`reads_stale`, `reads_unknown_value`); `ops_unanswered`; after the
window and the compaction, every key the window updated and a seeded
sample of untouched keys read back over the wire (`final_rows_wrong`);
the table has to end as one sstable (`sstables_beyond_one`), every
compaction of the table since set-up has to have run on the `device`
engine by the task's own choice with no fallback counter rising
(`compactions_off_device`), and the sstable's seven components have to be
the bytes the numpy engine writes from the same inputs
(`components_differing_from_host_engine`, compacted after the node is
shut down). `control(ctx)` runs the dict model in the node's place: as it
is, with one acknowledged update in a thousand dropped, and with every
value cut to 99 bytes.

The window's result carries `release_perf` (the release instant on
time.perf_counter, the program's span clock) and `spans`, the program's
span ring drained once a second during the window (None if the ring
wrapped between two drains): the per-layer readers compute from those.
"""
from __future__ import annotations

import os
import threading
import time

import numpy as np

WARM_KEY = b"warm-up"


class State:
    pass


# ------------------------------------------------------------- the data --

def _field_ids(table, cfg: dict) -> list:
    by_name = {c.name: c.column_id for c in table.regular_columns}
    return [by_name[f"field{i}"] for i in range(int(cfg["schema"]["fields"]))]


def _records_batch(st: State, keynums: np.ndarray):
    """The loaded records `keynums` as one sorted CellBatch: major_loop's
    vectorised row builder, once per key length (it takes keys of one
    width), merged."""
    from cassandra_tpu.storage import cellbatch as cb
    names = [st.names[k] for k in keynums]
    lens = np.array([len(n) for n in names])
    ts = int(st.cfg["data"]["load_time_base_us"]) + keynums.astype(np.int64)
    parts = []
    for width in np.unique(lens):
        pick = np.flatnonzero(lens == width)
        keys = np.frombuffer(b"".join(names[i] for i in pick),
                             dtype=np.uint8).reshape(len(pick), int(width))
        parts.append(st.rows.build_rows_batch(
            st.table, st.field_ids, keys, ts[pick],
            st.loaded[keynums[pick]]))
    return cb.merge_sorted(parts)


def _selfcheck(st: State) -> None:
    """The vectorised builder must give usertable's cells exactly as
    CellBatchBuilder, the program's own cell-by-cell path, gives them."""
    from cassandra_tpu.storage.cellbatch import (CellBatchBuilder,
                                                 merge_sorted)
    keynums = np.arange(min(6, len(st.names)))
    fast = _records_batch(st, keynums)
    slow = CellBatchBuilder(st.table)
    base = int(st.cfg["data"]["load_time_base_us"])
    for k in keynums:
        for f, cid in enumerate(st.field_ids):
            slow.add_cell(st.names[k], b"", cid, st.loaded[k, f].tobytes(),
                          base + int(k))
    sealed = merge_sorted([slow.seal()])
    for name in ("lanes", "ts", "ldt", "ttl", "flags", "off", "val_start",
                 "payload"):
        np.testing.assert_array_equal(getattr(fast, name),
                                      getattr(sealed, name), err_msg=name)
    assert fast.pk_map == sealed.pk_map


def _land(st: State) -> None:
    """Each of the configuration's sstables from its slice of the key
    numbers, side by side on threads."""
    from concurrent.futures import ThreadPoolExecutor

    from cassandra_tpu.storage.sstable import Descriptor, SSTableWriter
    d = st.cfg["data"]
    per, runs = int(d["records_per_sstable"]), int(d["sstables"])
    gens = [st.cfs.next_generation() for _ in range(runs)]

    def land(r: int) -> None:
        w = SSTableWriter(Descriptor(st.cfs.directory, gens[r]), st.table)
        w.append(_records_batch(st, np.arange(r * per, (r + 1) * per)))
        w.finish()

    with ThreadPoolExecutor(runs) as pool:
        list(pool.map(land, range(runs)))
    st.cfs.reload_sstables()


def _link_all(src: str, dst: str) -> None:
    os.makedirs(dst, exist_ok=True)
    for fn in os.listdir(src):
        p = os.path.join(src, fn)
        if os.path.isfile(p):
            os.link(p, os.path.join(dst, fn))


# ----------------------------------------------------- the served store --

def _compactions(st: State) -> list:
    """What the table's compactions since set-up say of themselves."""
    return [dict(h) for h in list(st.cfs.compaction_history or [])]


def _run_pending_compaction(st: State, wait_s: float) -> dict:
    """enableautocompaction + the flush notification's submit: the
    manager selects and runs; wait until the table is one sstable and no
    task is active. Returns {"seconds", "done"}."""
    cm = st.served.node.engine.compactions
    t0 = time.perf_counter()
    cm.paused = False
    cm.submit_background(st.cfs)
    return {"done": _await_compaction(st, wait_s),
            "seconds": time.perf_counter() - t0}


def _await_compaction(st: State, wait_s: float) -> bool:
    cm = st.served.node.engine.compactions
    end = time.monotonic() + wait_s
    while time.monotonic() < end:
        if len(st.cfs.live_sstables()) <= 1 and len(cm.active) == 0 \
                and cm.pending_tasks() == 0:
            return True
        time.sleep(0.05)
    return False


def _off_device(comp: dict) -> bool:
    return comp.get("engine") != "device" or not comp.get("engine_chosen")


# -------------------------------------------------------------- set-up --

def setup(ctx) -> State:
    import wire
    from cassandra_tpu.compaction import task as ctask
    if not hasattr(ctask, "choose_engine"):
        # before PR 27 a served compaction always ran on a host engine:
        # nothing this cell sends would reach the device. Fail at once.
        raise RuntimeError(
            "this program's CompactionTask cannot choose its engine "
            "(compaction/task.py has no choose_engine): the cell "
            "ycsb_a.wire cannot run on it")
    st = State()
    cfg, mix = ctx.config, ctx.traffic
    st.cfg, st.mix, st.scratch = cfg, mix, ctx.scratch
    st.ref = ctx.load("reference", "ycsb")
    st.rows = ctx.load("drivers", "major_loop")
    s, w, d = cfg["schema"], cfg["workload"], cfg["data"]
    st.records = int(w["recordcount"])
    st.fields, st.length = int(w["fieldcount"]), int(w["fieldlength"])
    assert st.records == int(d["sstables"]) * int(d["records_per_sstable"])
    assert st.fields == int(s["fields"])
    t0 = time.perf_counter()
    st.loaded = st.ref.loaded_values(ctx.seed, st.records, st.fields,
                                     st.length)
    st.names = st.ref.key_names(np.arange(st.records))
    st.served = wire.ServedNode(os.path.join(ctx.scratch, "node"),
                                s["keyspace"], cfg.get("node_config"))
    cm = st.served.node.engine.compactions
    cm.paused = True                 # nodetool disableautocompaction
    for stmt in s["ddl"]:
        st.served.session.execute(stmt)
    st.table, st.cfs = st.served.table(s["table"]), \
        st.served.store(s["table"])
    st.field_ids = _field_ids(st.table, cfg)
    _selfcheck(st)
    _land(st)
    st.lanes = int(st.cfs.live_sstables()[0].K)
    st.input_bytes = sum(r.data_size for r in st.cfs.live_sstables())
    st.copies = os.path.join(ctx.scratch, "inputs")
    _link_all(st.cfs.directory, st.copies)
    ctx.note("load_s", time.perf_counter() - t0)
    ctx.note("input_mib", st.input_bytes / 2.0 ** 20)

    # the statements, and which answered column is which field
    fmt = {"table": s["table"], "key": s["key"]}
    st.statements = {"read": mix["read"].format(**fmt)}
    for f in range(st.fields):
        st.statements[f"update{f}"] = mix["update"].format(field=f, **fmt)
    session = st.served.session
    qid = {n: session.prepare(c) for n, c in st.statements.items()}
    rng = np.random.default_rng([ctx.seed, 2])
    t0 = time.perf_counter()
    for i, k in enumerate(rng.integers(0, st.records,
                                       int(mix["warm_operations"]))):
        got = session.execute_prepared(qid["read"], [st.names[k]])
        session.execute_prepared(qid[f"update{i % st.fields}"],
                                 [b"w" * st.length, WARM_KEY])
    names = list(got.column_names)
    st.columns = [names.index(f"field{f}") for f in range(st.fields)]
    ctx.note("warm_statements_s", time.perf_counter() - t0)

    # every (program, shape) the served compaction will use: the same
    # compaction once, through the manager; then the inputs back
    st.fallbacks0 = st.rows._counters()      # the fallback counters
    warm = _run_pending_compaction(
        st, float(cfg["correct"]["warm_compaction_wait_s"]))
    cm.paused = True
    if not warm["done"]:
        raise RuntimeError("the warm-up compaction did not finish")
    st.warm_compactions = _compactions(st)
    ctx.note("warm_compaction_s", warm["seconds"])
    ctx.note("warm_compaction_engine",
             [c.get("engine") for c in st.warm_compactions])
    st.cfs.truncate()                # the warm-up row goes with it
    _link_all(st.copies, st.cfs.directory)
    st.cfs.reload_sstables()         # nodetool refresh
    assert len(st.cfs.live_sstables()) == int(d["sstables"])

    st.streams = [st.ref.op_stream(ctx.seed, c,
                                   int(mix["ops_per_connection"]),
                                   st.records, st.fields, st.length,
                                   float(w["readproportion"]))
                  for c in range(int(mix["connections"]))]
    st.children = wire.Children(os.path.join(ctx.scratch, "gen"), ctx.root)
    st.children.start([{
        "host": "127.0.0.1", "port": st.served.port,
        "keyspace": s["keyspace"], "statements": st.statements,
        "seconds": ctx.seconds, "timeout_s": float(mix["timeout_s"]),
        "ops": _job_ops(st, stream)} for stream in st.streams])
    return st


def _job_ops(st: State, stream: dict) -> list:
    ops = []
    for i in range(len(stream["keynum"])):
        key = st.names[stream["keynum"][i]]
        if stream["is_read"][i]:
            ops.append(("read", [key]))
        else:
            ops.append((f"update{int(stream['field'][i])}",
                        [stream["value"][i].tobytes(), key]))
    return ops


# ---------------------------------------------------------- the window --

class RingDrain:
    """The program's span ring copied out once every `period` seconds:
    the ring holds 32,768 records and a window makes more. `records` is
    None once a drain finds none of the records it saw last (the ring
    wrapped in between): a reader then has nothing sound to read."""

    def __init__(self, period: float):
        from cassandra_tpu.utils import pipeline_ledger
        self.pl, self.period = pipeline_ledger, period
        self.records: list | None = []
        self._last = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-ring-drain")

    def start(self) -> None:
        self.drain()                 # what set-up left: marks the start
        self.records = []
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(self.period):
            self.drain()

    def drain(self) -> None:
        recs = list(self.pl.RING)
        if not recs:
            return
        new = recs
        if self._last is not None:
            for i in range(len(recs) - 1, -1, -1):
                if recs[i] is self._last:
                    new = recs[i + 1:]
                    break
            else:
                self.records = None
        self._last = recs[-1]
        if self.records is not None:
            self.records.extend(new)

    def finish(self) -> list | None:
        self._stop.set()
        self._thread.join()
        self.drain()
        if self.records is None:
            return None
        fields = self.pl.RECORD_FIELDS
        return [dict(zip(fields, r)) for r in self.records]


def window(st: State, ctx) -> dict:
    import wire
    mix = st.mix
    cm = st.served.node.engine.compactions
    drain = RingDrain(float(mix["ring_drain_s"]))
    drain.start()
    t0 = st.children.release()
    release_perf = t0 + (time.perf_counter() - time.monotonic())
    time.sleep(max(t0 - time.monotonic(), 0))
    cm.paused = False                # nodetool enableautocompaction
    cm.submit_background(st.cfs)     # what a flush's notification does
    th = wire.trace_slice(ctx, t0, mix.get("trace", {}))
    with ctx.annotate("bench.window.wait_generators"):
        per_child = st.children.collect(
            ctx.seconds + float(mix["timeout_s"]) + 60.0)
    if th is not None:
        th.join()
    t_wait = time.perf_counter()
    finished = _await_compaction(
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
            op = {"conn": c, "index": index,
                  "keynum": int(stream["keynum"][index]),
                  "sent": t_sent, "done": t_done, "ok": ok, "err": err}
            if stream["is_read"][index]:
                row = None
                if ok and rows:
                    row = [_as_bytes(rows[0][i]) for i in st.columns]
                op.update(kind="read", row=row)
            else:
                op.update(kind="update", field=int(stream["field"][index]),
                          value=stream["value"][index].tobytes())
            ops.append(op)
    good = [o for o in ops if o["ok"]]
    elapsed = max([o["done"] for o in ops] + [ctx.seconds])
    lat = {k: sorted((o["done"] - o["sent"]) * 1000.0 for o in good
                     if o["kind"] == k) for k in ("read", "update")}
    return {"attempted": len(ops) + lost,
            "failed": len(ops) - len(good) + lost, "ops": ops,
            "elapsed_s": elapsed, "release_perf": release_perf,
            "spans": spans, "lanes": st.lanes,
            "end_to_end": {"ops_s": len(good) / elapsed},
            "detail": {
                "operations": len(ops), "children_lost": lost,
                "streams_exhausted": exhausted,
                "reads": len(lat["read"]), "updates": len(lat["update"]),
                "read_p50_ms": _mid(lat["read"]),
                "update_p50_ms": _mid(lat["update"]),
                "compaction_finished": finished,
                "compaction_waited_after_window_s": waited,
                "compactions": [
                    {k: c.get(k) for k in ("engine", "engine_chosen",
                                           "seconds", "inputs",
                                           "cells_read", "bytes_read")}
                    for c in _compactions(st)[len(st.warm_compactions):]],
                "spans_drained": None if spans is None else len(spans),
                "errors": sorted({o["err"] for o in ops if o["err"]})[:3]}}


def _as_bytes(v) -> bytes | None:
    if v is None:
        return None
    return v.encode("utf-8") if isinstance(v, str) else bytes(v)


def _mid(vals: list):
    return vals[len(vals) // 2] if vals else None


# ------------------------------------------------------------ `correct` --

def answer_checks(history, ops: list, attempted: int, final_rows: dict,
                  sstables: int, off_device: int, differing) -> list:
    """The numbers compared, from a run or from a control alike."""
    judged = history.judge_reads()
    answered = sum(1 for o in ops if o["ok"])
    reads = sum(1 for o in ops if o["ok"] and o["kind"] == "read")
    return [
        {"name": "ops_unanswered", "value": attempted - answered,
         "limit": 0, "of": attempted},
        {"name": "reads_stale", "value": judged["reads_stale"], "limit": 0,
         "of": reads},
        {"name": "reads_unknown_value",
         "value": judged["reads_unknown_value"], "limit": 0, "of": reads},
        {"name": "final_rows_wrong",
         "value": history.final_rows_wrong(final_rows), "limit": 0,
         "of": len(final_rows)},
        {"name": "sstables_beyond_one", "value": sstables - 1, "limit": 0},
        {"name": "compactions_off_device", "value": off_device, "limit": 0},
        {"name": "components_differing_from_host_engine",
         "value": differing, "limit": 0}]


def _final_keys(history, records: int, sample: int, seed: int) -> list:
    """Every key the window updated, and a seeded sample of the rest."""
    updated = history.updated_keys()
    rest = np.setdiff1d(np.arange(records), np.asarray(updated, dtype=int))
    rng = np.random.default_rng([int(seed), 3])
    pick = rng.choice(rest, min(int(sample), len(rest)), replace=False)
    return updated + [int(k) for k in pick]


def check(st: State, ctx, result: dict) -> list:
    from cassandra_tpu.compaction.task import CompactionTask
    history = st.ref.History(st.loaded, result["ops"])
    t0 = time.perf_counter()
    session = st.served.session
    qid = session.prepare(st.statements["read"])
    final = {}
    for k in _final_keys(history, st.records,
                         st.cfg["correct"]["final_sample_keys"], ctx.seed):
        rows = session.execute_prepared(qid, [st.names[k]]).rows
        final[k] = [_as_bytes(rows[0][i]) for i in st.columns] \
            if rows else None
    ctx.note("final_read_s", time.perf_counter() - t0)
    comps = _compactions(st)
    window_comps = comps[len(st.warm_compactions):]
    fallbacks = {c: v - st.fallbacks0[c]
                 for c, v in st.rows._counters().items()}
    off = sum(1 for c in comps if _off_device(c)) \
        + (0 if window_comps else 1) + sum(1 for v in fallbacks.values() if v)
    ctx.note("fallbacks", fallbacks)
    sstables = len(st.cfs.live_sstables())
    served_hashes = st.rows.component_hashes(st.cfs.directory)
    st.served.close()                # the program's state goes first
    st.served = None
    # the stated guarantee: the bytes the host engines write
    t0 = time.perf_counter()
    host = st.rows.standalone_store(
        st.table, os.path.join(st.scratch, "host_engine"), st.copies)
    CompactionTask(host, host.tracker.view(), engine="numpy",
                   use_device=False).execute()
    want = st.rows.component_hashes(host.directory)
    st.rows.close_store(host)
    ctx.note("host_engine_s", time.perf_counter() - t0)
    differing = sum(1 for k in want.keys() | served_hashes.keys()
                    if want.get(k) != served_hashes.get(k))
    return answer_checks(history, result["ops"], result["attempted"],
                         final, sstables, off, differing)


def control(ctx) -> list:
    """(name, checks) per control, at the cell's own size, no node and no
    chip: the dict model in the node's place as it is (has to read
    correct), then acknowledging one update in a thousand without
    applying it, then answering every value cut to one byte short (both
    have to read not correct), each through the comparison `check`
    makes."""
    ref = ctx.load("reference", "ycsb")
    cfg, mix = ctx.config, ctx.traffic
    w = cfg["workload"]
    records, fields = int(w["recordcount"]), int(w["fieldcount"])
    length, n = int(w["fieldlength"]), int(mix["control_ops_per_connection"])
    loaded = ref.loaded_values(ctx.seed, records, fields, length)
    streams = [ref.op_stream(ctx.seed, c, n, records, fields, length,
                             float(w["readproportion"]))
               for c in range(int(mix["connections"]))]
    out = []
    for name, kw in (("reference_in_place", {}),
                     ("update_dropped_per_1000", {"drop_every": 1000}),
                     ("values_truncated_to_99", {"truncate_to": length - 1})):
        model = ref.Model(loaded, **kw)
        ops = ref.serial_history(model, streams, n)
        history = ref.History(loaded, ops)
        final = {k: model.read(k) for k in _final_keys(
            history, records, cfg["correct"]["final_sample_keys"],
            ctx.seed)}
        out.append((name, answer_checks(history, ops, len(ops), final,
                                        1, 0, 0)))
    return out


def close(st: State) -> None:
    st.children.kill()
    if st.served is not None:
        st.served.close()
