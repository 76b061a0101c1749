"""ucs_cycle: cassandra-stress's standard1 under the Unified Compaction
Strategy Cassandra 5 recommends (conf/cassandra_latest.yaml's
`default_compaction`: T4, 1 GiB target, growth 1/3, 100 MiB minimum, 4
base shards) with Zstd in 64 KiB chunks, on one node in this process,
built as tools/noded.py builds one. A cycle is what the node's
CompactionManager does, by itself, when four level-0 flushes are there:
UCS finds the four overlapping, and the task merges them into one sstable
per token shard at level 1. Nothing of the benchmark's names a task, a
shard or an engine.

Set-up makes the rows from --seed as `major_loop` does (its seeded_runs,
build_rows_batch and selfcheck: the same rows, keys from
key_layouts[seed mod 6]), makes the table by CQL from the configuration's
compaction and compression maps, lands each run as one sstable while
automatic compaction is paused (`nodetool disableautocompaction`), keeps
hard links of the landed files, asks the strategy once what it would
compact (a program whose strategy raises on upstream's option strings
fails here, at once), and runs one whole cycle to warm every shape; set-up
also fails when that cycle starts no merge within `start_wait_s`.

One cycle: put the landed files back (TRUNCATE's store call, the links,
`nodetool refresh`'s call), re-enable automatic compaction, submit the
store as a flush's notification does, wait until the manager, its
executor and its queue are idle and no input is live any more. Cycles
run back to back for the window; the deadline starts no new cycle, the
one in flight is finished and counted. `compaction_mib_s` is the input
bytes of the merge tasks that ran on the device path (engine `device` by
the task's own choice, no fallback counter rising in their cycle) over the
window's time, as `twcs_cycle` counts it.

How `correct` is decided (reference/compaction.py merges, reference/ucs.py
holds UCS's rules and Murmur3's tokens; every limit 0, exact):
- `cells_wrong`: every cell of the last cycle's outputs, read back
  through the store's sequential reader, against the plain merge of the
  seeded runs;
- `shards_misplaced`: partitions of an output whose token (the
  reference's, from the key) lies in another shard than the output's
  first partition, plus the difference between the number of outputs and
  the number of shards the reference's boundaries fill;
- `levels_wrong`: outputs whose recorded level, or whose level by the
  reference's density (size over token span), is not the level the
  reference plans for the compaction;
- `compactions_beyond_one`: merges past the first in a cycle (the
  outputs selected again);
- `compression_wrong`: outputs not written by the configuration's
  compressor at its chunk length;
- `components_differing`: the seven components of each output, in every
  cycle of the window and in the warm-up, against the last cycle's;
- `components_differing_from_host_engine`: after the node is shut down
  the four inputs are compacted by the numpy engine in a standalone store
  with the reference's boundaries and level; every component has to be
  the same bytes;
- `compactions_off_device`: merge tasks that were not `device` by the
  task's own choice, cycles in which a fallback counter rose, and cycles
  that ran no merge.
`control(ctx)` puts the reference in the program's place: as it is, with
one input lost, with millisecond timestamps.
"""
from __future__ import annotations

import os
import time

import numpy as np


class State:
    pass


def ddl(cfg: dict) -> str:
    """CREATE TABLE for the configuration's schema, its compaction and
    compression maps as CQL map literals."""
    s = cfg["schema"]

    def cql_map(m: dict) -> str:
        return "{" + ", ".join(f"'{k}': '{v}'" for k, v in m.items()) + "}"
    cols = ", ".join(f'"{c}" blob' for c in s["columns"])
    return (f"CREATE TABLE {s['table']} ({s['key']} blob PRIMARY KEY, "
            f"{cols}) WITH compaction = {cql_map(s['compaction'])} AND "
            f"compression = {cql_map(s['compression'])} AND "
            f"gc_grace_seconds = {int(s['gc_grace_seconds'])}")


def key_matrix(keys: list, width: int) -> np.ndarray:
    return np.frombuffer(b"".join(keys), dtype=np.uint8).reshape(-1, width)


# ----------------------------------------------------------- one cycle --

def _counters(st: State) -> dict:
    from cassandra_tpu.service.metrics import GLOBAL as metrics
    return {c: metrics.counter(c) for c in st.rows.FALLBACK_COUNTERS}


def _settled(st: State) -> bool:
    live = {r.desc.generation for r in st.cfs.live_sstables()}
    return bool(live) and not live & set(st.input_gens)


def _await_idle(st: State, wait_s: float, done0: int,
                start_s: float | None = None) -> str:
    """"done" once the manager has nothing queued, running or active and
    no input is live; "idle" when it stays idle `idle_settle_s` in any
    other state (it will do nothing more); "no_task" when no merge began
    within `start_s`; "timeout" when `wait_s` runs out first."""
    cm = st.served.node.engine.compactions
    t0 = time.monotonic()
    idle_since, started = None, False
    while time.monotonic() - t0 < wait_s:
        started = started or len(cm.active) > 0 \
            or len(st.cfs.compaction_history) > done0
        if len(cm.active) == 0 and cm.pending_tasks() == 0 \
                and cm.executor.stats()["active"] == 0:
            if _settled(st):
                return "done"
            idle_since = idle_since or time.monotonic()
            if time.monotonic() - idle_since >= st.settle_s:
                return "idle"
        else:
            idle_since = None
        if start_s is not None and not started \
                and time.monotonic() - t0 >= start_s:
            return "no_task"
        time.sleep(st.poll_s)
    return "timeout"


def cycle(st: State, label: str, wait_s: float, start_s=None,
          annotate=None) -> dict:
    import contextlib
    span = annotate or (lambda _n: contextlib.nullcontext())
    cm = st.served.node.engine.compactions
    t0 = time.perf_counter()
    with span("bench.restore"):
        st.cfs.truncate()
        st.twcs._link_all(st.copies, st.cfs.directory)
        st.cfs.reload_sstables()           # nodetool refresh
    done0, before = len(st.cfs.compaction_history), _counters(st)
    t1 = time.perf_counter()
    with span("bench.cycle.compactions"):
        cm.paused = False                  # nodetool enableautocompaction
        cm.submit_background(st.cfs)       # what a flush's notification does
        ended = _await_idle(st, wait_s, done0, start_s)
        cm.paused = True
    t2 = time.perf_counter()
    after = _counters(st)
    tasks = [dict(t) for t in list(st.cfs.compaction_history)[done0:]]
    directory = os.path.join(st.scratch, "cycles", label)
    st.twcs._link_all(st.cfs.directory, directory)
    return {"label": label, "start": t0, "end": t2, "restore_s": t1 - t0,
            "wall_s": t2 - t1, "ended": ended, "tasks": tasks,
            "directory": directory,
            "outputs": sorted(r.desc.generation
                              for r in st.cfs.live_sstables()),
            "rose": {c: after[c] - before[c] for c in after}}


def merges(op: dict) -> list:
    return [t for t in op["tasks"] if not t.get("dropped")]


def fell_back(op: dict) -> bool:
    return any(op["rose"].values())


def off_device(op: dict) -> int:
    """Merge tasks of the cycle that did not drive the device path by the
    task's own choice; all of them if a fallback counter rose; one for a
    cycle that merged nothing."""
    ms = merges(op)
    bad = len(ms) if fell_back(op) else sum(
        1 for t in ms
        if t.get("engine") != "device" or not t.get("engine_chosen"))
    return bad + (0 if ms else 1)


# -------------------------------------------------------------- set-up --

def _plan(st: State) -> None:
    """The reference's view of the landed flushes: sizes on disk, token
    spans from the keys, the strategy's choice and the compaction's plan."""
    inputs = []
    for r, (keys, _ts, _vals) in zip(st.inputs, st.runs):
        tok = st.ucs.tokens(keys)
        inputs.append((r.data_size, int(tok.min()), int(tok.max())))
    st.picked = st.ucs.select(st.opts, inputs)
    st.plan = st.ucs.plan(st.opts, inputs)


def setup(ctx) -> State:
    import wire
    from cassandra_tpu.compaction.strategies import get_strategy
    cfg, mix = ctx.config, ctx.traffic
    s = cfg["schema"]
    st = State()
    st.cfg, st.scratch = cfg, ctx.scratch
    st.rows = ctx.load("drivers", "major_loop")
    st.twcs = ctx.load("drivers", "twcs_cycle")
    st.ref = ctx.load("reference", "compaction")
    st.ucs = ctx.load("reference", "ucs")
    st.opts = st.ucs.Options(s["compaction"])
    st.settle_s, st.poll_s = float(mix["idle_settle_s"]), \
        float(mix["poll_s"])
    st.served = wire.ServedNode(os.path.join(ctx.scratch, "node"),
                                s["keyspace"], cfg.get("node_config"))
    cm = st.served.node.engine.compactions
    cm.paused = True                 # nodetool disableautocompaction
    st.ddl = ddl(cfg)
    st.served.session.execute(st.ddl)
    st.table, st.cfs = st.served.table(s["table"]), \
        st.served.store(s["table"])
    t0 = time.perf_counter()
    st.runs = st.rows.load_backlog(st.cfs, ctx.seed, cfg)
    st.cfs.reload_sstables()
    st.inputs = sorted(st.cfs.live_sstables(),
                       key=lambda r: r.desc.generation)
    st.input_gens = [r.desc.generation for r in st.inputs]
    st.copies = os.path.join(ctx.scratch, "inputs")
    st.twcs._link_all(st.cfs.directory, st.copies)
    ctx.note("load_s", time.perf_counter() - t0)
    _plan(st)
    ctx.note("flush_mib", [r.data_size / 2.0 ** 20 for r in st.inputs])
    ctx.note("plan", {"density_mib": st.plan["density"] / 2.0 ** 20,
                      "level": st.plan["level"],
                      "shards": len(st.plan["boundaries"]),
                      "picked": st.picked})
    # what the program's strategy makes of upstream's options, asked
    # once: a strategy that raises on them, or picks nothing, cannot run
    # this cell, and waiting for a cycle would only wait
    try:
        task = get_strategy(st.cfs).next_background_task()
    except Exception as e:
        raise RuntimeError(
            f"the program's {s['compaction']['class']} cannot run this "
            f"cell's table ({st.ddl}): {e!r}") from e
    if task is None:
        raise RuntimeError("the program's strategy selects nothing from "
                           f"the {len(st.inputs)} landed flushes")
    # which codec wrote the flushes: native libzstd or the Python binding
    ctx.note("codec", type(st.inputs[0].compressor).__name__)
    # every (program, shape) a cycle uses: one whole cycle, which has to
    # start a merge within start_wait_s
    c = cfg["correct"]
    st.warm = cycle(st, "warm", float(c["warm_cycle_wait_s"]),
                    float(c["start_wait_s"]))
    ctx.note("warm_cycle_s", st.warm["wall_s"])
    ctx.note("warm_tasks", [
        {k: t.get(k) for k in ("engine", "engine_chosen", "inputs",
                               "outputs", "seconds")}
        for t in st.warm["tasks"]])
    ctx.note("warm_cycle_ended", st.warm["ended"])
    os.sync()
    if st.warm["ended"] != "done":
        raise RuntimeError(
            f"the warm-up cycle ended {st.warm['ended']!r}: live sstables "
            f"{st.warm['outputs']}, tasks {st.warm['tasks']}")
    return st


# ---------------------------------------------------------- the window --

def window(st: State, ctx) -> dict:
    tr = ctx.traffic.get("trace", {})
    first, count = int(tr.get("after_cycles", 1)), int(tr.get("cycles", 1))
    wait_s = float(st.cfg["correct"]["cycle_wait_s"])
    ops = []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < ctx.seconds:
        k = len(ops)
        if ctx.tracer is not None and k == first:
            ctx.tracer.start()
        ops.append(cycle(st, f"c{k}", wait_s, annotate=ctx.annotate))
        if ctx.tracer is not None and first <= k < first + count:
            ops[-1]["traced"] = True
            if k == first + count - 1:
                ctx.tracer.stop()
    if ctx.tracer is not None:
        ctx.tracer.stop()              # a window too short to reach `first`
    elapsed = ops[-1]["end"] - t0
    failed = sum(off_device(o) + max(len(merges(o)) - 1, 0) for o in ops)
    # the bytes of the merges that drove the device path, over ALL the
    # time: restoring and selecting add their time and no bytes
    mib = sum(t["bytes_read"] for o in ops if not fell_back(o)
              for t in merges(o)
              if t.get("engine") == "device" and t.get("engine_chosen")) \
        / 2.0 ** 20
    return {"attempted": max(sum(len(merges(o)) for o in ops), len(ops)),
            "failed": failed, "ops": ops, "elapsed_s": elapsed,
            "lanes": int(st.inputs[0].K),
            "end_to_end": {"compaction_mib_s": mib / elapsed},
            "detail": {
                "cycles": len(ops),
                "cycle_walls_s": [o["wall_s"] for o in ops],
                "restore_s": [o["restore_s"] for o in ops],
                "tasks": [[{k: t.get(k) for k in (
                    "engine", "engine_chosen", "inputs", "outputs",
                    "seconds", "cells_read", "cells_written", "bytes_read",
                    "bytes_written")} for t in o["tasks"]] for o in ops][:2],
                "ended": [o["ended"] for o in ops],
                "rose": [o["rose"] for o in ops]}}


# ------------------------------------------------------------ `correct` --

def cells_check(ref, got: dict, want: dict) -> dict:
    return {"name": "cells_wrong", "value": ref.cells_wrong(got, want),
            "limit": 0, "of": int(len(want["hi"]))}


def outputs_checks(st: State, outputs: list) -> list:
    """shards_misplaced, levels_wrong and compression_wrong of the live
    outputs, by the reference's tokens, density and plan."""
    bounds, width = st.plan["boundaries"], int(st.cfg["data"]["key_bytes"])
    comp = st.cfg["schema"]["compression"]
    filled = set(np.unique(st.ucs.shard_of(np.concatenate([
        st.ucs.tokens(keys) for keys, _ts, _vals in st.runs]), bounds)))
    misplaced = abs(len(outputs) - len(filled))
    levels = compression = 0
    for r in outputs:
        tok = st.ucs.tokens(key_matrix(list(r.partition_keys()), width))
        shard = st.ucs.shard_of(tok, bounds)
        misplaced += int((shard != shard[0]).sum())
        dens = st.ucs.density(r.data_size, int(tok.min()), int(tok.max()))
        levels += r.level != st.plan["level"] \
            or st.ucs.level(st.opts, dens) != st.plan["level"]
        compression += r.params.compressor_name != comp["class"] \
            or r.params.chunk_length != int(comp["chunk_length_in_kb"]) \
            * 1024
    return [{"name": "shards_misplaced", "value": misplaced, "limit": 0,
             "of": len(filled)},
            {"name": "levels_wrong", "value": int(levels), "limit": 0,
             "of": len(outputs)},
            {"name": "compression_wrong", "value": int(compression),
             "limit": 0, "of": len(outputs)}]


def check(st: State, ctx, result: dict) -> list:
    from cassandra_tpu.compaction.task import CompactionTask
    ops = [st.warm] + result["ops"]
    outputs = sorted(st.cfs.live_sstables(),
                     key=lambda r: r.desc.generation)
    t0 = time.perf_counter()
    got = st.rows.read_back(st.cfs, st.cfg, st.ref)
    checks = outputs_checks(st, outputs)
    ctx.note("read_back_s", time.perf_counter() - t0)
    ctx.note("outputs_mib", [r.data_size / 2.0 ** 20 for r in outputs])
    hashes = [st.rows.component_hashes(o["directory"]) for o in ops]
    st.served.close()                # the program's state goes first
    st.served = None
    t0 = time.perf_counter()
    want = st.ref.merge(st.runs)
    ctx.note("reference_s", time.perf_counter() - t0)
    expect = len(st.plan["boundaries"]) * len(st.rows.HASHED_COMPONENTS)
    checks = [cells_check(st.ref, got, want)] + checks + [
        {"name": "compactions_beyond_one",
         "value": sum(max(len(merges(o)) - 1, 0) for o in ops), "limit": 0,
         "of": len(ops)},
        {"name": "components_differing",
         "value": sum(st.twcs.differing(h, hashes[-1]) for h in hashes[:-1])
         + max(expect - len(hashes[-1]), 0),
         "limit": 0, "of": expect * (len(ops) - 1)},
        {"name": "compactions_off_device",
         "value": sum(off_device(o) for o in ops), "limit": 0,
         "of": len(ops)}]
    # the stated guarantee: the bytes the numpy engine writes from the
    # same four inputs with the reference's boundaries and level
    t0 = time.perf_counter()
    host = st.rows.standalone_store(
        st.table, os.path.join(st.scratch, "host_engine"), st.copies)
    CompactionTask(host, host.live_sstables(), level=st.plan["level"],
                   shard_boundaries=st.plan["boundaries"],
                   **st.cfg["host_engine_task"]).execute()
    numpy_hashes = st.rows.component_hashes(host.directory)
    st.rows.close_store(host)
    ctx.note("host_engine_s", time.perf_counter() - t0)
    checks.append({"name": "components_differing_from_host_engine",
                   "value": st.twcs.differing(numpy_hashes, hashes[-1])
                   + max(expect - len(numpy_hashes), 0),
                   "limit": 0, "of": expect})
    return checks


def control(ctx) -> list:
    """(name, checks) per control, at the cell's own size, no node and no
    chip: the plain reference in the program's place as it is (has to
    read correct), then with one stated guarantee broken (has to read not
    correct), through the comparison `check` makes."""
    rows = ctx.load("drivers", "major_loop")
    ref = ctx.load("reference", "compaction")
    runs = rows.seeded_runs(ctx.seed, ctx.config)
    want = ref.merge(runs)
    return [(name or "reference_in_place",
             [cells_check(ref, ref.merge(runs, control=name), want)])
            for name in (None,) + ref.CONTROLS]


def close(st: State) -> None:
    if st.served is not None:
        st.served.close()
