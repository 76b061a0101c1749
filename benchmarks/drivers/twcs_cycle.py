"""twcs_cycle: a TTL'd time-series table under TimeWindowCompactionStrategy
on one node in this process, built as tools/noded.py builds one. A cycle
is what the node's CompactionManager does, by itself, with the three daily
windows set-up landed: drop the window past TTL + gc grace whole, compact
the repaired window (2 sstables -> 1, every cell expired inside grace:
kept, converted to a tombstone), compact the window that has just closed
(4 -> 1, every cell live with a TTL). Nothing of the benchmark's names a
task, a window or an engine.

Set-up makes the readings from --seed (TSBS cpu-only's shape: one series
per host x metric x day, a reading every 10 s, values a clamped random
walk; an INSERT writes a row-liveness cell beside the value, both
expiring), builds each sstable's cells as one sorted CellBatch in bulk
(held against CellBatchBuilder + the numpy sort in every set-up) and
lands it with SSTableWriter while automatic compaction is paused
(`nodetool disableautocompaction`), keeps hard links of the landed files,
and runs one whole cycle to warm every shape. Days are relative to D, the
epoch day of set-up's clock (the configuration's `windows`): every expiry
boundary lies at least 4 days from now, so no cell changes state during
a run and the outputs' bytes do not depend on the cycle.

One cycle: put the landed files back (TRUNCATE's store call, the links,
`nodetool refresh`'s call), re-enable automatic compaction, submit the
store as a flush's notification does, wait until the manager, its
executor and its queue are idle and the store holds one sstable for each
of the two windows that stay. Cycles run back to back for the window; the
deadline starts no new cycle, the one in flight is finished and counted.
`compaction_mib_s` is the input bytes of the merge tasks that ran on the
device path (engine `device` by the task's own choice, no fallback
counter rising in their cycle) over the window's time: restoring,
selecting and dropping add their time and no bytes.

How `correct` is decided (reference/timeseries.py holds the rules; every
limit 0, exact):
- `cells_wrong`: every cell of the last cycle's two output sstables, read
  back through the store's sequential reader (series, reading, column,
  write timestamp, flags, expiry time, ttl, value length and value),
  against the plain numpy merge of the seeded readings at the cycle's own
  `now`: a cell only one side holds, or holds twice, is wrong;
- `components_differing`: the seven components of each window's output,
  in every cycle of the window and in the warm-up, against the last
  cycle's;
- `components_differing_from_host_engine`: after the node is shut down
  the same six inputs are compacted, window by window in the same order,
  by the numpy engine in a standalone store; every component has to be
  the same bytes;
- `compactions_off_device`: merge tasks that were not `device` by the
  task's own choice, cycles in which a fallback counter rose, and cycles
  that ran fewer than two merges;
- `windows_not_dropped`: cycles after which an sstable of `w_old` was
  still live, or whose first task was not the drop;
- `sstables_beyond_one`: the most sstables any cycle left in one of the
  two windows that stay, less one.
`control(ctx)` puts the reference in the program's place: as it is, with
expiry ignored (expired cells read back live), with a conversion that
keeps the value bytes, and with one input lost.
"""
from __future__ import annotations

import datetime
import glob
import hashlib
import os
import time

import numpy as np

WINDOWS = ("w_old", "w_mid", "w_new")
MERGED = ("w_mid", "w_new")            # the windows a cycle compacts
DAY_S = 86400
CHECKED_COLUMNS = ("lanes", "ts", "ldt", "ttl", "flags", "off",
                   "val_start", "payload")


class State:
    pass


# ------------------------------------------------------------- the data --

def epoch_day() -> int:
    return int(time.time()) // DAY_S


def series_names(cfg: dict, seed: int, day: int) -> list:
    """The partition keys of one day: host tags from the seed (a host
    keeps its tags from day to day), one series per host x metric."""
    d, tags = cfg["data"], cfg["data"]["tags"]
    rng = np.random.default_rng([int(seed), 11])
    date = (datetime.date(1970, 1, 1)
            + datetime.timedelta(days=int(day))).isoformat()
    names = []

    def pick(tag: str):
        values = tags[tag]
        return rng.integers(values) if isinstance(values, int) \
            else values[rng.integers(len(values))]

    for h in range(int(d["hosts"])):
        region = pick("region")
        host = ",".join([
            f"cpu,hostname=host_{h}", f"region={region}",
            f"datacenter={region}{pick('datacenter_suffix')}"]
            + [f"{tag}={pick(tag)}" for tag in (
                "rack", "os", "arch", "team", "service", "service_version",
                "service_environment")])
        names += [f"{host}#{m}#{date}".encode() for m in d["metrics"]]
    return names


def _rows_of(hours: list, per_hour: int) -> np.ndarray:
    """[h0, h1, h2, h3, ...] = the readings of hours [h0, h1) and
    [h2, h3) and ..."""
    return np.concatenate([np.arange(a * per_hour, b * per_hour)
                           for a, b in zip(hours[::2], hours[1::2])])


def seeded_windows(seed: int, cfg: dict, day0: int) -> dict:
    """Per window {"day", "reading_s" (R,), "names", "runs"}; a run is
    one input sstable as plain arrays, one entry a row, series-major:
    series and row indices, write timestamp (us), value. Nothing of the
    program: the plain reference and its controls read these."""
    d = cfg["data"]
    n_rows, step = int(d["rows_per_partition"]), int(d["interval_s"])
    assert n_rows * step == DAY_S
    per_hour = 3600 // step
    out = {}
    for w, name in enumerate(WINDOWS):
        spec = d["windows"][name]
        day = day0 + int(spec["day"])
        names = series_names(cfg, seed, day)
        n_series = len(names)
        rng = np.random.default_rng([int(seed), 12, w])
        value = np.empty((n_series, n_rows), dtype=np.int64)
        v = rng.integers(0, 101, n_series)
        steps = rng.integers(-1, 2, (n_rows, n_series))
        for r in range(n_rows):            # clamped: sequential by nature
            v = np.clip(v + steps[r], 0, 100)
            value[:, r] = v
        reading_s = day * DAY_S + np.arange(n_rows, dtype=np.int64) * step
        write_us = reading_s[None, :] * 1_000_000 \
            + rng.integers(0, 1_000_000, (n_series, n_rows))
        runs = []
        for hours in spec["sstables"]:
            rows = _rows_of(hours, per_hour)
            runs.append({
                "series": np.repeat(np.arange(n_series), len(rows)),
                "row": np.tile(rows, n_series),
                "write_us": write_us[:, rows].ravel(),
                "value": value[:, rows].ravel()})
        out[name] = {"day": day, "reading_s": reading_s, "names": names,
                     "runs": runs}
    return out


class Layout:
    """What every sstable of one window shares: each series' four
    partition lanes and each reading's clustering lanes and frame header,
    from the program's own key functions, once per series and reading."""

    def __init__(self, table, window: dict):
        from cassandra_tpu.schema import COL_ROW_LIVENESS
        from cassandra_tpu.storage import cellbatch as cb
        from cassandra_tpu.utils import murmur3
        from cassandra_tpu.utils import varint as vi
        C = table.clustering_lanes
        self.K = cb.lanes_for_table(table)
        self.columns = np.array(
            [COL_ROW_LIVENESS, table.regular_columns[0].column_id],
            dtype=np.uint32)
        self.names = window["names"]
        self.pk = np.array([cb.pk_lanes(n) for n in self.names],
                           dtype=np.uint32)
        self.pk_keys = [cb.pk_lane_key(n) for n in self.names]
        # series in lane order: how the store sorts partitions
        self.order = np.lexsort(tuple(self.pk[:, k] for k in (3, 2, 1, 0)))
        ck, hdr, fits = [], [], True
        for s in window["reading_s"]:
            frame = table.serialize_clustering([int(s) * 1_000_000_000])
            comp = table.clustering_comp(frame)
            fits = fits and len(comp) <= 4 * C
            h1, _ = murmur3.hash128(comp)
            ck.append(cb._pack_prefix(comp, C)
                      + [h1 >> 32, h1 & 0xFFFFFFFF])
            head = bytearray()
            vi.write_unsigned_vint(len(frame), head)
            head += frame
            vi.write_unsigned_vint(0, head)
            hdr.append(bytes(head))
        self.ck = np.array(ck, dtype=np.uint32)
        self.head = len(hdr[0])
        assert all(len(h) == self.head for h in hdr)
        self.hdr = np.frombuffer(b"".join(hdr), dtype=np.uint8).reshape(
            len(hdr), self.head)
        self.fits = fits


def build_batch(table, lay: Layout, run: dict, ttl: int):
    """One input sstable's cells as one SORTED CellBatch, vectorised: per
    row a row-liveness cell and a value cell, both expiring, in the lanes
    and frame layout CellBatchBuilder gives them (`selfcheck`)."""
    from cassandra_tpu.storage.cellbatch import (FLAG_EXPIRING,
                                                 FLAG_ROW_LIVENESS,
                                                 CellBatch)
    series, row = np.asarray(run["series"]), np.asarray(run["row"])
    rank = np.empty(len(lay.order), dtype=np.int64)
    rank[lay.order] = np.arange(len(lay.order))
    by = np.lexsort((row, rank[series]))    # partition, then reading
    series, row = series[by], row[by]
    write, value = run["write_us"][by], run["value"][by]
    n = 2 * len(by)
    lanes = np.zeros((n, lay.K), dtype=np.uint32)
    lanes[:, :4] = np.repeat(lay.pk[series], 2, axis=0)
    lanes[:, 4:4 + lay.ck.shape[1]] = np.repeat(lay.ck[row], 2, axis=0)
    lanes[:, 4 + lay.ck.shape[1]] = np.tile(lay.columns, len(by))
    ts = np.repeat(write, 2)
    head = lay.head
    payload = np.empty((len(by), 2 * head + 8), dtype=np.uint8)
    payload[:, :head] = lay.hdr[row]
    payload[:, head:2 * head] = lay.hdr[row]
    payload[:, 2 * head:] = value.astype(">i8").view(np.uint8).reshape(
        len(by), 8)
    lens = np.tile(np.array([head, head + 8], dtype=np.int64), len(by))
    off = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lens, out=off[1:])
    out = CellBatch(
        lanes, ts, (ts // 1_000_000 + ttl).astype(np.int32),
        np.full(n, ttl, dtype=np.int32),
        np.tile(np.array([FLAG_ROW_LIVENESS | FLAG_EXPIRING,
                          FLAG_EXPIRING], dtype=np.uint8), len(by)),
        off, off[:-1] + head, payload.reshape(-1),
        {lay.pk_keys[s]: lay.names[s] for s in np.unique(series)},
        sorted=True)
    out.ck_comp = table.clustering_comp
    out.ck_fits_prefix = lay.fits
    return out


def selfcheck(table, lay: Layout, window: dict, ttl: int) -> None:
    """build_batch must agree exactly with the program's own path for
    the same INSERTs: CellBatchBuilder cell by cell, then the numpy
    sort."""
    from cassandra_tpu.storage import cellbatch as cb
    n_series, n_rows = len(lay.names), len(window["reading_s"])
    rows = np.unique(np.array([0, 1, 2, n_rows // 3, n_rows // 2,
                               n_rows - 2, n_rows - 1]))
    some = np.unique(np.array([0, 1, n_series // 2, n_series - 1]))
    rng = np.random.default_rng(1)
    run = {"series": np.repeat(some, len(rows)),
           "row": np.tile(rows, len(some)),
           "write_us": window["reading_s"][np.tile(rows, len(some))]
           * 1_000_000 + rng.integers(0, 1_000_000, len(some) * len(rows)),
           "value": rng.integers(0, 101, len(some) * len(rows))}
    fast = build_batch(table, lay, run, ttl)
    slow = cb.CellBatchBuilder(table)
    column = int(lay.columns[1])
    for s, r, w, v in zip(run["series"], run["row"], run["write_us"],
                          run["value"]):
        ck = table.serialize_clustering(
            [int(window["reading_s"][r]) * 1_000_000_000])
        at = int(w) // 1_000_000
        slow.add_row_liveness(lay.names[s], ck, int(w), ttl=ttl, now=at)
        slow.add_cell(lay.names[s], ck, column,
                      int(v).to_bytes(8, "big", signed=True), int(w),
                      ttl=ttl, now=at)
    want = cb.merge_sorted([slow.seal()])
    for name in CHECKED_COLUMNS:
        np.testing.assert_array_equal(getattr(fast, name),
                                      getattr(want, name), err_msg=name)
    assert fast.pk_map == want.pk_map
    assert fast.ck_fits_prefix == want.ck_fits_prefix


def _link_all(src: str, dst: str) -> None:
    os.makedirs(dst, exist_ok=True)
    for fn in os.listdir(src):
        p = os.path.join(src, fn)
        if os.path.isfile(p):
            os.link(p, os.path.join(dst, fn))


def land(st: State) -> None:
    """Every run of every window as one sstable, side by side on threads
    (numpy and the native LZ4 release the GIL); then the hard links the
    cycles restore from."""
    from concurrent.futures import ThreadPoolExecutor

    from cassandra_tpu.storage.sstable import Descriptor, SSTableWriter
    jobs = []
    for name in WINDOWS:
        lay = Layout(st.table, st.windows[name])
        selfcheck(st.table, lay, st.windows[name], st.ttl)
        for run in st.windows[name]["runs"]:
            jobs.append((lay, run, st.cfs.next_generation()))

    def one(job) -> None:
        lay, run, gen = job
        w = SSTableWriter(Descriptor(st.cfs.directory, gen), st.table)
        w.append(build_batch(st.table, lay, run, st.ttl))
        w.finish()

    with ThreadPoolExecutor(len(jobs)) as pool:
        list(pool.map(one, jobs))
    st.cfs.reload_sstables()
    _link_all(st.cfs.directory, st.copies)


# ----------------------------------------------------------- one cycle --

def _counters(st: State) -> dict:
    from cassandra_tpu.service.metrics import GLOBAL as metrics
    return {c: metrics.counter(c)
            for c in st.rows.FALLBACK_COUNTERS + (st.converted_counter,)}


def _by_window(st: State) -> dict:
    """{window name or the day: the live sstables whose newest cell was
    written that day}: how the strategy buckets them."""
    names = {st.windows[n]["day"]: n for n in WINDOWS}
    out: dict = {}
    for r in st.cfs.live_sstables():
        day = int(r.max_ts // 1_000_000 // DAY_S)
        out.setdefault(names.get(day, day), []).append(r)
    return out


def _settled(st: State) -> bool:
    live = _by_window(st)
    return set(live) == set(MERGED) and all(len(v) == 1
                                            for v in live.values())


def _await_idle(st: State, wait_s: float) -> str:
    """"done" once the manager has nothing queued, running or active and
    the store holds what a finished cycle leaves; "idle" when it stays
    idle `idle_settle_s` in any other state (it will do nothing more);
    "timeout" when `wait_s` runs out first."""
    cm = st.served.node.engine.compactions
    end, idle_since = time.monotonic() + wait_s, None
    while time.monotonic() < end:
        if len(cm.active) == 0 and cm.pending_tasks() == 0 \
                and cm.executor.stats()["active"] == 0:
            if _settled(st):
                return "done"
            idle_since = idle_since or time.monotonic()
            if time.monotonic() - idle_since >= st.settle_s:
                return "idle"
        else:
            idle_since = None
        time.sleep(st.poll_s)
    return "timeout"


def cycle(st: State, label: str, wait_s: float, annotate=None) -> dict:
    import contextlib
    span = annotate or (lambda _n: contextlib.nullcontext())
    cm = st.served.node.engine.compactions
    t0 = time.perf_counter()
    with span("bench.restore"):
        st.cfs.truncate()
        _link_all(st.copies, st.cfs.directory)
        st.cfs.reload_sstables()           # nodetool refresh
    done0, before = len(st.cfs.compaction_history), _counters(st)
    t1, now = time.perf_counter(), int(time.time())
    with span("bench.cycle.compactions"):
        cm.paused = False                  # nodetool enableautocompaction
        cm.submit_background(st.cfs)       # what a flush's notification does
        ended = _await_idle(st, wait_s)
        cm.paused = True
    t2 = time.perf_counter()
    after = _counters(st)
    tasks = [dict(t) for t in list(st.cfs.compaction_history)[done0:]]
    live = _by_window(st)
    directory = os.path.join(st.scratch, "cycles", label)
    _link_all(st.cfs.directory, directory)
    return {"label": label, "start": t0, "end": t2, "now": now,
            "restore_s": t1 - t0, "wall_s": t2 - t1, "ended": ended,
            "tasks": tasks, "directory": directory,
            "outputs": {w: [r.desc.generation for r in live.get(w, [])]
                        for w in WINDOWS},
            "unknown_windows": [k for k in live if k not in WINDOWS],
            "rose": {c: after[c] - before[c] for c in after}}


def merges(op: dict) -> list:
    return [t for t in op["tasks"] if not t.get("dropped")]


def fell_back(st: State, op: dict) -> bool:
    return any(op["rose"][c] for c in st.rows.FALLBACK_COUNTERS)


def off_device(st: State, op: dict) -> int:
    """Merge tasks of the cycle that did not drive the device path by the
    task's own choice; all of them if a fallback counter rose; one for
    each merge the cycle lacks."""
    ms = merges(op)
    bad = len(ms) if fell_back(st, op) else sum(
        1 for t in ms
        if t.get("engine") != "device" or not t.get("engine_chosen"))
    return bad + max(len(MERGED) - len(ms), 0)


def not_dropped(op: dict) -> bool:
    first = op["tasks"][0] if op["tasks"] else {}
    return bool(op["outputs"]["w_old"]) or not first.get("dropped")


# -------------------------------------------------------------- set-up --

def setup(ctx) -> State:
    import wire
    from cassandra_tpu.compaction import task as ctask
    from cassandra_tpu.storage.cellbatch import FLAG_EXPIRING
    cfg, mix = ctx.config, ctx.traffic
    s = cfg["schema"]

    class Probe:
        n_cells, cell_flags = ctask.CompactionTask.DEVICE_MIN_CELLS, \
            FLAG_EXPIRING
    if not hasattr(ctask, "choose_engine") or ctask.choose_engine(
            [Probe()], lambda: True)[0] != "device":
        # before PR 31 every table with a TTL compacted on a host engine:
        # nothing this cell runs would reach the device. Fail at once.
        raise RuntimeError(
            "this program's CompactionTask sends TTL'd inputs to a host "
            "engine (compaction/task.py choose_engine): the cell "
            f"{ctx.cell['name']} cannot run on it")
    st = State()
    st.cfg, st.scratch = cfg, ctx.scratch
    st.rows = ctx.load("drivers", "major_loop")
    st.ref = ctx.load("reference", "timeseries")
    st.converted_counter = "compaction.device_expired_converted"
    st.ttl, st.gc_grace = int(s["default_time_to_live"]), \
        int(s["gc_grace_seconds"])
    st.settle_s, st.poll_s = float(mix["idle_settle_s"]), \
        float(mix["poll_s"])
    t0 = time.perf_counter()
    st.day0 = epoch_day()
    st.windows = seeded_windows(ctx.seed, cfg, st.day0)
    ctx.note("generate_s", time.perf_counter() - t0)
    st.served = wire.ServedNode(os.path.join(ctx.scratch, "node"),
                                s["keyspace"], cfg.get("node_config"))
    cm = st.served.node.engine.compactions
    cm.paused = True                 # nodetool disableautocompaction
    for stmt in s["ddl"]:
        st.served.session.execute(stmt)
    st.table, st.cfs = st.served.table(s["table"]), \
        st.served.store(s["table"])
    assert st.table.params.default_ttl == st.ttl
    assert st.table.params.gc_grace_seconds == st.gc_grace
    st.copies = os.path.join(ctx.scratch, "inputs")
    t0 = time.perf_counter()
    land(st)
    st.input_bytes = {w: sum(r.data_size for r in v)
                      for w, v in _by_window(st).items()}
    st.input_gens = {w: sorted(r.desc.generation for r in v)
                     for w, v in _by_window(st).items()}
    assert {w: len(g) for w, g in st.input_gens.items()} == {
        w: len(st.windows[w]["runs"]) for w in WINDOWS}
    ctx.note("load_s", time.perf_counter() - t0)
    ctx.note("input_mib", {w: b / 2.0 ** 20
                           for w, b in st.input_bytes.items()})
    ctx.note("day0", st.day0)
    # every (program, shape) a cycle uses: one whole cycle
    st.warm = cycle(st, "warm", float(cfg["correct"]["warm_cycle_wait_s"]))
    ctx.note("warm_cycle_s", st.warm["wall_s"])
    ctx.note("warm_tasks", [
        {k: t.get(k) for k in ("engine", "engine_chosen", "inputs",
                               "dropped", "seconds")}
        for t in st.warm["tasks"]])
    ctx.note("warm_cycle_ended", st.warm["ended"])
    # what set-up wrote (the landed windows, the warm-up's outputs) goes
    # to disk on set-up's time: a window's first restore once waited
    # 10.3 s on its file operations behind that writeback (PERF.md §6)
    os.sync()
    if st.warm["ended"] == "timeout":
        raise RuntimeError(
            "the warm-up cycle was still running after "
            f"{cfg['correct']['warm_cycle_wait_s']} s: live sstables "
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
    tasks = [t for o in ops for t in o["tasks"]]
    failed = sum(off_device(st, o) + not_dropped(o) for o in ops)
    # the bytes of the merges that drove the device path, over ALL the
    # time: restoring, selecting, dropping and a merge that fell to the
    # host add their time and no bytes
    mib = sum(t["bytes_read"] for o in ops if not fell_back(st, o)
              for t in merges(o)
              if t.get("engine") == "device" and t.get("engine_chosen")) \
        / 2.0 ** 20
    cells = sum(t["cells_read"] for o in ops for t in merges(o))
    return {"attempted": max(len(tasks), 3 * len(ops)),
            "failed": failed, "ops": ops, "elapsed_s": elapsed,
            "lanes": int(st.cfs.live_sstables()[0].K),
            "cells_merged": cells,
            "cells_converted": sum(o["rose"][st.converted_counter]
                                   for o in ops),
            "end_to_end": {"compaction_mib_s": mib / elapsed},
            "detail": {
                "cycles": len(ops),
                "cycle_walls_s": [o["wall_s"] for o in ops],
                "restore_s": [o["restore_s"] for o in ops],
                "tasks": [[{k: t.get(k) for k in (
                    "engine", "engine_chosen", "inputs", "dropped",
                    "seconds", "cells_read", "cells_written",
                    "bytes_read")} for t in o["tasks"]] for o in ops][:2],
                "ended": [o["ended"] for o in ops],
                "rose": [o["rose"] for o in ops]}}


# ------------------------------------------------------------ `correct` --

def window_hashes(st: State, op: dict) -> dict:
    """{"<window>:<rank>:<component>": sha256} of the cycle's output
    sstables, from the hard links kept when it ended."""
    out = {}
    for w in MERGED:
        for rank, gen in enumerate(sorted(op["outputs"][w])):
            for comp in st.rows.HASHED_COMPONENTS:
                for p in glob.glob(os.path.join(op["directory"],
                                                f"*-{gen}-{comp}")):
                    with open(p, "rb") as f:
                        out[f"{w}:{rank}:{comp}"] = hashlib.sha256(
                            f.read()).hexdigest()
    return out


def differing(a: dict, b: dict) -> int:
    return sum(1 for k in a.keys() | b.keys() if a.get(k) != b.get(k))


def read_back(st: State, readers: list, window: dict) -> dict:
    """Every cell of `readers`, read through the store's sequential
    reader, as the reference's plain columns."""
    from cassandra_tpu.schema import COL_ROW_LIVENESS
    index = {n: i for i, n in enumerate(window["names"])}
    n_rows = len(window["reading_s"])
    day_ns = int(window["reading_s"][0]) * 1_000_000_000
    step_ns = int(st.cfg["data"]["interval_s"]) * 1_000_000_000
    column_lane = 6 + st.table.clustering_lanes
    cols = {k: [] for k in ("id", "ts", "flags", "ldt", "ttl", "vlen",
                            "value")}
    for reader in readers:
        for seg in reader.scanner():
            n = len(seg)
            if not n:
                continue
            lane4 = np.ascontiguousarray(seg.lanes[:, :4].astype(">u4"))
            uniq, inv = np.unique(lane4.view([("k", "V16")]).ravel(),
                                  return_inverse=True)
            series = np.array([index.get(seg.pk_map[u.tobytes()], -1)
                               for u in uniq])[inv.ravel()]
            off = np.asarray(seg.off, dtype=np.int64)
            start = np.asarray(seg.val_start, dtype=np.int64)
            payload = np.asarray(seg.payload)
            # the frame: [vint 9][vint 8][timestamp_ns, 8 B][vint 0][value]
            stamp = payload[off[:-1, None] + 2 + np.arange(8)[None, :]]
            ns = np.ascontiguousarray(stamp).view(">i8").ravel()
            row = (ns - day_ns) // step_ns
            odd = (series < 0) | (row < 0) | (row >= n_rows) \
                | ((ns - day_ns) % step_ns != 0) | (start - off[:-1] != 11)
            vlen = off[1:] - start
            value = np.zeros(n, dtype=np.int64)
            full = vlen == 8
            value[full] = np.ascontiguousarray(payload[
                start[full, None] + np.arange(8)[None, :]]).view(
                ">i8").ravel()
            column = np.where(
                seg.lanes[:, column_lane] == COL_ROW_LIVENESS,
                st.ref.LIVENESS, st.ref.VALUE)
            ids = st.ref.cell_ids(series, row, n_rows, column)
            ids[odd] = -1 - np.flatnonzero(odd)   # matches nothing
            for name, col in zip(cols, (ids, seg.ts, seg.flags, seg.ldt,
                                        seg.ttl, vlen, value)):
                cols[name].append(np.asarray(col))
    return {k: np.concatenate(v) for k, v in cols.items()}


def reference_cells(st_ref, windows: dict, cfg: dict, now: int,
                    control: str | None = None) -> dict:
    """What a finished cycle leaves on disk, by the plain reference: the
    two merged windows' cells at the cycle's `now` (w_old is gone, and
    nothing outside a merge holds one of its series)."""
    s, d = cfg["schema"], cfg["data"]
    n_rows = int(d["rows_per_partition"])
    tables = []
    for k, w in enumerate(MERGED):
        merged = st_ref.merge(
            windows[w]["runs"], n_rows, int(s["default_time_to_live"]),
            now, now - int(s["gc_grace_seconds"]), control=control)
        tables.append((k * 2 * n_rows * len(windows[w]["names"]), merged))
    return st_ref.concat(tables)


def cells_check(ref, got: dict, want: dict) -> dict:
    return {"name": "cells_wrong", "value": ref.cells_wrong(got, want),
            "limit": 0, "of": int(len(want["id"]))}


def check(st: State, ctx, result: dict) -> list:
    from cassandra_tpu.compaction.task import CompactionTask
    ops = [st.warm] + result["ops"]
    last = ops[-1]
    n_rows = int(st.cfg["data"]["rows_per_partition"])
    t0 = time.perf_counter()
    live = _by_window(st)
    got = st.ref.concat([
        (k * 2 * n_rows * len(st.windows[w]["names"]),
         read_back(st, live.get(w, []), st.windows[w]))
        for k, w in enumerate(MERGED)])
    ctx.note("read_back_s", time.perf_counter() - t0)
    hashes = [window_hashes(st, o) for o in ops]
    st.served.close()                # the program's state goes first
    st.served = None
    t0 = time.perf_counter()
    want = reference_cells(st.ref, st.windows, st.cfg, last["now"])
    ctx.note("reference_s", time.perf_counter() - t0)
    expect = len(MERGED) * len(st.rows.HASHED_COMPONENTS)
    checks = [
        cells_check(st.ref, got, want),
        {"name": "components_differing",
         "value": sum(differing(h, hashes[-1]) for h in hashes[:-1])
         + max(expect - len(hashes[-1]), 0),
         "limit": 0, "of": expect * (len(ops) - 1)},
        {"name": "compactions_off_device",
         "value": sum(off_device(st, o) for o in ops), "limit": 0,
         "of": len(MERGED) * len(ops)},
        {"name": "windows_not_dropped",
         "value": sum(1 for o in ops if not_dropped(o)), "limit": 0,
         "of": len(ops)},
        {"name": "sstables_beyond_one",
         "value": max(max(len(o["outputs"][w]) for w in MERGED)
                      + len(o["unknown_windows"]) for o in ops) - 1,
         "limit": 0}]
    # the stated guarantee: the bytes the numpy engine writes, from the
    # same six inputs beside one another, window by window as the
    # strategy took them
    t0 = time.perf_counter()
    host = st.rows.standalone_store(
        st.table, os.path.join(st.scratch, "host_engine"))
    for fn in os.listdir(st.copies):
        if any(f"-{g}-" in fn for w in MERGED for g in st.input_gens[w]):
            os.link(os.path.join(st.copies, fn),
                    os.path.join(host.directory, fn))
    host.reload_sstables()
    outputs = {}
    for w in MERGED:
        mine = [r for r in host.live_sstables()
                if r.desc.generation in st.input_gens[w]]
        known = {r.desc.generation for r in host.live_sstables()}
        CompactionTask(host, mine, engine="numpy",
                       use_device=False).execute()
        outputs[w] = [r.desc.generation for r in host.live_sstables()
                      if r.desc.generation not in known]
    numpy_hashes = window_hashes(
        st, {"outputs": outputs, "directory": host.directory})
    st.rows.close_store(host)
    ctx.note("host_engine_s", time.perf_counter() - t0)
    checks.append({"name": "components_differing_from_host_engine",
                   "value": differing(numpy_hashes, hashes[-1])
                   + max(expect - len(numpy_hashes), 0),
                   "limit": 0, "of": expect})
    return checks


def control(ctx) -> list:
    """(name, checks) per control, at the cell's own size, no node and no
    chip: the plain reference in the program's place as it is (has to
    read correct), then with one stated guarantee broken (each has to
    read not correct), through the comparison `check` makes."""
    ref = ctx.load("reference", "timeseries")
    windows = seeded_windows(ctx.seed, ctx.config, epoch_day())
    now = int(time.time())
    want = reference_cells(ref, windows, ctx.config, now)
    return [(name or "reference_in_place",
             [cells_check(ref, reference_cells(ref, windows, ctx.config,
                                               now, control=name), want)])
            for name in (None,) + ref.CONTROLS]


def close(st: State) -> None:
    if st.served is not None:
        st.served.close()
