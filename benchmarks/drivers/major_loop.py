"""major_loop: back-to-back major compactions, each on a fresh hard-linked
copy of one seeded backlog in a standalone store, through
CompactionTask(...).execute() with the keyword arguments the configuration
file gives.

The backlog is the configuration's own shape: one row per partition, a
blob key and a handful of blob columns (cassandra-stress's standard1), the
rows of each input sstable built as one CellBatch by `build_rows_batch`
(the store's lane and frame layout, held against CellBatchBuilder in every
set-up) and landed by SSTableWriter. standalone_store and component_hashes
began as copies of chip_smoke.py's, proven on the chip in PR 21; later PRs
may change the program, not this yardstick.
"""
from __future__ import annotations

import glob
import hashlib
import os
import time

import numpy as np

HASHED_COMPONENTS = ("Data.db", "Index.db", "Partitions.db", "Filter.db",
                     "Statistics.db", "Digest.crc32", "ZoneMap.db")
FALLBACK_COUNTERS = ("compaction.device_compress_fallback",
                     "compaction.device_host_rounds",
                     "compaction.device_resident_fallback")


# ------------------------------------------------------------- the data --

def make_table(cfg: dict):
    from cassandra_tpu.ops.codec import CompressionParams
    from cassandra_tpu.schema import TableParams, make_table as mk
    s = cfg["schema"]
    cols = {s["key"]: "blob"}
    cols.update({c: "blob" for c in s["columns"]})
    return mk(s["keyspace"], s["table"], pk=[s["key"]], ck=[], cols=cols,
              params=TableParams(
                  compression=CompressionParams(
                      s["compression"],
                      chunk_length=s["chunk_length_kib"] * 1024),
                  gc_grace_seconds=s["gc_grace_seconds"]))


def standalone_store(table, base_dir: str, inputs_from: str | None = None):
    from cassandra_tpu.storage.table import ColumnFamilyStore
    cfs = ColumnFamilyStore(table, base_dir, commitlog=None)
    if inputs_from is not None:
        for fn in os.listdir(inputs_from):
            src = os.path.join(inputs_from, fn)
            if os.path.isfile(src):
                os.link(src, os.path.join(cfs.directory, fn))
    cfs.reload_sstables()
    return cfs


def close_store(cfs) -> None:
    for reader in cfs.live_sstables():
        reader.close()


def seeded_runs(seed: int, cfg: dict) -> list:
    """Per input sstable, the seeded rows as plain arrays: (keys (n, K)
    uint8, ts (n,) int64, vals (n, C, L) uint8). Nothing of the program:
    the plain reference and its controls read these.

    As `cassandra-stress write` leaves them: every key written once (its
    `seq` population), all columns of a row in one write, no delete, no
    TTL; the flushes follow one another in time, so each sstable holds a
    slice of the write times and keys that hash all over the ring.

    Values and write times come from the run's seed. The keys come from
    one of the configuration's `key_layouts`, the run's seed choosing
    which: where the inputs' segment ends fall on the ring decides how
    many of a compaction's merge rounds pass 2^19 cells and run padded to
    2^20, 0.4 s apiece (PERF.md, PR 24), so keys drawn from the run's seed
    made the seed change the work. The layouts listed cut into the same
    rounds; every seed has the same sizes."""
    d, s = cfg["data"], cfg["schema"]
    layouts = d["key_layouts"]
    krng = np.random.default_rng(int(layouts[seed % len(layouts)]))
    rng = np.random.default_rng(seed)
    n, out = int(d["rows_per_run"]), []
    span = int(d["write_time_span_us"]) // int(d["runs"])
    for r in range(int(d["runs"])):
        keys = krng.integers(0, 256, (n, int(d["key_bytes"])),
                             dtype=np.uint8)
        ts = int(d["write_time_base_us"]) + r * span \
            + np.sort(rng.integers(0, span, n)).astype(np.int64)
        vals = rng.integers(0, 256, (n, len(s["columns"]),
                                     int(d["value_bytes"])), dtype=np.uint8)
        out.append((keys, ts, vals))
    return out


def build_rows_batch(table, column_ids, keys, ts, vals):
    """One CellBatch of len(keys) x len(column_ids) live cells, vectorised:
    the lanes (biased token, key hash, no clustering, column id, no path)
    and payload frames ([vint 0][vint 0][value]) that CellBatchBuilder
    gives for the same cells (`selfcheck`)."""
    from cassandra_tpu.storage.cellbatch import CellBatch, lanes_for_table
    from cassandra_tpu.utils import murmur3, partitioners
    n, kw = keys.shape
    ncol, vw = vals.shape[1], vals.shape[2]
    lanes_n, ck_lanes = lanes_for_table(table), table.clustering_lanes
    width = (kw + 15) // 16 * 16 + 16
    padded = np.zeros((n, width), dtype=np.uint8)
    padded[:, :kw] = keys
    lens = np.full(n, kw, dtype=np.int64)
    h1, h2 = murmur3.hash128_mat(padded, lens)
    part = partitioners.current()
    if isinstance(part, partitioners.Murmur3Partitioner):
        tok = h1.astype(np.int64)
        tok = np.where(tok == np.iinfo(np.int64).min,
                       np.iinfo(np.int64).max, tok)
    else:
        tok = part.tokens_mat(padded, lens)
    with np.errstate(over="ignore"):
        ut = tok.astype(np.uint64) ^ np.uint64(1 << 63)
    row = np.zeros((n, lanes_n), dtype=np.uint32)
    row[:, 0] = (ut >> np.uint64(32)).astype(np.uint32)
    row[:, 1] = (ut & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    row[:, 2] = (h2 >> np.uint64(32)).astype(np.uint32)
    row[:, 3] = (h2 & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    lanes = np.repeat(row, ncol, axis=0)
    lanes[:, 6 + ck_lanes] = np.tile(
        np.asarray(column_ids, dtype=np.uint32), n)
    cells = n * ncol
    payload = np.zeros((cells, 2 + vw), dtype=np.uint8)
    payload[:, 2:] = vals.reshape(cells, vw)
    off = np.arange(cells + 1, dtype=np.int64) * (2 + vw)
    lane4 = np.ascontiguousarray(row[:, :4].astype(">u4"))
    pk_map = {lane4[i].tobytes(): keys[i].tobytes() for i in range(n)}
    if len(pk_map) != n:
        raise RuntimeError("two seeded keys share their 128-bit hash")
    out = CellBatch(lanes, np.repeat(np.asarray(ts, dtype=np.int64), ncol),
                    np.full(cells, 0x7FFFFFFF, dtype=np.int32),
                    np.zeros(cells, dtype=np.int32),
                    np.zeros(cells, dtype=np.uint8),
                    off, off[:-1] + 2, payload.reshape(-1), pk_map,
                    sorted=False)
    out.ck_comp = table.clustering_comp
    out.ck_fits_prefix = True
    return out


def column_ids(table, cfg: dict) -> list:
    by_name = {c.name: c.column_id for c in table.regular_columns}
    return [by_name[c] for c in cfg["schema"]["columns"]]


def selfcheck(table, cfg: dict) -> None:
    """build_rows_batch must agree exactly with CellBatchBuilder, the
    program's own cell-by-cell path."""
    from cassandra_tpu.storage.cellbatch import CellBatchBuilder
    d, ids = cfg["data"], column_ids(table, cfg)
    rng = np.random.default_rng(1)
    keys = rng.integers(0, 256, (5, int(d["key_bytes"])), dtype=np.uint8)
    vals = rng.integers(0, 256, (5, len(ids), int(d["value_bytes"])),
                        dtype=np.uint8)
    ts = rng.integers(1, 1 << 50, 5)
    fast = build_rows_batch(table, ids, keys, ts, vals)
    slow = CellBatchBuilder(table)
    for i in range(5):
        for j, cid in enumerate(ids):
            slow.add_cell(keys[i].tobytes(), b"", cid, vals[i, j].tobytes(),
                          int(ts[i]))
    sealed = slow.seal()
    for name in ("lanes", "ts", "ldt", "ttl", "flags", "off", "val_start",
                 "payload"):
        np.testing.assert_array_equal(getattr(fast, name),
                                      getattr(sealed, name), err_msg=name)
    assert fast.pk_map == sealed.pk_map


def load_backlog(cfs, seed: int, cfg: dict) -> list:
    """Each seeded run landed as one sstable, the runs side by side on
    threads (numpy and the native LZ4 release the GIL). Returns the seeded
    runs for the plain reference."""
    from concurrent.futures import ThreadPoolExecutor

    from cassandra_tpu.storage import cellbatch as cb
    from cassandra_tpu.storage.sstable import Descriptor, SSTableWriter
    selfcheck(cfs.table, cfg)
    ids = column_ids(cfs.table, cfg)
    runs = seeded_runs(seed, cfg)
    gens = [cfs.next_generation() for _ in runs]

    def land(job) -> None:
        (keys, ts, vals), gen = job
        w = SSTableWriter(Descriptor(cfs.directory, gen), cfs.table)
        w.append(cb.merge_sorted(
            [build_rows_batch(cfs.table, ids, keys, ts, vals)]))
        w.finish()

    with ThreadPoolExecutor(len(runs)) as pool:
        list(pool.map(land, zip(runs, gens)))
    return runs


def component_hashes(directory: str) -> dict:
    from cassandra_tpu.storage.sstable import Descriptor
    out = {}
    gens = sorted(d.generation for d in Descriptor.list_in(directory))
    for rank, gen in enumerate(gens):
        for comp in HASHED_COMPONENTS:
            for p in glob.glob(os.path.join(directory, f"*-{gen}-{comp}")):
                with open(p, "rb") as f:
                    out[f"{rank}:{comp}"] = hashlib.sha256(
                        f.read()).hexdigest()
    return out


def read_back(cfs, cfg: dict, ref) -> dict:
    """Every cell of the store's live sstables, read through the store's
    own sequential reader, as the reference's plain columns."""
    from cassandra_tpu.schema import COL_REGULAR_BASE
    width = int(cfg["data"]["value_bytes"])
    column_lane = 6 + cfs.table.clustering_lanes
    cols = {k: [] for k in ("hi", "lo", "ts", "flags", "ldt", "ttl",
                            "vlen", "vals")}
    for reader in cfs.live_sstables():
        for seg in reader.scanner():
            n = len(seg)
            if not n:
                continue
            lane4 = np.ascontiguousarray(seg.lanes[:, :4].astype(">u4"))
            uniq, inv = np.unique(lane4.view([("k", "V16")]).ravel(),
                                  return_inverse=True)
            keys = np.frombuffer(b"".join(
                seg.pk_map[u.tobytes()] for u in uniq), dtype=np.uint8
            ).reshape(len(uniq), -1)
            hi, lo = ref.cell_keys(keys, 1)
            column = seg.lanes[:, column_lane].astype(np.int64) \
                - COL_REGULAR_BASE
            off = np.asarray(seg.off, dtype=np.int64)
            start = np.asarray(seg.val_start, dtype=np.int64)
            payload = np.asarray(seg.payload)
            vlen = off[1:] - start
            vals = np.zeros((n, width), dtype=np.uint8)
            full = vlen == width
            vals[full] = payload[start[full, None]
                                 + np.arange(width)[None, :]]
            inv = inv.ravel()
            for name, col in zip(cols, (
                    hi[inv], lo[inv] | column.astype(np.uint32), seg.ts,
                    seg.flags, seg.ldt, seg.ttl, vlen, vals)):
                cols[name].append(np.asarray(col))
    return {k: np.concatenate(v) for k, v in cols.items()}


# ----------------------------------------------------------- the driver --

class State:
    pass


def _counters() -> dict:
    from cassandra_tpu.service.metrics import GLOBAL as metrics
    return {c: metrics.counter(c) for c in FALLBACK_COUNTERS}


def _compact_once(st: State, label: str, task_kw: dict | None = None,
                  annotate=None) -> dict:
    """One major compaction on a fresh hard-linked copy of the backlog."""
    import contextlib

    from cassandra_tpu.compaction.task import CompactionTask
    span = annotate or (lambda _n: contextlib.nullcontext())
    t0 = time.perf_counter()
    with span("bench.store_open"):
        cfs = standalone_store(st.table, os.path.join(st.scratch, label),
                               st.base.directory)
    before = _counters()
    task = CompactionTask(cfs, cfs.tracker.view(),
                          **(st.task_kw if task_kw is None else task_kw))
    t1, now = time.perf_counter(), int(time.time())
    with span("bench.compaction.execute"):
        stats = task.execute()
    t2 = time.perf_counter()
    after = _counters()
    st.stores.append(cfs)
    return {"label": label, "engine": task.engine, "start": t0, "end": t2,
            "open_s": t1 - t0, "wall_s": t2 - t1, "now": now,
            "bytes_read": int(stats["bytes_read"]),
            "bytes_written": int(stats["bytes_written"]),
            "cells_read": int(stats["cells_read"]),
            "cells_written": int(stats["cells_written"]),
            "profile": dict(task.profile), "directory": cfs.directory,
            "live_after": len(list(cfs.live_sstables())),
            "cfs": cfs,
            "fallbacks": {c: after[c] - before[c] for c in after}}


def setup(ctx) -> State:
    st = State()
    cfg = ctx.config
    st.cfg, st.task_kw = cfg, dict(cfg["task"])
    st.scratch = ctx.scratch
    st.table = make_table(cfg)
    st.stores = []
    t0 = time.perf_counter()
    st.base = standalone_store(st.table, os.path.join(st.scratch, "backlog"))
    st.runs = load_backlog(st.base, ctx.seed, cfg)
    st.base.reload_sstables()
    ctx.note("load_s", time.perf_counter() - t0)
    # every (program, shape) the window will use: the same compaction once
    st.warm = _compact_once(st, "warm")
    ctx.note("warm_compaction_s", st.warm["wall_s"])
    ctx.note("warm_profile", st.warm["profile"])
    return st


def off_device(st: State, op: dict) -> bool:
    """A compaction that did not drive the device path the configuration
    names: another engine ran, or a fallback counter rose during it."""
    return op["engine"] != st.task_kw.get("engine") \
        or any(op["fallbacks"].values())


def window(st: State, ctx) -> dict:
    seconds = ctx.seconds
    tr = ctx.traffic.get("trace", {})
    first, count = int(tr.get("after_ops", 1)), int(tr.get("ops", 1))
    ops = []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        k = len(ops)
        if ctx.tracer is not None and k == first:
            ctx.tracer.start()
        ops.append(_compact_once(st, f"w{k}", annotate=ctx.annotate))
        if ctx.tracer is not None and k == first + count - 1:
            ctx.tracer.stop()
            ops[-1]["traced"] = True
            for o in ops[first:k]:
                o["traced"] = True
    if ctx.tracer is not None:
        ctx.tracer.stop()              # a window too short to reach `first`
    elapsed = ops[-1]["end"] - t0
    failed = [o for o in ops if off_device(st, o)]
    # the bytes of the compactions that drove the device path, over ALL the
    # time: one that fell back to the host adds its time and no bytes
    mib = sum(o["bytes_read"] for o in ops
              if not off_device(st, o)) / 2.0 ** 20
    return {"attempted": len(ops), "failed": len(failed), "ops": ops,
            "elapsed_s": elapsed,
            "end_to_end": {"compaction_mib_s": mib / elapsed},
            "detail": {"compactions": len(ops),
                       "input_mib_each": ops[0]["bytes_read"] / 2.0 ** 20,
                       "walls_s": [o["wall_s"] for o in ops],
                       "profile_mean_s": {
                           k: sum(o["profile"].get(k, 0.0) for o in ops)
                           / len(ops) for k in sorted(ops[0]["profile"])},
                       "open_s": [o["open_s"] for o in ops],
                       "fallbacks": [o["fallbacks"] for o in ops
                                     if any(o["fallbacks"].values())]}}


def cells_check(ref, got: dict, want: dict) -> dict:
    return {"name": "cells_wrong", "value": ref.cells_wrong(got, want),
            "limit": 0, "of": int(len(want["hi"]))}


def check(st: State, ctx, result: dict) -> list:
    """Every output component of every compaction of the window (and of
    the warm-up), every cell of the last one against the plain reference,
    that each left one sstable and that each drove the device path."""
    ref = ctx.load("reference", "compaction")
    ops = [st.warm] + result["ops"]
    hashes = [component_hashes(o["directory"]) for o in ops]
    last = hashes[-1]
    differing = sum(1 for h in hashes[:-1]
                    for k in h.keys() | last.keys()
                    if h.get(k) != last.get(k))
    if len(last) < len(HASHED_COMPONENTS):
        differing += len(HASHED_COMPONENTS) - len(last)
    checks = [
        cells_check(ref, read_back(ops[-1]["cfs"], st.cfg, ref),
                    ref.merge(st.runs)),
        {"name": "components_differing", "value": differing, "limit": 0,
         "of": len(HASHED_COMPONENTS) * (len(ops) - 1)},
        # a major compaction leaves one sstable; inputs left beside it would
        # read back whole and hide what was not merged
        {"name": "sstables_beyond_one",
         "value": max(o["live_after"] for o in ops) - 1, "limit": 0},
        {"name": "compactions_off_device",
         "value": sum(1 for o in ops if off_device(st, o)), "limit": 0,
         "of": len(ops)}]
    host_kw = st.cfg.get("host_engine_task")
    if host_kw:
        t0 = time.perf_counter()
        host = _compact_once(st, "host", task_kw=host_kw)
        h = component_hashes(host["directory"])
        checks.append({"name": "components_differing_from_host_engine",
                       "value": sum(1 for k in h.keys() | last.keys()
                                    if h.get(k) != last.get(k)),
                       "limit": 0, "of": len(HASHED_COMPONENTS)})
        ctx.note("host_engine_s", time.perf_counter() - t0)
    return checks


def control(ctx) -> list:
    """(name, checks) per control, at the cell's own size, no store and no
    chip: the plain reference in the program's place as it is (has to read
    correct), then with one stated guarantee broken (has to read not
    correct), each through the comparison `check` makes."""
    ref = ctx.load("reference", "compaction")
    runs = seeded_runs(ctx.seed, ctx.config)
    want = ref.merge(runs)
    return [(name or "reference_in_place",
             [cells_check(ref, ref.merge(runs, control=name), want)])
            for name in (None,) + ref.CONTROLS]


def close(st: State) -> None:
    for cfs in st.stores + [st.base]:
        try:
            close_store(cfs)
        except Exception:
            pass
