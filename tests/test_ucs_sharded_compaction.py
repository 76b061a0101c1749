"""A UCS compaction writes one sstable per token shard, on every engine.

Four level-0 flushes of cassandra-stress's standard1 rows over the whole
ring, under cassandra_latest.yaml's UCS options (min_sstable_size cut to
256 KiB so that about 2 MB of flushes take the geometry of the benchmark's
407 MiB: level 0, four shards, outputs at level 1) with Zstd in 64 KiB
chunks: the strategy picks them, and the task it builds — run on the
numpy, native and device engines (the device program on the CPU) — writes
four outputs, each holding only its shard's tokens by the plain
reference's rule (benchmarks/reference/ucs.py), byte-identical across the
engines, never picked again. The rolls are spans and counters. The
device's partial rounds run at one padded shape, the mesh fan-out writes
the serial loop's bytes, and a round's cursor carries only the partition
keys it still holds. A task without boundaries cuts the rounds it always
cut (pinned on the `stress_stcs_lz4` inputs of
benchmarks/drivers/major_loop.py).
"""
import importlib.util
import os
import types

import numpy as np
import pytest

from cassandra_tpu.compaction.strategies import get_strategy
from cassandra_tpu.compaction.task import (CompactionTask, _Cursor,
                                           _full_key, _own_pk_map)
from cassandra_tpu.ops.codec import CompressionParams
from cassandra_tpu.ops.merge import _bucket
from cassandra_tpu.schema import TableParams, make_table
from cassandra_tpu.service.metrics import GLOBAL as METRICS
from cassandra_tpu.storage.table import ColumnFamilyStore
from cassandra_tpu.utils import pipeline_ledger

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENGINES = ("numpy", "native", "device")
COMPONENTS = ("Data.db", "Index.db", "Partitions.db", "Filter.db",
              "Statistics.db", "Digest.crc32", "ZoneMap.db")
UCS = {"class": "UnifiedCompactionStrategy", "scaling_parameters": "T4",
       "target_sstable_size": "1GiB",
       "sstable_growth": "0.3333333333333333",
       "min_sstable_size": "256KiB", "base_shard_count": "4"}
COUNTERS = ("compaction.shard_rolls", "compaction.rounds_cut_at_shard")


def _load(*rel):
    path = os.path.join(ROOT, "benchmarks", *rel)
    name = "_t_" + "_".join(rel).replace(".py", "")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bench():
    return _load("reference", "ucs.py"), _load("drivers", "major_loop.py")


def _stress_cfg(rows: int) -> dict:
    return {"schema": {"keyspace": "keyspace1", "table": "standard1",
                       "key": "key", "columns": ["C0", "C1", "C2", "C3",
                                                 "C4"],
                       "compression": "ZstdCompressor",
                       "chunk_length_kib": 64,
                       "gc_grace_seconds": 864000},
            "data": {"runs": 4, "rows_per_run": rows, "key_bytes": 10,
                     "value_bytes": 34,
                     "write_time_base_us": 1700000000000000,
                     "write_time_span_us": 3600000000,
                     "key_layouts": [1001, 1002, 1004, 1011, 1013, 1014]}}


def _ucs_table():
    cols = {"key": "blob"}
    cols.update({c: "blob" for c in ("C0", "C1", "C2", "C3", "C4")})
    return make_table("keyspace1", "standard1", pk=["key"], cols=cols,
                      params=TableParams(
                          compression=CompressionParams("ZstdCompressor",
                                                        64 * 1024),
                          compaction=dict(UCS)))


def _hashes(cfs) -> dict:
    import hashlib
    out = {}
    for rank, r in enumerate(sorted(cfs.live_sstables(),
                                    key=lambda r: r.desc.generation)):
        for comp in COMPONENTS:
            with open(r.desc.path(comp), "rb") as f:
                out[f"{rank}:{comp}"] = hashlib.sha256(f.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def compacted(bench, tmp_path_factory):
    """Per engine: the strategy's task over the same four flushes, run
    with that engine; what it wrote, the spans and counters it left."""
    ref, rows = bench
    table = _ucs_table()
    base = tmp_path_factory.mktemp("ucs_sharded")
    landed = ColumnFamilyStore(table, str(base / "landed"), commitlog=None)
    runs = rows.load_backlog(landed, 5, _stress_cfg(2000))
    landed.reload_sstables()
    out = {"runs": runs, "landed": landed.directory, "table": table}
    for engine in ENGINES:
        cfs = rows.standalone_store(table, str(base / engine),
                                    landed.directory)
        picked = get_strategy(cfs).next_background_task()
        task = CompactionTask(cfs, picked.inputs, engine=engine,
                              level=picked.level,
                              shard_boundaries=picked.shard_boundaries)
        before = {c: METRICS.counter(c) for c in COUNTERS}
        last_id = max((r["id"] for r in pipeline_ledger.ring_records()),
                      default=0)
        stats = task.execute()
        recs = [r for r in pipeline_ledger.ring_records()
                if r["id"] > last_id]
        out[engine] = {
            "picked": picked, "stats": stats, "cfs": cfs, "task": task,
            "outputs": sorted(cfs.live_sstables(),
                              key=lambda r: r.desc.generation),
            "hashes": _hashes(cfs),
            "rolls": [r for r in recs if r["name"] ==
                      "compaction.shard.roll"],
            "cuts": [r for r in recs if r["name"] == "compaction.round.cut"],
            "packs": [r for r in recs if r["name"] == "merge.resident.pack"],
            "rose": {c: METRICS.counter(c) - before[c] for c in COUNTERS},
            "again": get_strategy(cfs).next_background_task()}
    yield out
    for engine in ENGINES:
        rows.close_store(out[engine]["cfs"])
    rows.close_store(landed)


def test_the_strategy_picks_the_four_flushes_and_plans_the_references_shards(
        bench, compacted):
    ref, _rows = bench
    opts = ref.Options(UCS)
    picked = compacted["numpy"]["picked"]
    spans = [(r.data_size,) + tuple(int(t) for t in (
        ref.tokens(keys).min(), ref.tokens(keys).max()))
        for r, (keys, _ts, _v) in zip(
            sorted(picked.inputs, key=lambda r: r.desc.generation),
            compacted["runs"])]
    assert ref.select(opts, spans) == [0, 1, 2, 3]
    plan = ref.plan(opts, spans)
    assert len(plan["boundaries"]) == 4 and plan["level"] == 1
    assert picked.shard_boundaries == plan["boundaries"]
    assert picked.level == plan["level"]
    assert picked.max_output_bytes is None


@pytest.mark.parametrize("engine", ENGINES)
def test_each_output_holds_only_its_shard_at_level_1_in_zstd(
        bench, compacted, engine):
    ref, _rows = bench
    got = compacted[engine]
    bounds = got["picked"].shard_boundaries
    outs = got["outputs"]
    assert got["stats"]["outputs"] == len(outs) == 4
    keys = np.concatenate([k for k, _ts, _v in compacted["runs"]])
    assert sum(r.n_partitions for r in outs) == len(keys)
    for shard, r in enumerate(outs):
        tok = ref.tokens(np.frombuffer(b"".join(r.partition_keys()),
                                       dtype=np.uint8).reshape(-1, 10))
        assert (ref.shard_of(tok, bounds) == shard).all()
        np.testing.assert_array_equal(tok, r.partition_tokens)
        assert r.level == 1
        assert ref.level(ref.Options(UCS), ref.density(
            r.data_size, int(tok.min()), int(tok.max()))) == 1
        assert r.params.compressor_name == "ZstdCompressor"
        assert r.params.chunk_length == 64 * 1024
    # four disjoint shards at level 1 never reach T4's four overlapping
    assert got["again"] is None


@pytest.mark.parametrize("engine", ENGINES)
def test_rolls_are_spans_and_counters(compacted, engine):
    got = compacted[engine]
    outs = got["outputs"]
    # a roll between each two shards: items = the finished shard, bytes =
    # its Data.db
    assert [r["items"] for r in got["rolls"]] == [0, 1, 2]
    assert [r["bytes"] for r in got["rolls"]] == \
        [r.data_size for r in outs[:3]]
    assert all(r["thread"] == "compact-w" for r in got["rolls"])
    capped = [r for r in got["cuts"] if r["items"] == 1]
    assert len(capped) == 3
    assert got["rose"] == {"compaction.shard_rolls": 3,
                           "compaction.rounds_cut_at_shard": 3}


def test_outputs_are_byte_identical_across_engines(compacted):
    want = compacted["numpy"]["hashes"]
    assert len(want) == 4 * len(COMPONENTS)
    for engine in ENGINES[1:]:
        assert compacted[engine]["hashes"] == want, engine


# the rounds the parent's round loop cut on the stress_stcs_lz4 inputs at
# 30,000 rows a run, seed 7 (key layout 1002), at each engine's own
# round size: a task without boundaries, or with one shard, cuts the same
TODAYS_PLAN = {"numpy": [259965, 261510, 78420, 85, 15, 5],
               "device": [521475, 78420, 85, 15, 5]}


@pytest.mark.parametrize("engine", sorted(TODAYS_PLAN))
def test_a_task_without_boundaries_keeps_todays_round_plan(bench, engine,
                                                           tmp_path):
    _ref, rows = bench
    cfg = _stress_cfg(30000)
    cfg["schema"].update(compression="LZ4Compressor", chunk_length_kib=16)
    table = rows.make_table(cfg)
    landed = rows.standalone_store(table, str(tmp_path / "landed"))
    rows.load_backlog(landed, 7, cfg)
    landed.reload_sstables()
    plans = []
    for label, kw in (("none", {}),
                      ("one", {"shard_boundaries": [-(1 << 63)]})):
        cfs = rows.standalone_store(table, str(tmp_path / label),
                                    landed.directory)
        last_id = max((r["id"] for r in pipeline_ledger.ring_records()),
                      default=0)
        CompactionTask(cfs, cfs.tracker.view(), engine=engine,
                       **kw).execute()
        plans.append([r["cells"] for r in pipeline_ledger.ring_records()
                      if r["id"] > last_id
                      and r["name"] == "compaction.round.cut"])
        assert len(cfs.live_sstables()) == 1
        rows.close_store(cfs)
    rows.close_store(landed)
    assert plans == [TODAYS_PLAN[engine]] * 2


def test_the_devices_partial_rounds_share_one_padded_shape(compacted):
    got = compacted["device"]
    task, packs = got["task"], got["packs"]
    cells = sum(r.n_cells for r in got["picked"].inputs)
    # every round is partial here (the whole compaction is under one full
    # round): each runs at the compaction's bucket, whatever its length
    assert task.min_bucket == _bucket(cells) < _bucket(task.round_cells)
    assert len(packs) >= 4
    assert {r["items"] for r in packs} == {task.min_bucket}
    assert len({r["cells"] for r in packs}) > 1


@pytest.mark.parametrize("engine", ["numpy", "device"])
def test_the_mesh_fan_out_writes_the_serial_loops_sstables(
        bench, compacted, engine, tmp_path):
    _ref, rows = bench
    picked = compacted["numpy"]["picked"]
    cfs = rows.standalone_store(compacted["table"], str(tmp_path / "mesh"),
                                compacted["landed"])
    task = CompactionTask(cfs, cfs.live_sstables(), engine=engine,
                          level=picked.level, mesh_devices=2,
                          shard_boundaries=picked.shard_boundaries)
    before = METRICS.counter("compaction.shard_rolls")
    stats = task.execute()
    assert "mesh_plan" in task.profile      # the fan-out ran
    assert stats["outputs"] == 4
    assert METRICS.counter("compaction.shard_rolls") - before == 3
    assert _hashes(cfs) == compacted["numpy"]["hashes"]
    rows.close_store(cfs)


def test_a_cursor_carries_only_the_partition_keys_it_holds(compacted):
    # one input as segments of 1,000 cells, each with its own keys as
    # the reader gives them; each round splits inside the last segment
    # buffered, so a tail of a joined buffer is left over
    whole = next(compacted["numpy"]["picked"].inputs[0].scanner())
    segs = [whole.slice_range(i, min(i + 1000, len(whole)))
            for i in range(0, len(whole), 1000)]
    for seg in segs:
        seg.pk_map = _own_pk_map(seg)
    cur = _Cursor(types.SimpleNamespace(scanner=lambda: iter(segs)))
    K, heads, joined = whole.n_lanes, 0, 0
    while cur.has_data:
        cur.fill_to(2500)
        joined += len(cur.bufs) > 1
        last = cur.bufs[-1]
        boundary = _full_key(last, len(last) // 2)[:16] + \
            b"\xff" * (4 * K - 16)
        head = cur.split_at(boundary)
        if head is None:
            head = cur.split_at(b"\xff" * (4 * K))
        heads += len(head)
        for b in (head, *cur.bufs):
            own = {bytes(k) for k in b.lanes[:, :4].astype(">u4")
                   .view("V16").ravel().tolist()}
            assert own <= set(b.pk_map)
        for b in cur.bufs:
            assert len(b.pk_map) <= 1000
    assert heads == len(whole) and joined > 3
