"""An engine pinned to one jax device (config `device_ordinal`, ROADMAP
B5): every device program of its compactions (`merge.resident`, the write
lane's `write.append` and `write.serialize`), its vector index and its
scan kernels runs on that device, seen in the arrays' `.devices()`, the
per-device counters, the tasks' own tallies and the program registry's
per-device counts; its sstables are byte-identical to an unpinned device
engine's and to the numpy engine's; an ordinal jax does not see fails the
start; three in-process nodes on devices 1, 2 and 3 each compact on their
own and leave device 0 alone.

tests/conftest.py gives the CPU backend 8 devices."""
import collections
import hashlib
import os
import time

import jax
import numpy as np
import pytest

from cassandra_tpu.compaction import task as task_mod
from cassandra_tpu.compaction.task import CompactionTask
from cassandra_tpu.config import Config, ConfigError, Settings
from cassandra_tpu.cql import Session
from cassandra_tpu.ops import device_scan as ds
from cassandra_tpu.ops import device_write as dwrite
from cassandra_tpu.ops import merge as dmerge
from cassandra_tpu.schema import Schema, make_table
from cassandra_tpu.service.metrics import GLOBAL as METRICS
from cassandra_tpu.service.profiling import GLOBAL as PROGRAMS
from cassandra_tpu.storage import cellbatch as cb
from cassandra_tpu.storage.engine import StorageEngine
from cassandra_tpu.storage.sstable import Descriptor, SSTableWriter
from cassandra_tpu.tools import bulk
from cassandra_tpu.utils import pipeline_ledger as pl

TABLE = ("CREATE TABLE t (k int, c int, v blob, PRIMARY KEY (k, c)) WITH "
         "compression = {'class': 'LZ4Compressor', 'chunk_length_in_kb': "
         "16} AND id = 5e1f0c4e-6b1e-5d7a-9a42-0c2b7c3d9e10")
PROGRAM_NAMES = ("merge.resident", "write.append", "write.serialize")


def _engine(path, ordinal=None):
    config = {} if ordinal is None else {"device_ordinal": ordinal}
    eng = StorageEngine(str(path), Schema(), commitlog_sync="batch",
                        settings=Settings(Config.load(config)))
    s = Session(eng)
    s.execute("CREATE KEYSPACE ks WITH replication = "
              "{'class': 'SimpleStrategy', 'replication_factor': 1}")
    s.execute("USE ks")
    return eng, s


def _land(cfs, n_ssts=4, n_per=70_000, seed=11):
    """Seeded sstables written straight into the store's directory, two
    segments each (so a compaction runs two rounds): every leg compacts
    the same bytes."""
    rng = np.random.default_rng(seed)
    for _ in range(n_ssts):
        pk = rng.integers(0, 300, n_per)
        ck = rng.integers(0, 50_000, n_per)
        vals = rng.integers(0, 256, (n_per, 24), dtype=np.uint8)
        ts = rng.integers(1, 1 << 40, n_per).astype(np.int64)
        w = SSTableWriter(Descriptor(cfs.directory, cfs.next_generation()),
                          cfs.table, estimated_partitions=300)
        w.append(cb.merge_sorted([bulk.build_int_batch(cfs.table, pk, ck,
                                                       vals, ts)]))
        w.finish()
    cfs.reload_sstables()


def _hashes(directory) -> dict:
    out = {}
    for fn in sorted(os.listdir(directory)):
        p = os.path.join(directory, fn)
        if os.path.isfile(p) and fn.endswith((".db", ".crc32")):
            with open(p, "rb") as f:
                out[fn] = hashlib.sha256(f.read()).hexdigest()
    return out


def _compact(path, engine: str, ordinal=None):
    """One compaction of the seeded inputs by a store of an engine
    (pinned to `ordinal`, or not): (component hashes, stats, engine's
    device)."""
    eng, s = _engine(path, ordinal)
    try:
        s.execute(TABLE)
        cfs = eng.store("ks", "t")
        _land(cfs)
        task = CompactionTask(cfs, cfs.live_sstables(), engine=engine,
                              round_cells=1 << 16)
        stats = task.execute()
        assert len(cfs.live_sstables()) == 1
        return _hashes(cfs.directory), stats, eng.device
    finally:
        eng.close()


def _spy(monkeypatch) -> dict:
    """{program: set of device ids} of every operand and result of the
    device programs the compaction dispatches."""
    seen = collections.defaultdict(set)

    def watch(name, fn):
        def call(*args):
            out = fn(*args)
            for leaf in jax.tree_util.tree_leaves((args, out)):
                if isinstance(leaf, jax.Array):
                    seen[name] |= {d.id for d in leaf.devices()}
            return out
        return call
    monkeypatch.setattr(dwrite, "_resident_program",
                        watch("merge.resident", dwrite._resident_program))
    monkeypatch.setattr(dwrite, "_lane_append",
                        watch("write.append", dwrite._lane_append))
    monkeypatch.setattr(dwrite, "_meta_block_kernel",
                        watch("write.serialize",
                              dwrite._meta_block_kernel))
    return seen


def _device_counters(prefix: str) -> dict:
    return {n: v for n, v in METRICS.snapshot().items()
            if n.startswith(prefix + ".dev")}


# ------------------------------------------------------------ one engine --

def test_a_pinned_engines_compaction_commits_every_program_to_its_device(
        tmp_path, monkeypatch):
    seen = _spy(monkeypatch)
    rounds0 = _device_counters("merge.resident.rounds")
    lane0 = _device_counters("write.lane.dispatches")
    before = PROGRAMS.snapshot()["kernels"]
    _h, stats, device = _compact(tmp_path / "pinned", "device", ordinal=3)
    assert device == jax.devices()[3]
    assert dict(seen) == {name: {3} for name in PROGRAM_NAMES}
    # the task's own tallies, the per-device counters, the registry
    rounds = stats["rounds_by_device"]
    lane = stats["lane_dispatches_by_device"]
    assert set(rounds) == {3} and rounds[3] >= 2
    assert set(lane) == {3} and lane[3] > rounds[3]
    rounds1 = _device_counters("merge.resident.rounds")
    lane1 = _device_counters("write.lane.dispatches")
    assert {n: v - rounds0.get(n, 0) for n, v in rounds1.items()
            if v != rounds0.get(n, 0)} == {"merge.resident.rounds.dev3":
                                           rounds[3]}
    assert {n: v - lane0.get(n, 0) for n, v in lane1.items()
            if v != lane0.get(n, 0)} == {"write.lane.dispatches.dev3":
                                         lane[3]}
    after = PROGRAMS.snapshot()["kernels"]
    for name in PROGRAM_NAMES:
        grew = {d: c["calls"] - before.get(name, {}).get(
                    "devices", {}).get(d, {}).get("calls", 0)
                for d, c in after[name]["devices"].items()}
        assert {d for d, n in grew.items() if n} == {3}, (name, grew)
    assert after["merge.resident"]["devices"][3]["calls"] \
        - before.get("merge.resident", {}).get("devices", {}).get(
            3, {}).get("calls", 0) == rounds[3]


def test_a_rounds_operands_go_straight_to_its_device():
    """Packed on the host and pushed once to the round's device: no
    detour through jax's default device."""
    rng = np.random.default_rng(2)
    table = make_table("devres", "ops", pk=["id"], ck=["c"],
                       cols={"id": "int", "c": "int", "v": "blob"})
    n = 3000
    batch = cb.merge_sorted([bulk.build_int_batch(
        table, rng.integers(0, 50, n), rng.integers(0, 1000, n),
        rng.integers(0, 256, (n, 8), dtype=np.uint8),
        rng.integers(1, 1 << 40, n).astype(np.int64))])
    host = dmerge.build_operands(batch)
    assert not any(isinstance(v, jax.Array) for v in host.values())
    operands, _ = dwrite.build_resident_operands(batch, 0, 0, None,
                                                 device=jax.devices()[5])
    assert {d.id for v in operands.values() for d in v.devices()} == {5}
    assert all(v.committed for v in operands.values())


def test_pinned_unpinned_and_numpy_engines_write_the_same_bytes(tmp_path):
    pinned, p_stats, _ = _compact(tmp_path / "pinned", "device", ordinal=5)
    unpinned, u_stats, device = _compact(tmp_path / "unpinned", "device")
    numpy_, n_stats, _ = _compact(tmp_path / "numpy", "numpy")
    assert device is None
    assert pinned and pinned == unpinned == numpy_
    assert set(p_stats["rounds_by_device"]) == {5}
    assert set(u_stats["rounds_by_device"]) == {jax.devices()[0].id}
    assert n_stats["rounds_by_device"] == {}
    assert n_stats["lane_dispatches_by_device"] == {}


def test_the_bind_is_one_span_and_the_default_binds_nothing(tmp_path,
                                                           monkeypatch):
    ring = collections.deque(maxlen=pl.RING_CAP)
    monkeypatch.setattr(pl, "RING", ring)
    eng, _ = _engine(tmp_path / "a", ordinal=6)
    eng.close()
    eng, _ = _engine(tmp_path / "b")
    eng.close()
    binds = [dict(zip(pl.RECORD_FIELDS, r)) for r in ring
             if r[0] == "engine.device.bind"]
    assert [b["items"] for b in binds] == [6]


@pytest.mark.parametrize("ordinal", [len(jax.devices()), 1 << 20])
def test_an_ordinal_jax_does_not_see_is_refused_at_start(tmp_path,
                                                         ordinal):
    with pytest.raises(ConfigError, match="device_ordinal"):
        StorageEngine(str(tmp_path), Schema(), commitlog_sync="batch",
                      settings=Settings(Config.load(
                          {"device_ordinal": ordinal})))


def test_the_ordinal_is_read_once_and_not_a_runtime_knob(tmp_path):
    eng, _ = _engine(tmp_path, ordinal=2)
    try:
        with pytest.raises(ConfigError, match="not mutable"):
            eng.settings.set("device_ordinal", 4)
        assert eng.device == jax.devices()[2]
    finally:
        eng.close()


# ----------------------------------------------------- index and scans --

def _vec(v) -> str:
    return str([float(x) for x in v])


def test_the_vector_index_lives_on_the_pinned_device(tmp_path):
    eng, s = _engine(tmp_path, ordinal=4)
    try:
        s.execute("CREATE TABLE vt (id bigint PRIMARY KEY, "
                  "v vector<float, 8>)")
        s.execute("CREATE CUSTOM INDEX ON vt (v) USING "
                  "'StorageAttachedIndex'")
        rng = np.random.default_rng(3)
        rows = rng.normal(size=(64, 8)).astype(np.float32)
        for i, r in enumerate(rows):
            s.execute(f"INSERT INTO vt (id, v) VALUES ({i}, {_vec(r)})")
        eng.store("ks", "vt").flush()
        query = f"SELECT id FROM vt ORDER BY v ANN OF {_vec(rows[7])} " \
                f"LIMIT 3"
        assert s.execute(query).rows[0] == (7,)
        (idx,) = [i for i in eng.indexes.indexes.values()
                  if hasattr(i, "ann")]
        dev = jax.devices()[4]
        assert idx._entry.base.devices() == {dev}
        # a write: the delta and the validity vectors, on the same device
        s.execute(f"INSERT INTO vt (id, v) VALUES (99, {_vec(rows[7])})")
        s.execute("DELETE FROM vt WHERE id = 7")
        assert s.execute(query).rows[0] == (99,)
        e = idx._entry
        for arr in (e.base, e.delta, e.vb, e.vd):
            assert arr.devices() == {dev}
        for name in ("index.ann", "index.ann.live", "index.ann.delta_put",
                     "index.ann.unset"):
            devices = PROGRAMS.snapshot()["kernels"][name]["devices"]
            assert dev.id in devices, (name, devices)
    finally:
        eng.close()


def test_the_scan_kernels_run_on_the_pinned_device(tmp_path):
    eng, s = _engine(tmp_path, ordinal=7)
    try:
        s.execute("CREATE TABLE ag (k int PRIMARY KEY, v int)")
        for i in range(100):
            s.execute(f"INSERT INTO ag (k, v) VALUES ({i}, {i % 10})")
        eng.store("ks", "ag").flush()
        before = PROGRAMS.snapshot()["kernels"]
        assert s.execute("SELECT count(v), min(v), max(v) FROM ag WHERE "
                         "v = 3 ALLOW FILTERING").rows == [(10, 3, 3)]
        after = PROGRAMS.snapshot()["kernels"]
        for name in ("scan.mask_eq", "scan.fold"):
            old = before.get(name, {}).get("devices", {})
            grew = {d for d, c in after[name]["devices"].items()
                    if c["calls"] > old.get(d, {}).get("calls", 0)}
            assert grew == {7}, (name, grew)
    finally:
        eng.close()


def test_segment_mask_on_a_device_equals_the_host_reference():
    keys = np.arange(0, 1 << 20, 997, dtype=np.uint64) << np.uint64(20)
    pred = ds.CompiledPredicate(3, "v", "u64", 8, ">", "gt",
                                np.array([keys[40]], np.uint64), True, False)
    for device in (None, jax.devices()[2]):
        mask, on_dev = ds.segment_mask(keys, pred, True, device)
        assert on_dev and (mask == ds.mask_host(keys, pred)).all()


# ------------------------------------------------------------- registry --

def test_the_registry_counts_calls_and_compiles_per_device():
    from cassandra_tpu.service.profiling import DeviceProgramRegistry
    reg = DeviceProgramRegistry()
    assert reg.record_dispatch("k", ("s", 1), 0.5, 1)
    assert not reg.record_dispatch("k", ("s", 1), 0.1, 1)
    assert reg.record_dispatch("k", ("s", 2), 0.5, 2)
    reg.record_dispatch("k", ("s", None), 0.5)
    snap = reg.snapshot()["kernels"]["k"]
    assert snap["calls"] == 4 and snap["compiles"] == 3
    assert snap["devices"] == {1: {"calls": 2, "compiles": 1},
                               2: {"calls": 1, "compiles": 1},
                               jax.devices()[0].id: {"calls": 1,
                                                     "compiles": 1}}


def test_a_wrapped_program_is_counted_on_the_device_of_its_operands():
    from cassandra_tpu.service.profiling import DeviceProgramRegistry
    reg = DeviceProgramRegistry()
    fn = reg.wrap("double", jax.jit(lambda x: x * 2), cost=False)
    x = np.arange(8, dtype=np.float32)
    fn(x)
    fn(jax.device_put(x, jax.devices()[6]))
    fn(jax.device_put(x, jax.devices()[6]))
    snap = reg.snapshot()["kernels"]["double"]
    # the same shape on a second device is a compile of its own
    assert snap["compiles"] == 2 and snap["shape_count"] == 2
    assert snap["devices"] == {jax.devices()[0].id: {"calls": 1,
                                                     "compiles": 1},
                               6: {"calls": 2, "compiles": 1}}


# ------------------------------------------------- three pinned nodes --

def test_three_nodes_on_devices_1_2_3_compact_each_on_its_own(
        tmp_path, monkeypatch):
    from rf3_cluster import KEYSPACE, Ring3, table_ddl
    monkeypatch.setattr(task_mod, "tpu_backend", lambda: True)
    monkeypatch.setattr(CompactionTask, "DEVICE_MIN_CELLS", 1)
    seen = _spy(monkeypatch)
    rounds0 = _device_counters("merge.resident.rounds")
    ring = Ring3(tmp_path, [table_ddl(
        "t", "k int, c int, v text, PRIMARY KEY (k, c)")],
        devices=[1, 2, 3])
    try:
        assert [n.engine.device for n in ring.nodes] == jax.devices()[1:4]
        stores = []
        for node in ring.nodes:
            node.engine.compactions.paused = True   # disableautocompaction
            s = Session(node.engine)                # each replica's own
            s.execute(f"USE {KEYSPACE}")
            cfs = node.engine.store(KEYSPACE, "t")
            for f in range(4):                      # one STCS bucket
                for k in range(40):
                    s.execute(f"INSERT INTO t (k, c, v) VALUES "
                              f"({k}, {f}, 'v{k}.{f}') USING TIMESTAMP "
                              f"{1000 + f}")
                cfs.flush()
            stores.append(cfs)
        for node, cfs in zip(ring.nodes, stores):
            node.engine.compactions.paused = False  # enableautocompaction
            node.engine.compactions.submit_background(cfs)
        end = time.monotonic() + 60
        while time.monotonic() < end and any(
                len(cfs.live_sstables()) > 1 or node.engine.compactions.active
                for node, cfs in zip(ring.nodes, stores)):
            time.sleep(0.05)
        for i, cfs in enumerate(stores):
            (stats,) = [h for h in cfs.compaction_history if h.get("engine")]
            assert stats["engine"] == "device"
            assert set(stats["rounds_by_device"]) == {i + 1}, stats
            assert set(stats["lane_dispatches_by_device"]) == {i + 1}
            assert len(cfs.live_sstables()) == 1
        assert set().union(*seen.values()) == {1, 2, 3}
        rounds1 = _device_counters("merge.resident.rounds")
        assert rounds1.get("merge.resident.rounds.dev0", 0) \
            == rounds0.get("merge.resident.rounds.dev0", 0)
        hashes = [sorted(_hashes(cfs.directory).values()) for cfs in stores]
        assert hashes[0] == hashes[1] == hashes[2]
    finally:
        ring.close()
