"""Mesh execution mode of the data plane (docs/multichip.md): mesh
compaction byte-identity vs the serial path (host engines, and the
device engine's lanes up to eight jax devices), the host tie-break on a
lane, adversarial shard completion orders, an all-or-nothing round
when a shard's writer fails, corrupt-input quarantine under mesh mode,
boundary-planning balance on landed and skewed inputs, mesh batched
reads / range scans, knob hot-reload, and sim determinism."""
import importlib.util
import os
import time

import jax
import numpy as np
import pytest

from cassandra_tpu.compaction.task import CompactionTask
from cassandra_tpu.parallel import fanout
from cassandra_tpu.parallel.boundaries import (boundaries_from_indexes,
                                               boundaries_to_ranges,
                                               plan_token_boundaries,
                                               shard_imbalance)
from cassandra_tpu.schema import COL_REGULAR_BASE, make_table
from cassandra_tpu.storage import cellbatch as cb
from cassandra_tpu.storage.cellbatch import content_digest
from cassandra_tpu.storage.sstable import Descriptor, SSTableWriter
from cassandra_tpu.storage.table import ColumnFamilyStore
from cassandra_tpu.utils import faultfs

_AB = None


def _ab():
    """scripts/check_compaction_ab.py loaded once: the mesh tests reuse
    its fixture builder and component-hash machinery so the identity
    argument tested here is the same one CI pins."""
    global _AB
    if _AB is None:
        spec = importlib.util.spec_from_file_location(
            "check_compaction_ab",
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "scripts",
                "check_compaction_ab.py"))
        _AB = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(_AB)
    return _AB


@pytest.fixture(autouse=True)
def _mesh_off_after():
    yield
    fanout.reset()   # drops engine-owned demands too, not just ours
    fanout._TEST_SHARD_DELAY = None
    faultfs.disarm()


# ------------------------------------------------- boundary planning --

def test_plan_boundaries_balances_skewed_weights():
    """A hot token carrying 30% of the weight must not starve its
    neighbours: remaining shards re-balance around it and max/mean
    stays bounded by the hot token itself."""
    rng = np.random.default_rng(5)
    toks = np.sort(rng.choice(np.arange(10_000, dtype=np.uint64) * 7919,
                              4_000, replace=False))
    w = np.ones(len(toks), dtype=np.int64)
    w[123] = int(0.3 / 0.7 * len(toks))   # one token = 30% of total
    bounds = plan_token_boundaries(toks, w, 8)
    assert len(bounds) == 7
    sizes = np.zeros(8, dtype=np.int64)
    shard = np.searchsorted(bounds, toks, side="left")
    np.add.at(sizes, shard, w)
    # the hot token is unsplittable: its shard IS the max; everyone
    # else balances
    others = np.delete(sizes, int(shard[123]))
    assert shard_imbalance(others) <= 1.2, sizes.tolist()
    assert sizes.min() > 0


def test_mesh_really_has_8_devices():
    assert len(jax.devices()) >= 8, jax.devices()
    assert jax.default_backend() == "cpu"


T = make_table("ks", "t", pk=["id"], ck=["c"],
               cols={"id": "int", "c": "int", "v": "text"})
IDT = T.columns["id"].cql_type


def _workload(n_parts=40, n_cks=5, gens=3):
    """One batch a generation: every (partition, row) written again in
    each, with a newer timestamp."""
    batches = []
    for g in range(gens):
        b = cb.CellBatchBuilder(T)
        for p in range(n_parts):
            for c in range(n_cks):
                b.add_cell(IDT.serialize(p), T.serialize_clustering([c]),
                           COL_REGULAR_BASE, f"g{g}".encode(), 100 + g)
        batches.append(b.seal())
    return batches


def _skewed_workload(n=60_000, seed=9):
    """~40% of the cells in 2 hot partitions, the rest over 2,046."""
    from cassandra_tpu.tools import bulk
    rng = np.random.default_rng(seed)
    hot = rng.random(n) < 0.4
    pk = np.where(hot, rng.integers(0, 2, n), rng.integers(2, 2048, n))
    return [bulk.build_int_batch(
        T, pk, rng.integers(0, 10_000, n),
        np.full((n, 1), ord("v"), dtype=np.uint8),
        np.full(n, 100, dtype=np.int64))]


def _land(cfs, batches, table=T):
    """Each batch as one sstable of the store, generations 1, 2, ..."""
    for gen, batch in enumerate(batches, start=1):
        w = SSTableWriter(Descriptor(cfs.directory, gen), table,
                          estimated_partitions=256)
        w.append(cb.merge_sorted([batch]))
        w.finish()
    cfs.reload_sstables()
    return cfs.tracker.view()


@pytest.mark.parametrize("workload,max_imbalance,min_share", [
    pytest.param(lambda: _workload(n_parts=100, n_cks=3, gens=2),
                 None, None, id="no_token_split"),
    pytest.param(lambda: _workload(n_parts=200, n_cks=4, gens=1),
                 3.0, None, id="uniform_balance"),
    # the hot partitions are unsplittable (~20% of the cells each, 1.6x
    # the 1/8 mean), so max/mean ~1.6 is the floor: the shards around
    # them re-balance instead of starving
    pytest.param(_skewed_workload, 2.0, 1 / 3, id="skewed_balance"),
])
def test_index_boundaries_on_landed_workloads(tmp_path, workload,
                                              max_imbalance, min_share):
    """The mesh lanes' planner over landed sstables: every token's
    cells fall in exactly one shard's range, the ranges cover every
    cell, and the shards' input cells stay within the case's spread."""
    cfs = ColumnFamilyStore(T, str(tmp_path), commitlog=None)
    readers = _land(cfs, workload())
    bounds = boundaries_from_indexes(readers, 8)
    assert bounds is not None and len(bounds) == 7
    counts, owner = [], {}
    for s, (lo, hi) in enumerate(boundaries_to_ranges(bounds, 8)):
        n = 0
        for r in readers:
            w = r.scan_tokens(lo, hi)
            if w is None or not len(w):
                continue
            n += len(w)
            tok = (w.lanes[:, 0].astype(np.uint64) << np.uint64(32)) \
                | w.lanes[:, 1].astype(np.uint64)
            for t in np.unique(tok).tolist():
                assert owner.setdefault(t, s) == s, (t, owner[t], s)
        counts.append(n)
    assert sum(counts) == sum(r.n_cells for r in readers)
    if max_imbalance is not None:
        assert shard_imbalance(counts) <= max_imbalance, counts
    if min_share is not None:
        assert min(counts) >= min_share * np.mean(counts), counts


def test_boundaries_from_indexes_skewed_fixture(tmp_path):
    """Planning from the input sstables' partition directories must hold
    the skewed fixture's per-shard INPUT spread at max/mean <= 1.2 —
    the MULTICHIP_r05 skew (21x kept-cell spread) this PR fixes."""
    table = _ab()._mk_table("skew")
    cfs = ColumnFamilyStore(table, str(tmp_path), commitlog=None)
    rng = np.random.default_rng(3)
    from cassandra_tpu.tools import bulk
    for gen in (1, 2):
        n = 60_000
        hot = rng.random(n) < 0.4
        pk = np.where(hot, rng.integers(0, 2, n),
                      rng.integers(2, 2048, n))
        batch = cb.merge_sorted([bulk.build_int_batch(
            table, pk, rng.integers(1, 10_000, n),
            rng.integers(97, 122, (n, 16), dtype=np.uint8),
            rng.integers(1, 1 << 40, n).astype(np.int64))])
        w = SSTableWriter(Descriptor(cfs.directory, gen), table,
                          estimated_partitions=2048)
        w.append(batch)
        w.finish()
    cfs.reload_sstables()
    readers = cfs.tracker.view()
    bounds = boundaries_from_indexes(readers, 8)
    assert bounds is not None and len(bounds) == 7
    ranges = boundaries_to_ranges(bounds, 8)
    sizes = []       # post-merge (kept) cells per shard — the spread
    total_in = 0     # the planner's distinct weighting balances
    for lo, hi in ranges:
        slices = [w for r in readers
                  if (w := r.scan_tokens(lo, hi)) is not None and len(w)]
        total_in += sum(len(w) for w in slices)
        sizes.append(len(cb.merge_sorted(slices)) if slices else 0)
    assert total_in == sum(r.n_cells for r in readers)
    # index counts can't see CROSS-input duplicate collapse (they
    # max-combine per-sstable distinct counts), so the kept-cell spread
    # floor on this adversarial fixture is ~1.35 — still 15x better
    # than the 21x the single-batch sample produced (MULTICHIP_r05).
    # The input-cell spread of the lanes' own shards is pinned on the
    # skewed 1M-cell round by the multichip entry
    # (__graft_entry__._dryrun_inner).
    assert shard_imbalance(sizes) <= 1.5, sizes


# ------------------------------------------------ compaction identity --

@pytest.mark.parametrize("legs", [
    {"serial": dict(mesh_devices=0),
     "mesh1": dict(mesh_devices=1),
     "mesh4": dict(mesh_devices=4)},
    # the device engine's lanes: the serial loop's program
    # (merge.resident), one jax device per lane, against the numpy spec
    {"serial": dict(engine="numpy", mesh_devices=0),
     "device_mesh2": dict(engine="device", mesh_devices=2)},
    # a lane on each of eight jax devices
    {"serial": dict(engine="numpy", mesh_devices=0),
     "device_mesh8": dict(engine="device", mesh_devices=8)},
], ids=["host", "device", "device8"])
def test_mesh_compaction_byte_identity(tmp_path, legs):
    """serial vs mesh-1 vs mesh-4: sha256-identical components and
    equal merged-view digests — the mesh drains shard results in token
    order through the same writer, so bytes cannot depend on the lane
    count."""
    ab = _ab()
    table = ab._mk_table("meshid")
    pristine = os.path.join(str(tmp_path), "pristine")
    cfs = ColumnFamilyStore(table, pristine, commitlog=None)
    for gen in (1, 2, 3):
        w = SSTableWriter(Descriptor(cfs.directory, gen), table,
                          estimated_partitions=256)
        w.append(ab._mixed_batch(table, seed=gen, n=60_000))
        w.finish()
    results = {tag: ab._compaction_leg(str(tmp_path), pristine, table,
                                       tag, **kw)
               for tag, kw in legs.items()}
    ref_hashes, ref_digest = results["serial"]
    assert ref_hashes
    for tag, (hashes, digest) in results.items():
        assert hashes == ref_hashes, (tag, sorted(
            k for k in hashes if hashes[k] != ref_hashes.get(k)))
        assert digest == ref_digest, tag


def test_equal_ts_tombstone_wins_on_a_device_lane(tmp_path,
                                                  monkeypatch):
    """The device sort does not order a live cell and a tombstone of
    the same identity and timestamp: the lane's round resolves the tie
    on the host (materialize_round), and the tombstone wins, as the
    serial numpy engine has it."""
    ab = _ab()
    table = ab._mk_table("meshtie")
    vcol = table.columns["v"].column_id
    pk = table.serialize_partition_key([7])
    ck = table.serialize_clustering([1])
    b = cb.CellBatchBuilder(table)
    b.add_cell(pk, ck, vcol, b"live", 100)
    t = cb.CellBatchBuilder(table)
    # deleted now: inside gc_grace, so the winner is kept, not purged
    t.add_tombstone(pk, ck, vcol, 100, int(time.time()))
    tomb = t.seal()
    pristine = os.path.join(str(tmp_path), "pristine")
    cfs = ColumnFamilyStore(table, pristine, commitlog=None)
    _land(cfs, [cb.CellBatch.concat(
        [ab._mixed_batch(table, seed=1, n=20_000), b.seal()]),
        ab._mixed_batch(table, seed=2, n=20_000), tomb], table)
    ref_hashes, ref_digest = ab._compaction_leg(
        str(tmp_path), pristine, table, "serial", engine="numpy",
        mesh_devices=0)

    leg = os.path.join(str(tmp_path), "lanes")
    import shutil
    shutil.copytree(pristine, leg)
    cfs2 = ColumnFamilyStore(table, leg, commitlog=None)
    cfs2.reload_sstables()
    from cassandra_tpu.ops import merge as dmerge
    ties = []
    real_tiebreak = dmerge.host_tiebreak

    def tiebreak(cat, perm, keep, amb, *args):
        ties.append(int(amb.sum()))
        return real_tiebreak(cat, perm, keep, amb, *args)

    monkeypatch.setattr(dmerge, "host_tiebreak", tiebreak)
    task = CompactionTask(cfs2, cfs2.tracker.view(), engine="device",
                          mesh_devices=4)
    task.execute()
    assert len({d.id for d in task.mesh_lane_devices}) == 4
    assert ties == [1]      # one lane's round held the one tie
    assert ab._component_hashes(cfs2.directory) == ref_hashes
    assert ab._scan_digest(cfs2) == ref_digest
    [out] = cfs2.live_sstables()
    got = cb.CellBatch.concat(list(out.scanner()))
    key = np.flatnonzero((got.lanes == tomb.lanes[0]).all(axis=1))
    assert len(key) == 1
    assert got.flags[key[0]] & cb.FLAG_TOMBSTONE, "live cell beat tombstone"
    out.close()


def test_failed_shard_write_leaves_no_partial_round(tmp_path, monkeypatch):
    """A sharded task's third output shard fails to finish mid-compaction
    under four mesh lanes: the round is all-or-nothing
    (LifecycleTransaction) — no output sstable survives, not even the
    shards finished before it, and every input stays live."""
    from cassandra_tpu.storage.sstable import writer as writer_mod

    ab = _ab()
    table = ab._mk_table("meshfail")
    cfs = ColumnFamilyStore(table, str(tmp_path), commitlog=None)
    inputs = _land(cfs, [ab._mixed_batch(table, seed=g, n=20_000)
                         for g in (1, 2)], table)
    before = sorted(os.listdir(cfs.directory))
    digest = ab._scan_digest(cfs)
    calls = {"n": 0}
    real_finish = writer_mod.SSTableWriter.finish

    def failing_finish(self):
        calls["n"] += 1
        if calls["n"] == 3:          # the third shard's commit blows up
            raise OSError("injected shard write failure")
        return real_finish(self)

    monkeypatch.setattr(writer_mod.SSTableWriter, "finish", failing_finish)
    ring = [-(1 << 63) + i * (1 << 62) for i in range(4)]
    task = CompactionTask(cfs, inputs, mesh_devices=4,
                          shard_boundaries=ring)
    with pytest.raises(OSError, match="injected"):
        task.execute()
    assert calls["n"] == 3 and task._mesh_completion_order
    assert sorted(os.listdir(cfs.directory)) == before
    assert sorted(r.desc.generation for r in cfs.live_sstables()) == [1, 2]
    assert ab._scan_digest(cfs) == digest


def test_mesh_adversarial_completion_order(tmp_path):
    """Shards finishing in REVERSE order must not reorder output bytes:
    the drain walks shard 0..n-1 regardless of completion order."""
    ab = _ab()
    table = ab._mk_table("meshadv")
    pristine = os.path.join(str(tmp_path), "pristine")
    cfs = ColumnFamilyStore(table, pristine, commitlog=None)
    for gen in (1, 2):
        w = SSTableWriter(Descriptor(cfs.directory, gen), table,
                          estimated_partitions=256)
        w.append(ab._mixed_batch(table, seed=gen, n=40_000))
        w.finish()
    ref_hashes, ref_digest = ab._compaction_leg(
        str(tmp_path), pristine, table, "ref", mesh_devices=0)

    # make later shards finish FIRST (reverse completion)
    fanout._TEST_SHARD_DELAY = {0: 0.3, 1: 0.2, 2: 0.1, 3: 0.0}
    leg = os.path.join(str(tmp_path), "adv")
    import shutil
    shutil.copytree(pristine, leg)
    cfs2 = ColumnFamilyStore(table, leg, commitlog=None)
    cfs2.reload_sstables()
    task = CompactionTask(cfs2, cfs2.tracker.view(), mesh_devices=4)
    task.execute()
    fanout._TEST_SHARD_DELAY = None
    order = task._mesh_completion_order
    assert order != sorted(order), order   # the delays really inverted it
    assert ab._component_hashes(cfs2.directory) == ref_hashes
    assert ab._scan_digest(cfs2) == ref_digest
    for r in cfs2.live_sstables():
        r.close()


def test_mesh_compaction_purge_identity(tmp_path):
    """Tombstone/TTL purging interacts with sharding through gc_before
    and the purge gate: a mesh compaction that PURGES (deletions at
    every scope past gc_grace, expired TTLs) must still produce
    sha256-identical components to serial."""
    from cassandra_tpu.schema import COL_ROW_LIVENESS
    from cassandra_tpu.storage.cellbatch import (FLAG_ROW_LIVENESS,
                                                 CellBatchBuilder)

    ab = _ab()
    table = ab._mk_table("meshpurge")
    table.params.gc_grace_seconds = 0   # everything purgeable at once
    pristine = os.path.join(str(tmp_path), "pristine")
    cfs = ColumnFamilyStore(table, pristine, commitlog=None)
    vcol = table.columns["v"].column_id
    rng = np.random.default_rng(4)
    old = 1_600_000_000
    for gen in (1, 2, 3):
        b = CellBatchBuilder(table)
        ts0 = gen * 1_000_000
        for p in range(192):
            pk = table.serialize_partition_key([p])
            if p % 9 == 0 and gen == 2:
                b.add_partition_deletion(pk, ts0 + 900_000, ldt=old)
            for c in range(40):
                ck = table.serialize_clustering([c])
                if p % 4 == 0 and c % 5 == 0 and gen == 3:
                    b.add_row_deletion(pk, ck, ts0 + c + 50, ldt=old)
                elif p % 6 == 0 and gen == 1:
                    b.add_tombstone(pk, ck, vcol, ts0 + c, ldt=old)
                else:
                    b.add_row_liveness(pk, ck, ts0 + c)
                    b.add_cell(pk, ck, vcol,
                               rng.integers(0, 256, 32,
                                            dtype=np.uint8).tobytes(),
                               ts0 + c,
                               ttl=(60 if p % 10 == 0 else 0))
        w = SSTableWriter(Descriptor(cfs.directory, gen), table,
                          estimated_partitions=192)
        w.append(cb.merge_sorted([b.seal()]))
        w.finish()
    ref_hashes, ref_digest = ab._compaction_leg(
        str(tmp_path), pristine, table, "serial", mesh_devices=0)
    mesh_hashes, mesh_digest = ab._compaction_leg(
        str(tmp_path), pristine, table, "mesh", mesh_devices=4)
    assert ref_hashes and mesh_hashes == ref_hashes
    assert mesh_digest == ref_digest


def test_mesh_corrupt_input_quarantine(tmp_path):
    """PR 5 semantics survive mesh mode: a corrupt input aborts ONLY
    the task, the bad sstable is quarantined, and the manager re-plans
    without it in the same submission."""
    import sys
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_fault_tolerance import new_engine, pk_of, seeded

    eng, t = new_engine(tmp_path)
    cfs = seeded(eng, t, rounds=5)
    gens = [s.desc.generation for s in cfs.live_sstables()]
    bad = gens[1]
    fanout.configure(4)
    faultfs.arm("sstable.read", "bitflip", path_substr=f"-{bad}-Data.db")
    eng.compactions.submit_background(cfs)
    n = eng.compactions.run_pending()
    faultfs.disarm()
    assert [q["generation"] for q in cfs.quarantined] == [bad]
    assert bad not in [s.desc.generation for s in cfs.live_sstables()]
    assert n >= 1
    assert len(cfs.read_partition(pk_of(t, 3))) > 0
    eng.close()


def test_mesh_deterministic_under_sim(tmp_path):
    """Same seed, mesh-4 compaction under the sim scheduler: identical
    sstable digests across runs — lane scheduling cannot leak into
    bytes (keeps the mesh leg simulable)."""
    from cassandra_tpu.sim.scheduler import simulated

    ab = _ab()
    table = ab._mk_table("meshsim")

    def run(tag):
        with simulated(99):
            cfs = ColumnFamilyStore(table, str(tmp_path / tag),
                                    commitlog=None)
            for gen in (1, 2):
                w = SSTableWriter(Descriptor(cfs.directory, gen), table)
                w.append(ab._mixed_batch(table, seed=gen, n=30_000))
                w.finish()
            cfs.reload_sstables()
            CompactionTask(cfs, cfs.tracker.view(), mesh_devices=3,
                           round_cells=8192).execute()
            [s] = cfs.live_sstables()
            with open(s.desc.path("Digest.crc32")) as f:
                return f.read().strip()

    assert run("a") == run("b")


# -------------------------------------------------------- read routes --

def _read_fixture(tmp_path, n=30_000):
    table = _ab()._mk_table("meshread")
    cfs = ColumnFamilyStore(table, str(tmp_path), commitlog=None)
    _land(cfs, [_ab()._mixed_batch(table, seed=g, n=n) for g in (1, 2, 3)],
          table)
    return cfs, table


NOW = 1_700_000_000


def test_mesh_batched_reads_identical(tmp_path):
    cfs, table = _read_fixture(tmp_path)
    pks = [table.serialize_partition_key([k]) for k in range(0, 256, 2)]
    fanout.configure(0)
    ref = cfs.read_partitions(pks, now=NOW)
    fanout.configure(4)
    got = cfs.read_partitions(pks, now=NOW)
    assert len(ref) == len(got)
    for (pa, a), (pb, b) in zip(ref, got):
        assert pa == pb
        assert content_digest(a) == content_digest(b)


def test_mesh_batched_reads_small_batch_stays_serial(tmp_path):
    """Batches under MESH_READ_MIN_KEYS must not pay fan-out overhead:
    the mesh counters stay untouched."""
    from cassandra_tpu.service.metrics import GLOBAL
    cfs, table = _read_fixture(tmp_path, n=10_000)
    fanout.configure(4)
    before = GLOBAL.counter("mesh.batch_reads")
    pks = [table.serialize_partition_key([k]) for k in range(8)]
    cfs.read_partitions(pks, now=NOW)
    assert GLOBAL.counter("mesh.batch_reads") == before


def test_mesh_scan_all_identical(tmp_path):
    cfs, table = _read_fixture(tmp_path)
    fanout.configure(0)
    ref = cfs.scan_all(now=NOW)
    fanout.configure(4)
    got = cfs.scan_all(now=NOW)
    assert len(ref) == len(got)
    np.testing.assert_array_equal(ref.lanes, got.lanes)
    np.testing.assert_array_equal(ref.ts, got.ts)
    np.testing.assert_array_equal(ref.payload, got.payload)


def test_mesh_batched_reads_deletion_heavy_identity(tmp_path):
    """The shard-merge formulation (_shard_merge_slices: one merge per
    shard, sliced per partition) must survive deletions at every scope
    — partition deletions, row deletions, cell tombstones, TTL — with
    results identical to the per-key serial merges, including keys the
    merge fully purges and keys that don't exist."""
    from cassandra_tpu.schema import COL_ROW_LIVENESS
    from cassandra_tpu.storage.cellbatch import (FLAG_ROW_LIVENESS,
                                                 CellBatchBuilder)

    table = _ab()._mk_table("meshdel")
    cfs = ColumnFamilyStore(table, str(tmp_path), commitlog=None)
    vcol = table.columns["v"].column_id
    rng = np.random.default_rng(9)
    for gen in (1, 2, 3):
        b = CellBatchBuilder(table)
        ts0 = gen * 1_000_000
        for p in range(256):
            pk = table.serialize_partition_key([p])
            if p % 7 == 0 and gen == 2:
                b.add_partition_deletion(pk, ts0 + 500_000, ldt=NOW - 10)
            for c in range(12):
                ck = table.serialize_clustering([c])
                ts = ts0 + c
                if p % 5 == 0 and c % 3 == 0 and gen == 3:
                    b.add_row_deletion(pk, ck, ts + 10, ldt=NOW - 10)
                elif p % 11 == 0 and gen == 1:
                    b.add_tombstone(pk, ck, vcol, ts + 5, ldt=NOW - 10)
                else:
                    b.add_row_liveness(pk, ck, ts)
                    b.add_cell(pk, ck, vcol,
                               rng.integers(0, 256, 24,
                                            dtype=np.uint8).tobytes(),
                               ts, ttl=(600 if p % 13 == 0 else 0))
        w = SSTableWriter(Descriptor(cfs.directory, gen), table,
                          estimated_partitions=256)
        w.append(cb.merge_sorted([b.seal()], now=NOW))
        w.finish()
    cfs.reload_sstables()
    # include keys that don't exist (negative lookups must stay empty)
    pks = [table.serialize_partition_key([p]) for p in range(300)]
    fanout.configure(0)
    ref = cfs.read_partitions(pks, now=NOW)
    fanout.configure(4)
    got = cfs.read_partitions(pks, now=NOW)
    for (pa, a), (pb, b_) in zip(ref, got):
        assert pa == pb
        assert len(a) == len(b_), pa
        assert content_digest(a) == content_digest(b_), pa


def test_mesh_reads_cover_memtable(tmp_path):
    """The mesh scan/read routes go through scan_window/_batched_merge,
    both of which consult the memtable — unflushed writes must appear."""
    from cassandra_tpu.schema import COL_ROW_LIVENESS
    from cassandra_tpu.storage.cellbatch import FLAG_ROW_LIVENESS
    from cassandra_tpu.storage.mutation import Mutation

    cfs, table = _read_fixture(tmp_path, n=10_000)
    pk = table.serialize_partition_key([7])
    m = Mutation(table.id, pk)
    m.add(table.serialize_clustering([999_999]), COL_ROW_LIVENESS,
          b"", b"", 1 << 50, flags=FLAG_ROW_LIVENESS)
    cfs.apply(m)
    fanout.configure(0)
    ref = cfs.read_partitions([pk] * 1 + [
        table.serialize_partition_key([k]) for k in range(32)], now=NOW)
    ref_scan = cfs.scan_all(now=NOW)
    fanout.configure(4)
    got = cfs.read_partitions([pk] * 1 + [
        table.serialize_partition_key([k]) for k in range(32)], now=NOW)
    got_scan = cfs.scan_all(now=NOW)
    assert content_digest(ref[0][1]) == content_digest(got[0][1])
    assert content_digest(ref_scan) == content_digest(got_scan)
    assert len(got_scan) == len(ref_scan)


# ----------------------------------------------------- fanout + knob --

def test_fanout_preserves_shard_order_under_delay():
    fanout.configure(3)
    fan = fanout.get_fanout()
    fanout._TEST_SHARD_DELAY = {0: 0.2, 1: 0.1}
    out = fan.map_shards(lambda s: s * 10, 6)
    fanout._TEST_SHARD_DELAY = None
    assert out == [0, 10, 20, 30, 40, 50]


def test_fanout_propagates_errors():
    fanout.configure(2)
    fan = fanout.get_fanout()

    def boom(s):
        if s == 3:
            raise ValueError("shard 3 failed")
        return s

    with pytest.raises(ValueError, match="shard 3"):
        fan.map_shards(boom, 5)
    # the fanout survives for the next caller
    assert fan.map_shards(lambda s: s, 4) == [0, 1, 2, 3]


def test_fanout_knob_off_releases_queued_closures():
    """set_workers(0) drains the job queue: the last map_shards call's
    pull closures (which pin every shard result) must not stay
    referenced for the life of the process once the knob turns off."""
    fanout.configure(1)
    fan = fanout.get_fanout()
    assert fan.map_shards(lambda s: s, 8) == list(range(8))
    fanout.configure(0)
    assert fan.queue_depth() == 0


def test_mesh_knob_hot_reload(tmp_path):
    """compaction_mesh_devices wires through engine settings to the
    process-global fanout like compaction_compressor_threads does."""
    from cassandra_tpu.config import Config, Settings
    from cassandra_tpu.schema import Schema
    from cassandra_tpu.storage.engine import StorageEngine

    eng = StorageEngine(str(tmp_path), Schema(),
                        settings=Settings(Config.load({})))
    try:
        assert fanout.mesh_devices() == 0
        assert fanout.get_fanout() is None
        eng.settings.set("compaction_mesh_devices", 4)
        assert fanout.mesh_devices() == 4
        fan = fanout.get_fanout()
        assert fan is not None and fan.workers == 4
        eng.settings.set("compaction_mesh_devices", 2)
        assert fanout.get_fanout().workers == 2
        eng.settings.set("compaction_mesh_devices", 0)
        assert fanout.get_fanout() is None
    finally:
        eng.close()


def test_mesh_knob_engine_scoped(tmp_path):
    """Co-hosted engines (LocalCluster shape) each route by their OWN
    knob: the shared pool sizes to the max demand, and one engine
    setting 0 neither disables the other's mesh mode nor shrinks its
    lanes. Closing an engine retires its demand."""
    from cassandra_tpu.config import Config, Settings
    from cassandra_tpu.schema import Schema
    from cassandra_tpu.storage.engine import StorageEngine

    a = StorageEngine(str(tmp_path / "a"), Schema(),
                      settings=Settings(Config.load({})))
    b = StorageEngine(str(tmp_path / "b"), Schema(),
                      settings=Settings(Config.load({})))
    try:
        a.settings.set("compaction_mesh_devices", 4)
        assert fanout.mesh_devices() == 4
        assert a.compactions.mesh_devices_fn() == 4
        assert b.compactions.mesh_devices_fn() == 0
        # B's knob writes must not flip A's routing or shrink the pool
        b.settings.set("compaction_mesh_devices", 0)
        assert fanout.mesh_devices() == 4
        b.settings.set("compaction_mesh_devices", 2)
        assert fanout.mesh_devices() == 4
        assert b.compactions.mesh_devices_fn() == 2
        a.close()
        assert fanout.mesh_devices() == 2   # A's demand retired
    finally:
        b.close()
    assert fanout.mesh_devices() == 0


def test_task_inherits_knob(tmp_path):
    """mesh_devices=None inherits the knob; an explicit value wins."""
    cfs, table = _read_fixture(tmp_path, n=5_000)
    fanout.configure(3)
    t = CompactionTask(cfs, cfs.tracker.view())
    assert t._effective_mesh_devices() == 3
    t2 = CompactionTask(cfs, cfs.tracker.view(), mesh_devices=5)
    assert t2._effective_mesh_devices() == 5
    t3 = CompactionTask(cfs, cfs.tracker.view(), mesh_devices=0)
    assert t3._effective_mesh_devices() == 0
