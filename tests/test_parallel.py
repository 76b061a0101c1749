"""Mesh-sharded merge: results must match the single-device merge, shard
boundaries must never split a token, stats psum across the mesh."""
import numpy as np

import jax

from cassandra_tpu.parallel import make_mesh
from cassandra_tpu.parallel.mesh import run_sharded_merge, shard_batch
from cassandra_tpu.schema import COL_REGULAR_BASE, make_table
from cassandra_tpu.storage import cellbatch as cb

T = make_table("ks", "t", pk=["id"], ck=["c"],
               cols={"id": "int", "c": "int", "v": "text"})
IDT = T.columns["id"].cql_type


def build_workload(n_parts=40, n_cks=5, gens=3):
    batches = []
    for g in range(gens):
        b = cb.CellBatchBuilder(T)
        for p in range(n_parts):
            for c in range(n_cks):
                b.add_cell(IDT.serialize(p), T.serialize_clustering([c]),
                           COL_REGULAR_BASE, f"g{g}".encode(), 100 + g)
        batches.append(b.seal())
    return batches


def test_mesh_really_has_8_devices():
    assert len(jax.devices()) >= 8, jax.devices()
    assert jax.default_backend() == "cpu"


def test_sharded_merge_matches_reference():
    batches = build_workload()
    cat = cb.CellBatch.concat(batches)
    mesh = make_mesh(8)
    assert mesh.devices.size == 8
    keep, perm, stats, shard_of, pos = run_sharded_merge(cat, mesh)
    ref = cb.merge_sorted(batches)
    kept_total = int(stats[0])
    assert kept_total == len(ref)  # 40*5 newest cells
    # every shard's kept cells must equal the reference restricted to it
    assert int(stats[1]) == len(cat) - len(ref)


def test_equal_ts_tombstone_wins_on_mesh():
    # regression: the device sort doesn't order by death; the host
    # tie-break must run on the sharded path too
    b1 = cb.CellBatchBuilder(T)
    b1.add_cell(IDT.serialize(1), T.serialize_clustering([1]),
                COL_REGULAR_BASE, b"live", 100)
    b2 = cb.CellBatchBuilder(T)
    b2.add_tombstone(IDT.serialize(1), T.serialize_clustering([1]),
                     COL_REGULAR_BASE, 100, 1000)
    cat = cb.CellBatch.concat([b1.seal(), b2.seal()])
    mesh = make_mesh(8)
    keep, perm, stats, shard_of, pos = run_sharded_merge(cat, mesh)
    assert int(stats[0]) == 1
    s = int(shard_of[0])
    kept_pos = np.flatnonzero(keep[s])[0]
    members = np.flatnonzero(shard_of == s)
    cat_idx = members[perm[s, kept_pos]]
    assert cat.flags[cat_idx] & cb.FLAG_TOMBSTONE, "live cell beat tombstone"


def test_shards_do_not_split_tokens():
    batches = build_workload(n_parts=100, n_cks=3, gens=2)
    cat = cb.CellBatch.concat(batches)
    operands, shard_of, pos, members = shard_batch(cat, 8)
    tok = (cat.lanes[:, 0].astype(np.uint64) << np.uint64(32)) \
        | cat.lanes[:, 1].astype(np.uint64)
    for t in np.unique(tok):
        assert len(np.unique(shard_of[tok == t])) == 1


def test_shard_balance():
    batches = build_workload(n_parts=200, n_cks=4, gens=1)
    cat = cb.CellBatch.concat(batches)
    operands, shard_of, _, _ = shard_batch(cat, 8)
    counts = np.bincount(shard_of, minlength=8)
    assert counts.max() <= 3 * max(counts.mean(), 1)  # roughly balanced


def test_shard_balance_skewed():
    """Count-weighted boundaries: with ~40% of cells in 2 hot
    partitions the remaining shards must re-balance around the hot
    spots instead of starving (the positional quantile gave a
    min/mean of ~0.05 on the skewed multichip sweep)."""
    from cassandra_tpu.parallel.mesh import shard_imbalance
    rng = np.random.default_rng(9)
    n = 60_000
    hot = rng.random(n) < 0.4
    pk = np.where(hot, rng.integers(0, 2, n), rng.integers(2, 2048, n))
    b = cb.CellBatchBuilder(T)
    order_ck = rng.integers(0, 10_000, n)
    for i in range(n):
        b.add_cell(IDT.serialize(int(pk[i])),
                   T.serialize_clustering([int(order_ck[i])]),
                   COL_REGULAR_BASE, b"v", 100)
    cat = b.seal()
    _, shard_of, _, _ = shard_batch(cat, 8)
    counts = np.bincount(shard_of, minlength=8)
    mean = counts.mean()
    # hot partitions are unsplittable (~20% of cells each ≈ 1.6x the
    # 1/8 mean), so max/mean ~1.6 is the floor; the greedy boundaries
    # must land near it and must not starve any shard
    assert shard_imbalance(counts) <= 2.0, counts.tolist()
    assert counts.min() >= mean / 3, counts.tolist()
    # a partition still never splits
    tok = (cat.lanes[:, 0].astype(np.uint64) << np.uint64(32)) \
        | cat.lanes[:, 1].astype(np.uint64)
    for t in np.unique(tok[np.asarray(hot)]):
        assert len(np.unique(shard_of[tok == t])) == 1


def test_materialized_shards_bitmatch_single_device():
    from cassandra_tpu.parallel.mesh import materialize_sharded_merge
    batches = build_workload(n_parts=60, n_cks=4, gens=3)
    cat = cb.CellBatch.concat(batches)
    mesh = make_mesh(8)
    shards = materialize_sharded_merge(cat, mesh)
    assert len(shards) == 8
    merged = cb.CellBatch.concat([s for s in shards if len(s)])
    ref = cb.merge_sorted(batches)
    np.testing.assert_array_equal(merged.lanes, ref.lanes)
    np.testing.assert_array_equal(merged.ts, ref.ts)
    np.testing.assert_array_equal(merged.flags, ref.flags)
    np.testing.assert_array_equal(merged.payload, ref.payload)
    np.testing.assert_array_equal(merged.off, ref.off)


def test_sharded_compaction_writes_sstables_roundtrip(tmp_path):
    """8-shard compaction lands 8 sstables whose union round-trips to the
    single-device merge (ShardManager.java:33 — shards feed real writers)."""
    from cassandra_tpu.parallel.mesh import sharded_compact_to_sstables
    from cassandra_tpu.storage.sstable.reader import SSTableReader
    batches = build_workload(n_parts=80, n_cks=4, gens=2)
    mesh = make_mesh(8)
    results = sharded_compact_to_sstables(batches, T, mesh, str(tmp_path))
    assert len(results) >= 2        # real fan-out, not one writer
    ref = cb.merge_sorted(batches)
    segs = []
    last_max = None
    for desc, stats in results:
        r = SSTableReader(desc)
        assert r.min_token() is not None
        if last_max is not None:      # shards are token-ordered, disjoint
            assert r.min_token() >= last_max
        last_max = r.max_token()
        segs.extend(r.scanner())
        r.close()
    got = cb.CellBatch.concat(segs)
    assert len(got) == len(ref)
    np.testing.assert_array_equal(got.lanes, ref.lanes)
    np.testing.assert_array_equal(got.payload, ref.payload)


def test_failed_shard_write_leaves_no_partial_round(tmp_path, monkeypatch):
    """Fault injection: one shard's writer dies mid-round — the whole
    round must be all-or-nothing (LifecycleTransaction semantics): no
    earlier shard's sstable may survive as partial compaction output."""
    import os
    import pytest
    from cassandra_tpu.parallel.mesh import sharded_compact_to_sstables
    from cassandra_tpu.storage.sstable import writer as writer_mod

    batches = build_workload(n_parts=80, n_cks=4, gens=2)
    mesh = make_mesh(8)
    calls = {"n": 0}
    real_finish = writer_mod.SSTableWriter.finish

    def failing_finish(self):
        calls["n"] += 1
        if calls["n"] == 3:          # third shard's commit blows up
            raise OSError("injected shard write failure")
        return real_finish(self)

    monkeypatch.setattr(writer_mod.SSTableWriter, "finish", failing_finish)
    with pytest.raises(OSError, match="injected"):
        sharded_compact_to_sstables(batches, T, mesh, str(tmp_path))
    leftovers = [f for f in os.listdir(tmp_path)]
    assert leftovers == [], f"partial round left files: {leftovers}"
