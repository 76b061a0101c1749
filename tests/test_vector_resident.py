"""VectorIndex keeps ONE normalised matrix on the device per version of the
table (PR 32): the answers are those of the per-query formulation it
replaced, a query on an unchanged table reuses the resident matrix, every
change of the table (memtable write, overwrite, delete, flush, compaction)
refills exactly once, concurrent stale queries share one fill, and the
index holds no stacked host copy beside the device's."""
import collections
import threading
import time

import numpy as np
import pytest

from cassandra_tpu.cql import Session
from cassandra_tpu.index.manager import ann_program
from cassandra_tpu.schema import Schema
from cassandra_tpu.service.metrics import GLOBAL
from cassandra_tpu.storage.engine import StorageEngine
from cassandra_tpu.utils import pipeline_ledger as pl

DIM, ROWS, K = 8, 48, 5
SIMILARITIES = ("cosine", "euclidean")
FILLS, HITS = "index.ann.resident_fills", "index.ann.resident_hits"


@pytest.fixture
def ring(monkeypatch):
    r = collections.deque(maxlen=pl.RING_CAP)
    monkeypatch.setattr(pl, "RING", r)
    return r


def _spans(ring_, name: str) -> list:
    return [dict(zip(pl.RECORD_FIELDS, r)) for r in ring_ if r[0] == name]


def _insert(s, i: int, vec, ts: int | None = None) -> None:
    using = f" USING TIMESTAMP {ts}" if ts is not None else ""
    s.execute(f"INSERT INTO emb (id, v) VALUES ({i}, "
              f"{[float(x) for x in vec]}){using}")


@pytest.fixture
def table(tmp_path):
    """48 seeded vectors: 16 in each of two sstables, 16 in the memtable."""
    eng = StorageEngine(str(tmp_path / "data"), Schema(),
                        commitlog_sync="batch")
    s = Session(eng)
    s.execute("CREATE KEYSPACE ks WITH replication = "
              "{'class': 'SimpleStrategy', 'replication_factor': 1}")
    s.execute("USE ks")
    s.execute(f"CREATE TABLE emb (id int PRIMARY KEY, v vector<float, {DIM}>)")
    s.execute("CREATE CUSTOM INDEX ON emb (v) USING 'SAI'")
    cfs = eng.store("ks", "emb")
    vecs = np.random.default_rng(32).standard_normal(
        (ROWS, DIM)).astype(np.float32)
    for i, v in enumerate(vecs):
        _insert(s, i, v, ts=1000 + i)
        if i in (15, 31):
            cfs.flush()
    idx = eng.indexes.get("ks", "emb", "v")
    yield s, cfs, idx, {i: v for i, v in enumerate(vecs)}
    eng.close()


def _ids(hits: list) -> list:
    return [int.from_bytes(pk, "big", signed=True) for pk, _ck, _s in hits]


def _per_query_formulation(idx, q, k: int, similarity: str) -> list:
    """What VectorIndex.ann did on every query before the matrix was kept:
    assemble, normalise in numpy, hand the HOST array to the program."""
    m, keys = idx._gather()
    q = np.asarray(q, dtype=np.float32)
    if similarity == "cosine":
        m = m / np.maximum(np.linalg.norm(m, axis=1, keepdims=True), 1e-9)
        q = q / max(float(np.linalg.norm(q)), 1e-9)
    vals, at = ann_program()(m, q, k=min(k, len(m)), similarity=similarity)
    return [(keys[int(i)][0], keys[int(i)][1], float(v))
            for v, i in zip(np.asarray(vals), np.asarray(at))]


def _brute_force(rows: dict, q, k: int, similarity: str) -> list:
    """The k best ids in float64 over the rows the test itself wrote."""
    ids = sorted(rows)
    m = np.stack([rows[i] for i in ids]).astype(np.float64)
    q = np.asarray(q, dtype=np.float64)
    if similarity == "cosine":
        score = (m @ q) / (np.linalg.norm(m, axis=1) * np.linalg.norm(q))
    else:
        score = -((m - q) ** 2).sum(axis=1)
    return [ids[j] for j in np.argsort(-score, kind="stable")[:k]]


def _queries(n: int) -> np.ndarray:
    return np.random.default_rng(7).standard_normal(
        (n, DIM)).astype(np.float32)


@pytest.mark.parametrize("similarity", SIMILARITIES)
def test_answers_are_those_of_the_per_query_formulation(table, similarity):
    _s, _cfs, idx, rows = table
    for q in _queries(6):
        got = idx.ann(q, K, similarity)
        # keys, order and float scores, bit for bit
        assert got == _per_query_formulation(idx, q, K, similarity)
        assert _ids(got) == _brute_force(rows, q, K, similarity)


@pytest.mark.parametrize("similarity", SIMILARITIES)
def test_unchanged_table_is_prepared_once(table, ring, similarity):
    _s, _cfs, idx, _rows = table
    fills, hits = GLOBAL.counter(FILLS), GLOBAL.counter(HITS)
    qs = _queries(5)
    for q in qs:
        idx.ann(q, K, similarity)
    assert GLOBAL.counter(FILLS) - fills == 1
    assert GLOBAL.counter(HITS) - hits == len(qs) - 1
    matrix_bytes = ROWS * DIM * 4
    uploads = _spans(ring, "index.ann.upload")
    assert [(u["cells"], u["bytes"]) for u in uploads] == [
        (ROWS, matrix_bytes)]
    resident = _spans(ring, "index.ann.resident")
    assert [(r["items"], r["bytes"]) for r in resident] == [
        (0, matrix_bytes)] + [(1, 0)] * (len(qs) - 1)
    # the upload lies inside the miss's resident span, and nowhere else
    assert uploads[0]["parent"] == resident[0]["id"]
    # one of each span the benchmark reads per query; the call pushes the
    # query vector only, the matrix is there already
    calls = _spans(ring, "index.ann.call")
    assert [c["bytes"] for c in calls] == [qs[0].nbytes] * len(qs)
    assert [c["cells"] for c in calls] == [ROWS] * len(qs)
    for name in ("index.ann.gather", "index.ann.pull"):
        assert len(_spans(ring, name)) == len(qs)
    norm = _spans(ring, "index.ann.normalise")
    if similarity == "cosine":      # the matrix on the miss, then q alone
        assert [n["bytes"] for n in norm] == [
            matrix_bytes + qs[0].nbytes] + [qs[0].nbytes] * (len(qs) - 1)
    else:
        assert norm == []


def _insert_nearer(s, cfs, rows, q, best):
    rows[1000] = q.copy()
    _insert(s, 1000, q, ts=5000)


def _overwrite(s, cfs, rows, q, best):
    # the best row lives in an sstable; its newer embedding points away
    rows[best] = (-q).astype(np.float32)
    _insert(s, best, rows[best], ts=5000)


def _delete(s, cfs, rows, q, best):
    del rows[best]
    s.execute(f"DELETE FROM emb WHERE id = {best}")


def _flush(s, cfs, rows, q, best):
    n = len(cfs.live_sstables())
    cfs.flush()
    assert len(cfs.live_sstables()) == n + 1


def _compact(s, cfs, rows, q, best):
    from cassandra_tpu.compaction.task import CompactionTask
    CompactionTask(cfs, cfs.tracker.view()).execute()
    assert len(cfs.live_sstables()) == 1      # same rows, another live set


CHANGES = {"memtable_insert_nearer": _insert_nearer,
           "overwrite_newer_timestamp": _overwrite, "delete": _delete,
           "flush": _flush, "compaction": _compact}


@pytest.mark.parametrize("similarity", SIMILARITIES)
@pytest.mark.parametrize("change", sorted(CHANGES))
def test_every_change_of_the_table_refills_once(table, change, similarity):
    s, cfs, idx, rows = table
    q = rows[5] + 0.05 * _queries(1)[0]       # nearest: a row of an sstable
    best = _ids(idx.ann(q, K, similarity))[0]
    assert best == _brute_force(rows, q, 1, similarity)[0] == 5
    idx.ann(q, K, similarity)
    fills, hits = GLOBAL.counter(FILLS), GLOBAL.counter(HITS)
    CHANGES[change](s, cfs, rows, q, best)
    want = _brute_force(rows, q, K, similarity)
    got = idx.ann(q, K, similarity)
    assert got == _per_query_formulation(idx, q, K, similarity)
    assert idx.ann(q, K, similarity) == got
    assert GLOBAL.counter(FILLS) - fills == 1
    assert GLOBAL.counter(HITS) - hits == 1
    if change == "delete":
        # the index keeps no tombstones (index/sstable_index.py:
        # iter_column_cells): the dead row still ranks, and the read-back
        # of a CQL query, which scores by cosine, drops it
        assert _ids(got) == [best] + want[:K - 1]
        if similarity == "cosine":
            assert [r[0] for r in s.execute(
                f"SELECT id FROM emb ORDER BY v ANN OF "
                f"{[float(x) for x in q]} LIMIT {K}").rows] == want[:K - 1]
    else:
        assert _ids(got) == want
    if change == "overwrite_newer_timestamp":
        assert best not in _ids(got)          # the old embedding is gone
    if change == "memtable_insert_nearer":
        assert _ids(got)[0] == 1000


@pytest.mark.parametrize("similarity", SIMILARITIES)
def test_four_threads_on_a_stale_entry_share_one_fill(table, ring,
                                                      similarity):
    s, _cfs, idx, rows = table
    idx.ann(_queries(1)[0], K, similarity)
    _insert(s, 2000, np.ones(DIM, np.float32), ts=6000)     # stale now
    rows[2000] = np.ones(DIM, np.float32)
    fills, hits = GLOBAL.counter(FILLS), GLOBAL.counter(HITS)
    ring.clear()
    qs, got = _queries(4), [None] * 4
    start, release = threading.Barrier(4), threading.Barrier(2)
    gather, inside = idx._gather, threading.Event()

    def slow_gather():          # parks the filler inside the fill
        inside.set()
        release.wait(timeout=30)
        return gather()
    idx._gather = slow_gather

    def ask(j):
        start.wait(timeout=30)
        got[j] = idx.ann(qs[j], K, similarity)
    threads = [threading.Thread(target=ask, args=(j,)) for j in range(4)]
    for t in threads:
        t.start()
    assert inside.wait(timeout=30)
    # the filler is parked in _gather holding the fill lock: give the
    # other three the time to arrive at it
    time.sleep(0.2)
    release.wait(timeout=30)
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    idx._gather = gather
    assert GLOBAL.counter(FILLS) - fills == 1
    assert GLOBAL.counter(HITS) - hits == 3
    assert len(_spans(ring, "index.ann.upload")) == 1
    for j in range(4):
        assert _ids(got[j]) == _brute_force(rows, qs[j], K, similarity)
        assert got[j] == _per_query_formulation(idx, qs[j], K, similarity)


def test_query_in_flight_keeps_its_matrix_and_keys(table):
    """The entry is swapped in as one tuple: whoever read it before a
    refill scores against the matrix AND the key list of that version."""
    s, _cfs, idx, rows = table
    q = _queries(1)[0]
    before = idx.ann(q, K)
    old = idx._resident
    _insert(s, 3000, q, ts=7000)
    after = idx.ann(q, K)
    assert _ids(after)[0] == 3000 and idx._resident is not old
    assert idx._resident[0] != old[0]
    # the superseded tuple still answers as its version did
    _key, dev, keys = old
    qn = q / max(float(np.linalg.norm(q)), 1e-9)
    vals, at = ann_program()(dev, qn, k=K, similarity="cosine")
    assert [(keys[int(i)][0], keys[int(i)][1], float(v)) for v, i in
            zip(np.asarray(vals), np.asarray(at))] == before


@pytest.mark.parametrize("similarity", SIMILARITIES)
def test_after_a_refill_one_device_matrix_and_no_host_copy(table,
                                                           similarity):
    import jax
    s, _cfs, idx, _rows = table
    q = _queries(1)[0]
    idx.ann(q, K, similarity)
    first = idx._resident[1]
    _insert(s, 4000, q, ts=8000)
    idx.ann(q, K, similarity)
    key, dev, keys = idx._resident
    assert dev is not first and key[1] == similarity
    assert isinstance(dev, jax.Array) and dev.shape == (ROWS + 1, DIM)
    assert len(keys) == ROWS + 1
    # nothing else on the index is a matrix: the per-sstable components
    # (idx._cache) are what the host keeps
    held = [k for k, v in vars(idx).items()
            if isinstance(v, (np.ndarray, jax.Array))
            or (isinstance(v, tuple) and k != "_resident"
                and any(isinstance(x, (np.ndarray, jax.Array)) for x in v))]
    assert held == []
    assert sum(len(c[0]) for c in idx._cache.values()) == 32


@pytest.mark.parametrize("similarity", SIMILARITIES)
def test_empty_index_answers_nothing_and_uploads_nothing(tmp_path, ring,
                                                         similarity):
    eng = StorageEngine(str(tmp_path / "data"), Schema(),
                        commitlog_sync="batch")
    try:
        s = Session(eng)
        s.execute("CREATE KEYSPACE ks WITH replication = "
                  "{'class': 'SimpleStrategy', 'replication_factor': 1}")
        s.execute("USE ks")
        s.execute(f"CREATE TABLE emb (id int PRIMARY KEY, "
                  f"v vector<float, {DIM}>)")
        s.execute("CREATE CUSTOM INDEX ON emb (v) USING 'SAI'")
        idx = eng.indexes.get("ks", "emb", "v")
        fills = GLOBAL.counter(FILLS)
        assert idx.ann(_queries(1)[0], K, similarity) == []
        assert idx._resident is None
        assert GLOBAL.counter(FILLS) == fills
        assert _spans(ring, "index.ann.upload") == []
        assert _spans(ring, "index.ann.call") == []
        # a table emptied under a resident entry lets the matrix go
        _insert(s, 1, np.ones(DIM), ts=10)
        assert _ids(idx.ann(_queries(1)[0], K, similarity)) == [1]
        assert idx._resident is not None
        eng.store("ks", "emb").truncate()
        assert idx.ann(_queries(1)[0], K, similarity) == []
        assert idx._resident is None
    finally:
        eng.close()
