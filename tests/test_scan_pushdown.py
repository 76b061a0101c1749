"""Device-accelerated analytical scans: flush-time zone maps (ZMP1),
fused filter kernels vs the host numpy reference, mesh-fanned Phase A,
and the ALLOW FILTERING pushdown lane (reference counterparts: SAI
metadata pruning in index/sai/* + partition-restricted range reads).

The load-bearing invariant everywhere below: the device leg, the host
leg, the mesh legs and the naive Python scan are BIT-IDENTICAL —
pushdown is a latency optimization, never a semantics change."""
import os

import numpy as np
import pytest

from cassandra_tpu.config import Config, Settings
from cassandra_tpu.cql import Session
from cassandra_tpu.index import sstable_index as ssi
from cassandra_tpu.ops import device_scan as ds
from cassandra_tpu.schema import Schema
from cassandra_tpu.service.metrics import GLOBAL as METRICS
from cassandra_tpu.storage.engine import StorageEngine
from cassandra_tpu.utils import faultfs, timeutil


@pytest.fixture(autouse=True)
def _clean_faults():
    faultfs.disarm()
    yield
    faultfs.disarm()


@pytest.fixture
def eng(tmp_path):
    e = StorageEngine(str(tmp_path / "data"), Schema(),
                      commitlog_sync="batch",
                      settings=Settings(Config.load(
                          {"disk_failure_policy": "best_effort"})))
    yield e
    e.close()


@pytest.fixture
def session(eng):
    s = Session(eng)
    s.execute("CREATE KEYSPACE ks WITH replication = "
              "{'class': 'SimpleStrategy', 'replication_factor': 1}")
    s.execute("USE ks")
    return s


def _pred(cfs, col, op, val):
    p = ds.compile_predicate(cfs.table, [(cfs.table.columns[col], op, val)])
    assert p is not None
    return p


def _pks(cfs, pred, **kw):
    out, info = cfs.scan_filtered(pred, **kw)
    return sorted(pk for pk, _b in out), info


# ------------------------------------------------------------ key space --

def test_scan_keys_are_monotone():
    """u64 scan keys preserve value order for every exact kind — the
    property every zone-prune rule and range kernel rests on."""
    ints = [-(1 << 63), -12345, -1, 0, 1, 7, (1 << 62), (1 << 63) - 1]
    ks = [ds.key_of_value("i64", v) for v in ints]
    assert ks == sorted(ks) and len(set(ks)) == len(ks)
    fls = [float("-inf"), -1e300, -2.5, -0.0, 0.0, 1e-300, 3.14,
           float("inf")]
    kf = [ds.key_of_value("f64", v) for v in fls]
    assert kf == sorted(kf)
    assert kf[3] == kf[4]          # -0.0 and +0.0 collapse (CQL equality)
    assert ds.key_of_value("bool", False) < ds.key_of_value("bool", True)
    assert ds.key_of_value("f64", float("nan")) is None
    # round trips
    for v in ints:
        assert ds.value_of_key("i64", ds.key_of_value("i64", v)) == v
    for v in (-2.5, 0.0, 3.14, float("inf")):
        assert ds.value_of_key("f64", ds.key_of_value("f64", v)) == v


def test_prefix_keys_superset_not_exact():
    """Text keys (8-byte big-endian prefix) order correctly and share a
    key only when the prefixes collide — the executor re-verifies, so
    superset is the contract, not equality."""
    a = ds.key_of_value("prefix", "apple")
    b = ds.key_of_value("prefix", "banana")
    assert a < b
    long1 = ds.key_of_value("prefix", "same-prefix-A")
    long2 = ds.key_of_value("prefix", "same-prefix-B")
    assert long1 == long2          # first 8 bytes identical: collision


# ----------------------------------------------------- zone map component --

def test_zonemap_written_at_flush_and_loads(session, eng):
    session.execute("CREATE TABLE zm (k int PRIMARY KEY, v int, t text)")
    for i in range(50):
        session.execute(f"INSERT INTO zm (k, v, t) VALUES ({i}, {i}, 'x{i}')")
    cfs = eng.store("ks", "zm")
    cfs.flush()
    (r,) = cfs.live_sstables()
    path = ssi.zonemap_path(r.desc)
    assert os.path.exists(path)
    zm = ssi.load_zonemap(path, expected_segments=r.n_segments)
    assert zm is not None and zm.n_segments == r.n_segments
    # both the int and the text column carry bounds
    vcid = cfs.table.columns["v"].column_id
    tcid = cfs.table.columns["t"].column_id
    assert vcid in zm.cols and tcid in zm.cols


def test_zonemap_rebuilds_after_corruption(session, eng):
    """EQI1 contract: a torn/garbage component is rebuilt from the
    decoded segments (counted), never trusted, never fatal."""
    session.execute("CREATE TABLE zr (k int PRIMARY KEY, v int)")
    for i in range(40):
        session.execute(f"INSERT INTO zr (k, v) VALUES ({i}, {i % 10})")
    cfs = eng.store("ks", "zr")
    cfs.flush()
    (r,) = cfs.live_sstables()
    path = ssi.zonemap_path(r.desc)
    raw = bytearray(open(path, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    open(path, "wb").write(bytes(raw))
    before = METRICS.counter("scan.zonemap_rebuilds")
    got, _ = _pks(cfs, _pred(cfs, "v", "=", 3))
    assert METRICS.counter("scan.zonemap_rebuilds") > before
    assert len(got) == 4
    # the rebuild rewrote a parseable component
    assert ssi.load_zonemap(path, expected_segments=r.n_segments) is not None


# --------------------------------------------- kernel vs host identity --

def _seed_deletion_scopes(session, eng):
    session.execute("CREATE TABLE dt (k int, c int, v int, s text, "
                    "PRIMARY KEY (k, c))")
    for k in range(12):
        for c in range(4):
            session.execute(f"INSERT INTO dt (k, c, v, s) VALUES "
                            f"({k}, {c}, {k * 10 + c}, 'p{k % 3}')")
    session.execute("DELETE v FROM dt WHERE k = 1 AND c = 1")  # cell
    session.execute("DELETE FROM dt WHERE k = 2 AND c = 2")    # row
    session.execute("DELETE FROM dt WHERE k = 3")              # partition
    session.execute("DELETE FROM dt WHERE k = 4 AND c >= 2")   # range
    cfs = eng.store("ks", "dt")
    cfs.flush()
    # second generation with overwrites so reconciliation has work
    for k in range(6, 9):
        session.execute(f"INSERT INTO dt (k, c, v) VALUES ({k}, 0, "
                        f"{k * 10})")
    cfs.flush()
    return cfs


def test_kernel_vs_host_identity_across_deletion_scopes(session, eng):
    cfs = _seed_deletion_scopes(session, eng)
    for op, val in [("=", 10), (">", 30), ("<=", 25), ("!=", 42),
                    ("IN", [11, 23, 70])]:
        dev, _ = _pks(cfs, _pred(cfs, "v", op, val), use_device=True)
        host, _ = _pks(cfs, _pred(cfs, "v", op, val), use_device=False)
        assert dev == host, f"device/host diverged for v {op} {val}"
    # end-to-end: CQL rows identical under both gate pins
    q = ("SELECT k, c, v FROM dt WHERE v >= 20 AND v < 80 "
         "ALLOW FILTERING")
    eng.settings.set("scan_device_filter", True)
    dev_rows = session.execute(q).rows
    eng.settings.set("scan_device_filter", False)
    host_rows = session.execute(q).rows
    eng.settings.set("scan_device_filter", True)
    assert dev_rows == host_rows
    # deleted scopes really are invisible
    ks = {r[0] for r in dev_rows}
    assert 3 not in ks             # partition delete


def test_ttl_expiry_at_read_identity(session, eng):
    """Cells whose TTL lapses between write and read: Phase A may still
    nominate the partition (zone maps are write-time), Phase B + the
    executor drop it — and device == host at every now."""
    session.execute("CREATE TABLE tt (k int PRIMARY KEY, v int)")
    for i in range(10):
        session.execute(f"INSERT INTO tt (k, v) VALUES ({i}, {i}) "
                        f"USING TTL 100")
    session.execute("INSERT INTO tt (k, v) VALUES (50, 5)")  # immortal
    cfs = eng.store("ks", "tt")
    cfs.flush()
    pred = _pred(cfs, "v", "=", 5)
    now = timeutil.now_seconds()
    for when in (now, now + 1000):       # live, then all-TTL-expired
        dev, _ = _pks(cfs, pred, now=when, use_device=True)
        host, _ = _pks(cfs, pred, now=when, use_device=False)
        assert dev == host
    # after expiry only the immortal row still has a LIVE matching cell
    # in the reconciled merge (expired cells surface as tombstones)
    out, _ = cfs.scan_filtered(pred, now=now + 1000)
    live = [pk for pk, b in out
            if len(ds.batch_predicate_cells(b, pred, reconciled=True)[0])]
    assert live == [cfs.table.columns["k"].cql_type.serialize(50)]


def test_all_tombstone_segment_prunes(session, eng):
    """A flushed sstable holding only deletes has zero live cells in
    every zone: the scan skips all its segments without decoding."""
    session.execute("CREATE TABLE at (k int PRIMARY KEY, v int)")
    for i in range(20):
        session.execute(f"INSERT INTO at (k, v) VALUES ({i}, {i})")
    cfs = eng.store("ks", "at")
    cfs.flush()
    for i in range(20):
        session.execute(f"DELETE FROM at WHERE k = {i}")
    cfs.flush()                      # second sstable: tombstones only
    pred = _pred(cfs, "v", ">=", 0)
    got, info = _pks(cfs, pred)
    assert info["segments_skipped"] >= 1
    assert info["sstables_skipped"] >= 1
    # and correctness: everything is deleted
    assert session.execute(
        "SELECT k FROM at WHERE v >= 0 ALLOW FILTERING").rows == []


def test_min_eq_max_zone_boundaries(session, eng):
    """Constant-valued segments (kmin == kmax) exercise every strict /
    non-strict boundary in prune_keep_mask."""
    session.execute("CREATE TABLE mm (k int PRIMARY KEY, v int)")
    for i in range(30):
        session.execute(f"INSERT INTO mm (k, v) VALUES ({i}, 7)")
    cfs = eng.store("ks", "mm")
    cfs.flush()
    cases = [("=", 7, True), ("=", 8, False), ("<", 7, False),
             ("<=", 7, True), (">", 7, False), (">=", 7, True),
             ("!=", 7, False), ("IN", [6, 8], False), ("IN", [6, 7], True)]
    for op, val, any_kept in cases:
        got, info = _pks(cfs, _pred(cfs, "v", op, val))
        if any_kept:
            assert len(got) == 30, f"v {op} {val}"
        else:
            assert got == [], f"v {op} {val}"
            assert info["segments_skipped"] == info["segments_total"], \
                f"v {op} {val} decoded a provably-empty segment"


# ----------------------------------------------------- mesh + gate knob --

def test_mesh_and_serial_scans_identical(session, eng):
    session.execute("CREATE TABLE ms (k int PRIMARY KEY, v int, t text)")
    cfs = eng.store("ks", "ms")
    for i in range(200):
        session.execute(f"INSERT INTO ms (k, v, t) VALUES ({i}, {i % 17}, "
                        f"'w{i % 5}')")
        if i % 80 == 79:
            cfs.flush()
    cfs.flush()
    q = "SELECT k FROM ms WHERE v = 3 ALLOW FILTERING"
    legs = {}
    try:
        for n in (0, 1, 4):
            eng.settings.set("compaction_mesh_devices", n)
            legs[n] = sorted(session.execute(q).rows)
    finally:
        eng.settings.set("compaction_mesh_devices", 0)
    assert legs[0] == legs[1] == legs[4]
    assert len(legs[0]) == len([i for i in range(200) if i % 17 == 3])


def test_mesh_scan_counts_and_drains_token_order(session, eng):
    session.execute("CREATE TABLE mo (k int PRIMARY KEY, v int)")
    cfs = eng.store("ks", "mo")
    for i in range(150):
        session.execute(f"INSERT INTO mo (k, v) VALUES ({i}, {i % 2})")
    cfs.flush()
    pred = _pred(cfs, "v", "=", 1)
    serial, _ = cfs.scan_filtered(pred)
    try:
        eng.settings.set("compaction_mesh_devices", 2)
        before = METRICS.counter("scan.mesh_scans")
        meshed, info = cfs.scan_filtered(pred)
        fanned = METRICS.counter("scan.mesh_scans") > before
    finally:
        eng.settings.set("compaction_mesh_devices", 0)
    assert [pk for pk, _ in meshed] == [pk for pk, _ in serial]
    if fanned:                       # boundaries existed: shards ran
        assert info["segments_total"] >= 1


def test_mid_scan_gate_flip(session, eng):
    """A callable gate is consulted per segment: flipping it mid-scan
    moves later segments to the other leg with identical results."""
    session.execute("CREATE TABLE gf (k int PRIMARY KEY, v int)")
    cfs = eng.store("ks", "gf")
    for gen in range(3):
        for i in range(gen * 40, gen * 40 + 40):
            session.execute(f"INSERT INTO gf (k, v) VALUES ({i}, {i % 4})")
        cfs.flush()
    pred = _pred(cfs, "v", "=", 2)
    calls = [0]

    def flip():
        calls[0] += 1
        return calls[0] > 2          # host for 2 segments, then device

    flipped, info = cfs.scan_filtered(pred, use_device=flip)
    pinned, _ = cfs.scan_filtered(pred, use_device=True)
    assert [pk for pk, _ in flipped] == [pk for pk, _ in pinned]
    assert calls[0] >= 3             # gate re-read per segment
    assert info["host_segments"] >= 1
    assert info["device_segments"] + info["host_segments"] == calls[0]


# ------------------------------------------------------------ faults --

def test_eio_quarantines_per_source(session, eng):
    """EIO on one sstable mid-scan degrades THAT source (best_effort
    quarantine) — the other sstables' candidates still come back."""
    session.execute("CREATE TABLE io (k int PRIMARY KEY, v int)")
    cfs = eng.store("ks", "io")
    for i in range(30):
        session.execute(f"INSERT INTO io (k, v) VALUES ({i}, 1)")
    cfs.flush()
    for i in range(30, 60):
        session.execute(f"INSERT INTO io (k, v) VALUES ({i}, 1)")
    cfs.flush()
    gens = sorted(r.desc.generation for r in cfs.live_sstables())
    assert len(gens) == 2
    bad = gens[0]
    faultfs.arm("sstable.read", "error",
                path_substr=f"-{bad}-Data.db")
    try:
        got, _ = _pks(cfs, _pred(cfs, "v", "=", 1))
    finally:
        faultfs.disarm()
    live_gens = {r.desc.generation for r in cfs.live_sstables()}
    assert bad not in live_gens      # quarantined, not fatal
    assert len(got) >= 30            # healthy source fully scanned


# --------------------------------------------------------- eager index --

def test_eager_index_build_at_flush(session, eng):
    """An index created BEFORE data is flushed gets its component built
    in the flush tail (index.builds), not lazily at first query."""
    session.execute("CREATE TABLE ei (k int PRIMARY KEY, city text)")
    session.execute("CREATE INDEX ON ei (city)")
    cfs = eng.store("ks", "ei")
    for i in range(20):
        session.execute(f"INSERT INTO ei (k, city) VALUES ({i}, 'c{i % 3}')")
    b0 = METRICS.counter("index.builds")
    l0 = METRICS.counter("index.lazy_builds")
    cfs.flush()
    assert METRICS.counter("index.builds") > b0
    got = {r[0] for r in session.execute(
        "SELECT k FROM ei WHERE city = 'c1'").rows}
    assert got == {i for i in range(20) if i % 3 == 1}
    assert METRICS.counter("index.lazy_builds") == l0   # never lazy


def test_lazy_index_build_counted(session, eng):
    """An index created AFTER the flush has no component on the existing
    sstable: the first lookup builds it lazily (index.lazy_builds)."""
    session.execute("CREATE TABLE li (k int PRIMARY KEY, city text)")
    cfs = eng.store("ks", "li")
    for i in range(12):
        session.execute(f"INSERT INTO li (k, city) VALUES ({i}, 'c{i % 2}')")
    cfs.flush()
    session.execute("CREATE INDEX ON li (city)")
    l0 = METRICS.counter("index.lazy_builds")
    got = {r[0] for r in session.execute(
        "SELECT k FROM li WHERE city = 'c1'").rows}
    assert got == {i for i in range(12) if i % 2 == 1}
    assert METRICS.counter("index.lazy_builds") > l0


# --------------------------------------------------- pushdown counters --

def test_agg_pushdown_materializes_zero_rows(session, eng):
    session.execute("CREATE TABLE ag (k int PRIMARY KEY, v int)")
    cfs = eng.store("ks", "ag")
    for i in range(100):
        session.execute(f"INSERT INTO ag (k, v) VALUES ({i}, {i % 10})")
    cfs.flush()
    m0 = METRICS.counter("scan.rows_materialized")
    a0 = METRICS.counter("scan.agg_pushdown")
    rs = session.execute(
        "SELECT count(*) FROM ag WHERE v = 3 ALLOW FILTERING")
    assert rs.rows == [(10,)]
    rs = session.execute(
        "SELECT count(v), min(v), max(v), sum(v), avg(v) FROM ag "
        "WHERE v = 3 ALLOW FILTERING")
    assert rs.rows == [(10, 3, 3, 30, 3.0)]
    assert METRICS.counter("scan.agg_pushdown") == a0 + 2
    assert METRICS.counter("scan.rows_materialized") == m0, \
        "aggregate pushdown must not materialize row dicts"
    # empty-match aggregates: count 0, min/max None, sum 0
    rs = session.execute(
        "SELECT count(v), min(v), sum(v) FROM ag WHERE v = 99 "
        "ALLOW FILTERING")
    assert rs.rows == [(0, None, 0)]


def test_row_pushdown_and_fallback_counters(session, eng):
    session.execute("CREATE TABLE pf (k int PRIMARY KEY, v int, w varint)")
    cfs = eng.store("ks", "pf")
    for i in range(40):
        session.execute(f"INSERT INTO pf (k, v, w) VALUES ({i}, {i}, {i})")
    cfs.flush()
    p0 = METRICS.counter("scan.pushdown")
    f0 = METRICS.counter("scan.fallback")
    got = {r[0] for r in session.execute(
        "SELECT k FROM pf WHERE v < 5 ALLOW FILTERING").rows}
    assert got == set(range(5))
    assert METRICS.counter("scan.pushdown") == p0 + 1
    # varint has no scan-key kind: the Python scan answers, counted
    got = {r[0] for r in session.execute(
        "SELECT k FROM pf WHERE w = 7 ALLOW FILTERING").rows}
    assert got == {7}
    assert METRICS.counter("scan.fallback") == f0 + 1


def test_pushdown_respects_memtable_and_statics(session, eng):
    """Unflushed rows (no zone maps) and static columns both flow
    through the pushdown lane unchanged."""
    session.execute("CREATE TABLE st (k int, c int, s text STATIC, "
                    "v int, PRIMARY KEY (k, c))")
    cfs = eng.store("ks", "st")
    for k in range(8):
        session.execute(f"INSERT INTO st (k, s) VALUES ({k}, 'g{k % 2}')")
        for c in range(3):
            session.execute(f"INSERT INTO st (k, c, v) VALUES "
                            f"({k}, {c}, {k * 10 + c})")
    cfs.flush()
    for k in range(8, 12):           # memtable-only partitions
        session.execute(f"INSERT INTO st (k, c, v) VALUES ({k}, 0, "
                        f"{k * 10})")
    got = {r[0] for r in session.execute(
        "SELECT k, c FROM st WHERE v >= 80 ALLOW FILTERING").rows}
    assert got == {8, 9, 10, 11}     # memtable rows found
    # static predicate: every row of matching partitions comes back
    rows = session.execute(
        "SELECT k, c FROM st WHERE s = 'g1' ALLOW FILTERING").rows
    assert {r[0] for r in rows} == {1, 3, 5, 7}
    assert len(rows) == 4 * 3


def test_in_and_text_prefix_predicates(session, eng):
    session.execute("CREATE TABLE tp (k int PRIMARY KEY, t text, v int)")
    cfs = eng.store("ks", "tp")
    words = ["alpha", "beta", "gamma", "delta", "epsilon"]
    for i in range(50):
        session.execute(f"INSERT INTO tp (k, t, v) VALUES ({i}, "
                        f"'{words[i % 5]}-{i}', {i})")
    cfs.flush()
    got = {r[0] for r in session.execute(
        "SELECT k FROM tp WHERE t = 'beta-6' ALLOW FILTERING").rows}
    assert got == {6}
    got = {r[0] for r in session.execute(
        "SELECT k FROM tp WHERE v IN (3, 17, 44, 99) "
        "ALLOW FILTERING").rows}
    assert got == {3, 17, 44}


# ---------------------------------------------- zone entries, all columns --

def _zone_entries_column_by_column(zone_cols, col_lane, flags, vs, ve,
                                   payload):
    """segment_zone_entries the plain way: one column at a time, one
    value at a time (Python ints and bytes; no scan-key arithmetic
    shared with the program except key_of_value's definition of a
    prefix/int key)."""
    import struct

    from cassandra_tpu.storage.cellbatch import DEATH_FLAGS
    raw = bytes(payload)
    out = []
    for cid, kind, width in zone_cols:
        keys, live, dead, unkeyed = [], 0, 0, False
        for i in np.flatnonzero(np.asarray(col_lane) == cid):
            if flags[i] & DEATH_FLAGS:
                dead += 1
                continue
            live += 1
            v = raw[int(vs[i]):int(ve[i])]
            if kind == "prefix":
                keys.append(int.from_bytes(v[:8].ljust(8, b"\0"), "big"))
            elif len(v) != width:
                unkeyed = True
            elif kind == "bool":
                keys.append(v[0])
            elif kind == "i64":
                keys.append(int.from_bytes(v, "big", signed=True) + (1 << 63))
            else:
                f = struct.unpack(">f" if width == 4 else ">d", v)[0] + 0.0
                bits = struct.unpack(">Q", struct.pack(">d", f))[0]
                keys.append(bits ^ (2**64 - 1) if bits >> 63
                            else bits | 1 << 63)
        if not live:
            out.append((2**64 - 1, 0, 0, dead))
        elif unkeyed:
            out.append((0, 2**64 - 1, live, dead))
        else:
            out.append((min(keys), max(keys), live, dead))
    return out


ZONE_TABLES = {
    "ten_text_columns": [(8 + i, "prefix", 0) for i in range(10)],
    "mixed_kinds": [(8, "prefix", 0), (9, "i64", 4), (10, "f64", 8),
                    (11, "bool", 1), (12, "i64", 8), (13, "f64", 4),
                    (20, "prefix", 0)],
    "one_int_column": [(9, "i64", 4)],
    "ids_not_ascending": [(30, "prefix", 0), (10, "i64", 2)],
}


@pytest.mark.parametrize("dead_share, misfit_share",
                         [(0.0, 0.0), (0.2, 0.0), (1.0, 0.0), (0.1, 0.02)])
@pytest.mark.parametrize("table", list(ZONE_TABLES))
def test_zone_entries_match_column_by_column(table, dead_share,
                                             misfit_share):
    zone_cols = ZONE_TABLES[table]
    rng = np.random.default_rng(len(table))
    widths = {c: w for c, k, w in zone_cols if k != "prefix"}
    for n in (1, 7, 600):
        # cells of the mapped columns, of an unmapped one (3) and of one
        # past the last id, some dead, some with a value no kind fits
        col_lane = rng.choice([c for c, _, _ in zone_cols] + [3, 999],
                              n).astype(np.uint32)
        flags = np.where(rng.random(n) < dead_share,
                         rng.choice([1, 4, 8, 32], n), 0).astype(np.uint8)
        flags |= rng.choice([0, 2], n).astype(np.uint8)   # not a death flag
        lens = np.array([widths.get(int(c), int(rng.integers(0, 14)))
                         for c in col_lane])
        lens = np.where(rng.random(n) < misfit_share, lens + 1, lens)
        off = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(lens + 2, out=off[1:])
        payload = rng.integers(0, 256, int(off[-1]), dtype=np.uint8)
        # finite floats only: the reference orders by value
        for i in np.flatnonzero(np.isin(col_lane, [10, 13])):
            w = int(lens[i])
            if w in (4, 8) and table == "mixed_kinds":
                v = np.array([rng.normal() * 1e3], dtype=">f4" if w == 4
                             else ">f8")
                payload[off[i] + 2:off[i] + 2 + w] = v.view(np.uint8)
        args = (col_lane, flags, off[:-1] + 2, off[1:], payload)
        assert ds.segment_zone_entries(zone_cols, *args) == \
            _zone_entries_column_by_column(zone_cols, *args)
    assert ds.segment_zone_entries([], *args) == []
