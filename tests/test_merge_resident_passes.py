"""`merge.resident` runs only the LSD passes whose key varies among the
round's valid cells (ops/merge.py `_traced_sort_perm`). Every array the
program returns must be the one the sixteen unconditional passes give,
element for element — padding rows included — and the pass count it
returns must be the one the round's own lanes dictate. The reference is
kept here: `np.lexsort` over the same keys, the kept-cell compaction and
the expired -> tombstone conversion in plain numpy.

Since PR 36 the columns move through the sort's permutation as ROWS of
one uint32 matrix a stage (ops/merge.py `gather_rows`): the cases below
the first six hold what a packing could break — a flags byte with its
top bit set, negative int32 columns, all-ones words, indices past 2^16,
a round the host must tie-break, tables of other widths — and the last
test counts the traced program's gathers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cassandra_tpu.ops import device_write as dw
from cassandra_tpu.ops import merge as dmerge
from cassandra_tpu.service.metrics import GLOBAL as METRICS
from cassandra_tpu.storage import cellbatch as cb
from cassandra_tpu.utils import pipeline_ledger

K = 13            # 4 partition + 6 clustering (prefix, hash) + column + 2 path
COL = K - 3
TS0 = 1_700_000_000_000_000
RUN, SKIPPED = "merge.resident.passes_run", "merge.resident.passes_skipped"


def _batch(lanes, ts, flags=None, ldt=None, ttl=None):
    """A CellBatch over hand-made lanes: 8-byte frames, a 2-byte header."""
    n = len(ts)
    off = np.arange(n + 1, dtype=np.int64) * 8
    return cb.CellBatch(
        lanes=np.asarray(lanes, dtype=np.uint32),
        ts=np.asarray(ts, dtype=np.int64),
        ldt=(np.full(n, 0x7FFFFFFF, np.int32) if ldt is None
             else np.asarray(ldt, dtype=np.int32)),
        ttl=(np.zeros(n, np.int32) if ttl is None
             else np.asarray(ttl, dtype=np.int32)),
        flags=(np.zeros(n, np.uint8) if flags is None
               else np.asarray(flags, dtype=np.uint8)),
        off=off, val_start=off[:-1] + 2,
        payload=np.zeros(8 * n, dtype=np.uint8))


def _standard1(n, rng):
    """keyspace1.standard1's shape: no clustering, no path — the six
    clustering lanes and the two path lanes are 0 in every cell; write
    times inside one hour, so `~ts_h` takes one value."""
    lanes = np.zeros((n, K), dtype=np.uint32)
    lanes[:, :4] = rng.integers(0, 1 << 32, (n, 4), dtype=np.uint32)
    lanes[:, COL] = rng.integers(16, 21, n)
    return lanes, TS0 + rng.integers(0, 3_600_000_000, n)


# each case: (cells, gc_before, now, the passes the round must run)

def no_constant_lane(rng):
    n = 900
    lanes = rng.integers(0, 7, (n, K), dtype=np.uint32)
    ts = rng.integers(1, 1 << 40, n)          # both ts words vary
    return _batch(lanes, ts), 0, 0, 16


def eight_constant_lanes_a_third_padding(rng):
    lanes, ts = _standard1(683, rng)          # bucket 1024
    return _batch(lanes, ts), 0, 0, 7         # valid, 4 + column, ~ts_l


def constant_at_a_value_the_padding_does_not_hold(rng):
    lanes, ts = _standard1(683, rng)
    lanes[:, 5] = 7               # padding rows hold 0xFFFFFFFF there
    lanes[:, 11] = 0xFFFFFFFF     # and a lane that equals the padding's
    return _batch(lanes, ts), 0, 0, 7


def one_valid_cell_differs(rng):
    lanes, ts = _standard1(683, rng)
    lanes[341, 6] = 1             # the pass over lane 6 must run
    return _batch(lanes, ts), 0, 0, 8


def one_valid_cell(rng):
    lanes, ts = _standard1(1, rng)
    return _batch(lanes, ts), 0, 0, 1         # `valid` alone


def ttl_round(rng):
    """test_device_ttl.py's shape: two runs of the same readings, half
    of them run out (kept, converted), under a clustering column."""
    now = 1_790_000_000
    parts, rows = 5, 40
    lanes = np.zeros((2 * parts * rows, K), dtype=np.uint32)
    pk = np.tile(np.repeat(np.arange(parts), rows), 2)
    ck = np.tile(np.arange(rows), 2 * parts)
    lanes[:, 0], lanes[:, 4], lanes[:, COL] = pk * 977, ck, 16
    ts = 1_000 * (np.repeat([1, 2], parts * rows)) + ck
    ldt = np.where(ck % 2 == 0, now - 86400, now + 86400)
    n = len(ts)
    return (_batch(lanes, ts, flags=np.full(n, cb.FLAG_EXPIRING),
                   ldt=ldt, ttl=np.full(n, 30 * 86400)),
            now - 10 * 86400, now, 4)     # valid, pk, ck, ~ts_l


# ---- what the row packing could break (PR 36)

def top_bits_and_negative_columns(rng):
    """flags8 with bit 7 set must come back a uint8 with bit 7 set,
    ldt/ttl below zero must come back the same int32, and a lane, a
    frame length and a value offset of 0xFFFFFFFF the same words."""
    n = 683
    lanes, ts = _standard1(n, rng)
    lanes[:, 7] = 0xFFFFFFFF                   # a constant lane of ones
    lanes[::5, 8] = 0xFFFFFFFF                 # and one that varies
    flags = np.where(np.arange(n) % 3 == 0, 0x80 | cb.FLAG_EXPIRING,
                     0x80 | cb.FLAG_ROW_LIVENESS)
    ldt = rng.integers(-(1 << 31), 1 << 31, n)
    ttl = rng.integers(-(1 << 31), 0, n)
    cat = _batch(lanes, ts, flags=flags, ldt=ldt, ttl=ttl)
    # one frame of 0xFFFFFFFF bytes whose value starts at its last byte
    # but one (the payload is never read by the program)
    cat.off = cat.off.copy()
    cat.off[101:] += 0xFFFFFFFF - 8
    cat.val_start = cat.off[:-1] + 2
    cat.val_start[100] = cat.off[100] + 0xFFFFFFFE
    cat.val_start[200] = cat.off[200] + 8      # an empty value: vr == fl
    # a third of the cells expire; those whose ldt lies inside grace,
    # negative ones among them, are kept and converted
    return cat, -(1 << 30), 1 << 30, 8   # valid, 4 + lane 8 + column, ~ts_l


def indices_past_two_to_the_sixteenth(rng):
    lanes, ts = _standard1(70_000, rng)       # bucket 131,072
    return _batch(lanes, ts), 0, 0, 7


def equal_identity_and_timestamp(rng):
    """Three copies of every cell at ONE timestamp with three values:
    n_amb > 0, the round the host tie-breaks (larger value wins)."""
    ids = 120
    lanes, _ = _standard1(ids, rng)
    lanes = np.tile(lanes, (3, 1))
    ts = np.tile(TS0 + np.arange(ids) // 7, 3)
    cat = _batch(lanes, ts)
    cat.payload[np.arange(3 * ids) * 8 + 7] = rng.permutation(3 * ids) % 251
    return cat, 0, 0, 7


def _narrow(k):
    def table(rng):
        n = 500
        lanes = np.zeros((n, k), dtype=np.uint32)
        lanes[:, :4] = rng.integers(0, 1 << 32, (n // 2, 4),
                                    dtype=np.uint32).repeat(2, axis=0)
        lanes[:, k - 3] = rng.integers(16, 21, n)
        ts = TS0 + rng.integers(0, 3_600_000_000, n)
        passes = 1 + len(set(range(4)) | {k - 3}) + 1
        return _batch(lanes, ts), 0, 0, passes
    table.__name__ = f"table_of_{k}_lanes"
    return table


CASES = [no_constant_lane, eight_constant_lanes_a_third_padding,
         constant_at_a_value_the_padding_does_not_hold,
         one_valid_cell_differs, one_valid_cell, ttl_round,
         top_bits_and_negative_columns, indices_past_two_to_the_sixteenth,
         equal_identity_and_timestamp, _narrow(5), _narrow(9)]


def _reference(operands):
    """What the parent's program returns, computed plainly: a stable
    lexicographic sort over ALL sixteen keys, the unchanged reconcile
    over that order, then the compact and convert stages in numpy."""
    o = {k: np.asarray(v) for k, v in operands.items()}
    keys = [np.asarray(k) for k in dmerge._sort_keys(operands)]
    perm = np.lexsort(keys[::-1]).astype(np.int32)    # last key = primary
    packed = np.asarray(dmerge.reconcile_sorted(
        dmerge.reconcile_columns(operands, jnp.asarray(perm)),
        operands["now"], operands["gc_before"]))
    keep, amb, expired, _ = dmerge.unpack_masks(packed)
    order = np.argsort(~keep, kind="stable")
    perm_out = perm[order]
    cols = {k: o[k][perm_out] for k in dw.RESIDENT_COLS}
    exp_out = expired[order]
    cols["flags8"] = np.where(exp_out, cols["flags8"] | cb.FLAG_TOMBSTONE,
                              cols["flags8"]).astype(np.uint8)
    cols["fl"] = np.where(exp_out, cols["vr"], cols["fl"])
    live = o["valid"] == 0
    passes = 1 + sum(int(k[live].min() != k[live].max())
                     for k in keys[1:])
    return (int(keep.sum()), int(amb.sum()), int((expired & keep).sum()),
            passes, perm_out, cols, perm, packed)


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__)
def test_every_array_is_the_unconditional_passes(case):
    cat, gc_before, now, want_passes = case(np.random.default_rng(34))
    operands, _ = dw.build_resident_operands(cat, gc_before, now, None)
    assert (len(dmerge._sort_keys(operands))
            == dmerge.n_sort_keys(cat.n_lanes) == cat.n_lanes + 3)
    got = dw._resident_program(operands)
    want = _reference(operands)
    assert [int(x) for x in got[:4]] == list(want[:4])
    assert int(got[3]) == want_passes
    np.testing.assert_array_equal(np.asarray(got[4]), want[4], "perm_out")
    assert set(got[5]) == set(want[5]) == set(dw.RESIDENT_COLS)
    for name in dw.RESIDENT_COLS:
        np.testing.assert_array_equal(np.asarray(got[5][name]),
                                      want[5][name], name)
    np.testing.assert_array_equal(np.asarray(got[6]), want[6], "perm")
    np.testing.assert_array_equal(np.asarray(got[7]), want[7], "packed")
    for name in dw.RESIDENT_COLS:
        assert got[5][name].dtype == operands[name].dtype, name
    if want[1]:
        # the host's turn: the same program's perm and masks through
        # `_materialize` give what the numpy spec gives
        merged = dw.materialize_round(
            dw.submit_merge_resident([cat], gc_before, now))
        spec = cb.merge_sorted([cat], gc_before, now)
        for f in ("lanes", "ts", "ldt", "ttl", "flags", "off",
                  "val_start", "payload"):
            np.testing.assert_array_equal(getattr(merged, f),
                                          getattr(spec, f), f)


def _gathers(jaxpr, inside=False, out=None):
    """(inside `_lsd_pass`?, the operand's shape) of every gather of a
    traced program, through its nested jits and cond branches."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            out.append((inside, eqn.invars[0].aval.shape))
        here = inside or eqn.params.get("name") == "_lsd_pass"
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _gathers(sub, here, out)
    return out


def test_columns_travel_as_rows_outside_the_passes():
    """A gather is paid per index on the chip: beside the sixteen
    one-lane gathers of the passes (a key through perm) the program
    holds at most three, none of them of a one-dimensional operand
    (before PR 36: twenty, eighteen of them one-lane)."""
    cat, gc_before, now, _ = ttl_round(None)
    operands, _ = dw.build_resident_operands(cat, gc_before, now, None)
    found = _gathers(jax.make_jaxpr(dw._resident_program)(operands).jaxpr)
    passes = [shape for inside, shape in found if inside]
    outside = [shape for inside, shape in found if not inside]
    assert passes == [(1024,)] * 16
    assert 1 <= len(outside) <= 3, outside
    assert all(len(shape) == 2 for shape in outside), outside
    # at 13 lanes both stacked matrices stay within 24 words: at 2^20
    # rows a 25-wide one gathers four times slower on the chip (PERF.md)
    assert sorted(shape[1] for shape in outside) == [22, 23]


def test_the_ttl_round_keeps_and_converts_what_the_host_spec_does():
    """The case above is a real TTL round: 200 winners, 100 of them run
    out inside grace and kept as tombstones."""
    cat, gc_before, now, _ = ttl_round(None)
    operands, _ = dw.build_resident_operands(cat, gc_before, now, None)
    n_keep, n_amb, n_exp_kept = (int(x) for x in
                                 dw._resident_program(operands)[:3])
    assert (n_keep, n_amb, n_exp_kept) == (200, 0, 100)


def test_valid_rows_need_not_start_at_row_zero():
    """Valid rows may start anywhere: the question a pass asks is masked
    by `valid`, never read off row 0."""
    rng = np.random.default_rng(5)
    lanes, ts = _standard1(600, rng)
    operands, _ = dw.build_resident_operands(_batch(lanes, ts), 0, 0, None)
    roll = lambda a: jnp.roll(a, 300, axis=0)
    rolled = {k: (roll(v) if getattr(v, "ndim", 0) else v)
              for k, v in operands.items()}
    got = dw._resident_program(rolled)
    keys = [np.asarray(k) for k in dmerge._sort_keys(rolled)]
    np.testing.assert_array_equal(np.asarray(got[6]),
                                  np.lexsort(keys[::-1]))
    assert int(got[3]) == 7
    assert int(got[0]) == int((np.asarray(got[7]) & 1).sum()) == 600


@pytest.mark.parametrize("collect", ["write_lane", "host_batch"])
def test_counters_and_span_read_what_the_program_returned(collect):
    """`merge.resident.wait` carries items = the passes the sort has and
    cells = the passes this round ran; the two counters add the same."""
    rng = np.random.default_rng(9)
    lanes, ts = _standard1(683, rng)
    cat = _batch(lanes, ts)
    before = {n: METRICS.counter(n) for n in (RUN, SKIPPED)}
    mark = pipeline_ledger.new_task_id()
    h = dw.submit_merge_resident([cat])
    ran = int(h.out[3])
    if collect == "write_lane":
        out = dw.collect_merge_resident(h)
        assert isinstance(out, dw.DeviceRound) and out.n == 683
    else:
        assert len(dw.materialize_round(h)) == 683
    assert ran == 7
    assert METRICS.counter(RUN) - before[RUN] == ran
    assert METRICS.counter(SKIPPED) - before[SKIPPED] == 16 - ran
    waits = [r for r in pipeline_ledger.ring_records()
             if r["id"] > mark and r["name"] == "merge.resident.wait"]
    assert [(r["items"], r["cells"]) for r in waits] == [(16, ran)]
