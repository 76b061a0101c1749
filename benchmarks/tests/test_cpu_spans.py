"""The off-CPU and GIL hand-off readers (PR 35; cpu_spans.py and the seven
metrics that read it): the right number on a hand-made ring of each driver
shape, None on parent-shaped records (no `cpu`), on an empty window and on
a wrapped ring; and one tiny run per driver shape with the new metrics on
a traced run's line, absent (rc 0, correct) from a program that writes the
parent's records."""
import collections
import json
import os
import sys

import pytest

from conftest import BENCH, run_cell
from test_program_spans import Ctx, _reader
from test_twcs_tiny import twcs_tree  # noqa: F401
from test_ycsb_rf3_tiny import rf3_tree  # noqa: F401
from test_ycsb_tiny import ON_A_TPU, ycsb_tree  # noqa: F401

sys.path.insert(0, BENCH)

HANDOFF = "runtime.gil.handoff"
NEW = {
    "stcs_lz4.major": {"gil_handoff_mean_ms", "write_lane_off_cpu_pct"},
    "twcs_ttl.major": {"gil_handoff_mean_ms", "write_lane_off_cpu_pct"},
    "glove_100.ann_top10": {"serve_gil_handoff_mean_ms",
                            "ann_rows_read_off_cpu_ms_per_query"},
    "ycsb_a.wire": {"serve_gil_handoff_mean_ms",
                    "ycsb_compact_w_off_cpu_pct",
                    "ycsb_engine_read_off_cpu_ms_per_read"},
    "ycsb_a.wire_rf3": {"serve_gil_handoff_mean_ms",
                        "rf3_engine_read_off_cpu_ms_per_read"},
}
FIELDS = ("name", "kind", "thread", "start", "end", "id", "parent", "task",
          "cells", "bytes", "items", "cpu")


class Records:
    """A hand-made ring: `with_cpu=False` makes the parent's records
    (eleven fields)."""

    def __init__(self, with_cpu: bool = True):
        self.with_cpu, self.rows = with_cpu, []

    def add(self, name, start, end, cpu, thread="MainThread", kind="busy",
            parent=0, task=1):
        row = (name, kind, thread, float(start), float(end),
               len(self.rows) + 1, parent, task, 0, 0, 0, cpu)
        self.rows.append(row if self.with_cpu else row[:-1])
        return len(self.rows)

    def dicts(self) -> list:
        return [dict(zip(FIELDS, r)) for r in self.rows]

    def into_ring(self, monkeypatch, cap=None):
        from cassandra_tpu.utils import pipeline_ledger as pl
        cap = cap or pl.RING_CAP
        monkeypatch.setattr(pl, "RING_CAP", cap)
        monkeypatch.setattr(pl, "RING",
                            collections.deque(self.rows, maxlen=cap))


def test_the_bench_declares_the_seven_metrics(bench_json):
    mine = {m["name"]: m for m in bench_json["per_layer"]
            if m["name"] in set().union(*NEW.values())}
    assert len(mine) == 7 and len(bench_json["per_layer"]) == 61
    for cell, names in NEW.items():
        assert names == {n for n, m in mine.items()
                         if cell in m["workloads"]}
    assert all(m["source"] == "program_span" and m["better"] == "lower"
               for m in mine.values())
    assert [m["name"] for m in bench_json["per_layer"][-7:]] == list(mine)


# ------------------------------------------- major_loop and twcs_cycle --

OPS = [{"start": 199.5, "end": 210.5}, {"start": 299.5, "end": 310.5}]


def _compaction(rec: Records, t0: float, handoff: float) -> None:
    """A compaction of 10 s from t0. `compact-w`: append 1 s on the CPU
    0.25; a cut of 3 s = slice 0.5 (CPU 0.125), the two pulls 1.5 (CPU 0:
    they wait for the device) and 1 s of its own on the CPU 0.5; a parked
    second (idle) that counts for nobody. Self off-CPU of its busy spans
    without the pulls: 0.75 + 0.375 + 0.5 = 1.625 s. Three beats of the
    probe, `handoff` seconds each."""
    rec.add("compaction.task", t0, t0 + 10, 4.0, task=t0)
    w = "compact-w"
    rec.add("compaction.writeq.get_wait", t0, t0 + 1, 0.0, w, "idle",
            task=t0)
    rec.add("write.lane.append", t0 + 2, t0 + 3, 0.25, w, task=t0)
    cut = rec.add("write.lane.cut", t0 + 3, t0 + 6, 0.625, w, task=t0)
    rec.add("write.lane.cut.slice", t0 + 3, t0 + 3.5, 0.125, w, parent=cut,
            task=t0)
    rec.add("write.lane.cut.pull_lanes", t0 + 3.5, t0 + 4.5, 0.0, w,
            parent=cut, task=t0)
    rec.add("write.lane.cut.kernel_pull", t0 + 4.5, t0 + 5, 0.0, w,
            parent=cut, task=t0)
    for i in range(3):
        rec.add(HANDOFF, t0 + 1 + i, t0 + 1 + i + handoff, 1e-5,
                "gil-probe", "stall", task=0)


@pytest.mark.parametrize("with_cpu", [True, False])
def test_compaction_readers(monkeypatch, with_cpu):
    rec = Records(with_cpu)
    _compaction(rec, 100.0, 0.5)          # the warm-up: left out
    _compaction(rec, 200.0, 0.002)
    _compaction(rec, 300.0, 0.004)
    if not with_cpu:                      # the parent has no probe either
        rec.rows = [r for r in rec.rows if r[0] != HANDOFF]
    rec.into_ring(monkeypatch)
    ctx = Ctx({"ops": OPS})
    got = {n: _reader(n).read(ctx) for n in NEW["stcs_lz4.major"]}
    if not with_cpu:
        assert got == {n: None for n in got}
        # the readers that were there read the parent's records as before
        assert _reader("write_lane_busy_pct").read(ctx) == \
            pytest.approx(100.0 * 4.0 / 10.0)
        return
    assert got["gil_handoff_mean_ms"] == pytest.approx(3.0)
    assert got["write_lane_off_cpu_pct"] == pytest.approx(16.25)
    assert got["write_lane_off_cpu_pct"] <= \
        _reader("write_lane_busy_pct").read(ctx)


def test_compaction_readers_where_one_root_in_n_reads_the_clock(
        monkeypatch):
    """A host whose thread clock is dear: the second compaction's spans
    carry no `cpu`. The read half's off-CPU share of its busy seconds
    (1.625 of 4.0 s) stands for the lane's 8.0 busy seconds of the 20 s."""
    rec = Records()
    _compaction(rec, 200.0, 0.002)
    _compaction(rec, 300.0, 0.004)
    rec.rows = [r if r[3] < 300 or r[0] in (HANDOFF, "compaction.task")
                else r[:-1] + (None,) for r in rec.rows]
    rec.into_ring(monkeypatch)
    ctx = Ctx({"ops": OPS})
    assert _reader("write_lane_off_cpu_pct").read(ctx) == \
        pytest.approx(100.0 * (1.625 / 4.0) * (8.0 / 20.0))
    assert _reader("gil_handoff_mean_ms").read(ctx) == pytest.approx(3.0)


def test_compaction_readers_read_none_when_there_is_nothing(monkeypatch):
    names = sorted(NEW["stcs_lz4.major"])
    Records().into_ring(monkeypatch)
    for ctx in (Ctx({}), Ctx({"ops": []}), Ctx({"ops": OPS})):
        assert [_reader(n).read(ctx) for n in names] == [None, None]
    # a ring that wrapped inside the window: full, and its oldest record
    # ended after the first operation began
    rec = Records()
    _compaction(rec, 200.0, 0.002)
    _compaction(rec, 300.0, 0.002)
    rec.rows = rec.rows[3:]
    rec.into_ring(monkeypatch, cap=len(rec.rows))
    assert [_reader(n).read(Ctx({"ops": OPS})) for n in names] == \
        [None, None]
    # the same records in a ring with room read a number
    rec.into_ring(monkeypatch, cap=len(rec.rows) + 1)
    assert _reader("gil_handoff_mean_ms").read(Ctx({"ops": OPS})) == \
        pytest.approx(2.0)


# ----------------------------------------- wire_ycsb, wire_ycsb_cluster --

def _served_window(with_cpu: bool) -> dict:
    """Released at 1,000 s, 20 s long. Two reads whose `engine.read` (30
    and 50 ms) ran 10 and 20 ms of it, an update, a read of the warm-up and
    one of `check` (outside); a served compaction of 10 s whose
    `compact-w` appends 4 s on the CPU 1 s and pulls for 2 s; beats of 1,
    2 and 6 ms inside the window and one of 500 ms before it."""
    rec = Records(with_cpu)

    def request(t0, span, wall, cpu, thread="cql-exec-9042-0"):
        req = rec.add("transport.request", t0, t0 + wall + 0.002, cpu,
                      thread, task=77)
        ex = rec.add("cql.execute", t0 + 0.001, t0 + wall + 0.001, cpu,
                     thread, parent=req, task=77)
        rec.add(span, t0 + 0.001, t0 + 0.001 + wall, cpu, thread,
                parent=ex, task=77)
    request(990.0, "engine.read", 0.9, 0.1)
    request(1001.0, "engine.read", 0.030, 0.010)
    request(1002.0, "engine.read", 0.050, 0.020, "cql-exec-9042-1")
    request(1003.0, "engine.write", 0.004, 0.003)
    request(1030.0, "engine.read", 0.7, 0.1)
    rec.add("compaction.task", 1005.0, 1015.0, 3.0, "compact-0", task=5)
    rec.add("write.lane.append", 1006.0, 1010.0, 1.0, "compact-w", task=5)
    rec.add("write.lane.cut.pull_lanes", 1010.0, 1012.0, 0.0, "compact-w",
            task=5)
    for t0, wall in ((995.0, 0.5), (1001.5, 0.001), (1008.0, 0.002),
                     (1012.0, 0.006)):
        if with_cpu:
            rec.add(HANDOFF, t0, t0 + wall, 1e-5, "gil-probe", "stall",
                    task=0)
    ops = [{"ok": True, "kind": k, "done": d}
           for k, d in (("read", 1.1), ("read", 2.1), ("update", 3.1))]
    return {"spans": rec.dicts(), "release_perf": 1000.0,
            "elapsed_s": 20.0, "ops": ops}


def test_served_readers():
    ctx = Ctx(_served_window(True))
    got = {n: _reader(n).read(ctx)
           for n in NEW["ycsb_a.wire"] | NEW["ycsb_a.wire_rf3"]}
    assert got["serve_gil_handoff_mean_ms"] == pytest.approx(3.0)
    assert got["ycsb_engine_read_off_cpu_ms_per_read"] == \
        pytest.approx((20.0 + 30.0) / 2)
    assert got["rf3_engine_read_off_cpu_ms_per_read"] == \
        pytest.approx((20.0 + 30.0) / 2)
    assert got["ycsb_compact_w_off_cpu_pct"] == pytest.approx(30.0)
    # beside the wall's readers: the off-CPU part is inside them
    assert _reader("ycsb_engine_read_ms_per_read").read(ctx) == \
        pytest.approx(40.0)
    assert _reader("ycsb_compact_w_busy_pct").read(ctx) == \
        pytest.approx(60.0)


@pytest.mark.parametrize("window", [
    _served_window(False),                              # the parent's
    dict(_served_window(True), spans=None),             # the ring wrapped
    dict(_served_window(True), spans=[]),
    dict(_served_window(True), elapsed_s=None),
    {}], ids=["parent", "wrapped", "no_spans", "no_window", "empty"])
def test_served_readers_read_none_when_there_is_nothing(window):
    ctx = Ctx(window)
    for n in sorted(NEW["ycsb_a.wire"] | NEW["ycsb_a.wire_rf3"]):
        assert _reader(n).read(ctx) is None, n
    if window.get("spans") and window.get("elapsed_s"):
        assert _reader("ycsb_engine_read_ms_per_read").read(ctx) == \
            pytest.approx(40.0)


# ----------------------------------------------------- wire_closedloop --

def _query(rec: Records, t0: float, thread: str, rows_cpu) -> None:
    """A vector query of 1 s from t0 whose `cql.ann.rows` takes 0.4 s."""
    req = rec.add("transport.request", t0, t0 + 1.0, 0.5, thread, task=7)
    ex = rec.add("cql.execute", t0, t0 + 1.0, 0.5, thread, parent=req,
                 task=7)
    rec.add("index.ann.call", t0 + 0.1, t0 + 0.2, 0.05, thread, parent=ex,
            task=7)
    rec.add("cql.ann.rows", t0 + 0.5, t0 + 0.9, rows_cpu, thread,
            parent=ex, task=7)


CLOSED_OPS = [{"sent": 0.0, "done": 1.0, "ok": True},
              {"sent": 0.0, "done": 2.0, "ok": True}]


@pytest.mark.parametrize("with_cpu", [True, False])
def test_closedloop_readers(monkeypatch, with_cpu):
    rec = Records(with_cpu)
    _query(rec, 40.0, "cql-exec-1-0", 0.0)       # the warm-up's: left out
    _query(rec, 50.0, "cql-exec-1-0", 0.3)       # released at 50.0
    _query(rec, 51.0, "cql-exec-1-1", 0.1)       # the window ends at 52.0
    if with_cpu:
        for t0, wall in ((45.0, 0.5), (50.5, 0.0002), (51.5, 0.0004),
                         (53.0, 0.5)):
            rec.add(HANDOFF, t0, t0 + wall, 1e-5, "gil-probe", "stall",
                    task=0)
    rec.into_ring(monkeypatch)
    ctx = Ctx({"ops": CLOSED_OPS})
    got = {n: _reader(n).read(ctx) for n in NEW["glove_100.ann_top10"]}
    if not with_cpu:
        assert got == {n: None for n in got}
        assert _reader("ann_rows_read_ms_per_query").read(ctx) == \
            pytest.approx(400.0)
        return
    assert got["serve_gil_handoff_mean_ms"] == pytest.approx(0.3)
    assert got["ann_rows_read_off_cpu_ms_per_query"] == \
        pytest.approx((100.0 + 300.0) / 2)


def test_closedloop_readers_read_none_when_there_is_nothing(monkeypatch):
    names = sorted(NEW["glove_100.ann_top10"])
    Records().into_ring(monkeypatch)
    for ctx in (Ctx({}), Ctx({"ops": []}), Ctx({"ops": CLOSED_OPS})):
        assert [_reader(n).read(ctx) for n in names] == [None, None]


def test_closedloop_readers_read_what_a_wrapped_ring_still_holds(
        monkeypatch):
    """The window alone fills the ring: the queries and beats that began
    after the ring's oldest record ended are whole, the rest is left
    out."""
    rec = Records()
    _query(rec, 50.0, "cql-exec-1-0", 0.3)
    rec.add(HANDOFF, 50.05, 50.15, 1e-5, "gil-probe", "stall", task=0)
    _query(rec, 51.0, "cql-exec-1-1", 0.1)
    rec.add(HANDOFF, 51.5, 51.5004, 1e-5, "gil-probe", "stall", task=0)
    whole, ctx = list(rec.rows), Ctx({"ops": CLOSED_OPS})
    rec.into_ring(monkeypatch, cap=len(whole) + 1)   # room: nothing lost
    assert _reader("serve_gil_handoff_mean_ms").read(ctx) == \
        pytest.approx((100.0 + 0.4) / 2)
    assert _reader("ann_rows_read_off_cpu_ms_per_query").read(ctx) == \
        pytest.approx((100.0 + 300.0) / 2)
    # the first query lost its first two spans: the ring's floor is the
    # end of its `index.ann.call`, 50.2 s
    rec.rows = whole[2:]
    rec.into_ring(monkeypatch, cap=len(rec.rows))
    assert _reader("serve_gil_handoff_mean_ms").read(ctx) == \
        pytest.approx(0.4)
    assert _reader("ann_rows_read_off_cpu_ms_per_query").read(ctx) == \
        pytest.approx(300.0)


# ------------------------------------------------ whole runs, tiny size --

# the parent of PR 35: records of eleven fields, and no probe
AS_THE_PARENT = """
from cassandra_tpu.utils import gil_probe as _gp, pipeline_ledger as _pl
_pl.RECORD_FIELDS = _pl.RECORD_FIELDS[:-1]
_gp.GLOBAL.set_demand = lambda owner, on: None
"""
TREES = {"stcs_lz4.major": ("tiny_tree", "", 2.0),
         "glove_100.ann_top10": ("tiny_tree", "", 2.0),
         "twcs_ttl.major": ("twcs_tree", ON_A_TPU, 3.0),
         "ycsb_a.wire": ("ycsb_tree", ON_A_TPU, 4.0),
         "ycsb_a.wire_rf3": ("rf3_tree", ON_A_TPU, 4.0)}


@pytest.mark.parametrize("cell", sorted(TREES))
@pytest.mark.parametrize("program", ["change", "parent"])
def test_a_traced_tiny_run_prints_the_new_metrics(request, tmp_path, cell,
                                                  program):
    fixture, patch, seconds = TREES[cell]
    tree = request.getfixturevalue(fixture)
    if program == "parent":
        patch += AS_THE_PARENT
    rc, line, err = run_cell(tree, cell, seed=3000003500, seconds=seconds,
                             trace=1, patch=patch, tmp=str(tmp_path))
    assert rc == 0 and line is not None, err[-3000:]
    assert line["correct"] is True, err[-3000:]
    assert line["breakdown"]["compiles_in_window"] == []
    with open(os.path.join(tree, "BENCHMARK.json")) as f:
        declared = {m["name"] for m in json.load(f)["per_layer"]
                    if cell in m.get("workloads", [])}
    assert NEW[cell] <= declared
    seen = set(line["metrics"]) & NEW[cell]
    if program == "parent":
        assert not seen
        assert set(line["metrics"]) - NEW[cell]     # the others still read
        return
    assert seen == NEW[cell]
    for n in seen:
        v = line["metrics"][n]["value"]
        assert v >= 0, (n, v)
        if n.endswith("_pct"):
            assert v <= 100
