"""The readers of the program's span ring (program_spans.py and the ten
`program_span` metrics PR 25 added): a number in range on the tiny cells,
None when the span is missing, the warm-up's spans left out, and the
by-hand gap tool's arithmetic."""
import collections
import importlib.util
import os
import sys

import pytest

from conftest import BENCH, run_cell

sys.path.insert(0, BENCH)

COMPACTION = ["write_lane_busy_pct", "write_emit_directory_pct",
              "write_lane_pull_pct", "write_lane_eager_pct",
              "merge_writeq_wait_pct", "merge_resident_padding_pct"]
ANN = ["ann_dispatch_queue_wait_p95_ms", "ann_host_prepare_ms_per_query",
       "ann_device_call_ms_per_query", "ann_rows_read_ms_per_query"]


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"_t_span_{name}", os.path.join(BENCH, "layer_metrics",
                                        name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Ctx:
    def __init__(self, window):
        import stats
        self.window, self.stats = window, stats


@pytest.fixture
def ring(monkeypatch):
    from cassandra_tpu.utils import pipeline_ledger as pl
    r = collections.deque(maxlen=pl.RING_CAP)
    monkeypatch.setattr(pl, "RING", r)
    ids = iter(range(1, 10 ** 6))

    def add(name, start, end, thread="MainThread", kind="busy", parent=0,
            task=1, cells=0, nbytes=0, items=0):
        i = next(ids)
        r.append((name, kind, thread, float(start), float(end), i, parent,
                  task, cells, nbytes, items))
        return i
    return add


def _compaction(add, t0: float, task: int) -> None:
    """One compaction of 10 s from t0: the write lane busy 6 s of it."""
    add("compaction.task", t0, t0 + 10, task=task)
    add("merge.resident.pack", t0 + 0.1, t0 + 0.3, task=task,
        cells=600, items=1024)
    add("compaction.writeq.put_wait", t0 + 1, t0 + 4, kind="stall",
        task=task)
    add("compaction.writeq.drain", t0 + 8, t0 + 9, kind="stall", task=task)
    w = "compact-w"
    add("compaction.writeq.get_wait", t0, t0 + 2, w, "idle", task=task)
    add("write.lane.append", t0 + 2, t0 + 3, w, task=task)
    cut = add("write.lane.cut", t0 + 3, t0 + 6, w, task=task)
    add("write.lane.cut.slice", t0 + 3, t0 + 3.5, w, parent=cut, task=task)
    add("write.lane.cut.pull_lanes", t0 + 3.5, t0 + 4.5, w, parent=cut,
        task=task)
    add("write.lane.cut.kernel_pull", t0 + 4.5, t0 + 5, w, parent=cut,
        task=task)
    emit = add("write.emit", t0 + 6, t0 + 9, w, task=task)
    add("write.emit.directory", t0 + 6, t0 + 8, w, parent=emit, task=task)
    add("write.emit.attempt_wait", t0 + 8, t0 + 9, w, "stall", parent=emit,
        task=task)


def test_compaction_readers_keep_the_windows_spans_only(ring):
    _compaction(ring, 100.0, task=1)            # the warm-up: outside
    _compaction(ring, 200.0, task=2)
    _compaction(ring, 300.0, task=3)
    ring("write.emit.directory", 400.0, 409.0, "compact-w", task=4)  # check
    ops = [{"start": 199.5, "end": 210.5}, {"start": 299.5, "end": 310.5}]
    got = {n: _reader(n).read(Ctx({"ops": ops})) for n in COMPACTION}
    assert got == {
        # busy self-seconds of compact-w: append 1 + cut 3 + emit less
        # its stall 2, of 10
        "write_lane_busy_pct": pytest.approx(60.0),
        "write_emit_directory_pct": pytest.approx(20.0),
        "write_lane_pull_pct": pytest.approx(15.0),
        "write_lane_eager_pct": pytest.approx(15.0),
        "merge_writeq_wait_pct": pytest.approx(40.0),
        "merge_resident_padding_pct": pytest.approx(
            100.0 * (1024 - 600) / 1024)}
    # with the warm-up's and check's spans alone in the ring: nothing
    for n in COMPACTION:
        assert _reader(n).read(Ctx({"ops": [{"start": 500.0,
                                             "end": 510.0}]})) is None, n


@pytest.mark.parametrize("name", COMPACTION + ANN)
def test_reader_returns_none_when_its_span_is_missing(ring, name):
    ring("compaction.task", 200.0, 210.0)
    ring("some.other.span", 201.0, 202.0, "sstable-io")
    req = ring("transport.request", 50.0, 51.0, "cql-exec-1-0")
    ring("cql.execute", 50.0, 51.0, "cql-exec-1-0", parent=req)
    window = {"ops": [{"start": 199.0, "end": 211.0, "sent": 0.0,
                       "done": 1.0, "ok": True}]}
    assert _reader(name).read(Ctx(window)) is None
    assert _reader(name).read(Ctx({})) is None


def _query(add, t0: float, thread: str, wait_s: float) -> None:
    req = add("transport.request", t0, t0 + 1.0, thread, task=7)
    add("transport.queue_wait", t0 - wait_s, t0, thread, "stall",
        parent=req, task=7)
    ex = add("cql.execute", t0, t0 + 1.0, thread, parent=req, task=7)
    add("index.ann.gather", t0, t0 + 0.1, thread, parent=ex, task=7)
    add("index.ann.normalise", t0 + 0.1, t0 + 0.5, thread, parent=ex,
        task=7)
    add("index.ann.call", t0 + 0.5, t0 + 0.8, thread, parent=ex, task=7)
    add("index.ann.pull", t0 + 0.8, t0 + 0.9, thread, "stall", parent=ex,
        task=7)
    add("cql.ann.rows", t0 + 0.9, t0 + 0.95, thread, parent=ex, task=7)


def test_ann_readers_leave_the_warm_up_queries_out(ring):
    # two warm-up queries (slow: they compile and build), ended before the
    # children's common start at t = 100
    _query(ring, 80.0, "cql-exec-1-0", 5.0)
    _query(ring, 90.0, "cql-exec-1-0", 5.0)
    # a request that is no vector query
    req = ring("transport.request", 100.5, 100.6, "cql-exec-1-1")
    ring("cql.execute", 100.5, 100.6, "cql-exec-1-1", parent=req)
    for i in range(4):
        _query(ring, 101.0 + i, f"cql-exec-1-{i % 2}", 0.010 * (i + 1))
    ops = [{"sent": 1.0 + i - 0.01 * (i + 1), "done": 2.0 + i, "ok": True}
           for i in range(4)]
    got = {n: _reader(n).read(Ctx({"ops": ops})) for n in ANN}
    assert got == {
        "ann_dispatch_queue_wait_p95_ms": pytest.approx(40.0, abs=2.0),
        "ann_host_prepare_ms_per_query": pytest.approx(500.0),
        "ann_device_call_ms_per_query": pytest.approx(400.0),
        "ann_rows_read_ms_per_query": pytest.approx(50.0)}
    import program_spans
    assert len(program_spans.window_queries(ops)) == 4


CELLS = {"stcs_lz4.major": COMPACTION, "glove_100.ann_top10": ANN}
ONE_THREAD_PCT = {"write_lane_busy_pct", "write_emit_directory_pct",
                  "write_lane_pull_pct", "write_lane_eager_pct",
                  "merge_writeq_wait_pct", "merge_resident_padding_pct"}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_traced_tiny_cell_reports_every_new_metric_in_range(
        tiny_tree, tmp_path, cell):
    rc, line, err = run_cell(tiny_tree, cell, seed=3000000025, trace=1,
                             tmp=str(tmp_path))
    assert rc == 0 and line is not None and line["correct"], err[-3000:]
    for name in CELLS[cell]:
        assert name in line["metrics"], (name, sorted(line["metrics"]))
        value = line["metrics"][name]["value"]
        assert value >= 0.0, (name, value)
        if name in ONE_THREAD_PCT:
            assert value <= 100.0, (name, value)
    # the benchmark's own gap attribution is untouched: trace_reduce
    # reads `bench.` spans only
    assert all(n.startswith("bench.") or n == "outside_benchmark_spans"
               for n, _s in line["breakdown"]["idle_gaps"])


def test_gap_tool_self_time_and_innermost_span():
    import span_gaps
    spans = [("ctpu.a", 0.0, 100.0), ("ctpu.a.b", 10.0, 40.0),
             ("ctpu.a.b.c", 20.0, 30.0), ("ctpu.a.d", 50.0, 60.0),
             ("ctpu.e", 200.0, 300.0)]
    nested = span_gaps.nest(list(reversed(spans)))
    assert [(n, p) for n, _s, _e, p in nested] == [
        ("ctpu.a", None), ("ctpu.a.b", 0), ("ctpu.a.b.c", 1),
        ("ctpu.a.d", 0), ("ctpu.e", None)]
    own = span_gaps.self_times(nested)
    assert own["ctpu.a"] == [1, pytest.approx(1e-7), pytest.approx(6e-8)]
    assert own["ctpu.a.b"][2] == pytest.approx(2e-8)
    assert span_gaps.innermost_at(
        nested, [5.0, 25.0, 35.0, 45.0, 55.0, 150.0, 250.0, 350.0]) == [
        "ctpu.a", "ctpu.a.b.c", "ctpu.a.b", "ctpu.a", "ctpu.a.d", "-",
        "ctpu.e", "-"]
    assert span_gaps.covered([(10.0, 40.0), (30.0, 60.0)], 0.0,
                             100.0) == pytest.approx(0.5)
