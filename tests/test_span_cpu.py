"""A span's CPU clock and the GIL hand-off probe (PR 35): the ring
record's twelfth field, self-CPU arithmetic, `Stage.busy_cpu_s` and its
surfaces, the real thread clock on sleeping, spinning and worker threads,
and the probe's beats, rate and demand counting. Every wait here carries
its own timeout; a probe test holds the probe well under 2 s."""
from __future__ import annotations

import json
import statistics
import sys
import threading
import time

import pytest

from cassandra_tpu.compaction.task import CompactionTask
from cassandra_tpu.service.metrics import GLOBAL as METRICS
from cassandra_tpu.storage.engine import StorageEngine
from cassandra_tpu.tools import nodetool
from cassandra_tpu.utils import gil_probe
from cassandra_tpu.utils import pipeline_ledger as pl

from test_spans import (FakeClock, _records, _store,  # noqa: F401
                        clock, ring)

HANDOFF = gil_probe.HANDOFF_SPAN


@pytest.fixture
def cpu(monkeypatch):
    c = FakeClock()
    c.t = 7.0
    monkeypatch.setattr(pl, "CPU_CLOCK", c)
    return c


def _probe_threads() -> list:
    return [t for t in threading.enumerate()
            if t.name == gil_probe.THREAD_NAME]


def _handoffs(ring_) -> list:
    return [r for r in _records(ring_) if r["name"] == HANDOFF]


# ------------------------------------------------------- the fake clocks --

def test_the_records_last_field_is_cpu(clock, cpu, ring):
    assert pl.RECORD_FIELDS[-1] == "cpu" and len(pl.RECORD_FIELDS) == 12
    assert pl.RECORD_FIELDS[:11] == (
        "name", "kind", "thread", "start", "end", "id", "parent", "task",
        "cells", "bytes", "items")
    with pl.span("t.cpu", items=3) as sp:
        clock.t += 2.0
        cpu.t += 0.5
    (rec,) = ring
    assert len(rec) == 12 and rec[-1] == 0.5 == sp.cpu_s
    assert _records(ring)[0]["cpu"] == 0.5 and sp.seconds == 2.0
    assert pl.ring_records()[0]["cpu"] == 0.5


def test_parent_child_self_cpu_arithmetic(clock, cpu, ring):
    """cpu, like seconds, includes the children: a span's self CPU is its
    own less theirs, and wall - cpu is the time its thread did not run."""
    with pl.span("t.root"):
        clock.t += 1.0
        cpu.t += 1.0                      # ran
        with pl.span("t.child"):
            clock.t += 4.0
            cpu.t += 0.25                 # mostly did not
            with pl.span("t.wait", kind="stall"):
                clock.t += 2.0            # parked: no CPU at all
        clock.t += 1.0
        cpu.t += 0.5
    recs = {r["name"]: r for r in _records(ring)}
    assert recs["t.wait"]["cpu"] == 0.0
    assert recs["t.child"]["cpu"] == 0.25
    assert recs["t.root"]["cpu"] == 1.75
    by_id = {r["id"]: r for r in recs.values()}
    self_cpu = {i: r["cpu"] for i, r in by_id.items()}
    for r in recs.values():
        if r["parent"] in by_id:
            self_cpu[r["parent"]] -= r["cpu"]
    assert self_cpu[recs["t.root"]["id"]] == pytest.approx(1.5)
    off = {n: r["end"] - r["start"] - r["cpu"] for n, r in recs.items()}
    assert off == {"t.wait": 2.0, "t.child": 5.75, "t.root": 6.25}


def test_a_back_dated_span_has_no_cpu(clock, cpu, ring):
    stamp = clock.t
    clock.t += 2.0
    cpu.t += 1.0
    st = pl.ledger("spantest").stage("cpu_queue")
    with pl.span("t.request"):
        with st.stall("t.queue_wait", since=stamp) as wait:
            pass
        clock.t += 1.0
        cpu.t += 0.5
    got = {r["name"]: r["cpu"] for r in _records(ring)}
    assert got == {"t.queue_wait": None, "t.request": 0.5}
    assert wait.cpu_s is None and wait.seconds == 2.0


def test_stage_busy_cpu_and_its_gauge_carry_the_rings_seconds(clock, cpu,
                                                              ring):
    st = pl.ledger("spantest").stage("cpu_stage")
    before = st.snapshot()
    with st.busy():
        clock.t += 1.0
        cpu.t += 0.375
    with st.busy("spantest.cpu_stage.part"):
        clock.t += 0.5
        cpu.t += 0.125
    with st.stall():                      # a stall bills no CPU seconds
        clock.t += 3.0
        cpu.t += 0.0625
    with st.idle():
        clock.t += 1.0
    st.add_busy(0.25)                     # timed by hand: wall only
    snap = st.snapshot()
    in_ring = sum(r["cpu"] for r in _records(ring) if r["kind"] == "busy")
    assert in_ring == 0.5
    assert snap["busy_cpu_s"] - before["busy_cpu_s"] == pytest.approx(0.5)
    assert snap["busy_s"] - before["busy_s"] == pytest.approx(1.75)
    assert snap["stall_s"] - before["stall_s"] == pytest.approx(3.0)
    assert METRICS.snapshot()["pipeline.spantest.cpu_stage.busy_cpu_s"] \
        == snap["busy_cpu_s"]
    assert pl.snapshot_all()["spantest"]["cpu_stage"]["busy_cpu_s"] \
        == snap["busy_cpu_s"]
    st.reset()
    assert st.snapshot()["busy_cpu_s"] == 0.0


def test_pipelinestats_and_the_vtable_show_busy_cpu(tmp_path, clock, cpu):
    eng = StorageEngine(str(tmp_path / "e"))
    try:
        st = pl.ledger("spantest").stage("cpu_shown")
        with st.busy():
            clock.t += 2.0
            cpu.t += 0.75
        want = st.snapshot()["busy_cpu_s"]
        assert want >= 0.75
        assert nodetool.pipelinestats(eng)["spantest"]["cpu_shown"][
            "busy_cpu_s"] == want
        rows = {(r["pipeline"], r["stage"]): r
                for r in eng.virtual_tables.get(
                    "system_views", "pipelines").rows()}
        row = rows[("spantest", "cpu_shown")]
        assert row["busy_cpu_seconds"] == want <= row["busy_seconds"]
    finally:
        eng.close()


def test_boundaries_close_together_share_one_reading(clock, ring,
                                                     monkeypatch):
    """A boundary under CPU_REUSE_S after a READING of the thread clock
    takes that reading plus the wall since; the estimate never moves the
    anchor, and a later boundary reads again."""
    reads = []

    def counting():
        reads.append(clock.t)
        return 50.0 + (clock.t - 100.0) / 2     # on the CPU half the time
    monkeypatch.setattr(pl, "CPU_CLOCK", counting)
    monkeypatch.setattr(pl._TLS, "cpu_at", None, raising=False)
    step = pl.CPU_REUSE_S / 4
    with pl.span("t.a"):                  # reads at 100.0
        clock.t += step
        with pl.span("t.b"):              # reuses: + 1 step of wall
            clock.t += step
        clock.t += step                   # t.b's exit, t.a's exit: reuse
    assert reads == [100.0]
    recs = {r["name"]: r for r in _records(ring)}
    assert recs["t.b"]["cpu"] == pytest.approx(step)
    assert recs["t.a"]["cpu"] == pytest.approx(3 * step)
    clock.t += 2 * step                   # 5 steps after the reading
    with pl.span("t.c"):                  # past the window: reads again
        clock.t += 1.0                    # and again
    assert reads == pytest.approx([100.0, 100.0 + 5 * step,
                                   101.0 + 5 * step])
    assert _records(ring)[-1]["cpu"] == pytest.approx(0.5)


def test_a_dear_thread_clock_is_read_for_one_root_in_n(clock, cpu, ring,
                                                       monkeypatch):
    """Where one reading costs more than the budget, one root span in
    `_CPU_EVERY` reads the clock, with everything below it; the others'
    `cpu` is None and the stage's CPU seconds are scaled up."""
    calls = []

    def dear():                                   # 6.3 µs a reading
        calls.append(1)
        clock.t += 6.3e-6
        return 0.0
    monkeypatch.setattr(pl, "_CPU_EVERY", 0)      # restored at the end
    monkeypatch.setattr(pl, "CPU_CLOCK", dear)
    assert pl._calibrate() == 9 == pl._CPU_EVERY and len(calls) == 7
    monkeypatch.setattr(pl, "CPU_CLOCK", cpu)
    monkeypatch.setattr(pl, "_CPU_EVERY", 4)
    st = pl.ledger("spantest").stage("cpu_dear")
    before = st.snapshot()["busy_cpu_s"]
    for _ in range(400):
        with st.busy("t.root"):
            clock.t += 1.0
            cpu.t += 0.25
            with pl.span("t.child"):
                clock.t += 1.0
                cpu.t += 0.5
    recs = _records(ring)
    by_id = {r["id"]: r for r in recs}
    roots = [r for r in recs if r["name"] == "t.root"]
    read = [r for r in roots if r["cpu"] is not None]
    assert 60 <= len(read) <= 140                 # about one in four
    assert all(r["cpu"] == pytest.approx(0.75) for r in read)
    for r in recs:
        if r["name"] == "t.child":                # as its root decided
            want = 0.5 if by_id[r["parent"]]["cpu"] is not None else None
            assert r["cpu"] == (pytest.approx(want) if want else None)
    # an estimate of all 400 roots' 0.75 s from those that read
    assert st.snapshot()["busy_cpu_s"] - before == \
        pytest.approx(4 * 0.75 * len(read))
    # a stock kernel's clock is cheap: every span reads it
    monkeypatch.setattr(pl, "CPU_CLOCK", time.thread_time)
    assert pl._calibrate() == 1


# ------------------------------------------------------- the real clocks --

def _spin_cpu(seconds: float) -> None:
    """Burn `seconds` of THIS thread's CPU (by its own clock: a loaded
    machine stretches the wall, not this)."""
    t0 = time.thread_time()
    while time.thread_time() - t0 < seconds:
        pass


@pytest.mark.parametrize("body, low, high", [
    (lambda: time.sleep(0.05), 0.0, 0.010),
    (lambda: _spin_cpu(0.05), 0.030, 10.0)], ids=["sleeps", "spins"])
def test_a_span_reads_its_threads_cpu(ring, body, low, high):
    with pl.span("t.real") as sp:
        body()
    assert sp.seconds >= 0.05
    assert low <= sp.cpu_s <= high
    assert sp.cpu_s <= sp.seconds + 0.005
    assert _records(ring)[0]["cpu"] == sp.cpu_s


def test_a_worker_threads_span_reads_that_threads_cpu(ring):
    """The worker sleeps inside its span while the main thread burns the
    process's CPU: a process clock would read the main thread's."""
    go, done = threading.Event(), threading.Event()

    def worker():
        go.wait(5)
        with pl.span("t.on_worker"):
            time.sleep(0.05)
        done.set()
    t = threading.Thread(target=worker, name="span-cpu-worker")
    t.start()
    with pl.span("t.on_main") as main:
        go.set()
        deadline = time.monotonic() + 5
        while not done.is_set() and time.monotonic() < deadline:
            pass
    t.join(5)
    assert done.is_set() and not t.is_alive()
    recs = {r["name"]: r for r in _records(ring)}
    assert recs["t.on_worker"]["thread"] == "span-cpu-worker"
    assert recs["t.on_worker"]["end"] - recs["t.on_worker"]["start"] >= 0.05
    assert recs["t.on_worker"]["cpu"] < 0.010
    assert main.cpu_s > 0.020


# -------------------------------------------------------------- the probe --

def _beat_for(seconds: float, ring_) -> tuple:
    """A probe of this test's own held for `seconds`: (its records, the
    seconds it was held)."""
    probe = gil_probe.GilProbe()
    t0 = time.monotonic()
    probe.set_demand("test", True)
    try:
        assert probe.running and len(_probe_threads()) >= 1
        time.sleep(seconds)
    finally:
        probe.set_demand("test", False)
    held = time.monotonic() - t0
    assert not probe.running
    return _handoffs(ring_), held


def test_probe_alone_hands_off_in_microseconds(ring):
    hist = METRICS.hist(HANDOFF)
    count0 = hist.count
    beats, _held = _beat_for(0.55, ring)
    assert len(beats) >= 3
    for r in beats:
        assert r["kind"] == "stall" and r["thread"] == gil_probe.THREAD_NAME
        assert r["parent"] == 0 and r["task"] == 0
        assert r["cpu"] is not None and r["cpu"] >= 0.0
    assert statistics.median(r["end"] - r["start"] for r in beats) < 0.001
    # the same seconds went to the histogram, one update a beat
    assert hist.count - count0 >= len(beats)
    assert METRICS.snapshot()[HANDOFF + ".count"] == hist.count


def test_probe_leaves_at_most_ten_records_a_second(ring):
    beats, held = _beat_for(0.45, ring)
    assert 1 <= len(beats) <= 1 + held / gil_probe.BEAT_S
    assert gil_probe.BEAT_S == 0.1
    starts = [r["start"] for r in beats]
    assert all(b - a >= gil_probe.BEAT_S * 0.99
               for a, b in zip(starts, starts[1:]))


def test_probe_beside_python_hogs_pays_the_switch_interval(ring):
    stop = threading.Event()

    def hog():
        n = 0
        while not stop.is_set():
            n += 1
    hogs = [threading.Thread(target=hog, daemon=True, name=f"gil-hog-{i}")
            for i in range(2)]
    for h in hogs:
        h.start()
    try:
        beats, _held = _beat_for(0.6, ring)
    finally:
        stop.set()
        for h in hogs:
            h.join(5)
    assert not any(h.is_alive() for h in hogs)
    assert len(beats) >= 2
    walls = [r["end"] - r["start"] for r in beats]
    assert statistics.median(walls) >= 0.5 * sys.getswitchinterval()
    # it waited, it did not run: the wait is not in its CPU seconds
    assert sum(r["cpu"] for r in beats) < 0.5 * sum(walls)


def test_probe_demand_counting_first_starts_last_stops():
    probe = gil_probe.GilProbe()
    before = len(_probe_threads())
    assert not probe.running
    probe.set_demand("a", True)
    probe.set_demand("b", True)
    probe.set_demand("a", True)           # the same owner twice: one demand
    try:
        assert probe.running and len(_probe_threads()) == before + 1
        probe.set_demand("a", False)
        assert probe.running and len(_probe_threads()) == before + 1
        probe.set_demand("nobody", False)
        assert probe.running
    finally:
        probe.set_demand("b", False)
    assert not probe.running and len(_probe_threads()) == before
    probe.set_demand("b", False)          # releasing twice is harmless
    assert not probe.running


def test_three_engines_share_one_probe_thread(tmp_path):
    assert not gil_probe.GLOBAL.running and not _probe_threads()
    engines = []
    try:
        for i in range(3):
            engines.append(StorageEngine(str(tmp_path / f"e{i}")))
            assert gil_probe.GLOBAL.running and len(_probe_threads()) == 1
        engines.pop().close()
        engines.pop().close()
        assert gil_probe.GLOBAL.running and len(_probe_threads()) == 1
    finally:
        for eng in engines:
            eng.close()
    assert not gil_probe.GLOBAL.running and not _probe_threads()


def test_a_compaction_task_holds_the_probe_and_leaves_none_behind(tmp_path,
                                                                  ring):
    """A bare ColumnFamilyStore compacts with no engine open
    (stcs_lz4.major): the task itself is the demand."""
    cfs = _store(tmp_path, "probe", n_ssts=2, n_per=20_000)
    assert not _probe_threads()
    task = CompactionTask(cfs, cfs.tracker.view(), engine="numpy",
                          mesh_devices=0)
    task.execute()
    for r in cfs.live_sstables():
        r.close()
    assert not gil_probe.GLOBAL.running and not _probe_threads()
    recs = _records(ring)
    (root,) = [r for r in recs if r["name"] == "compaction.task"]
    beats = _handoffs(ring)
    assert beats and all(root["start"] <= r["start"] <= root["end"]
                         for r in beats)
    assert len(beats) <= 1 + (root["end"] - root["start"]) / gil_probe.BEAT_S
    # every span of the task carries cpu (none of them is back-dated)
    assert all(r["cpu"] is not None for r in recs
               if r["task"] == root["task"])


def test_flight_bundle_spans_carry_cpu(tmp_path):
    eng = StorageEngine(str(tmp_path / "e"))
    try:
        with pl.span("t.before_the_dump_cpu"):
            _spin_cpu(0.002)
        deadline = time.monotonic() + 2
        while time.monotonic() < deadline and not any(
                r[0] == HANDOFF for r in list(pl.RING)[-512:]):
            time.sleep(0.02)
        with open(eng.flight_recorder.dump("test")) as fh:
            tail = json.load(fh)["pipeline_spans"]
        assert all(set(r) == set(pl.RECORD_FIELDS) for r in tail)
        mine = [r for r in tail if r["name"] == "t.before_the_dump_cpu"]
        assert mine and mine[-1]["cpu"] >= 0.002
        assert any(r["name"] == HANDOFF and r["cpu"] is not None
                   for r in tail)
    finally:
        eng.close()
