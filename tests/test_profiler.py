"""Continuous profiler (docs/observability.md layer 6): wall-clock
sampler lifecycle + classification + collapsed round-trip, the
device-program registry's bounded shape tracking and retrace sentinel,
remote-trace re-basing (`tracing.merge_remote`) and the shipped
trace-event cap in cluster messaging. scripts/check_profiler.py drives
the same surfaces end-to-end through an engine; these pin the units."""
import threading
import time

import pytest

from cassandra_tpu.service import diagnostics, profiling, sampler
from cassandra_tpu.service.metrics import GLOBAL as METRICS
from cassandra_tpu.service.sampler import WallProfiler, parse_collapsed
from cassandra_tpu.service.tracing import TraceState


# ------------------------------------------------- merge_remote re-base --


def test_merge_remote_rebases_preserving_spacing():
    st = TraceState()
    st.started = time.perf_counter() - 0.050   # 50 000 us elapsed
    st.add("coordinator sends")
    # replica offsets arrive OUT OF ORDER (concurrent replica stages
    # append racily); the tail anchor must be the max, not the last
    events = [(500, "replica", "b"), (100, "replica", "a"),
              (900, "replica", "c")]
    st.merge_remote(events, "n2")
    merged = {a: us for us, src, a in st.events if src == "n2"}
    assert set(merged) == {"a", "b", "c"}
    # internal spacing survives the re-base exactly
    assert merged["b"] - merged["a"] == 400
    assert merged["c"] - merged["a"] == 800
    # the run is re-based to END at the merge instant: tail lands at
    # now-ish (>= the 50ms already elapsed minus the 900us span), and
    # never ahead of the timeline's own now
    now_us = round((time.perf_counter() - st.started) * 1e6)
    assert merged["c"] >= 49_100 - 1
    assert merged["c"] <= now_us
    assert all(us >= 0 for us in merged.values())


def test_merge_remote_rebase_clamps_at_zero():
    # replica span LONGER than the coordinator's elapsed time: base
    # clamps to 0 rather than going negative (offsets stay valid)
    st = TraceState()
    st.merge_remote([(10_000_000, "replica", "slow")], "n2")
    (us, src, activity), = st.events
    assert (src, activity) == ("n2", "slow")
    assert us == 10_000_000   # base 0 + raw offset


def test_merge_remote_empty_events_is_noop():
    st = TraceState()
    st.add("x")
    before = list(st.events)
    st.merge_remote([], "n2")
    assert st.events == before


# ------------------------------------------- shipped trace-event cap --


def _msg_pair():
    from cassandra_tpu.cluster.messaging import (
        LocalTransport, Message, MessagingService)
    from cassandra_tpu.cluster.ring import Endpoint
    transport = LocalTransport()
    ep_a = Endpoint("n1")
    ep_b = Endpoint("n2")
    svc_b = MessagingService(ep_b, transport)
    original = Message("READ_REQ", {"q": 1}, ep_a, ep_b, id=7,
                       trace_session="sess")
    return transport, svc_b, original


def test_respond_caps_trace_events_keeps_head_counts_drops():
    from cassandra_tpu.cluster.messaging import TRACE_EVENTS_CAP
    transport, svc_b, original = _msg_pair()
    captured = []
    transport.filters.intercept(captured.append)
    events = [(i, "n2", f"e{i}") for i in range(TRACE_EVENTS_CAP + 9)]
    before = METRICS.snapshot().get("verb.READ_RSP.trace_dropped", 0)
    svc_b.respond(original, "READ_RSP", {"rows": []},
                  trace_events=list(events))
    (msg,) = captured
    # chronological HEAD kept: merge_remote anchors its re-base on the
    # max remaining offset, so a truncated TAIL only shortens the
    # merged timeline instead of shifting it
    assert msg.trace_events == events[:TRACE_EVENTS_CAP]
    after = METRICS.snapshot().get("verb.READ_RSP.trace_dropped", 0)
    assert after - before == 9


def test_respond_under_cap_ships_untouched():
    transport, svc_b, original = _msg_pair()
    captured = []
    transport.filters.intercept(captured.append)
    events = [(1, "n2", "only")]
    before = METRICS.snapshot().get("verb.READ_RSP.trace_dropped", 0)
    svc_b.respond(original, "READ_RSP", {}, trace_events=events)
    assert captured[0].trace_events == events
    assert METRICS.snapshot().get(
        "verb.READ_RSP.trace_dropped", 0) == before
    # and None stays None (untraced responses ship no event list)
    svc_b.respond(original, "READ_RSP", {})
    assert captured[1].trace_events is None


# --------------------------------------------------- sampler lifecycle --


def _await(pred, timeout_s=5.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.005)
    return pred()


def test_sampler_zero_cost_off_demand_pattern():
    prof = WallProfiler(interval_s=0.01)
    assert not prof.running          # off = NO thread, not an idle one
    prof.set_demand("eng-a", True)
    assert _await(lambda: prof.running)
    prof.set_demand("eng-b", True)
    prof.set_demand("eng-a", False)  # peer demand keeps it alive
    assert prof.running
    prof.set_demand("eng-b", False)
    assert _await(lambda: not prof.running)
    # sample_once needs no thread (on-demand callers)
    assert prof.sample_once() >= 1
    assert prof.stats()["ring"]["ticks"] == 1


def test_sampler_session_without_knob_parks_on_stop():
    prof = WallProfiler(interval_s=0.01)
    sid = prof.start_session("t")
    assert _await(lambda: prof.running)
    split = prof.stop_session(sid)
    assert split["target"] == sid and "wall_s" in split
    assert _await(lambda: not prof.running)
    assert sid in prof.stats()["finished_sessions"]


def test_sampler_idle_overhead_under_one_percent():
    # the always-on ring acceptance (satellite): at the DEFAULT 50ms
    # interval, capture cost over an idle second stays under 1% —
    # sample_seconds is the sampler's own clock-measured capture time
    prof = WallProfiler(interval_s=0.05)
    prof.set_demand("idle", True)
    try:
        t0 = time.perf_counter()
        time.sleep(1.0)
        elapsed = time.perf_counter() - t0
        assert prof.samples >= 5, "ring thread is not sampling"
        assert prof.sample_seconds / elapsed < 0.01
    finally:
        prof.set_demand("idle", False)


# ----------------------------------- classification + collapsed export --


def test_classification_and_collapsed_round_trip():
    prof = WallProfiler()
    ev = threading.Event()
    ready = threading.Barrier(3)

    def _park():
        ready.wait()
        ev.wait(30.0)

    def _poll():
        ready.wait()
        while not ev.is_set():   # hot loop touching threading.py
            pass                 # through a NON-blocking call

    t1 = threading.Thread(target=_park, name="t-park", daemon=True)
    t2 = threading.Thread(target=_poll, name="t-poll", daemon=True)
    t1.start()
    t2.start()
    ready.wait()
    time.sleep(0.05)             # both threads are past bootstrap
    sid = prof.start_session()
    for _ in range(6):
        prof.sample_once()
    split = prof.stop_session(sid)
    ev.set()
    lines = prof.collapsed(sid)
    parsed = parse_collapsed(lines)
    # one aggregate, two encodings: text totals == structured split
    assert parsed["cpu"] == split["cpu"]
    assert parsed["blocked"] == split["blocked"]
    assert parsed["stacks"] == split["stacks"]
    assert split["ticks"] == 6
    states = {}
    for line in lines:
        stack, _, _n = line.rpartition(" ")
        state, tname = stack.split(";")[:2]
        states.setdefault(tname, set()).add(state)
    # Event.wait leaf -> blocked; the is_set poller must NOT read as
    # blocked (module match alone is not enough — the classifier also
    # requires a wait-shaped leaf function)
    assert states["t-park"] == {"blocked"}
    assert states["t-poll"] == {"cpu"}
    # leaf frame of the parked stack is the stdlib wait
    park_line = next(line for line in lines
                     if line.split(";")[1] == "t-park")
    assert "threading:wait" in park_line


def test_parse_collapsed_rejects_malformed():
    with pytest.raises(ValueError):
        parse_collapsed(["no-count-here"])
    with pytest.raises(ValueError):
        parse_collapsed(["too-few-fields 3"])


# --------------------------------------------- device-program registry --


def test_registry_bounds_tracked_shapes_with_lru_eviction():
    reg = profiling.DeviceProgramRegistry()
    n = profiling.SHAPE_CAP + 40
    for i in range(n):
        assert reg.record_dispatch("k", ("s", i), 0.001)   # all compile
    snap = reg.snapshot()["kernels"]["k"]
    assert snap["compiles"] == n
    assert snap["shape_count"] == profiling.SHAPE_CAP
    assert snap["shape_evictions"] == 40
    # an EVICTED shape reappearing counts as a fresh compile (mirrors
    # a bounded compilation cache); a LIVE shape does not
    assert reg.record_dispatch("k", ("s", 0), 0.001)
    assert not reg.record_dispatch("k", ("s", n - 1), 0.001)


def test_retrace_sentinel_counter_per_breach_event_once():
    reg = profiling.DeviceProgramRegistry()
    reg.set_retrace_budget(2)
    diagnostics.GLOBAL.set_demand("test-prof", True)
    diagnostics.GLOBAL.clear()
    try:
        before = METRICS.snapshot().get("profile.retraces", 0)
        for i in range(6):
            reg.record_dispatch("churny", ("shape", i), 0.001)
        snap = reg.snapshot()["kernels"]["churny"]
        assert snap["compiles"] == 6 and snap["retraces"] == 4
        assert METRICS.snapshot()["profile.retraces"] - before == 4
        evs = [e.to_dict()
               for e in diagnostics.GLOBAL.events("profile.retrace")]
        assert len(evs) == 1      # once per program, not per breach
        assert evs[0]["program"] == "churny"
        assert evs[0]["budget"] == 2
        # reset() re-arms the sentinel
        diagnostics.GLOBAL.clear()
        reg.reset()
        for i in range(4):
            reg.record_dispatch("churny", ("shape", i), 0.001)
        assert len(diagnostics.GLOBAL.events("profile.retrace")) == 1
    finally:
        diagnostics.GLOBAL.set_demand("test-prof", False)
        diagnostics.GLOBAL.clear()


def test_retrace_budget_zero_disables_sentinel():
    reg = profiling.DeviceProgramRegistry()
    reg.set_retrace_budget(0)
    diagnostics.GLOBAL.set_demand("test-prof0", True)
    diagnostics.GLOBAL.clear()
    try:
        for i in range(5):
            reg.record_dispatch("k0", ("shape", i), 0.001)
        assert reg.snapshot()["kernels"]["k0"]["retraces"] == 0
        assert diagnostics.GLOBAL.events("profile.retrace") == []
    finally:
        diagnostics.GLOBAL.set_demand("test-prof0", False)
        diagnostics.GLOBAL.clear()


def test_the_registry_is_the_one_profiler():
    # the pre-registry alias and the duplicate `shapes` snapshot key are
    # gone (ROADMAP C7): one class, one process-global instance, one
    # name per number
    assert not hasattr(profiling, "KernelProfiler")
    assert isinstance(profiling.GLOBAL, profiling.DeviceProgramRegistry)
    reg = profiling.DeviceProgramRegistry()
    reg.record_dispatch("k", ("s", 0), 0.001)
    assert "shapes" not in reg.snapshot()["kernels"]["k"]


def test_sampler_global_engine_knob_wiring(tmp_path):
    # the knob lands on the PROCESS-GLOBAL sampler via the demand
    # pattern and close() withdraws it (check_profiler.py drives the
    # full lifecycle; this pins the wiring exists at all)
    from cassandra_tpu.config import Config, Settings
    from cassandra_tpu.schema import Schema
    from cassandra_tpu.storage.engine import StorageEngine
    assert not sampler.GLOBAL.running
    eng = StorageEngine(
        str(tmp_path), Schema(), commitlog_sync="periodic",
        settings=Settings(Config.load({"profiler_enabled": True,
                                       "profiler_interval": "10ms"})))
    try:
        assert _await(lambda: sampler.GLOBAL.running)
        assert sampler.GLOBAL.interval_s == pytest.approx(0.01)
    finally:
        eng.close()
    assert _await(lambda: not sampler.GLOBAL.running)
