"""The reduction from a profiler trace to busy seconds, idle share,
time per executable and attributed gaps, on a small trace recorded on a
TPU v5e (data/tpu_small.xplane.pb, record_fixture.py: three runs of one
jitted program, a 20 ms sleep after each) and on one with no device
plane."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import trace_reduce  # noqa: E402

TPU = os.path.join(HERE, "data", "tpu_small.xplane.pb")
NO_DEVICE = os.path.join(HERE, "data", "cpu_no_device_plane.xplane.pb")


def test_union_merges_overlaps():
    total, merged = trace_reduce.union_seconds(
        [(0, 10), (5, 20), (30, 40), (40, 45), (100, 101)])
    assert merged == [[0, 20], [30, 45], [100, 101]]
    assert total == pytest.approx(36e-9)


def test_busy_idle_and_executable_time():
    r = trace_reduce.reduce_trace(TPU, window_s=0.3657)
    assert r["device_planes"] == 1 and r["device_planes_with_ops"] == 1
    exe = r["executables"]["jit_fixture_program"]
    assert exe["calls"] == 3
    # three runs of about a third of a millisecond each
    assert 0.5e-3 < exe["seconds"] < 2e-3
    assert 0 < r["busy_s"] <= exe["seconds"] * 1.01
    assert r["idle_share"] == pytest.approx(1 - r["busy_s"] / 0.3657)
    assert 0.99 < r["idle_share"] < 1.0
    assert r["device_ops"][0][0].startswith("jit_fixture_program/sort")
    assert len(r["device_ops"]) <= trace_reduce.TOP


def test_gaps_go_to_the_benchmarks_own_spans():
    r = trace_reduce.reduce_trace(TPU, window_s=0.3657)
    gaps = dict(r["idle_gaps"])
    assert set(r["spans"]) == {"bench.outer", "bench.step", "bench.sleep"}
    # two sleeps of 20 ms lie between the three runs
    assert gaps["bench.sleep"] == pytest.approx(0.04, rel=0.2)


def test_busy_is_averaged_over_the_cells_chips():
    one = trace_reduce.reduce_trace(TPU, window_s=1.0, chips=1)
    four = trace_reduce.reduce_trace(TPU, window_s=1.0, chips=4)
    assert four["busy_s"] == pytest.approx(one["busy_s"] / 4)


def test_no_device_event_reads_idle_100_percent():
    r = trace_reduce.reduce_trace(NO_DEVICE, window_s=0.5)
    assert r["busy_s"] == 0.0 and r["idle_share"] == 1.0
    assert r["executables"] == {} and r["device_ops"] == []


def test_find_xplane(tmp_path):
    assert trace_reduce.find_xplane(str(tmp_path)) is None
    d = tmp_path / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(b"")
    assert trace_reduce.find_xplane(str(tmp_path)).endswith("host.xplane.pb")
