"""`twcs_ttl.major` whole at a tiny size on the CPU (a tiny tree of its own:
conftest.py's, with this cell's files cut further), each fault the cell can
have planted under a full run of the harness, the controls of its plain
reference, and the reference against hand-written cases at every expiry
boundary.

On the CPU the engine choice's probe is faked and its size floor lowered,
in the child process and nowhere else: the harness still passes no engine.
"""
import json
import os
import sys

import numpy as np
import pytest

from conftest import BENCH, make_tiny_tree, run_cell

sys.path.insert(0, BENCH)

CELL = "twcs_ttl.major"
CHECKS = {"cells_wrong", "components_differing", "compactions_off_device",
          "windows_not_dropped", "sstables_beyond_one",
          "components_differing_from_host_engine"}
ON_A_TPU = """
from cassandra_tpu.compaction import task as T
T.tpu_backend = lambda: True
T.CompactionTask.DEVICE_MIN_CELLS = 1000
"""


@pytest.fixture(scope="module")
def twcs_tree(tmp_path_factory):
    """3 hosts, a reading every 10 minutes: 30 series x 144 rows x 2
    cells a day."""
    tree = make_tiny_tree(str(tmp_path_factory.mktemp("twcs_tiny")))
    path = os.path.join(tree, "benchmarks", "configs",
                        "tsbs_cpu_twcs_ttl.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["data"].update(hosts=3, interval_s=600, rows_per_partition=144)
    cfg["correct"].update(warm_cycle_wait_s=120, cycle_wait_s=60)
    with open(path, "w") as f:
        json.dump(cfg, f)
    return tree


def _bad(line):
    return {k for k, c in line["checks"].items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_end_to_end_and_is_correct(twcs_tree, tmp_path, trace):
    rc, line, err = run_cell(twcs_tree, CELL, seed=3000000200 + trace,
                             seconds=3.0, trace=trace, patch=ON_A_TPU,
                             tmp=str(tmp_path))
    assert rc == 0 and line is not None, err[-3000:]
    assert line["correct"] is True, err[-3000:]
    assert set(line["checks"]) == CHECKS
    # a cycle is three tasks: the drop and the two merges
    assert line["attempted"] >= 6 and line["attempted"] % 3 == 0
    assert line["failed"] == 0
    assert err.rstrip().endswith("correct: True")
    if trace == 0:
        assert set(line["metrics"]) == {"compaction_mib_s", "setup_s"}
        assert line["metrics"]["compaction_mib_s"]["value"] > 0
    else:
        with open(os.path.join(twcs_tree, "BENCHMARK.json")) as f:
            mine = {m["name"] for m in json.load(f)["per_layer"]
                    if CELL in m.get("workloads", [])}
        assert len(mine) == 8 and all(m.startswith("twcs_") for m in mine)
        # no TPU plane on the CPU: the roofline is left out, never 0
        assert set(line["metrics"]) == mine - {
            "twcs_merge_resident_roofline"}
        m = {k: v["value"] for k, v in line["metrics"].items()}
        # half of what a cycle merges is the repaired window, all of it
        # converted on the device; nothing merged is purged
        assert m["twcs_expired_converted_pct"] == 50.0
        assert m["twcs_kept_cells_pct"] == 100.0
        assert 0 < m["twcs_write_lane_busy_pct"] <= 100
        assert m["twcs_select_drop_ms_per_cycle"] > 0
        assert line["breakdown"]["compiles_in_window"] == []


FAULTS = {
    # a TTL'd task falls to the host engine: the same bytes, the same
    # cells, and nothing on the device
    "ttl_task_falls_to_the_host_engine": ("""
T.tpu_backend = lambda: False
""", {"compactions_off_device"}),
    # the strategy never finds the expired window droppable
    "w_old_left_on_disk": ("""
T.CompactionController.fully_expired = staticmethod(lambda cfs, c: [])
""", {"windows_not_dropped"}),
    # a cycle that skips the repaired window: the strategy does not see a
    # window that holds exactly two sstables
    "a_cycle_skips_w_mid": ("""
from cassandra_tpu.compaction import strategies as S
_all = S.AbstractCompactionStrategy.candidates
def _hide(self):
    live, per = _all(self), {}
    for s in live:
        per.setdefault(s.max_ts // 86400000000, []).append(s)
    return [s for s in live if len(per[s.max_ts // 86400000000]) != 2]
S.AbstractCompactionStrategy.candidates = _hide
""", {"compactions_off_device", "sstables_beyond_one", "cells_wrong",
      "components_differing_from_host_engine"}),
    # the conversion keeps the value bytes of an expired cell
    "conversion_keeps_the_values": ("""
import jax.numpy as jnp
from cassandra_tpu.ops import device_write as W
_where = jnp.where
class _J:
    def __getattr__(self, name):
        return getattr(jnp, name)
    @staticmethod
    def where(c, a, b):
        return b if getattr(b, "dtype", None) == jnp.uint32 \\
            and getattr(a, "dtype", None) == jnp.uint32 else _where(c, a, b)
W.jnp = _J()
""", {"cells_wrong", "components_differing_from_host_engine"}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_reads_not_correct(twcs_tree, tmp_path, fault):
    patch, bad = FAULTS[fault]
    rc, line, err = run_cell(twcs_tree, CELL, seed=3000000300,
                             seconds=2.0, patch=ON_A_TPU + patch,
                             tmp=str(tmp_path))
    assert rc == 0 and line is not None, err[-3000:]
    assert line["correct"] is False, (fault, line["checks"])
    assert bad <= _bad(line), (fault, line["checks"])
    if fault != "conversion_keeps_the_values":
        assert line["failed"] > 0


def test_the_parents_program_is_refused_in_setup(twcs_tree, tmp_path):
    """Before PR 31 choose_engine sent every TTL'd input to a host
    engine: the cell fails at once, with no result line."""
    rc, line, err = run_cell(twcs_tree, CELL, seed=3000000400, seconds=2.0,
                             patch=ON_A_TPU + """
from cassandra_tpu.storage import cellbatch as _cb
T._HOST_ROUND_FLAGS |= _cb.FLAG_EXPIRING
""", tmp=str(tmp_path))
    assert rc == 1 and line is None
    assert "sends TTL'd inputs to a host engine" in err


# ------------------------------------------------------------ controls --

def test_controls_read_not_correct_and_the_reference_reads_correct(
        twcs_tree):
    import run as harness
    with open(os.path.join(twcs_tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    with open(os.path.join(twcs_tree, "benchmarks", "configs",
                           cell["config"] + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(twcs_tree, "benchmarks", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    driver = harness.load("drivers", traffic["driver"])
    ctx = harness.Ctx(cell, config, traffic, 3000000007, 0, None, None)
    out = {name: (harness.decide(checks), checks)
           for name, checks in driver.control(ctx)}
    assert sorted(out) == sorted(["reference_in_place", "expiry_ignored",
                                  "values_kept_on_conversion", "lose_run"])
    assert out["reference_in_place"][0] is True
    cells = 30 * 144 * 2
    wrong = {n: c[0]["value"] for n, (_ok, c) in out.items()}
    # w_mid read back live: its every cell; values kept: its value cells;
    # the last input of each window lost: 3 h and 6 h of a day
    assert wrong == {"reference_in_place": 0, "expiry_ignored": cells,
                     "values_kept_on_conversion": cells // 2,
                     "lose_run": cells // 8 + cells // 4}
    assert not any(out[n][0] for n in wrong if n != "reference_in_place")


# ------------------------------------- the reference, case by case --

TTL, GRACE, NOW = 1000, 300, 50_000


def _run(series, row, write_s, value, us=0):
    return {"series": np.array(series), "row": np.array(row),
            "write_us": np.array(write_s, dtype=np.int64) * 1_000_000 + us,
            "value": np.array(value)}


def _merge(runs, **kw):
    from reference import timeseries as ref
    return ref.merge(runs, 10, TTL, NOW, NOW - GRACE, **kw)


@pytest.mark.parametrize("ldt,state", [
    (NOW + 1, "live"), (NOW, "tombstone"), (NOW - 1, "tombstone"),
    (NOW - GRACE, "tombstone"), (NOW - GRACE - 1, "purged")])
def test_reference_at_each_expiry_boundary(ldt, state):
    """Expired from `ldt <= now` on; purged only below gc_before."""
    from reference import timeseries as ref
    out = _merge([_run([0], [3], [ldt - TTL], [42], us=999_999)])
    if state == "purged":
        assert len(out["id"]) == 0
        return
    assert out["id"].tolist() == [6, 7]              # liveness, value
    assert out["ldt"].tolist() == [ldt, ldt]
    assert out["ts"].tolist() == [(ldt - TTL) * 1_000_000 + 999_999] * 2
    assert out["ttl"].tolist() == [TTL, TTL]
    dead = ref.FLAG_TOMBSTONE if state == "tombstone" else 0
    assert out["flags"].tolist() == [
        ref.FLAG_ROW_LIVENESS | ref.FLAG_EXPIRING | dead,
        ref.FLAG_EXPIRING | dead]
    assert out["vlen"].tolist() == ([0, 0] if dead else [0, 8])
    assert out["value"].tolist() == ([0, 0] if dead else [0, 42])


@pytest.mark.parametrize("older_outside_us,purged", [
    (None, True), (1, True), (0, False), (-1, False)])
def test_reference_purge_guard_at_the_timestamp_boundary(older_outside_us,
                                                         purged):
    """Past grace a cell goes only if its timestamp is BELOW the oldest
    one an sstable outside the compaction holds for its series."""
    write_s = NOW - GRACE - 1 - TTL
    run = _run([0, 1], [0, 0], [write_s, write_s], [1, 2])
    guard = None if older_outside_us is None else np.array(
        [write_s * 1_000_000 + older_outside_us, 2 ** 62])
    out = _merge([run], purgeable_us=guard)
    assert out["id"].tolist() == ([] if purged else [0, 1])
    assert (out["flags"] & 1).all()


def test_reference_newest_wins_and_refuses_a_tie():
    from reference import timeseries as ref
    live = NOW + 5 - TTL
    a = _run([0, 0], [1, 2], [live, live], [10, 20])
    b = _run([0], [2], [live + 1], [99])
    out = _merge([a, b])
    assert out["id"].tolist() == [2, 3, 4, 5]
    assert out["value"].tolist() == [0, 10, 0, 99]
    assert out["ts"].tolist() == [live * 10 ** 6] * 2 \
        + [(live + 1) * 10 ** 6] * 2
    assert ref.cells_wrong(out, _merge([b, a])) == 0
    assert ref.cells_wrong(out, _merge([a])) == 2
    with pytest.raises(ValueError, match="tie"):
        _merge([a, _run([0], [2], [live], [7])])
    # a cell one side holds twice is counted, not hidden
    twice = {k: np.concatenate([v, v[:1]]) for k, v in out.items()}
    assert ref.cells_wrong(twice, out) == 1


@pytest.mark.parametrize("control,wrong", [
    ("expiry_ignored", 4), ("values_kept_on_conversion", 2),
    ("lose_run", 2)])
def test_reference_controls_break_what_they_name(control, wrong):
    from reference import timeseries as ref
    dead = NOW - 10 - TTL
    runs = [_run([0, 0], [0, 1], [dead, dead], [5, 6]),
            _run([0], [2], [NOW - TTL + 7], [8])]
    assert ref.cells_wrong(_merge(runs, control=control),
                           _merge(runs)) == wrong
