"""`ycsb_a.wire` whole at a tiny size on the CPU (a tiny tree of its own:
conftest.py's, with this cell's files cut further), each fault the cell can
have planted under a full run of the harness, the controls of its plain
reference, the scrambled-zipfian chooser and the staleness rule.

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

CELL = "ycsb_a.wire"
CHECKS = {"ops_unanswered", "reads_stale", "reads_unknown_value",
          "final_rows_wrong", "sstables_beyond_one",
          "compactions_off_device",
          "components_differing_from_host_engine"}
ON_A_TPU = """
from cassandra_tpu.compaction import task as T
T.tpu_backend = lambda: True
T.CompactionTask.DEVICE_MIN_CELLS = 1000
"""


def _edit(tree, rel, change):
    path = os.path.join(tree, "benchmarks", rel)
    with open(path) as f:
        cfg = json.load(f)
    change(cfg)
    with open(path, "w") as f:
        json.dump(cfg, f)


@pytest.fixture(scope="module")
def ycsb_tree(tmp_path_factory):
    tree = make_tiny_tree(str(tmp_path_factory.mktemp("ycsb_tiny")))

    def config(c):
        c["workload"]["recordcount"] = 4000
        c["data"]["records_per_sstable"] = 1000
        c["correct"].update(final_sample_keys=200, compaction_wait_s=20,
                            warm_compaction_wait_s=60)
    _edit(tree, "configs/ycsb_a.json", config)
    _edit(tree, "traffic/ycsb_closedloop_8.json", lambda c: c.update(
        ops_per_connection=4000, control_ops_per_connection=300,
        ring_drain_s=0.25, trace={"start_s": 0.2, "seconds": 1.5}))
    return tree


def _bad(line):
    return {k for k, c in line["checks"].items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_end_to_end_and_is_correct(ycsb_tree, tmp_path, trace):
    rc, line, err = run_cell(ycsb_tree, CELL, seed=3000000100 + trace,
                             seconds=4.0, trace=trace, patch=ON_A_TPU,
                             tmp=str(tmp_path))
    assert rc == 0 and line is not None, err[-3000:]
    assert line["correct"] is True, err[-3000:]
    assert set(line["checks"]) == CHECKS
    assert line["attempted"] > 100 and line["failed"] == 0
    assert err.rstrip().endswith("correct: True")
    if trace == 0:
        assert set(line["metrics"]) == {"ops_s", "setup_s"}
        assert line["metrics"]["ops_s"]["value"] > 0
    else:
        with open(os.path.join(ycsb_tree, "BENCHMARK.json")) as f:
            mine = {m["name"] for m in json.load(f)["per_layer"]
                    if CELL in m.get("workloads", [])}
        assert len(mine) == 12
        # no TPU plane on the CPU: the roofline is left out, never 0; a
        # table this small sits in the chunk cache after the warm-up, so
        # the segment-decode reader may find nothing to read
        optional = {"ycsb_merge_resident_roofline",
                    "ycsb_segment_decode_ms_per_read",
                    "ycsb_ops_s_outside_compaction"}
        assert mine - optional <= set(line["metrics"]) <= mine
        assert 0 < line["metrics"]["ycsb_compaction_overlap_pct"][
            "value"] <= 100
        assert line["metrics"]["ycsb_compact_w_busy_pct"]["value"] <= 100
        assert line["breakdown"]["compiles_in_window"] == []


FAULTS = {
    # an acknowledged update that was never applied, one in twenty
    "dropped_acknowledged_update": ("""
from cassandra_tpu.storage import table as S
_apply, _n = S.ColumnFamilyStore.apply, [0]
def _drop(self, mutation, *a, **kw):
    if self.table.name == "usertable":
        _n[0] += 1
        if _n[0] % 20 == 0:
            return None
    return _apply(self, mutation, *a, **kw)
S.ColumnFamilyStore.apply = _drop
""", None),
    # the served compaction falls to the host engine: the same bytes,
    # the same answers, and nothing on the device
    "compaction_falls_to_native": ("""
T.tpu_backend = lambda: False
""", {"compactions_off_device"}),
    # the window's compaction leaves one input out (the warm-up's, the
    # first task built for the table, runs whole)
    "one_input_left_uncompacted": ("""
_init, _seen = T.CompactionTask.__init__, [0]
def _short(self, cfs, inputs, *a, **kw):
    if cfs.table.name == "usertable" and len(inputs) == 4 \\
            and not kw.get("engine"):
        _seen[0] += 1
        if _seen[0] > 1:
            inputs = list(inputs)[:3]
    _init(self, cfs, inputs, *a, **kw)
T.CompactionTask.__init__ = _short
""", None),
    # once the sstables are swapped, reads are answered from what a
    # reader saw before the swap
    "stale_read_from_a_pre_swap_reader": ("""
from cassandra_tpu.storage import table as S
_read, _seen = S.ColumnFamilyStore.read_partition, {}
def _pre_swap(self, pk, *a, **kw):
    if self.table.name != "usertable":
        return _read(self, pk, *a, **kw)
    if len(self.live_sstables()) == 1 and pk in _seen:
        return _seen[pk]
    return _seen.setdefault(pk, _read(self, pk, *a, **kw))
S.ColumnFamilyStore.read_partition = _pre_swap
""", None),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_reads_not_correct(ycsb_tree, tmp_path, fault):
    patch, only = FAULTS[fault]
    rc, line, err = run_cell(ycsb_tree, CELL, seed=43, seconds=4.0,
                             patch=ON_A_TPU + patch, tmp=str(tmp_path))
    assert rc == 0 and line is not None, err[-3000:]
    assert line["correct"] is False, line["checks"]
    assert err.rstrip().endswith("correct: False")
    bad = _bad(line)
    if only is not None:
        assert bad == only
    if fault == "dropped_acknowledged_update":
        assert bad & {"reads_stale", "final_rows_wrong"}
        assert "reads_unknown_value" not in bad
    if fault == "one_input_left_uncompacted":
        assert "sstables_beyond_one" in bad
    if fault == "stale_read_from_a_pre_swap_reader":
        assert bad and bad <= {"reads_stale", "final_rows_wrong"}


def test_the_parent_program_fails_at_once(ycsb_tree, tmp_path):
    """A program whose task cannot choose (the parent of PR 27) is
    refused in set-up's first lines: an exit code, no result line."""
    rc, line, err = run_cell(ycsb_tree, CELL, seed=1, seconds=1.0, patch="""
from cassandra_tpu.compaction import task as T
del T.choose_engine
""", tmp=str(tmp_path))
    assert rc != 0 and line is None
    assert "cannot choose its engine" in err


def test_controls_read_not_correct_and_the_reference_reads_correct(
        ycsb_tree):
    import run as harness
    with open(os.path.join(ycsb_tree, "benchmarks", "configs",
                           "ycsb_a.json")) as f:
        config = json.load(f)
    with open(os.path.join(ycsb_tree, "benchmarks", "traffic",
                           "ycsb_closedloop_8.json")) as f:
        traffic = json.load(f)
    driver = harness.load("drivers", "wire_ycsb")
    ctx = harness.Ctx({"name": CELL}, config, traffic, 3000000007, 0, None,
                      None)
    out = {name: (harness.decide(checks), checks)
           for name, checks in driver.control(ctx)}
    assert sorted(out) == ["reference_in_place", "update_dropped_per_1000",
                           "values_truncated_to_99"]
    assert out["reference_in_place"][0] is True
    assert {c["name"] for c in out["reference_in_place"][1]} == CHECKS
    assert out["update_dropped_per_1000"][0] is False
    assert out["values_truncated_to_99"][0] is False
    truncated = {c["name"]: c["value"]
                 for c in out["values_truncated_to_99"][1]}
    assert truncated["reads_unknown_value"] > 0
    assert truncated["reads_stale"] == 0


# ------------------------------------------------------------ the chooser --

def test_the_scrambled_zipfian_is_seeded_and_ycsbs():
    from reference import ycsb
    z = ycsb.ScrambledZipfian(300000)
    a = z.draw(np.random.default_rng(5), 200000)
    b = z.draw(np.random.default_rng(5), 200000)
    c = z.draw(np.random.default_rng(6), 200000)
    assert (a == b).all() and (a != c).any()
    assert a.min() >= 0 and a.max() < 300000
    # YCSB's first keys under insertorder=hashed
    assert ycsb.key_names([0, 1]) == [b"user6284781860667377211",
                                      b"user8517097267634966620"]
    assert int(ycsb.fnvhash64([0])[0]) == 6284781860667377211


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_hottest_keys_share_lies_in_its_band(seed):
    """Rank 0 of a zipfian(0.99) over 10^10 items draws 1/zeta = 3.78% of
    the operations, rank 1 half of that to the power 0.99; hashing moves
    the ranks, not their shares."""
    from reference import ycsb
    z = ycsb.ScrambledZipfian(300000)
    draws = z.draw(np.random.default_rng(seed), 1000000)
    counts = np.sort(np.bincount(draws, minlength=300000))[::-1] / 1e6
    assert 0.0378 * 0.93 < counts[0] < 0.0378 * 1.07
    assert 0.0190 * 0.90 < counts[1] < 0.0190 * 1.10
    assert abs(z.hottest_share() - 0.03778) < 1e-4
    # the head is heavy and the tail is long
    assert counts[:100].sum() > 0.18
    assert (counts > 0).sum() > 200000


def test_an_operation_stream_is_half_reads_and_one_field_updates():
    from reference import ycsb
    s = ycsb.op_stream(9, 0, 20000, 300000, 10, 100, 0.5)
    t = ycsb.op_stream(9, 1, 20000, 300000, 10, 100, 0.5)
    assert 0.48 < s["is_read"].mean() < 0.52
    assert set(np.unique(s["field"])) == set(range(10))
    assert s["value"].shape == (20000, 100)
    assert s["value"].min() >= 32 and s["value"].max() < 127
    assert (s["keynum"] != t["keynum"]).any()
    again = ycsb.op_stream(9, 0, 20000, 300000, 10, 100, 0.5)
    assert (again["value"] == s["value"]).all()


# ------------------------------------------------------ the history rules --

def _ops(*ops):
    return [dict(o, ok=o.get("ok", True)) for o in ops]


def _w(value, sent, done, **kw):
    return dict(kind="update", keynum=0, field=0, value=value, sent=sent,
                done=done, **kw)


def _r(first, sent, done, loaded):
    return dict(kind="read", keynum=0, sent=sent, done=done,
                row=[first, loaded[0, 1].tobytes()])


@pytest.mark.parametrize("name,stale,unknown", [
    ("fresh", 0, 0), ("stale_loaded", 1, 0), ("stale_older_write", 1, 0),
    ("racing", 0, 0), ("in_flight", 0, 0), ("nobody_wrote", 0, 1),
    ("from_the_future", 0, 1)])
def test_the_staleness_rule_on_hand_made_histories(name, stale, unknown):
    from reference import ycsb
    loaded = ycsb.loaded_values(1, 2, 2, 8)
    old = loaded[0, 0].tobytes()
    a, b = b"A" * 8, b"B" * 8
    histories = {
        "fresh": _ops(_w(a, 1, 2), _r(a, 3, 4, loaded)),
        "stale_loaded": _ops(_w(a, 1, 2), _r(old, 3, 4, loaded)),
        "stale_older_write": _ops(_w(a, 1, 2), _w(b, 3, 4),
                                  _r(a, 5, 6, loaded)),
        "racing": _ops(_w(a, 1, 4), _w(b, 2, 3), _r(a, 5, 6, loaded),
                       _r(b, 5, 6, loaded)),
        "in_flight": _ops(_w(a, 1, 6), _r(old, 2, 3, loaded),
                          _r(a, 4, 5, loaded)),
        "nobody_wrote": _ops(_r(b"?" * 8, 1, 2, loaded)),
        "from_the_future": _ops(_r(a, 1, 2, loaded), _w(a, 3, 4)),
    }
    assert ycsb.History(loaded, histories[name]).judge_reads() == {
        "reads_stale": stale, "reads_unknown_value": unknown}
