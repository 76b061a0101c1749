"""`ycsb_a.wire_rf3_chips4` whole at a tiny size on the CPU, the backend
given four devices in the child: three nodes, RF 3, QUORUM both ways, node
i pinned to device i; each fault the cell can have planted under a full
run of the harness; the parent's program refused in set-up; the controls,
which are `ycsb_a.wire_rf3`'s.

On the CPU the engine choice's probe is faked and its size floor lowered,
in the child process and nowhere else: the harness still passes no engine.
"""
import json
import os
import sys

import pytest

from conftest import BENCH, make_tiny_tree, run_cell

sys.path.insert(0, BENCH)

CELL = "ycsb_a.wire_rf3_chips4"
CHECKS = {"ops_unanswered", "reads_stale", "reads_unknown_value",
          "final_rows_wrong", "sstables_beyond_one",
          "compactions_off_device",
          "components_differing_from_host_engine", "replicas_diverging",
          "levels_not_coordinated", "quorum_not_enforced",
          "rounds_off_home_device"}
FOUR_DEVICES_ON_A_TPU = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
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
def chips_tree(tmp_path_factory):
    tree = make_tiny_tree(str(tmp_path_factory.mktemp("rf3_chips_tiny")))

    def config(c):
        c["workload"]["recordcount"] = 4000
        c["data"]["records_per_sstable"] = 1000
        c["node_config"]["commitlog_segment_size"] = "1MiB"
        c["correct"].update(final_sample_keys=200, compaction_wait_s=60,
                            warm_compaction_wait_s=60, settle_wait_s=20,
                            refusal_wait_s=20)
    _edit(tree, "configs/ycsb_a_rf3_chip_per_node.json", config)
    _edit(tree, "traffic/ycsb_closedloop_8_quorum_chips.json",
          lambda c: c.update(ops_per_connection=4000,
                             control_ops_per_connection=3000,
                             ring_drain_s=0.25,
                             trace={"start_s": 0.2, "seconds": 1.5}))
    return tree


def _bad(line):
    return {k for k, c in line["checks"].items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_end_to_end_and_is_correct(chips_tree, tmp_path,
                                                 trace):
    rc, line, err = run_cell(chips_tree, CELL, seed=3000004400 + trace,
                             seconds=4.0, trace=trace,
                             patch=FOUR_DEVICES_ON_A_TPU, tmp=str(tmp_path))
    assert rc == 0 and line is not None, err[-3000:]
    assert line["correct"] is True, (line["checks"], err[-3000:])
    assert set(line["checks"]) == CHECKS
    assert line["checks"]["rounds_off_home_device"]["value"] == 0
    assert line["attempted"] > 100 and line["failed"] == 0
    assert line["device"]["count"] == 4
    assert err.rstrip().endswith("correct: True")
    if trace == 0:
        assert set(line["metrics"]) == {"ops_s", "setup_s"}
        assert line["metrics"]["ops_s"]["value"] > 0
        return
    with open(os.path.join(chips_tree, "BENCHMARK.json")) as f:
        mine = {m["name"] for m in json.load(f)["per_layer"]
                if CELL in m.get("workloads", [])}
    assert len(mine) == 5 and all(m.startswith("chips4_") for m in mine)
    # no TPU plane on the CPU: the roofline is left out, never 0
    assert mine - {"chips4_merge_resident_roofline"} \
        <= set(line["metrics"]) <= mine
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    # three nodes, three chips, the same compaction on each
    assert 33 <= metrics["chips4_rounds_max_device_share_pct"] <= 34
    assert metrics["chips4_merge_wait_ms_per_round"] > 0
    assert metrics["chips4_served_compaction_mib_s"] > 0
    assert line["breakdown"]["compiles_in_window"] == []


FAULTS = {
    # the engines report their devices, but every store's compactions
    # run on jax's default device, chip 0
    "every_engine_left_on_device_0": ("""
from cassandra_tpu.storage import engine as E
_open = E.StorageEngine._open_store
def _open_store(self, t):
    cfs = _open(self, t)
    cfs.jax_device = None
    return cfs
E.StorageEngine._open_store = _open_store
""", {"rounds_off_home_device"}),
    # a replica that acknowledges a mutation and does not apply it, one
    # in twenty of those that reach it over the wire
    "replica_acknowledges_without_applying": ("""
from cassandra_tpu.cluster import node as N
_handle, _n = N.Node._handle_mutation, [0]
def _ack_only(self, msg):
    _n[0] += 1
    if _n[0] % 20 == 0:
        from cassandra_tpu.cluster.messaging import Verb
        return Verb.MUTATION_RSP, b""
    return _handle(self, msg)
N.Node._handle_mutation = _ack_only
""", None),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_reads_not_correct(chips_tree, tmp_path, fault):
    patch, only = FAULTS[fault]
    rc, line, err = run_cell(chips_tree, CELL, seed=4401, seconds=4.0,
                             patch=FOUR_DEVICES_ON_A_TPU + patch,
                             tmp=str(tmp_path))
    assert rc == 0 and line is not None, err[-3000:]
    assert line["correct"] is False, line["checks"]
    assert err.rstrip().endswith("correct: False")
    bad = _bad(line)
    if only is not None:
        assert bad == only, bad
    if fault == "every_engine_left_on_device_0":
        # nodes 2 and 3: every round and lane program off their chip,
        # and no round on it
        assert line["checks"]["rounds_off_home_device"]["value"] > 2
    if fault == "replica_acknowledges_without_applying":
        assert "replicas_diverging" in bad
        assert "reads_unknown_value" not in bad


def test_the_parent_program_fails_at_once(chips_tree, tmp_path):
    """A program whose node config takes no device ordinal (this cell's
    parent) is refused in set-up's first lines: an exit code, no result
    line."""
    rc, line, err = run_cell(chips_tree, CELL, seed=1, seconds=1.0,
                             patch=FOUR_DEVICES_ON_A_TPU + """
from cassandra_tpu import config as C
_load = C.Config.load.__func__
def load(cls, raw):
    if "device_ordinal" in raw:
        raise C.ConfigError("unknown config key: 'device_ordinal'")
    return _load(cls, raw)
C.Config.load = classmethod(load)
""", tmp=str(tmp_path))
    assert rc != 0 and line is None
    assert "takes no device_ordinal" in err


def test_a_run_on_fewer_chips_is_refused(chips_tree, tmp_path):
    rc, line, err = run_cell(chips_tree, CELL, seed=1, seconds=1.0,
                             patch=FOUR_DEVICES_ON_A_TPU.replace(
                                 "count=4", "count=2"), tmp=str(tmp_path))
    assert rc == 3 and line is None
    assert "needs 4 chip(s)" in err


def test_the_controls_are_the_rf3_cells(chips_tree):
    import run as harness
    with open(os.path.join(chips_tree, "benchmarks", "configs",
                           "ycsb_a_rf3_chip_per_node.json")) as f:
        config = json.load(f)
    with open(os.path.join(chips_tree, "benchmarks", "traffic",
                           "ycsb_closedloop_8_quorum_chips.json")) as f:
        traffic = json.load(f)
    driver = harness.load("drivers", "wire_ycsb_cluster_chips")
    ctx = harness.Ctx({"name": CELL}, config, traffic, 3000004407, 0, None,
                      None)
    out = {name: harness.decide(checks)
           for name, checks in driver.control(ctx)}
    assert out == {"reference_in_place": True,
                   "read_and_written_at_one": False,
                   "replica_drops_per_1000": False,
                   "values_truncated_to_99": False}


def test_the_configuration_is_ycsb_a_rf3_but_for_the_layout():
    with open(os.path.join(BENCH, "configs", "ycsb_a_rf3.json")) as f:
        one = json.load(f)
    with open(os.path.join(BENCH, "configs",
                           "ycsb_a_rf3_chip_per_node.json")) as f:
        four = json.load(f)
    changed = {"name", "source", "cluster", "guarantees", "reduced",
               "assumed", "reference", "not_reduced"}
    assert {k: v for k, v in one.items() if k not in changed} \
        == {k: v for k, v in four.items() if k not in changed}
    assert {k: v for k, v in one["cluster"].items()
            if k != "layout"} == {k: v for k, v in four["cluster"].items()
                                  if k not in ("layout", "devices")}
    assert four["cluster"]["devices"] == [0, 1, 2]
    assert four["guarantees"][:-1] == one["guarantees"]
    assert set(four["reduced"]) == {"recordcount"}
    assert four["reduced"]["recordcount"] == one["reduced"]["recordcount"]


@pytest.mark.parametrize("counters,want", [
    ({"merge.resident.rounds.dev0": 4, "merge.resident.rounds.dev1": 4,
      "merge.resident.rounds.dev2": 4}, 100.0 / 3),
    ({"merge.resident.rounds.dev0": 12,
      "write.lane.dispatches.dev0": 50}, 100.0),
    ({}, None)])
def test_the_share_reads_the_busiest_chip(counters, want):
    import run as harness
    reader = harness.load("layer_metrics",
                          "chips4_rounds_max_device_share_pct")

    class Ctx:
        window = {"device_counters": counters}
    got = reader.read(Ctx)
    assert got == pytest.approx(want) if want is not None else got is None


def test_the_check_counts_what_ran_off_a_nodes_chip():
    import run as harness
    driver = harness.load("drivers", "wire_ycsb_cluster_chips")
    homes = [0, 1, 2]

    def comp(dev, rounds=3, lane=9):
        return {"rounds_by_device": {dev: rounds},
                "lane_dispatches_by_device": {dev: lane}}
    mine = [[comp(0), comp(0)], [comp(1), comp(1)], [comp(2), comp(2)]]
    served = [m[1:] for m in mine]
    assert driver.rounds_off_home_device(homes, mine, served, {}, {}) == 0
    # node 2's served compaction on chip 0: 3 rounds + 9 lane programs
    # off its chip, and no round on it
    bad = [mine[0], [comp(1), comp(0)], mine[2]]
    assert driver.rounds_off_home_device(
        homes, bad, [m[1:] for m in bad], {}, {}) == 13
    # chip 3 is no node's: whatever ran there counts
    assert driver.rounds_off_home_device(
        homes, mine, served, {"merge.resident.rounds.dev3": 2,
                              "merge.resident.rounds.dev0": 6},
        {("write.append", 3): 5, ("write.append", 1): 7}) == 7
