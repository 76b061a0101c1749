"""`ycsb_a.wire_rf3` whole at a tiny size on the CPU (a tiny tree of its
own, as test_ycsb_tiny.py builds one): three nodes, RF 3, QUORUM both
ways; each fault the cell can have planted under a full run of the
harness; the parent's program refused in set-up; the controls of the
replica-set reference through control.py's path.

On the CPU the engine choice's probe is faked and its size floor lowered,
in the child process and nowhere else: the harness still passes no engine.
"""
import json
import os
import sys

import pytest

from conftest import BENCH, make_tiny_tree, run_cell

sys.path.insert(0, BENCH)

CELL = "ycsb_a.wire_rf3"
CHECKS = {"ops_unanswered", "reads_stale", "reads_unknown_value",
          "final_rows_wrong", "sstables_beyond_one",
          "compactions_off_device",
          "components_differing_from_host_engine", "replicas_diverging",
          "levels_not_coordinated", "quorum_not_enforced"}
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
def rf3_tree(tmp_path_factory):
    tree = make_tiny_tree(str(tmp_path_factory.mktemp("rf3_tiny")))

    def config(c):
        c["workload"]["recordcount"] = 4000
        c["data"]["records_per_sstable"] = 1000
        c["node_config"]["commitlog_segment_size"] = "1MiB"
        c["correct"].update(final_sample_keys=200, compaction_wait_s=60,
                            warm_compaction_wait_s=60, settle_wait_s=20,
                            refusal_wait_s=20)
    _edit(tree, "configs/ycsb_a_rf3.json", config)
    _edit(tree, "traffic/ycsb_closedloop_8_quorum.json", lambda c: c.update(
        ops_per_connection=4000, control_ops_per_connection=3000,
        ring_drain_s=0.25, trace={"start_s": 0.2, "seconds": 1.5}))
    return tree


def _bad(line):
    return {k for k, c in line["checks"].items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_end_to_end_and_is_correct(rf3_tree, tmp_path, trace):
    rc, line, err = run_cell(rf3_tree, CELL, seed=3000000300 + trace,
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
        with open(os.path.join(rf3_tree, "BENCHMARK.json")) as f:
            mine = {m["name"] for m in json.load(f)["per_layer"]
                    if CELL in m.get("workloads", [])}
        assert len(mine) == 12 and all(m.startswith("rf3_") for m in mine)
        # no TPU plane on the CPU: the roofline is left out, never 0
        optional = {"rf3_merge_resident_roofline"}
        assert mine - optional <= set(line["metrics"]) <= mine
        assert 0 < line["metrics"]["rf3_compaction_overlap_pct"][
            "value"] <= 100
        assert line["metrics"]["rf3_coordinator_write_await_ms_per_update"][
            "value"] > 0
        assert line["metrics"]["rf3_replica_handle_ms_per_op"]["value"] > 0
        assert line["breakdown"]["compiles_in_window"] == []


FAULTS = {
    # the parent's behaviour: the front door parses the level and drops
    # it, so every request is coordinated at the node's default, ONE
    "server_drops_the_declared_level": ("""
from cassandra_tpu.cql import processor as P
def _drop(fn):
    def call(*a, consistency=None, **kw):
        return fn(*a, **kw)
    return call
P.QueryProcessor.execute_statement = _drop(P.QueryProcessor.execute_statement)
P.QueryProcessor.process = _drop(P.QueryProcessor.process)
""", {"levels_not_coordinated", "quorum_not_enforced"}),
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
    # an acknowledged update that no replica applied, one in twenty
    "lost_acknowledged_update": ("""
from cassandra_tpu.cluster import coordinator as C
_mutate, _n = C.StorageProxy.mutate, [0]
def _lose(self, keyspace, mutation, cl="ONE"):
    if keyspace == "ycsb":
        _n[0] += 1
        if _n[0] % 20 == 0:
            return None
    return _mutate(self, keyspace, mutation, cl)
C.StorageProxy.mutate = _lose
""", None),
    # ONE node's served compaction falls to a host engine (the warm-up's,
    # the first task built for the table, runs on the device)
    "one_nodes_compaction_falls_to_native": ("""
_choose, _seen = T.choose_engine, [0]
def _third_is_native(*a, **kw):
    _seen[0] += 1
    if _seen[0] == 3:
        keep, T.tpu_backend = T.tpu_backend, lambda: False
        try:
            return _choose(*a, **kw)
        finally:
            T.tpu_backend = keep
    return _choose(*a, **kw)
T.choose_engine = _third_is_native
""", {"compactions_off_device"}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_reads_not_correct(rf3_tree, tmp_path, fault):
    patch, only = FAULTS[fault]
    rc, line, err = run_cell(rf3_tree, CELL, seed=53, seconds=4.0,
                             patch=ON_A_TPU + patch, tmp=str(tmp_path))
    assert rc == 0 and line is not None, err[-3000:]
    assert line["correct"] is False, line["checks"]
    assert err.rstrip().endswith("correct: False")
    bad = _bad(line)
    if only is not None:
        assert bad == only, bad
    if fault == "replica_acknowledges_without_applying":
        assert "replicas_diverging" in bad
        assert "reads_unknown_value" not in bad
    if fault == "lost_acknowledged_update":
        assert bad & {"reads_stale", "final_rows_wrong"}
        assert "levels_not_coordinated" in bad


def test_the_parent_program_fails_at_once(rf3_tree, tmp_path):
    """A program whose executor takes no consistency level (the parent of
    PR 33) is refused in set-up's first lines: an exit code, no result
    line."""
    rc, line, err = run_cell(rf3_tree, CELL, seed=1, seconds=1.0, patch="""
from cassandra_tpu.cql import execution as E
_execute = E.Executor.execute
def execute(self, stmt, params=(), keyspace=None, now_micros=None,
            user=None, page_size=None, paging_state=None):
    return _execute(self, stmt, params, keyspace, now_micros, user,
                    page_size, paging_state)
E.Executor.execute = execute
""", tmp=str(tmp_path))
    assert rc != 0 and line is None
    assert "takes no consistency level" in err


def test_controls_read_not_correct_and_the_reference_reads_correct(
        rf3_tree):
    import run as harness
    with open(os.path.join(rf3_tree, "benchmarks", "configs",
                           "ycsb_a_rf3.json")) as f:
        config = json.load(f)
    with open(os.path.join(rf3_tree, "benchmarks", "traffic",
                           "ycsb_closedloop_8_quorum.json")) as f:
        traffic = json.load(f)
    driver = harness.load("drivers", "wire_ycsb_cluster")
    ctx = harness.Ctx({"name": CELL}, config, traffic, 3000000307, 0, None,
                      None)
    out = {name: (harness.decide(checks),
                  {c["name"]: c["value"] for c in checks})
           for name, checks in driver.control(ctx)}
    assert sorted(out) == ["read_and_written_at_one", "reference_in_place",
                           "replica_drops_per_1000",
                           "values_truncated_to_99"]
    assert out["reference_in_place"][0] is True
    assert set(out["reference_in_place"][1]) == CHECKS
    at_one = out["read_and_written_at_one"]
    assert at_one[0] is False and at_one[1]["reads_stale"] > 0
    assert at_one[1]["quorum_not_enforced"] == 2
    assert at_one[1]["replicas_diverging"] == 0     # it does converge
    drops = out["replica_drops_per_1000"]
    assert drops[0] is False and drops[1]["replicas_diverging"] > 0
    assert drops[1]["reads_stale"] == 0             # the quorum hides it
    cut = out["values_truncated_to_99"]
    assert cut[0] is False and cut[1]["reads_unknown_value"] > 0


# ------------------------------------------------- the replica-set model --

def test_the_replica_set_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", "ycsb_quorum.py")) as f:
        text = f.read()
    imports = [ln for ln in text.splitlines()
               if ln.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations",
                       "import numpy as np"]


@pytest.mark.parametrize("w,r,fresh", [(2, 2, True), (3, 1, True),
                                       (1, 3, True), (1, 1, False),
                                       (2, 1, False)])
def test_r_plus_w_over_n_is_what_makes_a_read_fresh(w, r, fresh):
    """A write through coordinator 0, then at once a read through each
    other coordinator: fresh exactly when R + W > N."""
    import numpy as np
    from reference import ycsb_quorum
    loaded = np.full((2, 2, 4), 65, dtype=np.uint8)
    seen = []
    for via in (1, 2):
        model = ycsb_quorum.ReplicaSet(loaded, 3, w=w, r=r, lag=100)
        model.update(0, 0, b"new!", 0)
        seen.append(model.read(0, via)[0] == b"new!")
    assert all(seen) is fresh
    model.settle()
    assert [model.local_row(i, 0)[0] for i in range(3)] == [b"new!"] * 3


def test_a_read_repairs_the_replicas_it_read():
    import numpy as np
    from reference import ycsb_quorum
    loaded = np.full((1, 1, 4), 65, dtype=np.uint8)
    model = ycsb_quorum.ReplicaSet(loaded, 3, w=2, r=2, lag=100)
    model.update(0, 0, b"new!", 0)             # on replicas 0 and 1
    assert model.local_row(2, 0) == [b"AAAA"]
    assert model.read(0, 1) == [b"new!"]       # reads 1 and 2, repairs 2
    assert model.local_row(2, 0) == [b"new!"]
