"""`ucs_zstd.cycle` whole at a tiny size on the CPU (a tiny tree of its own:
conftest.py's, with this cell's files cut further), each fault the cell can
have planted under a full run of the harness, the parent's program refused
in set-up, and the controls of its plain reference.

The tiny cut keeps the cell's geometry: with min_sstable_size cut to 256
KiB (level 0 below 1 MiB) four flushes of 2,000 rows (about 0.5 MB each)
are level 0, their compaction (about 2 MB) writes four shards, and each
shard reads as level 1. On the CPU the engine choice's probe is faked and
its size floor lowered, in the child process and nowhere else: the harness
still passes no engine.
"""
import json
import os
import sys
import time

import pytest

from conftest import BENCH, make_tiny_tree, run_cell

sys.path.insert(0, BENCH)

CELL = "ucs_zstd.cycle"
CHECKS = {"cells_wrong", "shards_misplaced", "levels_wrong",
          "compactions_beyond_one", "compression_wrong",
          "components_differing", "components_differing_from_host_engine",
          "compactions_off_device"}
ON_A_TPU = """
from cassandra_tpu.compaction import task as T
T.tpu_backend = lambda: True
T.CompactionTask.DEVICE_MIN_CELLS = 1000
"""


@pytest.fixture(scope="module")
def ucs_tree(tmp_path_factory):
    tree = make_tiny_tree(str(tmp_path_factory.mktemp("ucs_tiny")))
    path = os.path.join(tree, "benchmarks", "configs",
                        "stress_ucs_zstd.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["data"].update(rows_per_run=2000)
    cfg["schema"]["compaction"].update(min_sstable_size="256KiB")
    cfg["correct"].update(warm_cycle_wait_s=120, cycle_wait_s=60,
                          start_wait_s=20)
    with open(path, "w") as f:
        json.dump(cfg, f)
    return tree


def _bad(line):
    return {k for k, c in line["checks"].items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_end_to_end_and_is_correct(ucs_tree, tmp_path, trace):
    rc, line, err = run_cell(ucs_tree, CELL, seed=3000003900 + trace,
                             seconds=3.0, trace=trace, patch=ON_A_TPU,
                             tmp=str(tmp_path))
    assert rc == 0 and line is not None, err[-3000:]
    assert line["correct"] is True, (line["checks"], err[-3000:])
    assert set(line["checks"]) == CHECKS
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert err.rstrip().endswith("correct: True")
    if trace == 0:
        assert set(line["metrics"]) == {"compaction_mib_s", "setup_s"}
        assert line["metrics"]["compaction_mib_s"]["value"] > 0
    else:
        with open(os.path.join(ucs_tree, "BENCHMARK.json")) as f:
            mine = {m["name"] for m in json.load(f)["per_layer"]
                    if CELL in m.get("workloads", [])}
        # the cell's four and the compaction cells' generic readers
        assert sorted(m for m in mine if not m.startswith("ucs_")) == [
            "gil_handoff_mean_ms", "merge_resident_padding_pct",
            "merge_writeq_wait_pct", "write_emit_directory_pct",
            "write_lane_busy_pct"]
        assert len(mine) == 9
        # no TPU plane on the CPU: the roofline is left out, never 0
        assert set(line["metrics"]) == mine - {"ucs_merge_resident_roofline"}
        m = {k: v["value"] for k, v in line["metrics"].items()}
        # partial rounds pad to the compaction's one shape
        assert 0 < m["merge_resident_padding_pct"] < 100
        # three rounds a compaction stop at a boundary; the last shard's
        # cells end in rounds cut where the inputs end
        assert 0 < m["ucs_rounds_cut_at_shard_pct"] <= 75.0
        assert m["ucs_shard_roll_ms_per_compaction"] > 0
        assert m["ucs_zstd_compress_ms_per_mib"] > 0
        assert line["breakdown"]["compiles_in_window"] == []


PARENTS_RULE = """
from cassandra_tpu.compaction import strategies as S
def _parents_pick(self):
    from cassandra_tpu.compaction.task import CompactionTask
    levels = {}
    for s in self.candidates():
        levels.setdefault(self.level_of(float(s.data_size)), []).append(s)
    for lvl in sorted(levels):
        group = levels[lvl]
        if len(group) >= self.threshold(lvl):
            inputs = group[: self.max_threshold]
            total = float(sum(s.data_size for s in inputs))
            return CompactionTask(
                self.cfs, inputs, level=lvl + 1,
                max_output_bytes=max(int(total // self.num_shards(total)),
                                     self.min_sstable_size))
    return None
S.UnifiedCompactionStrategy.next_background_task = _parents_pick
"""

FAULTS = {
    # the parent's UCS: density by size alone, a count threshold and a
    # byte roll in place of the token boundaries
    "the_parents_byte_roll": (PARENTS_RULE, {"shards_misplaced"}),
    # the table's Zstd read as LZ4
    "lz4_in_zstds_place": ("""
from cassandra_tpu.ops import codec as C
_from = C.CompressionParams.from_dict.__func__
def _lz4(cls, d):
    d = dict(d or {})
    if "Zstd" in str(d.get("class", "")):
        d["class"] = "LZ4Compressor"
    return _from(cls, d)
C.CompressionParams.from_dict = classmethod(_lz4)
""", {"compression_wrong"}),
    # every task reads nothing of its fourth input and still obsoletes it
    "one_input_lost": ("""
_cursor, _made = T._Cursor, [0]
def _lossy(reader, prof=None):
    c = _cursor(reader, prof)
    _made[0] += 1
    if _made[0] % 4 == 0:
        c.bufs, c.exhausted = [], True
    return c
T._Cursor = _lossy
""", {"cells_wrong"}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_reads_not_correct(ucs_tree, tmp_path, fault):
    patch, bad = FAULTS[fault]
    rc, line, err = run_cell(ucs_tree, CELL, seed=3000003910,
                             seconds=2.0, patch=ON_A_TPU + patch,
                             tmp=str(tmp_path))
    assert rc == 0 and line is not None, err[-3000:]
    assert line["correct"] is False, (fault, line["checks"])
    assert bad <= _bad(line), (fault, line["checks"])


@pytest.mark.parametrize("patch,says", [
    # the parent's strategy: int('100MiB') raises in its constructor
    ("""
from cassandra_tpu.compaction import strategies as S
def _parents_init(self, cfs, options=None, repaired=None):
    int(dict(options or {}).get("min_sstable_size", 0))
S.UnifiedCompactionStrategy.__init__ = _parents_init
""", "cannot run this cell's table"),
    # a manager that never runs what it selects: the warm-up ends idle
    ("""
from cassandra_tpu.compaction import manager as M
M.CompactionManager._maybe_compact = lambda self, cfs, locked=False: 0
""", "the warm-up cycle ended 'idle'")])
def test_setup_fails_at_once_where_no_merge_can_start(ucs_tree, tmp_path,
                                                       patch, says):
    t0 = time.monotonic()
    rc, line, err = run_cell(ucs_tree, CELL, seed=3000003920, seconds=2.0,
                             patch=ON_A_TPU + patch, tmp=str(tmp_path))
    assert rc == 1 and line is None
    assert says in err, err[-3000:]
    assert time.monotonic() - t0 < 60


def test_controls_read_not_correct_and_the_reference_reads_correct(
        ucs_tree):
    import run as harness
    with open(os.path.join(ucs_tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    with open(os.path.join(ucs_tree, "benchmarks", "configs",
                           cell["config"] + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(ucs_tree, "benchmarks", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    driver = harness.load("drivers", traffic["driver"])
    ctx = harness.Ctx(cell, config, traffic, 3000003907, 0, None, None)
    out = {name: (harness.decide(checks), checks)
           for name, checks in driver.control(ctx)}
    assert sorted(out) == sorted(["reference_in_place", "lose_run",
                                  "millisecond_timestamps"])
    assert out["reference_in_place"][0] is True
    # the last of four flushes lost: its 2,000 rows x 5 cells
    assert out["lose_run"][1][0]["value"] == 2000 * 5
    assert not out["lose_run"][0] and not out["millisecond_timestamps"][0]


def test_reference_tokens_shards_and_plan_at_the_cells_size():
    """reference/ucs.py on the full-size configuration's arithmetic: four
    flushes of 101.6 MiB (the least a v5e host's Zstd wrote of them) over
    the whole ring are picked, written as four shards of the ring at level
    1, none of which is picked again."""
    from reference import ucs
    with open(os.path.join(BENCH, "configs", "stress_ucs_zstd.json")) as f:
        opts = ucs.Options(json.load(f)["schema"]["compaction"])
    lo, hi, size = -(1 << 63) + 1, (1 << 63) - 1, int(101.6 * (1 << 20))
    flushes = [(size, lo, hi)] * 4
    assert ucs.select(opts, flushes) == [0, 1, 2, 3]
    plan = ucs.plan(opts, flushes)
    assert plan["level"] == 1
    assert plan["boundaries"] == [-(1 << 63) + i * (1 << 62)
                                  for i in range(4)]
    shards = [(size, b + 1, b + (1 << 62) - 1)
              for b in plan["boundaries"]]
    assert all(ucs.level(opts, ucs.density(*s)) == 1 for s in shards)
    assert ucs.select(opts, shards) is None
    assert list(ucs.shard_of([lo, -1, 0, hi], plan["boundaries"])) == \
        [0, 1, 2, 3]
