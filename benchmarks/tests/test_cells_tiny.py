"""Every driver end to end at a tiny size on the CPU (the harness's look
for a chip skipped, here and nowhere else), the same runs with the timed
path broken underneath, the controls of the plain references, and the
proof that a later PR adds a cell, a configuration, a traffic mix and a
per-layer metric by new files and new entries alone."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, ROOT, make_tiny_tree, run_cell

sys.path.insert(0, BENCH)

CELLS = {
    "stcs_lz4.major": ("compaction_mib_s",
                       {"cells_wrong", "components_differing",
                        "compactions_off_device", "sstables_beyond_one",
                        "components_differing_from_host_engine"}),
    "glove_100.ann_top10": ("ops_s", {"queries_unanswered",
                                      "ann_lists_malformed",
                                      "ann_widest_score_gap"}),
}


def _cell_files_present(tree, cell):
    with open(os.path.join(tree, "BENCHMARK.json")) as f:
        return cell in [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_end_to_end_and_is_correct(tiny_tree, tmp_path, cell,
                                             trace):
    if not _cell_files_present(tiny_tree, cell):
        pytest.skip(f"{cell} is not a cell of BENCHMARK.json")
    rc, line, err = run_cell(tiny_tree, cell, seed=3000000000 + trace,
                             trace=trace, tmp=str(tmp_path))
    assert rc == 0 and line is not None, err[-3000:]
    metric, checks = CELLS[cell]
    assert line["correct"] is True, err[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["checks"]) == checks
    assert list(line)[-1] == "checks"          # the comparisons come last
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]
    assert "compared " in err and err.rstrip().endswith("correct: True")
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        assert key in line["device"]
    if trace == 0:
        assert set(line["metrics"]) == {metric, "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())
        assert "breakdown" not in line
    else:
        assert line["metrics"], "a traced run reports per-layer metrics"
        assert "setup_s" not in line["metrics"]
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert {"device_ops", "idle_gaps"} <= set(line["breakdown"])
        # no TPU plane on the CPU: the rooflines have nothing to read and
        # are left out, never reported as 0
        assert not [m for m in line["metrics"] if m.endswith("_roofline")]


FAULTS = {
    # a step that returns its state unchanged
    ("stcs_lz4.major", "state_unchanged"): """
import time
from cassandra_tpu.compaction import task as T
def _noop(self):
    time.sleep(0.3)
    r = self.inputs
    return {"inputs": len(r), "outputs": len(r),
            "bytes_read": sum(x.data_size for x in r), "bytes_written": 0,
            "cells_read": sum(x.n_cells for x in r), "cells_written": 0}
T.CompactionTask.execute = _noop
""",
    # half of the batch left out
    ("stcs_lz4.major", "half_left_out"): """
from cassandra_tpu.compaction import task as T
_init = T.CompactionTask.__init__
def _half(self, cfs, inputs, *a, **kw):
    _init(self, cfs, list(inputs)[:len(inputs) // 2], *a, **kw)
T.CompactionTask.__init__ = _half
""",
    # an answer altered where it is produced: write times cut to the
    # millisecond on their way into the merge
    ("stcs_lz4.major", "answer_altered"): """
from cassandra_tpu.compaction import task as T
_fetch = T._Cursor._fetch
def _coarse(self):
    got = _fetch(self)
    if self.bufs:
        self.bufs[-1].ts = self.bufs[-1].ts // 1000 * 1000
    return got
T._Cursor._fetch = _coarse
""",
    # the device path left for a host engine, which writes the same bytes
    ("stcs_lz4.major", "off_device"): """
from cassandra_tpu.compaction import task as T
_init = T.CompactionTask.__init__
def _host(self, *a, **kw):
    kw.update(engine="numpy", use_device=False)
    _init(self, *a, **kw)
T.CompactionTask.__init__ = _host
""",
    # the best row dropped from every answer
    ("glove_100.ann_top10", "answer_altered"): """
from cassandra_tpu.index import manager as M
_ann = M.VectorIndex.ann
def _second_best(self, query, k, *a, **kw):
    return _ann(self, query, k + 1, *a, **kw)[1:]
M.VectorIndex.ann = _second_best
""",
}


@pytest.mark.parametrize("cell,fault", sorted(FAULTS))
def test_a_broken_timed_path_reads_not_correct(tiny_tree, tmp_path, cell,
                                               fault):
    if not _cell_files_present(tiny_tree, cell):
        pytest.skip(f"{cell} is not a cell of BENCHMARK.json")
    rc, line, err = run_cell(tiny_tree, cell, seed=41, patch=FAULTS[
        (cell, fault)], tmp=str(tmp_path))
    assert rc == 0 and line is not None, err[-3000:]
    assert line["correct"] is False, line["checks"]
    assert err.rstrip().endswith("correct: False")
    if fault == "off_device":
        # same bytes, so only the count of compactions off the device
        # catches it; and a compaction off the device earns no bytes
        bad = {k for k, c in line["checks"].items()
               if c["value"] > c["limit"]}
        assert bad == {"compactions_off_device"}
        assert line["failed"] == line["attempted"]
        assert line["metrics"]["compaction_mib_s"]["value"] == 0


# ------------------------------------------------------------ controls --

def _control(tiny_tree, cell, seed):
    """control.py's own loop over a tiny tree's cell: {control: correct}
    and the checks, through the harness's decide()."""
    sys.path.insert(0, BENCH)
    import run as harness
    with open(os.path.join(tiny_tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    w = [x for x in bench["workloads"] if x["name"] == cell][0]
    cfg = [c for c in bench["configs"] if c["name"] == w["config"]][0]
    with open(os.path.join(tiny_tree, cfg["file"])) as f:
        config = json.load(f)
    with open(os.path.join(tiny_tree, "benchmarks", "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    driver = harness.load("drivers", traffic["driver"])
    ctx = harness.Ctx(w, config, traffic, seed, 0, None, None)
    return {name: (harness.decide(checks), checks)
            for name, checks in driver.control(ctx)}


@pytest.mark.parametrize("cell,controls", [
    ("stcs_lz4.major", ["lose_run", "millisecond_timestamps"]),
    ("glove_100.ann_top10", ["bfloat16"])])
def test_controls_read_not_correct_and_the_reference_reads_correct(
        tiny_tree, cell, controls):
    out = _control(tiny_tree, cell, seed=3000000007)
    assert sorted(out) == sorted(controls + ["reference_in_place"])
    assert out["reference_in_place"][0] is True
    for name in controls:
        assert out[name][0] is False, out[name][1]


def _runs(seed=0, runs=4, n=500, cols=5):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, 256, (n, 10), dtype=np.uint8),
             np.sort(rng.integers(10 ** 15, 2 * 10 ** 15, n)),
             rng.integers(0, 256, (n, cols, 34), dtype=np.uint8))
            for _ in range(runs)]


def test_compaction_reference_keeps_every_cell_once_newest_winning():
    from reference import compaction as ref
    runs = _runs()
    out = ref.merge(runs)
    assert len(out["hi"]) == 4 * 500 * 5
    pairs = set(zip(out["hi"].tolist(), out["lo"].tolist()))
    assert len(pairs) == len(out["hi"])               # one cell per key
    assert (out["flags"] == 0).all() and (out["vlen"] == 34).all()
    assert ref.cells_wrong(out, ref.merge(runs)) == 0
    # a key written again later: the newer write wins, cell for cell
    keys, ts, vals = runs[0]
    again = (keys[:7], ts[:7] + 10 ** 12, 255 - vals[:7])
    out2 = ref.merge(runs + [again])
    assert len(out2["hi"]) == len(out["hi"])
    assert ref.cells_wrong(out2, out) == 7 * 5
    # a cell one side holds twice is counted, not hidden
    twice = {k: np.concatenate([v, v[:3]]) for k, v in out.items()}
    assert ref.cells_wrong(twice, out) == 3
    some = {k: v[5:] for k, v in out.items()}
    assert ref.cells_wrong(some, out) == 5


def test_ann_reference_in_float32_is_in_and_a_bad_list_is_counted():
    from reference import ann as ref
    rng = np.random.default_rng(5)
    mat = rng.standard_normal((20000, 100), dtype=np.float32)
    qs = rng.standard_normal((8, 100), dtype=np.float32)
    exact = ref.scores(mat, qs)
    assert ref.compare(exact, [[1, 1, 2, 3, 4, 5, 6, 7, 8, 9]] * 8,
                       10)[1] == 8                  # a row twice
    f32 = ref.scores(mat, qs, precision="float32")
    gap, malformed = ref.compare(
        exact, [ref.top_k(f32[:, j], 10) for j in range(8)], 10)
    assert malformed == 0 and gap < 1e-6


def test_bf16_round_is_round_to_nearest_even():
    from reference import ann as ref
    x = np.array([1.0, 1.00390625, 1.01171875, 3.14159274], np.float32)
    # 1 + 2^-8 ties to even (1.0); 1 + 3*2^-8 ties to even (1 + 2^-6)
    assert ref.bf16_round(x).tolist() == [1.0, 1.0, 1.015625, 3.140625]


# ------------------------------------------- the harness, not the cells --

def test_refuses_a_cpu_and_prints_no_result(tiny_tree):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "stcs_lz4.major",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tiny_tree, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "not 'tpu'" in p.stderr


def test_refuses_a_directory_without_the_program(tiny_tree):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "stcs_lz4.major",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tiny_tree, env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and '"correct"' not in p.stdout


def test_a_later_pr_adds_cell_config_mix_and_metric_by_files_alone(
        tmp_path):
    tree = make_tiny_tree(str(tmp_path / "tree"))
    before = {}
    for dirpath, _d, files in os.walk(tree):
        for fn in files:
            p = os.path.join(dirpath, fn)
            with open(p, "rb") as f:
                before[p] = f.read()
    b = os.path.join(tree, "benchmarks")
    with open(os.path.join(b, "configs", "stress_stcs_lz4.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "dummy_cfg"
    cfg["data"]["rows_per_run"] = 1500
    with open(os.path.join(b, "configs", "dummy_cfg.json"), "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(b, "traffic", "major_loop.json"),
                os.path.join(b, "traffic", "dummy_mix.json"))
    with open(os.path.join(b, "layer_metrics", "dummy_metric.py"), "w") as f:
        f.write("def read(ctx):\n    return 42.0 if ctx.window.get('ops') "
                "else None\n")
    with open(os.path.join(tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "dummy_cfg", "source": "test", "reduced": ["n"],
        "file": "benchmarks/configs/dummy_cfg.json", "why": "test"})
    bench["workloads"].append({
        "name": "dummy.cell", "config": "dummy_cfg", "traffic": "dummy_mix",
        "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "compaction_mib_s":
            m["workloads"].append("dummy.cell")
    bench["per_layer"].append({
        "name": "dummy_metric", "unit": "x", "better": "higher",
        "source": "program_counter", "layer": "test",
        "moves": "compaction_mib_s", "workloads": ["dummy.cell"]})
    with open(os.path.join(tree, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    rc, line, err = run_cell(tree, "dummy.cell", trace=1, seconds=1.0,
                             tmp=str(tmp_path))
    assert rc == 0 and line and line["correct"], err[-3000:]
    assert line["metrics"]["dummy_metric"] == {"value": 42.0, "unit": "x"}
    rc, line, err = run_cell(tree, "dummy.cell", trace=0, seconds=1.0,
                             tmp=str(tmp_path))
    assert rc == 0 and set(line["metrics"]) == {"compaction_mib_s",
                                                "setup_s"}, err[-3000:]
    # no file that was there has changed, BENCHMARK.json's entries apart
    for p, content in before.items():
        if os.path.basename(p) != "BENCHMARK.json":
            with open(p, "rb") as f:
                assert f.read() == content, p
