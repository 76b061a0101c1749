"""Fixtures of the benchmark's own tests (run with
`JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider`;
tier-1's `pytest tests/` never collects this directory).

Nothing runs while a module is imported: the tiny tree is built by a
fixture, runs of a cell are child processes.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)

TINY = {
    "configs/stress_stcs_lz4.json": lambda c: c["data"].update(
        runs=4, rows_per_run=3000),
    "configs/ann_glove_100.json": lambda c: c["data"].update(rows=3000),
    "traffic/closedloop_4.json": lambda c: c.update(
        trace={"start_s": 0.3, "seconds": 0.7}, queries_per_connection=64),
}


def make_tiny_tree(dst: str) -> str:
    """A copy of BENCHMARK.json and benchmarks/ with every configuration
    cut to a size the CPU holds in seconds: the only place a size or a
    platform is overridden."""
    shutil.copytree(BENCH, os.path.join(dst, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    for rel, edit in TINY.items():
        path = os.path.join(dst, "benchmarks", rel)
        with open(path) as f:
            cfg = json.load(f)
        edit(cfg)
        with open(path, "w") as f:
            json.dump(cfg, f)
    return dst


BOOT = """
import sys
sys.path.insert(0, {bench!r})
{patch}
import run
sys.exit(run.main({argv!r}, check_platform=False))
"""


def run_cell(tree: str, workload: str, seed: int = 5, seconds: float = 2.0,
             trace: int = 0, patch: str = "", tmp: str | None = None):
    """One run of a cell of the tree in a child process, the harness's
    look for a chip skipped; (exit code, last stdout line as JSON or None,
    stderr)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=os.path.join(tree, ".jax_cache"))
    if tmp:
        env["TMPDIR"] = tmp
    argv = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    code = BOOT.format(bench=os.path.join(tree, "benchmarks"), patch=patch,
                       argv=argv)
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=tree,
                       capture_output=True, text=True, timeout=600)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    line = None
    if lines:
        try:
            line = json.loads(lines[-1])
        except ValueError:
            line = None
    if line is not None and "correct" not in line:
        line = None
    return p.returncode, line, p.stderr


@pytest.fixture(scope="session")
def tiny_tree(tmp_path_factory):
    return make_tiny_tree(str(tmp_path_factory.mktemp("tiny")))


@pytest.fixture(scope="session")
def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
