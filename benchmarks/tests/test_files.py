"""Every data file and reader loads, and every name and unit in
BENCHMARK.json keeps to the contract's alphabet."""
import importlib.util
import json
import os
import re

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _files(sub: str, ext: str) -> list:
    return sorted(f[:-len(ext)] for f in os.listdir(os.path.join(BENCH, sub))
                  if f.endswith(ext))


def _module(sub: str, name: str):
    spec = importlib.util.spec_from_file_location(
        f"_t_{sub}_{name}", os.path.join(BENCH, sub, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_every_config_file_loads_and_states_its_guarantees():
    names = _files("configs", ".json")
    assert names
    for n in names:
        with open(os.path.join(BENCH, "configs", n + ".json")) as f:
            cfg = json.load(f)
        assert cfg["name"] == n and NAME.match(n)
        for key in ("source", "schema", "data", "guarantees", "reduced",
                    "assumed"):
            assert key in cfg, (n, key)
        assert cfg["guarantees"]


def test_every_traffic_file_names_a_driver_that_exists():
    for n in _files("traffic", ".json"):
        with open(os.path.join(BENCH, "traffic", n + ".json")) as f:
            mix = json.load(f)
        assert NAME.match(n)
        assert mix["driver"] in _files("drivers", ".py"), n
        assert "trace" in mix and "what" in mix


def test_every_driver_reader_and_kernel_loads_without_jax_backend():
    import sys
    for sub, need in (("drivers", ("setup", "window", "check", "close")),
                      ("layer_metrics", ("read",)),
                      ("kernels", ("PROGRAM", "TRACE_MODULE",
                                   "least_bytes")),
                      ("reference", ())):
        for n in _files(sub, ".py"):
            mod = _module(sub, n)
            for attr in need:
                assert hasattr(mod, attr), (sub, n, attr)


def test_importing_the_harness_starts_no_backend_and_no_profiler():
    """run.py refuses a platform in main(), never at import."""
    import subprocess
    import sys
    code = ("import sys; sys.path.insert(0, %r); import run, trace_reduce, "
            "stats, wire, loadgen, control; "
            "jax = sys.modules.get('jax'); "
            "assert jax is None or not jax._src.xla_bridge._backends, "
            "'a backend was started at import'" % BENCH)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=dict(os.environ, JAX_PLATFORMS="cpu",
                                           PYTHONPATH=ROOT))
    assert p.returncode == 0, p.stderr[-2000:]


def test_benchmark_json_keeps_to_the_contract(bench_json):
    b = bench_json
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmarks"]
    assert 1 <= b["run_seconds"] <= 51
    cfg_names = [c["name"] for c in b["configs"]]
    cell_names = [w["name"] for w in b["workloads"]]
    assert len(set(cfg_names)) == len(cfg_names)
    assert len(set(cell_names)) == len(cell_names)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert c["file"].startswith("benchmarks/configs/")
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["name"] in {w["config"] for w in b["workloads"]}
        with open(os.path.join(ROOT, c["file"])) as f:
            assert sorted(json.load(f)["reduced"]) == sorted(c["reduced"])
        for text in (c["source"], c["why"]):
            assert 1 <= len(text) <= 200 and "\n" not in text
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cfg_names and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json"))
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = list(e2e) + [m["name"] for m in b["per_layer"]]
    assert len(set(names)) == len(names)
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        for w in m.get("workloads", []):
            assert w in cell_names
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".py")), m["name"]
        # each cell the reader serves reports the metric it moves
        for w in m.get("workloads", cell_names):
            assert w in e2e[m["moves"]].get("workloads", cell_names)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for w in cell_names:     # setup_s, one more end-to-end, one per-layer
        assert any(w in m.get("workloads", cell_names)
                   for m in b["end_to_end"] if m["name"] != "setup_s")
        assert any(w in m.get("workloads", cell_names)
                   for m in b["per_layer"])


def test_readers_return_nothing_when_there_is_nothing_to_read():
    import sys
    sys.path.insert(0, BENCH)
    import stats as stats_mod

    class Empty:
        stats = stats_mod
        window, trace, config = {}, None, {"lanes": 13, "data": {
            "rows": 1, "dim": 1}}
        device = {"kind": "TPU v5 lite"}

        def load(self, kind, name):
            return _module(kind, name)

        def executable(self, kernel):
            return None

        def peaks(self):
            return {"hbm_bytes_per_s": 819e9}
    for n in _files("layer_metrics", ".py"):
        assert _module("layer_metrics", n).read(Empty()) is None, n
