#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json on the machine it is started on.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A new process per run: place the compile cache, refuse anything but a TPU
(in main(), never at import), rebuild the native library, set up and warm
every shape the window uses (all of that is setup_s), measure for
--seconds, read the device's memory peak, decide `correct` against the
plain reference, print the comparisons and the contract's one last line.

The harness is driven by data. A cell names a configuration and a traffic
mix; the mix names a driver; BENCHMARK.json names the per-layer metrics.
Each is a file of its own under this directory, found by that name:
configs/<config>.json, traffic/<mix>.json, drivers/<driver>.py,
layer_metrics/<metric>.py, kernels/<program>.py. Nothing here knows a cell
by name. See README.md.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import re
import shutil
import sys
import tempfile
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
KINDS = ("drivers", "layer_metrics", "kernels", "reference")


class Refused(Exception):
    """The run cannot be made here; no result line is printed."""


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def read_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load(kind: str, name: str):
    """The module <kind>/<name>.py of this directory, by its file."""
    if kind not in KINDS or not NAME.match(name):
        raise Refused(f"bad module name {kind}/{name!r}")
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise Refused(f"no such file: benchmarks/{kind}/{name}.py")
    modname = f"_bench_{kind}_{name.replace('.', '_').replace('-', '_')}"
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def load_bench() -> dict:
    return read_json(ROOT, "BENCHMARK.json")


def find_cell(bench: dict, workload: str) -> tuple:
    cells = [w for w in bench["workloads"] if w["name"] == workload]
    if not cells:
        raise Refused(f"no cell {workload!r} in BENCHMARK.json")
    cell = cells[0]
    cfg_entry = [c for c in bench["configs"] if c["name"] == cell["config"]]
    if not cfg_entry:
        raise Refused(f"cell {workload!r} names no listed configuration")
    if not NAME.match(cell["traffic"]):
        raise Refused(f"bad traffic name {cell['traffic']!r}")
    config = read_json(ROOT, cfg_entry[0]["file"])
    traffic = read_json(HERE, "traffic", cell["traffic"] + ".json")
    return cell, config, traffic


def decide(checks: list) -> bool:
    """`correct`: every number compared is there and within its limit."""
    return bool(checks) and all(
        c["value"] is not None and c["value"] <= c["limit"] for c in checks)


def metrics_of(bench: dict, group: str, cell_name: str) -> list:
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


class Tracer:
    """jax.profiler around a slice of the window; drivers call start/stop
    at the boundaries their traffic file names. Python-level tracing is
    off: only the device, the runtime and TraceAnnotation spans."""

    def __init__(self, directory: str):
        self.directory = directory
        self.t_start = self.t_stop = None

    def start(self) -> None:
        if self.t_start is not None:
            return
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        self.t_start = time.perf_counter()

    def stop(self) -> None:
        if self.t_start is None or self.t_stop is not None:
            return
        import jax
        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    @property
    def window_s(self):
        if self.t_start is None or self.t_stop is None:
            return None
        return self.t_stop - self.t_start


class Ctx:
    """What a driver gets: the cell's data files, the seed, the window's
    length, a scratch directory, the tracer (traced runs only), and
    note() for anything worth an earlier line of output."""

    def __init__(self, cell, config, traffic, seed, seconds, scratch,
                 tracer):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds = int(seed), float(seconds)
        self.scratch, self.tracer = scratch, tracer
        self.notes: dict = {}
        self.root = ROOT

    def note(self, key: str, value) -> None:
        self.notes[key] = value

    def load(self, kind: str, name: str):
        return load(kind, name)

    def annotate(self, name: str):
        """A span of the benchmark's own in the profiler's trace."""
        if self.tracer is None:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


class LayerCtx:
    """What a per-layer reader gets."""

    def __init__(self, ctx: Ctx, result: dict, trace: dict | None,
                 device: dict):
        self.config, self.traffic, self.cell = ctx.config, ctx.traffic, \
            ctx.cell
        self.window, self.trace, self.device = result, trace, device
        self.load = ctx.load
        import stats
        self.stats = stats

    def peaks(self) -> dict:
        return self.stats.peaks_for(self.device["kind"])

    def executable(self, kernel) -> dict | None:
        """{"seconds", "calls"} of a kernel's executable in the traced
        slice, by the trace name kept in kernels/<program>.py."""
        if not self.trace:
            return None
        hit = [v for k, v in self.trace["executables"].items()
               if k == kernel.TRACE_MODULE]
        return hit[0] if hit and hit[0]["seconds"] > 0 else None


def rebuild_native() -> float:
    """Build the C++ library from what git commits, as chip_smoke.py
    does: a checkout never runs a .so another machine left behind."""
    import subprocess
    t0 = time.perf_counter()
    from cassandra_tpu.ops import host_merge
    from cassandra_tpu.ops.native import build as native_build
    try:
        native_build.rebuild()
    except subprocess.CalledProcessError as e:
        raise Refused("g++ failed on ops/native: "
                      + e.stderr.decode("utf-8", "replace")[-2000:])
    native_build.load()
    if not host_merge.available():
        raise Refused("native merge engine did not load")
    return time.perf_counter() - t0


def device_info(check_platform: bool, chips: int) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if check_platform and info["platform"] != "tpu":
        raise Refused(f"jax's platform is {info['platform']!r}, not 'tpu': "
                      "the benchmark does not run without the chip")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chip(s), jax sees "
                      f"{len(devs)}")
    return info


def memory_peak() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") or 0
             for d in jax.devices()]
    return int(max(peaks))


def compiles_now() -> dict:
    from cassandra_tpu.service.profiling import GLOBAL as registry
    return {n: k["compiles"]
            for n, k in registry.snapshot()["kernels"].items()}


def run_cell(args, check_platform: bool = True) -> int:
    t_start = time.perf_counter()
    for p in (ROOT, HERE):
        if p not in sys.path:
            sys.path.insert(0, p)
    bench = load_bench()
    cell, config, traffic = find_cell(bench, args.workload)
    try:
        import cassandra_tpu  # noqa: F401
    except ImportError:
        raise Refused("the program (cassandra_tpu/) is not in this "
                      "checkout: nothing to measure")
    from cassandra_tpu.utils import compile_cache
    cache_dir = compile_cache.configure()
    device = device_info(check_platform, int(cell["chips"]))
    native_s = rebuild_native()
    scratch = tempfile.mkdtemp(prefix="ctpu-bench-")
    trace_dir = os.path.join(scratch, "trace")
    tracer = Tracer(trace_dir) if args.trace else None
    ctx = Ctx(cell, config, traffic, args.seed, args.seconds,
              os.path.join(scratch, "data"), tracer)
    driver = load("drivers", traffic["driver"])
    state = None
    try:
        state = driver.setup(ctx)
        compiles0 = compiles_now()
        setup_s = time.perf_counter() - t_start
        emit({"phase": "setup", "cell": cell["name"], "seed": args.seed,
              "setup_s": setup_s, "native_build_s": native_s,
              "compile_cache_dir": cache_dir, "device": device,
              **ctx.notes})
        ctx.notes.clear()
        result = driver.window(state, ctx)
        if tracer is not None:
            tracer.stop()
        compiled = {n: c - compiles0.get(n, 0)
                    for n, c in compiles_now().items()
                    if c - compiles0.get(n, 0)}
        device["memory_peak_bytes"] = memory_peak()
        emit({"phase": "window", "compiles_in_window": compiled,
              "elapsed_s": result.get("elapsed_s"),
              **result.get("detail", {}), **ctx.notes})
        ctx.notes.clear()

        trace = None
        if tracer is not None:
            import trace_reduce
            path = trace_reduce.find_xplane(trace_dir)
            if path is None or tracer.window_s is None:
                raise Refused("the traced run left no .xplane.pb")
            trace = trace_reduce.reduce_trace(path, tracer.window_s,
                                              int(cell["chips"]))

        t_check = time.perf_counter()
        checks = driver.check(state, ctx, result)
        check_s = time.perf_counter() - t_check
    finally:
        if tracer is not None:
            with contextlib.suppress(Exception):
                tracer.stop()
        if state is not None:
            with contextlib.suppress(Exception):
                driver.close(state)
        shutil.rmtree(scratch, ignore_errors=True)

    correct = decide(checks)
    end_to_end = dict(result["end_to_end"], setup_s=setup_s)
    metrics = {}
    if not args.trace:
        for m in metrics_of(bench, "end_to_end", cell["name"]):
            if m["name"] not in end_to_end:
                raise Refused(f"driver {traffic['driver']} gave no "
                              f"{m['name']}")
            metrics[m["name"]] = {"value": end_to_end[m["name"]],
                                  "unit": m["unit"]}
    else:
        lctx = LayerCtx(ctx, result, trace, device)
        for m in metrics_of(bench, "per_layer", cell["name"]):
            value = load("layer_metrics", m["name"]).read(lctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics,
            "device": device}
    if trace is not None:
        line["breakdown"] = {
            "device_ops": trace["device_ops"],
            "idle_gaps": trace["idle_gaps"],
            "compiles_in_window": sorted(compiled.items())}
    line.update(cell=cell["name"], seed=args.seed, seconds=args.seconds,
                end_to_end_seen=end_to_end,
                check_s=check_s, notes=ctx.notes,
                checks={c["name"]: {"value": c["value"],
                                    "limit": c["limit"]} for c in checks})
    for c in checks:
        log(f"compared {c['name']}: value {c['value']} limit {c['limit']}"
            + (f" (of {c['of']})" if "of" in c else ""))
    log(f"correct: {correct}")
    emit(line)
    return 0


def main(argv=None, check_platform: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        return run_cell(args, check_platform)
    except Refused as e:
        log(f"refused: {e}")
        return 3
    except Exception:
        traceback.print_exc()
        return 1


if __name__ == "__main__":
    sys.exit(main())
