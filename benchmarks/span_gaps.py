#!/usr/bin/env python3
"""Where the device's idle gaps go, by the program's own spans, by hand:

    python benchmarks/span_gaps.py <cell> <seed> [--seconds S] [--out DIR]

Runs one traced slice of the cell through the cell's own driver (set-up,
warm-up and window as a run makes them; no `check`, no result line), keeps
the .xplane.pb under DIR (default chiprun_out/span_gaps/; a traced
compaction's is ≈ 43 MB and an ANN slice's ≈ 50 MB, so under the chip tool
point DIR outside chiprun_out/), and prints one JSON object:

- `threads`: per Python thread, each `ctpu.` span's count, seconds and self
  seconds (its time less its children's) in the trace. The program's spans
  carry the thread's name as an event stat: the profiler names a host line
  after the process, not after a Python thread.
- `gaps`: for every idle gap of the device (between the merged intervals of
  "XLA Ops"), the innermost `ctpu.` span on EACH thread at the gap's
  middle, seconds summed by thread and span; a thread with no span open
  there reads `-`.
- `coverage`: how much of each `ctpu.compaction.task` (per thread, by the
  union of the thread's other spans inside it) and of each vector query's
  `ctpu.transport.request` (by the union of the spans below it) the finer
  spans cover.
- `ring`: records in the program's span ring, its bound, and the bytes it
  holds at that bound (measured on the records it holds now).

`trace_reduce.py` reads only the benchmark's own `bench.` spans; folding
this attribution into it is a `benchmark` issue's (ROADMAP). The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

import run as harness
import trace_reduce

PREFIX = "ctpu."
TASK = "ctpu.compaction.task"
REQUEST = "ctpu.transport.request"
ANN_CALL = "ctpu.index.ann.call"
NO_SPAN = "-"


def host_spans(path: str) -> dict:
    """{thread: [(name, start_ns, end_ns)]} of the program's spans."""
    from jax.profiler import ProfileData
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if not e.name.startswith(PREFIX):
                    continue
                thread = dict(e.stats).get("thread", line.name)
                out.setdefault(str(thread), []).append(
                    (e.name, float(e.start_ns),
                     float(e.start_ns + e.duration_ns)))
    return out


def nest(spans: list) -> list:
    """[(name, start, end, parent index or None)] of one thread's spans,
    by containment (a thread's spans nest; they never cross)."""
    order = sorted(spans, key=lambda s: (s[1], -s[2]))
    out, stack = [], []
    for name, s, e in order:
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        out.append((name, s, e, stack[-1] if stack else None))
        stack.append(len(out) - 1)
    return out


def self_times(nested: list) -> dict:
    """{name: [count, seconds, self seconds]}."""
    own = [e - s for _n, s, e, _p in nested]
    for _n, s, e, p in nested:
        if p is not None:
            own[p] -= e - s
    out: dict = {}
    for (name, s, e, _p), mine in zip(nested, own):
        rec = out.setdefault(name, [0, 0.0, 0.0])
        rec[0] += 1
        rec[1] += (e - s) / 1e9
        rec[2] += mine / 1e9
    return out


def innermost_at(nested: list, times: list) -> list:
    """The innermost span open at each instant of `times` (ascending),
    NO_SPAN where none is: one sweep over the thread's spans, which
    nest() left in order of their start."""
    out, stack, i = [], [], 0
    for t in times:
        while i < len(nested) and nested[i][1] <= t:
            while stack and nested[stack[-1]][2] <= nested[i][1]:
                stack.pop()
            stack.append(i)
            i += 1
        while stack and nested[stack[-1]][2] <= t:
            stack.pop()
        out.append(nested[stack[-1]][0] if stack else NO_SPAN)
    return out


def device_gaps(path: str) -> tuple:
    """(busy seconds, [(start_ns, end_ns)] of the idle gaps) of the
    device planes, as trace_reduce.py merges them."""
    planes = trace_reduce.read_planes(path)
    busy, gaps = 0.0, []
    for _name, plane in sorted(planes["devices"].items()):
        events = plane["ops"] or plane["modules"]
        if not events:
            continue
        b, merged = trace_reduce.union_seconds((s, e) for _n, s, e in events)
        busy += b
        gaps += [(e0, s1) for (_s0, e0), (s1, _e1) in zip(merged,
                                                          merged[1:])]
    return busy, gaps


def covered(inner: list, lo: float, hi: float) -> float:
    """Share of [lo, hi) that the intervals cover."""
    clipped = [(max(s, lo), min(e, hi)) for s, e in inner
               if e > lo and s < hi]
    seconds, _m = trace_reduce.union_seconds(clipped)
    return seconds * 1e9 / (hi - lo) if hi > lo else 0.0


def coverage(nested_by_thread: dict) -> dict:
    out = {"compaction_task": {}, "ann_request": None}
    tasks = [(s, e) for nested in nested_by_thread.values()
             for n, s, e, _p in nested if n == TASK]
    for thread, nested in sorted(nested_by_thread.items()):
        inner = [(s, e) for n, s, e, _p in nested if n != TASK]
        shares = [covered(inner, lo, hi) for lo, hi in tasks]
        if shares and any(shares):
            out["compaction_task"][thread] = sum(shares) / len(shares)
    got = []
    for nested in nested_by_thread.values():
        for i, (n, s, e, _p) in enumerate(nested):
            if n != REQUEST:
                continue
            below = [(cs, ce) for cn, cs, ce, _cp in nested[i + 1:]
                     if cs >= s and ce <= e]
            if any(cn == ANN_CALL and cs >= s and ce <= e
                   for cn, cs, ce, _cp in nested[i + 1:]):
                got.append(covered(below, s, e))
    if got:
        out["ann_request"] = {"queries": len(got),
                              "mean": sum(got) / len(got),
                              "least": min(got)}
    return out


def ring_memory() -> dict | None:
    try:
        from cassandra_tpu.utils import pipeline_ledger as led
        ring = list(led.RING)
    except (ImportError, AttributeError):
        return None
    if not ring:
        return {"records": 0, "bound": led.RING_CAP}
    # names, kinds and thread names are shared strings; the floats and the
    # larger ints are each record's own
    per = sum(sys.getsizeof(r) + sum(
        sys.getsizeof(v) for v in r if isinstance(v, float)
        or (isinstance(v, int) and v > 256)) for r in ring) / len(ring)
    return {"records": len(ring), "bound": led.RING_CAP,
            "bytes_per_record": per,
            "bytes_at_bound": int(per * led.RING_CAP)
            + sys.getsizeof(led.RING)}


def attribute(path: str) -> dict:
    spans = host_spans(path)
    nested = {t: nest(s) for t, s in spans.items()}
    busy, gaps = device_gaps(path)
    gaps.sort()
    by: dict = {}
    for thread, n in nested.items():
        names = innermost_at(n, [(s + e) / 2.0 for s, e in gaps])
        for (s, e), name in zip(gaps, names):
            by[thread, name] = by.get((thread, name), 0.0) + (e - s) / 1e9
    return {
        "device_busy_s": busy,
        "gap_s": sum(e - s for s, e in gaps) / 1e9,
        "threads": {t: {n: {"count": c, "seconds": sec, "self_s": own}
                        for n, (c, sec, own) in sorted(
                            self_times(n_).items(),
                            key=lambda kv: -kv[1][2])}
                    for t, n_ in sorted(nested.items())},
        "gaps": {t: {n: v for (tt, n), v in sorted(
            by.items(), key=lambda kv: -kv[1]) if tt == t}
            for t in sorted(nested)},
        "coverage": coverage(nested)}


def main(argv=None, check_platform: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("seed", type=int)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", default=os.path.join(
        harness.ROOT, "chiprun_out", "span_gaps"))
    args = ap.parse_args(argv)
    for p in (harness.ROOT, harness.HERE):
        if p not in sys.path:
            sys.path.insert(0, p)
    bench = harness.load_bench()
    cell, config, traffic = harness.find_cell(bench, args.cell)
    from cassandra_tpu.utils import compile_cache
    compile_cache.configure()
    device = harness.device_info(check_platform, int(cell["chips"]))
    harness.rebuild_native()
    scratch = tempfile.mkdtemp(prefix="ctpu-gaps-")
    tracer = harness.Tracer(os.path.join(scratch, "trace"))
    seconds = args.seconds or float(bench["run_seconds"])
    ctx = harness.Ctx(cell, config, traffic, args.seed, seconds,
                      os.path.join(scratch, "data"), tracer)
    driver = harness.load("drivers", traffic["driver"])
    state = None
    try:
        state = driver.setup(ctx)
        result = driver.window(state, ctx)
        tracer.stop()
        path = trace_reduce.find_xplane(tracer.directory)
        if path is None:
            harness.log("the traced slice left no .xplane.pb")
            return 3
        os.makedirs(args.out, exist_ok=True)
        kept = os.path.join(args.out,
                            f"{cell['name']}.{args.seed}.xplane.pb")
        shutil.copy(path, kept)
        out = attribute(kept)
        traced = [o for o in result.get("ops", []) if o.get("traced")]
        tr = traffic.get("trace", {})
        if "start_s" in tr:     # a slice of a served window: its rate
            lo, hi = tr["start_s"], tr["start_s"] + tr["seconds"]
            out["slice_ops_per_s"] = sum(
                1 for o in result.get("ops", [])
                if o.get("ok") and lo <= o["done"] < hi) / (hi - lo)
        out.update(cell=cell["name"], seed=args.seed, device=device,
                   xplane=kept, trace_window_s=tracer.window_s,
                   traced_walls_s=[o["wall_s"] for o in traced],
                   end_to_end=result["end_to_end"], ring=ring_memory())
        print(json.dumps(out), flush=True)
        return 0
    finally:
        tracer.stop()
        if state is not None:
            try:
                driver.close(state)
            except Exception:
                pass
        shutil.rmtree(scratch, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
