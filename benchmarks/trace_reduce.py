"""From a jax.profiler trace (.xplane.pb) to the numbers the benchmark
reports: device busy seconds (union of the intervals in which an operation
ran on the device), time per executable, the operations that took most
device time, and the idle gaps attributed to the benchmark's own
TraceAnnotation spans.

Reads the file with jax.profiler.ProfileData and nothing else; imports JAX
only inside the functions that need it, never a backend.

What a TPU trace looks like (my chip runs, PR 24): one plane per chip named
"/device:TPU:<n>", with a line "XLA Modules" (one event per executable run,
named "<jit name>(<fingerprint>)") and a line "XLA Ops" (one event per HLO
op); the host is "/host:CPU" with one line per thread, where a
TraceAnnotation shows under its own name. Event times are nanoseconds on
one clock for every plane.
"""
from __future__ import annotations

import bisect
import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."          # the benchmark's own annotations
TOP = 10


def find_xplane(trace_dir: str) -> str | None:
    """The newest .xplane.pb under a jax.profiler log directory."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def union_seconds(intervals) -> tuple:
    """(total seconds covered, merged [start, end) list) of intervals given
    in nanoseconds."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged) / 1e9, merged


def module_name(event_name: str) -> str:
    """'jit_program(123456789)' -> 'jit_program'."""
    cut = event_name.find("(")
    return event_name if cut < 0 else event_name[:cut]


def op_name(event_name: str) -> str:
    """'%sort.6 = (f32[...]) sort(...)' -> 'sort.6': the HLO text of an
    "XLA Ops" event runs to hundreds of characters."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def read_planes(path: str) -> dict:
    """{"devices": {plane: {"ops": [...], "modules": [...]}},
    "spans": [(name, start, end)]} from one .xplane.pb."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    first, last = None, None
    for plane in data.planes:
        for line in plane.lines:
            # a line's events are in order of their start
            evs = list(line.events)
            if evs:
                s0, e1 = float(evs[0].start_ns), float(
                    evs[-1].start_ns + evs[-1].duration_ns)
                first = s0 if first is None else min(first, s0)
                last = e1 if last is None else max(last, e1)
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += _events(line)
                elif line.name == MODULES_LINE:
                    modules += _events(line)
            devices[plane.name] = {"ops": ops, "modules": modules}
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [ev for ev in _events(line)
                          if ev[0].startswith(SPAN_PREFIX)]
    extent_s = (last - first) / 1e9 if first is not None else 0.0
    return {"devices": devices, "spans": spans, "extent_s": extent_s}


def _span_at(spans, t: float) -> str:
    """The innermost benchmark span that covers instant t."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "outside_benchmark_spans"


def reduce_trace(path: str, window_s: float, chips: int = 1) -> dict:
    """The reduction. `window_s` is the length of the traced slice by the
    host's clock around start_trace/stop_trace, or the extent of the
    trace's own events where that is longer (the profiler goes on
    recording while it stops: a 10.0 s slice held device events 11.3 s
    apart, my chip run, PR 24); busy seconds are averaged over the
    `chips` the cell uses. A device plane with no event (or no
    device plane at all) is busy 0 — idle 100% — and not an error."""
    planes = read_planes(path)
    window_s = max(float(window_s), planes["extent_s"])
    busy_total, op_time, exe = 0.0, {}, {}
    gap_by_span: dict = {}
    planes_with_ops = 0
    for _name, plane in sorted(planes["devices"].items()):
        events = plane["ops"] or plane["modules"]
        if not events:
            continue
        planes_with_ops += 1
        busy, merged = union_seconds((s, e) for _n, s, e in events)
        busy_total += busy
        mods = sorted((s, e, module_name(n))
                      for n, s, e in plane["modules"])
        starts = [m[0] for m in mods]
        for n, s, e in plane["ops"]:
            # the executable whose run the op lies in: op names repeat
            # from one executable to the next
            i = bisect.bisect_right(starts, s) - 1
            owner = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
            label = f"{owner}/{op_name(n)}"
            op_time[label] = op_time.get(label, 0.0) + (e - s) / 1e9
        for n, s, e in plane["modules"]:
            rec = exe.setdefault(module_name(n), {"seconds": 0.0,
                                                  "calls": 0})
            rec["seconds"] += (e - s) / 1e9
            rec["calls"] += 1
        for (_s0, e0), (s1, _e1) in zip(merged, merged[1:]):
            span = _span_at(planes["spans"], (e0 + s1) / 2.0)
            gap_by_span[span] = gap_by_span.get(span, 0.0) \
                + (s1 - e0) / 1e9
    top = lambda d: [[k, v] for k, v in sorted(   # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:TOP]]
    busy_s = busy_total / max(int(chips), 1)
    return {"window_s": float(window_s), "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "device_planes": len(planes["devices"]),
            "device_planes_with_ops": planes_with_ops,
            "executables": exe, "device_ops": top(op_time),
            "idle_gaps": top(gap_by_span),
            "spans": sorted({n for n, _s, _e in planes["spans"]})}
