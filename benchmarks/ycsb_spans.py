"""What the `ycsb_*` per-layer readers share: the window of a `wire_ycsb`
run cut out of the program's spans.

The driver's result carries `spans` (the program's ring, drained during
the window so that it cannot wrap; a record is program_spans.py's dict)
and `release_perf`, the release instant on the spans' own clock. A request
belongs to the window when it began at or after the release; the warm-up's
requests and the reads `check` makes afterwards lie outside
[release, release + elapsed]. A program without the spans, a run whose
ring wrapped, or a window without operations gives None everywhere.
"""
from __future__ import annotations

import program_spans

REQUEST_SPAN = program_spans.REQUEST_SPAN
TASK_SPAN = program_spans.TASK_SPAN
READ_SPAN = "engine.read"
WRITE_SPAN = "engine.write"


def window_bounds(window: dict):
    t0 = window.get("release_perf")
    if t0 is None or not window.get("elapsed_s"):
        return None
    return t0, t0 + float(window["elapsed_s"])


def records(window: dict) -> list | None:
    """The drained records that ended inside the window, widened to the
    end of the served compaction (the driver waits for it)."""
    recs, bounds = window.get("spans"), window_bounds(window)
    if not recs or bounds is None:
        return None
    return [r for r in recs if r["end"] >= bounds[0]]


def requests(window: dict) -> list | None:
    """Per request of the window, {"kind": "read" | "update" | None,
    "spans": {name: seconds summed over the request's spans}}."""
    recs, bounds = records(window), window_bounds(window)
    if not recs:
        return None
    by_id = {r["id"]: r for r in recs}

    def root(r):
        while r["parent"] in by_id:
            r = by_id[r["parent"]]
        return r
    out: dict = {}
    for r in recs:
        top = root(r)
        if top["name"] != REQUEST_SPAN \
                or not bounds[0] <= top["start"] <= bounds[1]:
            continue
        q = out.setdefault(top["id"], {"kind": None, "spans": {}})
        q["spans"][r["name"]] = q["spans"].get(r["name"], 0.0) \
            + r["end"] - r["start"]
        if r["name"] == READ_SPAN:
            q["kind"] = "read"
        elif r["name"] == WRITE_SPAN:
            q["kind"] = "update"
    return list(out.values()) or None


def span_values_ms(window: dict, name: str, kind: str | None = None):
    """Milliseconds of span `name` per request that has one (of `kind`
    only, if given); None when no request has."""
    vals = [1000.0 * q["spans"][name] for q in requests(window) or []
            if name in q["spans"] and kind in (None, q["kind"])]
    return vals or None


def mean_ms_per_request(window: dict, name: str, kind: str):
    """Span `name` summed inside the window's requests of `kind`, over
    ALL of them, one without the span included; None when none has it."""
    qs = [q for q in requests(window) or [] if q["kind"] == kind]
    if not qs or not any(name in q["spans"] for q in qs):
        return None
    return 1000.0 * sum(q["spans"].get(name, 0.0) for q in qs) / len(qs)


def served_tasks(window: dict) -> list | None:
    """The `compaction.task` root spans that began inside the window:
    the compactions the manager ran while the traffic ran."""
    recs, bounds = records(window), window_bounds(window)
    if not recs:
        return None
    return [r for r in recs if r["name"] == TASK_SPAN
            and bounds[0] <= r["start"] <= bounds[1]] or None


def overlap_s(window: dict):
    """(seconds of the window during which a served compaction was open,
    the window's seconds)."""
    tasks, bounds = served_tasks(window), window_bounds(window)
    if not tasks:
        return None
    cut = sorted((max(t["start"], bounds[0]), min(t["end"], bounds[1]))
                 for t in tasks)
    total, upto = 0.0, bounds[0]
    for lo, hi in cut:
        lo = max(lo, upto)
        if hi > lo:
            total += hi - lo
            upto = hi
    return total, bounds[1] - bounds[0]


def ops_split(window: dict):
    """{"during": (operations, seconds), "outside": (...)}: acknowledged
    operations by whether they were answered while a served compaction
    was open."""
    tasks, bounds = served_tasks(window), window_bounds(window)
    over = overlap_s(window)
    if not tasks or over is None:
        return None
    t0 = bounds[0]
    spans = [(t["start"] - t0, t["end"] - t0) for t in tasks]
    done = [o["done"] for o in window.get("ops", []) if o.get("ok")]
    inside = sum(1 for d in done if any(lo <= d <= hi for lo, hi in spans))
    return {"during": (inside, over[0]),
            "outside": (len(done) - inside, over[1] - over[0])}


def task_records(window: dict) -> list | None:
    """Every record of the served compactions' tasks (all threads)."""
    tasks = served_tasks(window)
    if not tasks:
        return None
    ids = {t["task"] for t in tasks}
    return [r for r in records(window) if r["task"] in ids]
