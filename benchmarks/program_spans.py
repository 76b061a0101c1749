"""The program's own spans, read in-process from its ring
(cassandra_tpu/utils/pipeline_ledger.py: RING), as run.py's compiles_now()
reads the registry: the readers of the ten `program_span` per-layer
metrics that PR 25 added share this file.

A record is a dict: name, kind (busy | stall | idle), thread, start and end
(time.perf_counter of the program's process), id, parent (the span open on
the same thread when this one opened, 0 for none), task (the compaction
task or the request that caused it), cells, bytes, items.

Only spans that belong to the window's operations are kept:

- `major_loop`: the driver stamps each compaction with `start` and `end`
  by the same time.perf_counter; a span is kept when it lies inside one of
  the window's operations. The warm-up compaction, and the host-engine
  compaction that `check` runs after the window, lie outside them.
- `wire_closedloop`: the driver's operations carry `sent` and `done` in
  seconds after the children's common start, an instant of
  time.monotonic() that the result does not keep. It is recovered as the
  end of the ring's last vector-query request less the largest `done`
  (both clocks are CLOCK_MONOTONIC on Linux, and the readers run before
  anything else queries the node); a request is kept when it began no
  earlier than 0.1 s before that instant. The warm-up's queries ended
  before the children were even started, more than the release's lead of
  0.25 s earlier.

A program without the ring (the parent of PR 25) gives None everywhere, as
does a window without operations; the program is imported only here, inside
the functions, so loading a reader starts no backend.
"""
from __future__ import annotations

TASK_SPAN = "compaction.task"
REQUEST_SPAN = "transport.request"
ANN_CALL_SPAN = "index.ann.call"
RELEASE_SLACK_S = 0.1


def records() -> list | None:
    """Every record the ring holds, oldest first; None where the program
    has no ring."""
    try:
        from cassandra_tpu.utils import pipeline_ledger
    except ImportError:
        return None
    read = getattr(pipeline_ledger, "ring_records", None)
    return read() if read is not None else None


def self_seconds(recs: list) -> dict:
    """{id: seconds of the span not covered by its children}. A child that
    began before its parent is back-dated (a queue wait stamped at submit)
    and is not thread time: it takes nothing from its parent."""
    by_id = {r["id"]: r for r in recs}
    out = {r["id"]: r["end"] - r["start"] for r in recs}
    for r in recs:
        parent = by_id.get(r["parent"])
        if parent is not None and r["start"] >= parent["start"]:
            out[parent["id"]] -= r["end"] - r["start"]
    return out


# ------------------------------------------------------------- major_loop --

def in_operations(ops: list) -> list | None:
    """The ring's records that lie inside one of the window's operations
    (each with `start` and `end` by time.perf_counter)."""
    if not ops:
        return None
    recs = records()
    if recs is None:
        return None
    spans = sorted((o["start"], o["end"]) for o in ops)
    return [r for r in recs
            if any(s <= r["start"] and r["end"] <= e for s, e in spans)]


def task_wall(recs: list) -> float:
    return sum(r["end"] - r["start"] for r in recs
               if r["name"] == TASK_SPAN)


def share_of_task_wall(ops: list, names: tuple) -> float | None:
    """Percent: the seconds of the spans named, over the wall of the
    window's compaction tasks. None when no such span is in the ring."""
    recs = in_operations(ops)
    if not recs:
        return None
    wall = task_wall(recs)
    hit = [r for r in recs if r["name"] in names]
    if wall <= 0 or not hit:
        return None
    return 100.0 * sum(r["end"] - r["start"] for r in hit) / wall


def thread_busy_share(ops: list, thread: str) -> float | None:
    """Percent: the busy self-seconds of one thread (a span's time less
    its children's, busy spans only) over the tasks' wall. One thread, so
    at most 100."""
    recs = in_operations(ops)
    if not recs:
        return None
    wall = task_wall(recs)
    mine = [r for r in recs if r["thread"] == thread]
    if wall <= 0 or not mine:
        return None
    own = self_seconds(mine)
    return 100.0 * sum(own[r["id"]] for r in mine
                       if r["kind"] == "busy") / wall


# -------------------------------------------------------- wire_closedloop --

def window_queries(ops: list) -> list | None:
    """Per vector query of the window, {name: seconds} summed over the
    spans of its request (the request span itself, its queue wait, and
    every span below them), oldest first."""
    done = [o["done"] for o in ops or [] if o.get("done") is not None]
    if not done:
        return None
    recs = records()
    if not recs:
        return None
    by_id = {r["id"]: r for r in recs}

    def root(r):
        while r["parent"] in by_id:
            r = by_id[r["parent"]]
        return r
    roots = {r["id"]: root(r) for r in recs}
    ann = {roots[r["id"]]["id"] for r in recs
           if r["name"] == ANN_CALL_SPAN
           and roots[r["id"]]["name"] == REQUEST_SPAN}
    if not ann:
        return None
    release = max(by_id[i]["end"] for i in ann) - max(done)
    kept = {i for i in ann
            if by_id[i]["start"] >= release - RELEASE_SLACK_S}
    out = {i: {} for i in kept}
    for r in recs:
        i = roots[r["id"]]["id"]
        if i in kept:
            q = out[i]
            q[r["name"]] = q.get(r["name"], 0.0) + r["end"] - r["start"]
    return [out[i] for i in sorted(out, key=lambda i: by_id[i]["start"])]


def mean_ms_per_query(ops: list, names: tuple) -> float | None:
    """Milliseconds per query, mean over the window's queries, of the
    spans named; None when no query has one."""
    queries = window_queries(ops)
    if not queries:
        return None
    per = [sum(q.get(n, 0.0) for n in names) for q in queries
           if any(n in q for n in names)]
    return 1000.0 * sum(per) / len(per) if per else None
