"""What the off-CPU and GIL hand-off readers share (PR 35).

Since PR 35 a record of the program's span ring ends in `cpu`: the CPU
seconds of the span's own thread between `start` and `end` (None for a
back-dated span). `wall - cpu` is the time the thread was OFF the CPU
inside the span: waiting for the GIL, a lock, blocking I/O or the device.
The program's probe thread (`gil-probe`) also leaves ten
`runtime.gil.handoff` records a second, each the wall of ONE
release-and-retake of the GIL.

The records come the way each driver kept them:

- `major_loop`, `twcs_cycle` stamp operations (`start`, `end`) and leave the
  ring alone: `program_spans.in_operations(ops)`;
- `wire_ycsb`, `wire_ycsb_cluster` drained the ring during the window
  (`window["spans"]`, `release_perf`): `ycsb_spans` / `rf3_spans`;
- `wire_closedloop` leaves the ring alone and its operations carry `done`:
  the window is recovered as `program_spans.window_queries` recovers it.

On a host whose thread clock is dear the program reads it for one root
span in N and everything below it; the other records' `cpu` is None and
the readers leave them out: a mean over the spans that carry `cpu`.

Everything gives None where there is nothing sound to read: a program
whose records carry no `cpu` (the parent of PR 35) and have no probe, a
window without operations, a ring that wrapped inside the window (a
drained ring between two drains, an undrained one before the first
operation began; the closed loop's readers, whose window fills the ring
by itself, read the part of it the ring still holds whole).
"""
from __future__ import annotations

import program_spans
import rf3_spans
import ycsb_spans

HANDOFF_SPAN = "runtime.gil.handoff"
WRITE_LANE = "compact-w"
# the write lane's spans that wait for the device by design
DEVICE_PULLS = ("write.lane.cut.pull_lanes", "write.lane.cut.kernel_pull")


def off_s(r: dict) -> float | None:
    """Seconds the span's thread was off the CPU; None without `cpu`."""
    cpu = r.get("cpu")
    if cpu is None:
        return None
    return max(r["end"] - r["start"] - cpu, 0.0)


def self_off_seconds(recs: list) -> dict:
    """{id: off-CPU seconds of the span not inside its children}, as
    program_spans.self_seconds takes the children's wall from a span's. A
    record without `cpu` (back-dated) counts nothing either way."""
    by_id = {r["id"]: r for r in recs}
    out = {r["id"]: off_s(r) or 0.0 for r in recs}
    for r in recs:
        parent = by_id.get(r["parent"])
        if parent is not None and r["start"] >= parent["start"]:
            out[parent["id"]] -= off_s(r) or 0.0
    return out


def root_ids(recs: list) -> dict:
    """{record id: id of the topmost ancestor the records still hold}."""
    by_id = {r["id"]: r for r in recs}
    out = {}
    for r in recs:
        top = r
        while top["parent"] in by_id:
            top = by_id[top["parent"]]
        out[r["id"]] = top["id"]
    return out


def ring_floor() -> float | None:
    """Where a FULL ring's memory begins: the end of its oldest record
    (records are appended as spans end, so every span that began after
    that instant is whole in the ring, children and all). None while the
    ring has room: nothing was dropped."""
    from cassandra_tpu.utils import pipeline_ledger as pl
    ring = pl.RING
    return ring[0][4] if len(ring) >= pl.RING_CAP else None


def stamped(ops: list) -> list | None:
    """The records inside the operations a driver stamped."""
    if not ops:
        return None
    recs = program_spans.in_operations(ops)
    floor = ring_floor()
    if not recs or (floor is not None
                    and floor > min(o["start"] for o in ops)):
        return None
    return recs


def closedloop(ops: list):
    """(records, the ids of the window's vector-query requests,
    {record id: root id}, (from, end)) of a `wire_closedloop` window, cut
    as program_spans.window_queries cuts it; None without one. A window
    makes about as many records as the ring holds: where the ring wrapped
    inside it, `from` is the ring's floor and not the release, and the
    readers speak for the part of the window the ring still holds
    whole."""
    done = [o["done"] for o in ops or [] if o.get("done") is not None]
    recs = program_spans.records() if done else None
    if not recs:
        return None
    by_id = {r["id"]: r for r in recs}
    roots = root_ids(recs)
    ann = {roots[r["id"]] for r in recs
           if r["name"] == program_spans.ANN_CALL_SPAN
           and by_id[roots[r["id"]]]["name"] == program_spans.REQUEST_SPAN}
    if not ann:
        return None
    end = max(by_id[i]["end"] for i in ann)
    start = end - max(done) - program_spans.RELEASE_SLACK_S
    start = max(start, ring_floor() or start)
    kept = {i for i in ann if by_id[i]["start"] >= start}
    return (recs, kept, roots, (start, end)) if kept else None


def handoffs(window: dict) -> list | None:
    """The probe's records inside the window, whichever way the driver
    kept the ring."""
    ops = window.get("ops")
    if "release_perf" in window:
        return rf3_spans.in_window(window, [HANDOFF_SPAN])
    if ops and "start" in ops[0]:
        return [r for r in stamped(ops) or []
                if r["name"] == HANDOFF_SPAN] or None
    cut = closedloop(ops)
    if cut is None:
        return None
    recs, _kept, _roots, (start, end) = cut
    return [r for r in recs if r["name"] == HANDOFF_SPAN
            and start <= r["start"] <= end] or None


def handoff_mean_ms(window: dict) -> float | None:
    """Milliseconds of one GIL hand-off, mean over the window's beats."""
    beats = handoffs(window)
    if not beats:
        return None
    return 1000.0 * sum(r["end"] - r["start"] for r in beats) / len(beats)


def mean_off_ms(recs: list | None) -> float | None:
    """Milliseconds off the CPU a span, children included, mean over the
    records that carry `cpu`."""
    offs = [o for o in map(off_s, recs or []) if o is not None]
    return 1000.0 * sum(offs) / len(offs) if offs else None


def thread_off_cpu_share(recs: list | None, thread: str,
                         leave_out: tuple = ()) -> float | None:
    """Percent of the tasks' wall: the off-CPU self-seconds of one
    thread's busy spans, those named in `leave_out` set aside (their
    seconds count for nobody). Where only some root spans read the clock
    (`cpu` None on the rest: a host whose thread clock is dear), the
    share of the busy self-seconds that carry `cpu` stands for all of
    them. At most thread_busy_share's number."""
    if not recs:
        return None
    wall = program_spans.task_wall(recs)
    busy = [r for r in recs if r["thread"] == thread
            and r["kind"] == "busy"]
    read = [r for r in busy if r.get("cpu") is not None]
    if wall <= 0 or not read:
        return None
    mine = [r for r in recs if r["thread"] == thread]
    own, off = program_spans.self_seconds(mine), self_off_seconds(mine)
    busy_s = sum(own[r["id"]] for r in busy)
    read_s = sum(own[r["id"]] for r in read)
    if read_s <= 0:
        return None
    off_s_ = sum(off[r["id"]] for r in read if r["name"] not in leave_out)
    return 100.0 * (off_s_ / read_s) * (busy_s / wall)


def request_off_ms(window: dict, name: str) -> float | None:
    """`wire_ycsb`: milliseconds off the CPU inside span `name`, summed
    per request of the window that has one, mean over those requests."""
    recs, bounds = ycsb_spans.records(window), \
        ycsb_spans.window_bounds(window)
    if not recs:
        return None
    roots = root_ids(recs)
    kept = {r["id"] for r in recs if r["name"] == ycsb_spans.REQUEST_SPAN
            and bounds[0] <= r["start"] <= bounds[1]}
    return _mean_off_per_root(recs, name, roots, kept)


def _mean_off_per_root(recs, name, roots, kept) -> float | None:
    """Milliseconds off the CPU inside the spans `name`, summed per root
    in `kept` that has one, mean over those roots."""
    per: dict = {}
    for r in recs:
        off = off_s(r)
        if r["name"] == name and off is not None and roots[r["id"]] in kept:
            per[roots[r["id"]]] = per.get(roots[r["id"]], 0.0) + off
    return 1000.0 * sum(per.values()) / len(per) if per else None


def query_off_ms(ops: list, name: str) -> float | None:
    """`wire_closedloop`: the same per vector query of the window."""
    cut = closedloop(ops)
    if cut is None:
        return None
    recs, kept, roots, _bounds = cut
    return _mean_off_per_root(recs, name, roots, kept)
