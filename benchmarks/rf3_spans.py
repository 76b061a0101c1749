"""What the `rf3_*` per-layer readers share: the window of a
`wire_ycsb_cluster` run cut out of the program's spans, which three nodes
in one process write into ONE ring. The window, its records and the served
compactions are ycsb_spans.py's (the driver's result has the same shape);
here are the sums over spans that no single request owns: a replica's
handler runs on its node's dispatch pool, the codec on a socket's thread.
Everything gives None where there is nothing to read (a program without
the spans, a ring that wrapped, a window without operations).
"""
from __future__ import annotations

import ycsb_spans

HANDLE = ("messaging.handle.mutation_req", "messaging.handle.read_req")
CODEC = ("messaging.encode", "messaging.decode")


def in_window(window: dict, names) -> list | None:
    """The records named in `names` that began inside the window."""
    recs, bounds = ycsb_spans.records(window), \
        ycsb_spans.window_bounds(window)
    if not recs:
        return None
    names = set(names)
    return [r for r in recs if r["name"] in names
            and bounds[0] <= r["start"] <= bounds[1]] or None


def answered(window: dict, kind: str | None = None) -> int:
    return sum(1 for o in window.get("ops", [])
               if o.get("ok") and kind in (None, o.get("kind")))


def ms_per(window: dict, names, per: int):
    """Milliseconds of the spans `names` inside the window, over `per`."""
    mine = in_window(window, names)
    if not mine or not per:
        return None
    return 1000.0 * sum(r["end"] - r["start"] for r in mine) / per


def await_ms_per_request(window: dict, verb: str):
    """Milliseconds parked in `coordinator.<verb>.await`, over the
    window's `coordinator.<verb>` requests."""
    roots = in_window(window, [f"coordinator.{verb}"])
    return ms_per(window, [f"coordinator.{verb}.await"],
                  len(roots) if roots else 0)


def union_s(tasks: list) -> float:
    """Seconds during which at least one of the spans was open."""
    total, upto = 0.0, float("-inf")
    for lo, hi in sorted((t["start"], t["end"]) for t in tasks):
        lo = max(lo, upto)
        if hi > lo:
            total += hi - lo
            upto = hi
    return total
