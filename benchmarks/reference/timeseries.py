"""Plain reference of a compaction over a TTL'd time-series table: numpy
over the seeded readings, nothing of the program imported.

A row is one reading of one series: a row-liveness cell and a value cell
(an INSERT writes both), same write timestamp, same TTL, same expiry time
(`ldt` = floor(write seconds) + ttl). A cell is named by
(series, row, column): column 0 the liveness marker, column 1 the value.

Semantics (Cassandra's, as the configuration states them), for one
compaction at the instant `now` (seconds) with `gc_before` = now -
gc_grace_seconds:

- per cell the newest write timestamp wins and shadows every older one.
  Two versions of a cell with the SAME timestamp are a tie, which this
  traffic does not make (the repaired window's sstables are disjoint):
  the reference refuses them rather than guess;
- a winner whose TTL has run out (`ldt <= now`) is a tombstone: flagged,
  its value gone (length 0), timestamp, ttl and ldt kept;
- such a tombstone is purged when it is past grace (`ldt < gc_before`) AND
  no data it could shadow lies outside the compaction: its timestamp is
  below the series' purgeable timestamp (the oldest timestamp any sstable
  outside the compaction holds for that series; none: purged). The
  program asks bloom filters and may keep what this rule lets go; no cell
  of this traffic is both merged and past grace, so the cells agree.

The source's traffic has no explicit delete; this reference has none.
"""
from __future__ import annotations

import numpy as np

FLAG_TOMBSTONE, FLAG_EXPIRING, FLAG_ROW_LIVENESS = 1, 2, 16
LIVENESS, VALUE = 0, 1
CONTROLS = ("expiry_ignored", "values_kept_on_conversion", "lose_run")


def cell_ids(series, row, rows_per_series: int, column: int) -> np.ndarray:
    """One int64 per cell: ((series * rows_per_series) + row) * 2 +
    column. Exact: no two cells share one."""
    return (np.asarray(series, dtype=np.int64) * int(rows_per_series)
            + np.asarray(row, dtype=np.int64)) * 2 + column


def cells_of(run: dict, rows_per_series: int, ttl: int) -> dict:
    """The two cells of every row of one input sstable, as columns.
    run: {"series", "row", "write_us", "value"}, one entry a row."""
    write = np.asarray(run["write_us"], dtype=np.int64)
    n = len(write)
    ldt = write // 1_000_000 + int(ttl)
    out = {"id": [], "ts": [], "flags": [], "ldt": [], "ttl": [],
           "vlen": [], "value": [], "series": []}
    for column, flags, vlen, value in (
            (LIVENESS, FLAG_ROW_LIVENESS | FLAG_EXPIRING, 0,
             np.zeros(n, dtype=np.int64)),
            (VALUE, FLAG_EXPIRING, 8,
             np.asarray(run["value"], dtype=np.int64))):
        out["id"].append(cell_ids(run["series"], run["row"],
                                  rows_per_series, column))
        out["ts"].append(write)
        out["flags"].append(np.full(n, flags, dtype=np.uint8))
        out["ldt"].append(ldt)
        out["ttl"].append(np.full(n, int(ttl), dtype=np.int64))
        out["vlen"].append(np.full(n, vlen, dtype=np.int64))
        out["value"].append(value)
        out["series"].append(np.asarray(run["series"], dtype=np.int64))
    return {k: np.concatenate(v) for k, v in out.items()}


def merge(runs: list, rows_per_series: int, ttl: int, now: int,
          gc_before: int, purgeable_us=None,
          control: str | None = None) -> dict:
    """The cells one compaction of `runs` leaves, sorted by id.
    purgeable_us: per series index, the oldest timestamp an sstable
    outside the compaction holds for it (None: nothing outside).

    `control` breaks one stated guarantee, for the control run only:
    "expiry_ignored" reads expired cells back live, with their values;
    "values_kept_on_conversion" flags them as tombstones and keeps the
    value bytes; "lose_run" leaves the last input out."""
    if control not in (None,) + CONTROLS:
        raise ValueError(control)
    if control == "lose_run":
        runs = runs[:-1]
    parts = [cells_of(r, rows_per_series, ttl) for r in runs]
    cat = {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
    order = np.lexsort((cat["ts"], cat["id"]))
    ids, ts = cat["id"][order], cat["ts"][order]
    if ((ids[1:] == ids[:-1]) & (ts[1:] == ts[:-1])).any():
        raise ValueError("two versions of a cell share a timestamp: a "
                         "tie, which this reference does not resolve")
    last = np.ones(len(order), dtype=bool)
    last[:-1] = ids[1:] != ids[:-1]
    out = {k: v[order[last]] for k, v in cat.items()}
    if control != "expiry_ignored":
        expired = out["ldt"] <= int(now)
        out["flags"] = np.where(expired, out["flags"] | FLAG_TOMBSTONE,
                                out["flags"]).astype(np.uint8)
        if control != "values_kept_on_conversion":
            out["vlen"] = np.where(expired, 0, out["vlen"])
            out["value"] = np.where(expired, 0, out["value"])
        purged = expired & (out["ldt"] < int(gc_before))
        if purgeable_us is not None:
            purged &= out["ts"] < np.asarray(
                purgeable_us, dtype=np.int64)[out["series"]]
        out = {k: v[~purged] for k, v in out.items()}
    del out["series"]
    return out


def concat(tables: list) -> dict:
    """Several windows' cells as one column set; `tables` holds
    (offset added to every id, columns)."""
    out = {k: np.concatenate([t[k] for _o, t in tables])
           for k in tables[0][1]}
    out["id"] = np.concatenate([t["id"] + int(o) for o, t in tables])
    return out


def cells_wrong(got: dict, want: dict) -> int:
    """How many cells differ between two column sets keyed by id: cells
    only one side has (a cell one side has twice counts once as such),
    plus cells both have whose timestamp, flags, expiry time, ttl, value
    length or value differ."""
    ng, nw = len(got["id"]), len(want["id"])
    ids = np.concatenate([got["id"], want["id"]])
    side = np.zeros(ng + nw, dtype=np.uint8)
    side[ng:] = 1
    order = np.lexsort((side, ids))
    i, s = ids[order], side[order]
    pair = (i[1:] == i[:-1]) & (s[:-1] == 0) & (s[1:] == 1)
    gi, wi = order[:-1][pair], order[1:][pair] - ng
    bad = np.zeros(len(gi), dtype=bool)
    for col in ("ts", "flags", "ldt", "ttl", "vlen", "value"):
        bad |= np.asarray(got[col])[gi] != np.asarray(want[col])[wi]
    return int(ng + nw - 2 * len(gi) + bad.sum())
