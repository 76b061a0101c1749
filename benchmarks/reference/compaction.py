"""Plain reference of a major compaction: numpy over the seeded cells,
nothing of the program imported.

Semantics (Cassandra's, as the configuration states them): the output holds
every cell of every input once; per (partition key, column) the cell with
the newest write timestamp wins and shadows every older one. The
configuration writes no delete and no TTL, so nothing is purgeable: every
cell stays live, with its own timestamp and value.
"""
from __future__ import annotations

import numpy as np

NO_DELETION = 0x7FFFFFFF                   # ldt of a live, non-TTL cell
CONTROLS = ("lose_run", "millisecond_timestamps")


def cell_keys(keys: np.ndarray, columns: int) -> tuple:
    """(hi, lo) per cell of `columns` cells a row: the row's key bytes
    (n, at most 10) packed big-endian into a uint64 and a uint32 whose low
    byte is the column's index. Exact: no two keys share a pair."""
    n, width = keys.shape
    if width > 10:
        raise ValueError("a key of more than 10 bytes does not pack")
    pad = np.zeros((n, 10), dtype=np.uint8)
    pad[:, :width] = keys
    hi = pad[:, :8].copy().view(">u8").ravel().astype(np.uint64)
    lo = (pad[:, 8].astype(np.uint32) << 16) | (pad[:, 9].astype(np.uint32)
                                                << 8)
    col = np.arange(columns, dtype=np.uint32)
    return (np.repeat(hi, columns),
            (lo[:, None] | col[None, :]).ravel())


def merge(runs: list, control: str | None = None) -> dict:
    """runs: per input sstable (keys (n, K) uint8, ts (n,) int64, vals
    (n, C, L) uint8), one row per partition. Returns the surviving cells
    as columns, sorted by key.

    `control` breaks one stated guarantee, for the control run only:
    "lose_run" leaves the last input out of the merge (an acknowledged
    write that the compacted sstable no longer holds);
    "millisecond_timestamps" keeps write timestamps to the millisecond
    (the step below the microseconds the store promises)."""
    if control not in (None,) + CONTROLS:
        raise ValueError(control)
    if control == "lose_run":
        runs = runs[:-1]
    columns = runs[0][2].shape[1]
    hi, lo = (np.concatenate(x) for x in zip(
        *(cell_keys(keys, columns) for keys, _ts, _vals in runs)))
    ts = np.concatenate([np.repeat(np.asarray(t, dtype=np.int64), columns)
                         for _keys, t, _vals in runs])
    if control == "millisecond_timestamps":
        ts = ts // 1000 * 1000
    vals = np.concatenate([v.reshape(-1, v.shape[2])
                           for _keys, _ts, v in runs])
    order = np.lexsort((ts, lo, hi))
    h, l = hi[order], lo[order]
    last = np.ones(len(order), dtype=bool)
    last[:-1] = (h[1:] != h[:-1]) | (l[1:] != l[:-1])
    win = order[last]                      # newest cell of each key
    n = len(win)
    return {"hi": hi[win], "lo": lo[win], "ts": ts[win],
            "flags": np.zeros(n, dtype=np.uint8),
            "ldt": np.full(n, NO_DELETION, dtype=np.int64),
            "ttl": np.zeros(n, dtype=np.int64),
            "vlen": np.full(n, vals.shape[1], dtype=np.int64),
            "vals": vals[win]}


def cells_wrong(got: dict, want: dict) -> int:
    """How many cells differ between two column sets keyed by (hi, lo):
    cells only one side has (a cell one side has twice counts once as
    such), plus cells both have whose timestamp, flags, deletion time, ttl
    or value bytes differ."""
    ng, nw = len(got["hi"]), len(want["hi"])
    hi = np.concatenate([got["hi"], want["hi"]])
    lo = np.concatenate([got["lo"], want["lo"]])
    side = np.zeros(ng + nw, dtype=np.uint8)
    side[ng:] = 1
    order = np.lexsort((side, lo, hi))
    h, l, s = hi[order], lo[order], side[order]
    pair = (h[1:] == h[:-1]) & (l[1:] == l[:-1]) \
        & (s[:-1] == 0) & (s[1:] == 1)
    gi, wi = order[:-1][pair], order[1:][pair] - ng
    only = ng + nw - 2 * len(gi)
    bad = np.zeros(len(gi), dtype=bool)
    for col in ("ts", "flags", "ldt", "ttl", "vlen"):
        bad |= got[col][gi] != want[col][wi]
    bad |= (got["vals"][gi] != want["vals"][wi]).any(axis=1)
    return int(only + bad.sum())
