"""The benchmark's arithmetic: percentiles and the table of peaks.

Nothing here imports the program or JAX.
"""
from __future__ import annotations

import json
import math
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def percentile(values, p: float) -> float:
    """Nearest-rank percentile (p in 0..100) of all `values`: the smallest
    value with at least p% of the sample at or below it. No interpolation,
    so a tail is always a latency some operation really had."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no values")
    rank = max(1, math.ceil(p / 100.0 * len(vals)))
    return float(vals[min(rank, len(vals)) - 1])


def peaks_for(device_kind: str) -> dict:
    """The published peaks of the chip a run reports. A device that is not
    in the table is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       "benchmarks/peaks.json")
    return table[device_kind]
