#!/usr/bin/env python3
"""The controls of `correct`, at the cells' own sizes, by hand:

    python benchmarks/control.py <cell> <seed> [<seed> ...]

For each seed the cell's driver (`drivers/<driver>.py: control(ctx)`) puts
the plain reference in the program's place: once as it is, which has to
read correct, and once per control, broken the way a later PR would be
tempted to break it, which has to read NOT correct: the nearest precision
below the stated one where the configuration states a precision, one
stated guarantee broken where it states none. Each goes through the same
comparison and the same limits as a run's `check`, and through the
harness's own `decide`. Needs no chip and takes none; it is run on the
chip's machine so that the sizes and the numpy are the cell's own. Prints
one JSON line per seed and control; exits 1 if any reads the wrong way.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import json
import sys
import time

import run as harness


def main(argv) -> int:
    cell, config, traffic = harness.find_cell(harness.load_bench(), argv[0])
    driver = harness.load("drivers", traffic["driver"])
    wrong_way = 0
    for seed in (int(s) for s in argv[1:]):
        t0 = time.perf_counter()
        ctx = harness.Ctx(cell, config, traffic, seed, 0, None, None)
        for name, checks in driver.control(ctx):
            correct = harness.decide(checks)
            wrong_way += correct != (name == "reference_in_place")
            print(json.dumps({
                "cell": cell["name"], "seed": seed, "control": name,
                "correct": correct,
                "checks": {c["name"]: {"value": c["value"],
                                       "limit": c["limit"]}
                           for c in checks},
                "seconds": time.perf_counter() - t0}), flush=True)
    return 1 if wrong_way else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
