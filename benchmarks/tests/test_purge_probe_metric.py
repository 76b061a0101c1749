"""ycsb_purge_probe_pct (PR 28): the seconds of `compaction.purge.probe`
over the wall of the compactions the window served; None where the
program has no such span (the parent), the warm-up compaction left out."""
import pytest

from test_program_spans import Ctx, _reader

NAME = "ycsb_purge_probe_pct"
FIELDS = ("name", "kind", "thread", "start", "end", "id", "parent", "task",
          "cells", "bytes", "items")


class Recorded:
    """A ring as the wire_ycsb driver drained it: dict records."""

    def __init__(self):
        self.recs = []

    def add(self, name, start, end, thread="compact-m", kind="busy",
            parent=0, task=1, cells=0, items=0):
        rec = dict(zip(FIELDS, (name, kind, thread, float(start),
                                float(end), len(self.recs) + 1, parent,
                                task, cells, 0, items)))
        self.recs.append(rec)
        return rec["id"]

    def compaction(self, t0: float, task: int, probes: int) -> None:
        """A compaction of 10 s from t0 in rounds of 1 s, the first
        `probes` of which passed the guard's early return (2 ms each)."""
        self.add("compaction.task", t0, t0 + 10, task=task)
        for j in range(4):
            pack = self.add("merge.resident.pack", t0 + j, t0 + j + 0.5,
                            task=task, cells=500_000, items=1 << 19)
            if j < probes:
                self.add("compaction.purge.probe", t0 + j + 0.1,
                         t0 + j + 0.102, parent=pack, task=task)
        self.add("write.emit", t0 + 1, t0 + 9, "compact-w", task=task)

    def window(self, release: float, elapsed: float) -> dict:
        return {"spans": self.recs, "release_perf": release,
                "elapsed_s": elapsed}


def test_probe_seconds_over_the_served_compactions_wall():
    ring = Recorded()
    ring.compaction(50.0, task=1, probes=4)      # the warm-up: left out
    ring.compaction(101.0, task=2, probes=3)     # the memtable filled late
    ring.compaction(120.0, task=3, probes=4)
    got = _reader(NAME).read(Ctx(ring.window(100.0, 45.0)))
    assert got == pytest.approx(100.0 * 7 * 0.002 / 20.0)
    # the accepted reader beside it reads the same tasks as before
    assert _reader("ycsb_compact_w_busy_pct").read(
        Ctx(ring.window(100.0, 45.0))) == pytest.approx(80.0)


def test_a_program_without_the_span_reads_none():
    ring = Recorded()
    ring.compaction(101.0, task=2, probes=0)
    assert _reader(NAME).read(Ctx(ring.window(100.0, 45.0))) is None
    # no served compaction, no spans at all, no release instant
    assert _reader(NAME).read(Ctx(ring.window(200.0, 45.0))) is None
    assert _reader(NAME).read(Ctx({})) is None
    assert _reader(NAME).read(Ctx({"spans": ring.recs})) is None
