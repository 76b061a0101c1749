"""ann_segment_decode_ms_per_query (PR 26): the seconds of
`sstable.read.segment` inside the window's requests over ALL of its queries;
None where the program has no such span (the parent), the warm-up's decodes
left out."""
import pytest

from test_program_spans import Ctx, _reader, ring  # noqa: F401

NAME = "ann_segment_decode_ms_per_query"


def _query(add, t0: float, thread: str, decodes: int) -> None:
    """One vector query of 1 s from t0 whose row read-back (0.1 s) decodes
    `decodes` segments, 10 ms each."""
    req = add("transport.request", t0, t0 + 1.0, thread, task=7)
    ex = add("cql.execute", t0, t0 + 1.0, thread, parent=req, task=7)
    add("index.ann.call", t0 + 0.5, t0 + 0.8, thread, parent=ex, task=7)
    rows = add("cql.ann.rows", t0 + 0.9, t0 + 1.0, thread, parent=ex,
               task=7)
    for j in range(decodes):
        add("sstable.read.segment", t0 + 0.9 + 0.01 * j,
            t0 + 0.91 + 0.01 * j, thread, parent=rows, task=7,
            nbytes=31719424)


def test_decode_seconds_are_spread_over_every_query_of_the_window(ring):
    _query(ring, 80.0, "cql-exec-1-0", 5)          # warm-up: left out
    for i, n in enumerate((4, 0, 2, 2)):           # one query decodes nothing
        _query(ring, 101.0 + i, f"cql-exec-1-{i % 2}", n)
    # a compaction's decode on another thread belongs to no request
    ring("sstable.read.segment", 102.0, 103.0, "compact-prefetch", task=9)
    ops = [{"sent": 1.0 + i, "done": 2.0 + i, "ok": True} for i in range(4)]
    assert _reader(NAME).read(Ctx({"ops": ops})) == pytest.approx(
        1000.0 * 8 * 0.01 / 4)
    # the accepted reader beside it reads its own span as before
    assert _reader("ann_rows_read_ms_per_query").read(
        Ctx({"ops": ops})) == pytest.approx(100.0)


def test_a_program_without_the_span_reads_none(ring):
    for i in range(3):
        _query(ring, 101.0 + i, "cql-exec-1-0", 0)
    ops = [{"sent": 1.0 + i, "done": 2.0 + i, "ok": True} for i in range(3)]
    assert _reader(NAME).read(Ctx({"ops": ops})) is None
    assert _reader(NAME).read(Ctx({})) is None
    assert _reader(NAME).read(Ctx({"ops": []})) is None
