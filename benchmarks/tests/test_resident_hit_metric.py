"""ann_resident_hit_pct (PR 32): of the window's queries that hold an
`index.ann.resident` span, the share without an `index.ann.upload`; None
where the program has no such span (the parent), the warm-up's fill left
out."""
import pytest

from test_program_spans import Ctx, _reader, ring  # noqa: F401

NAME = "ann_resident_hit_pct"


def _query(add, t0: float, thread: str, lookup: str | None) -> None:
    """One vector query of 1 s from t0: `hit` finds the matrix on the
    device, `fill` uploads it inside the lookup, None is a program
    without the lookup span."""
    req = add("transport.request", t0, t0 + 1.0, thread, task=7)
    ex = add("cql.execute", t0, t0 + 1.0, thread, parent=req, task=7)
    if lookup is not None:
        res = add("index.ann.resident", t0, t0 + 0.5, thread, parent=ex,
                  task=7, items=int(lookup == "hit"),
                  nbytes=0 if lookup == "hit" else 473405600)
        add("index.ann.gather", t0, t0 + 0.1, thread, parent=res, task=7)
        if lookup == "fill":
            add("index.ann.upload", t0 + 0.2, t0 + 0.5, thread, parent=res,
                task=7, nbytes=473405600)
    add("index.ann.call", t0 + 0.5, t0 + 0.8, thread, parent=ex, task=7)


def _ops(n: int) -> list:
    return [{"sent": 1.0 + i, "done": 2.0 + i, "ok": True} for i in range(n)]


@pytest.mark.parametrize("lookups, want", [
    (("hit", "hit", "hit", "hit"), 100.0),
    (("hit", "fill", "hit", "hit"), 75.0),
    (("fill",), 0.0),
    ((None, None, None), None)])
def test_share_of_lookups_that_uploaded_nothing(ring, lookups, want):
    _query(ring, 80.0, "cql-exec-1-0", "fill")     # warm-up: left out
    for i, lookup in enumerate(lookups):
        _query(ring, 101.0 + i, f"cql-exec-1-{i % 2}", lookup)
    got = _reader(NAME).read(Ctx({"ops": _ops(len(lookups))}))
    assert got == (pytest.approx(want) if want is not None else None)


def test_nothing_to_read_reads_none(ring):
    assert _reader(NAME).read(Ctx({})) is None
    assert _reader(NAME).read(Ctx({"ops": []})) is None
    assert _reader(NAME).read(Ctx({"ops": _ops(2)})) is None   # empty ring
