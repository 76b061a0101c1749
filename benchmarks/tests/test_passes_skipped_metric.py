"""merge_resident_passes_skipped_pct (PR 34): over the window's
`merge.resident.wait` spans, the passes the sort has (`items`) less the
passes the program ran (`cells`), per hundred of the former; None where
the span sets no `items` (the parent), the warm-up's rounds left out."""
import pytest

from test_program_spans import Ctx, _reader, ring  # noqa: F401

NAME = "merge_resident_passes_skipped_pct"
OPS = [{"start": 199.5, "end": 210.5}, {"start": 299.5, "end": 310.5}]


def _compaction(add, t0: float, rounds) -> None:
    """One compaction of 10 s from t0 whose rounds wait 0.5 s each;
    `rounds` = (passes the sort has, passes run) per round."""
    add("compaction.task", t0, t0 + 10)
    for i, (have, ran) in enumerate(rounds):
        add("merge.resident.pack", t0 + i, t0 + i + 0.2, cells=600,
            items=1024)
        add("merge.resident.wait", t0 + i + 0.3, t0 + i + 0.8,
            kind="stall", cells=ran, items=have)


@pytest.mark.parametrize("rounds, want", [
    (((16, 7), (16, 7), (16, 8)), 100.0 * (48 - 22) / 48),
    (((16, 16), (16, 16)), 0.0),
    (((16, 1),), 100.0 * 15 / 16),
    (((16, 7), (19, 19)), 100.0 * 9 / 35),      # a wider table's round
    (((0, 0), (0, 0)), None)])                  # the parent's span
def test_share_of_passes_the_program_did_not_run(ring, rounds, want):
    _compaction(ring, 100.0, ((16, 16),) * 3)   # warm-up: left out
    _compaction(ring, 200.0, rounds)
    _compaction(ring, 300.0, rounds)
    got = _reader(NAME).read(Ctx({"ops": OPS}))
    assert got == (pytest.approx(want) if want is not None else None)


def test_nothing_to_read_reads_none(ring):
    assert _reader(NAME).read(Ctx({})) is None
    assert _reader(NAME).read(Ctx({"ops": []})) is None
    assert _reader(NAME).read(Ctx({"ops": OPS})) is None    # empty ring
    ring("compaction.task", 200.0, 210.0)
    ring("merge.resident.pack", 201.0, 201.2, cells=600, items=1024)
    assert _reader(NAME).read(Ctx({"ops": OPS})) is None    # no wait span
