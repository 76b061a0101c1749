"""UCS reference-shape semantics: per-level scaling vector, density
level geometry, density-aware shard counts, density by token span,
selection by overlap and even shard boundaries — the last four held
against the plain reference the benchmark decides `correct` with
(benchmarks/reference/ucs.py).

Reference: db/compaction/unified/Controller.java:154 (scaling vector,
getNumShards), UnifiedCompactionStrategy.java:106
(fanout/thresholdFromScalingParameter), getMaxLevelDensity level
geometry, ShardManager.java:33 (density, boundaries).
"""
import importlib.util
import os

import pytest

from cassandra_tpu.compaction.strategies import UnifiedCompactionStrategy

MiB = 1 << 20
RING_MIN, QUARTER = -(1 << 63), 1 << 62


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "reference", "ucs.py")
    spec = importlib.util.spec_from_file_location("_reference_ucs", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


class FakeSST:
    def __init__(self, size, first=RING_MIN + 1, last=(1 << 63) - 1):
        self.data_size = size
        self.first, self.last = first, last
        self.is_repaired = False
        self.n_cells = 1

    def min_token(self):
        return self.first

    def max_token(self):
        return self.last


def _ucs(sstables=(), **options):
    class _CFS:
        def live_sstables(self):
            return list(sstables)
    o = {"min_sstable_size": 1024, "base_shard_count": 4,
         "target_sstable_size": 1 << 20, "sstable_growth": 0.0}
    o.update(options)
    return UnifiedCompactionStrategy(_CFS(), o)


LATEST = {"scaling_parameters": "T4", "target_sstable_size": "1GiB",
          "sstable_growth": "0.3333333333333333",
          "min_sstable_size": "100MiB", "base_shard_count": "4"}


def _latest(sstables=()):
    class _CFS:
        def live_sstables(self):
            return list(sstables)
    return UnifiedCompactionStrategy(_CFS(), dict(LATEST))


def test_scaling_vector_parsing_and_repeat():
    u = _ucs(scaling_parameters="T4, T8, N, L4")
    assert u.scaling_vector == [2, 6, 0, -2]
    # per-level lookup; beyond the end repeats the LAST entry
    assert [u.scaling_w(i) for i in range(6)] == [2, 6, 0, -2, -2, -2]
    # raw integers are accepted too (reference pattern allows [+-]?d+)
    assert _ucs(scaling_parameters="2, -2, 0").scaling_vector == [2, -2, 0]


def test_fanout_and_threshold_per_level():
    u = _ucs(scaling_parameters="T4, N, L4")
    # T4: w=2 -> tiered: fanout 4, threshold 4
    assert (u.fanout(0), u.threshold(0)) == (4, 4)
    # N: w=0 -> fanout 2, threshold 2
    assert (u.fanout(1), u.threshold(1)) == (2, 2)
    # L4: w=-2 -> leveled: fanout 4, threshold 2 (eager)
    assert (u.fanout(2), u.threshold(2)) == (4, 2)


def test_density_level_geometry_mixed_vector():
    """Level ceilings multiply by each level's OWN fanout
    (getMaxLevelDensity iterated): min=1024, vector T4,N,L8 gives
    ceilings 1024*4=4096, *2=8192, *8=65536, *8=..."""
    u = _ucs(scaling_parameters="T4, N, L8")
    assert u.level_of(1023) == 0
    assert u.level_of(4095) == 0
    assert u.level_of(4096) == 1
    assert u.level_of(8191) == 1
    assert u.level_of(8192) == 2
    assert u.level_of(65535) == 2
    assert u.level_of(65536) == 3
    # uniform-vector sanity: T4 everywhere -> pure log base 4
    v = _ucs(scaling_parameters="T4")
    assert v.level_of(1024 * 4 - 1) == 0
    assert v.level_of(1024 * 4) == 1
    assert v.level_of(1024 * 16) == 2


def test_num_shards_growth_modes():
    u0 = _ucs(sstable_growth=0.0)
    # fixed mode: growth 1 always yields the base count
    u1 = _ucs(sstable_growth=1.0)
    assert u1.num_shards(1 << 30) == 4
    # growth 0: power-of-two multiple of base targeting ~target size
    # density = 64 MiB, target 1 MiB, base 4 -> ~64 shards
    s = u0.num_shards(64 << 20)
    assert s % 4 == 0 and s & (s - 1) == 0 or s % 4 == 0
    assert 32 <= s <= 128
    # shard count never shrinks as density grows
    prev = 0
    for d in (1 << 20, 8 << 20, 64 << 20, 512 << 20):
        n = u0.num_shards(d)
        assert n >= prev
        prev = n
    # intermediate growth: between fixed and full splitting
    uh = _ucs(sstable_growth=0.5)
    assert u1.num_shards(64 << 20) <= uh.num_shards(64 << 20) \
        <= u0.num_shards(64 << 20)


def test_num_shards_min_size_clamp():
    """Densities below base_shard_count x min size split only to
    power-of-two DIVISORS of the base so boundaries align upward."""
    u = _ucs(min_sstable_size=1 << 20, base_shard_count=4)
    assert u.num_shards(512 << 10) == 1       # half a min-size sstable
    assert u.num_shards(2 << 20) <= 4


def test_selection_uses_per_level_threshold(tmp_path):
    """Level 0 (T4) needs 4 sstables that overlap; level 1 (L4) compacts
    at 2 — the vector changes WHICH group fires, not just how big it
    is."""
    u = _ucs(scaling_parameters="T4, L4", min_sstable_size=1024)
    # three small (level 0, threshold 4: not enough), two big (level 1,
    # threshold 2: fires), every one over the whole ring
    small = [FakeSST(1000) for _ in range(3)]
    big = [FakeSST(5000) for _ in range(2)]
    levels = u.form_levels(small + big)
    assert set(levels) == {0, 1}
    assert len(levels[0]) == 3 and len(levels[1]) == 2
    assert len(u.overlap_set(levels[0])) < u.threshold(0)
    assert len(u.overlap_set(levels[1])) >= u.threshold(1)
    task = _ucs(small + big, scaling_parameters="T4, L4",
                min_sstable_size=1024).next_background_task()
    assert task is not None and task.inputs == big


def test_option_strings_parse_and_defaults_are_upstreams():
    """cassandra_latest.yaml's own strings ('100MiB', '1GiB') parse, and
    an absent option takes upstream's default — the reference's."""
    u = _latest()
    ref = REF.Options(LATEST)
    assert (u.min_sstable_size, u.target_sstable_size) == \
        (100 * MiB, 1 << 30) == (ref.min_size, ref.target)
    assert u.sstable_growth == ref.growth == 1 / 3
    assert u.base_shard_count == ref.base == 4
    assert (u.fanout(0), u.threshold(0)) == REF.fanout_threshold("T4")
    bare = UnifiedCompactionStrategy(type("C", (), {
        "live_sstables": lambda self: []})(), {})
    ref0 = REF.Options({})
    assert (bare.min_sstable_size, bare.target_sstable_size,
            bare.sstable_growth, bare.base_shard_count,
            bare.scaling_vector) == (ref0.min_size, ref0.target,
                                     ref0.growth, ref0.base, [2])
    assert bare.min_sstable_size == 100 * MiB


@pytest.mark.parametrize("span_quarters", [4, 2, 1])
def test_density_is_size_over_token_span(span_quarters):
    """A quarter of the ring holding 100 MiB is as dense as the whole
    ring holding 400 MiB: the level follows."""
    last = RING_MIN + span_quarters * QUARTER - 1
    s = FakeSST(100 * MiB, RING_MIN, last)
    u = _latest([s])
    want = REF.density(100 * MiB, RING_MIN, last)
    assert u.density([s]) == pytest.approx(want, rel=1e-12)
    assert u.density([s]) == pytest.approx(100 * MiB * 4 / span_quarters,
                                           rel=1e-9)
    assert u.level_of(u.density([s])) == REF.level(REF.Options(LATEST),
                                                   want)
    # one partition: the minimum coverage, not a division by zero
    one = FakeSST(MiB, 7, 7)
    assert u.density([one]) == REF.density(MiB, 7, 7) == MiB * 2.0 ** 48


def _flushes(n=4, size=123 * MiB):
    return [FakeSST(size) for _ in range(n)]


def _shards(n=4, size=123 * MiB):
    return [FakeSST(size, RING_MIN + i * QUARTER + 1,
                    RING_MIN + (i + 1) * QUARTER - 1) for i in range(n)]


@pytest.mark.parametrize("case,picked", [
    ("four_flushes", [0, 1, 2, 3]),
    ("three_flushes", None),
    ("four_shards_at_level_1", None),
    ("four_shards_and_four_flushes", [4, 5, 6, 7]),
    ("flushes_side_by_side", None)])
def test_selection_by_overlap_matches_the_reference(case, picked):
    """Four level-0 flushes over the whole ring are compacted; four
    disjoint shard outputs at level 1 never are, nor flushes that only
    sit side by side in token order."""
    ssts = {"four_flushes": _flushes(),
            "three_flushes": _flushes(3),
            "four_shards_at_level_1": _shards(),
            "four_shards_and_four_flushes": _shards() + _flushes(),
            "flushes_side_by_side": _shards(size=20 * MiB)}[case]
    u = _latest(ssts)
    want = REF.select(REF.Options(LATEST), [
        (s.data_size, s.min_token(), s.max_token()) for s in ssts])
    assert want == picked
    task = u.next_background_task()
    if picked is None:
        assert task is None
        return
    assert task.inputs == [ssts[i] for i in picked]
    plan = REF.plan(REF.Options(LATEST), [
        (s.data_size, s.min_token(), s.max_token()) for s in task.inputs])
    assert task.level == plan["level"] == 1
    assert task.shard_boundaries == plan["boundaries"]
    assert task.max_output_bytes is None


@pytest.mark.parametrize("density_mib,shards", [
    (50, 1), (250, 2), (399, 2), (407, 4), (493, 4), (4 * 1024, 4),
    (16 * 1024, 8), (64 * 1024, 32), (1024 * 1024, 128)])
def test_even_boundaries_match_the_reference(density_mib, shards):
    """num_shards at cassandra_latest's options and the ring split
    evenly: four shards start at -2^63 + i * 2^62."""
    u = _latest()
    ref = REF.Options(LATEST)
    n = u.num_shards(density_mib * MiB)
    assert n == REF.num_shards(ref, density_mib * MiB) == shards
    assert u.shard_boundaries(n) == REF.boundaries(n)
    if n == 4:
        assert u.shard_boundaries(4) == [RING_MIN + i * QUARTER
                                         for i in range(4)]
