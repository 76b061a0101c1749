"""Plain reference of the Unified Compaction Strategy's choices and of a
sharded compaction's outputs: plain Python and numpy, nothing of the
program imported.

Rules (upstream's UnifiedCompactionStrategy.md, Controller, ShardManager,
as the configuration states them):
- a size option is a number of bytes or '<n>B|KiB|MiB|GiB';
- scaling parameter Tn: fanout and threshold n; Ln: fanout n, threshold
  2; N: both 2;
- an sstable's density is its on-disk size over the fraction of the
  node's token space its first..last partition spans (at least 2^-48 of
  it); the node here owns the whole Murmur3 ring, 2^64 tokens from
  -2^63;
- level 0 holds densities below min_sstable_size x fanout; each next
  level's ceiling is the last one's times the fanout;
- a level compacts when the largest set of its sstables that all hold
  one token reaches the threshold;
- a compaction of density d writes num_shards(d) outputs, split at the
  even boundaries of the token space: below base_shard_count x
  min_sstable_size the largest power of two not above d / min (and not
  above the base's lowest set bit), else base x 2^e with
  e = round(log2(d / (target x base)) x (1 - growth)), e >= 0;
- the outputs hold the merged cells (reference/compaction.py's merge),
  each output exactly the partitions whose token lies in its shard
  (`shard_of`);
- a key's token is Murmur3Partitioner's: the first word of
  MurmurHash3_x64_128 (seed 0) as a signed long, -2^63 read as 2^63 - 1,
  with upstream's tail quirk (MurmurHash.hash3_x64_128 XORs the tail
  bytes in SIGN-EXTENDED).
"""
from __future__ import annotations

import math
import re

import numpy as np

RING_MIN = -(1 << 63)
RING_SPAN = 1 << 64
MIN_COVERAGE = 2.0 ** -48
UNITS = {"B": 1, "KiB": 1 << 10, "MiB": 1 << 20, "GiB": 1 << 30}


_C1, _C2 = np.uint64(0x87C37B91114253D5), np.uint64(0x4CF5AD432745937F)


def _rotl(x, r: int):
    return (x << np.uint64(r)) | (x >> np.uint64(64 - r))


def _fmix(k):
    k ^= k >> np.uint64(33)
    k *= np.uint64(0xFF51AFD7ED558CCD)
    k ^= k >> np.uint64(33)
    k *= np.uint64(0xC4CEB9FE1A85EC53)
    k ^= k >> np.uint64(33)
    return k


def tokens(keys: np.ndarray) -> np.ndarray:
    """Tokens of keys (n, L) uint8, all of one length L < 16: no whole
    16-byte block, the tail alone."""
    n, length = keys.shape
    if length >= 16:
        raise ValueError("keys of 16 bytes or more are not covered")
    signed = keys.astype(np.int8).astype(np.int64)
    k1 = np.zeros(n, dtype=np.uint64)
    k2 = np.zeros(n, dtype=np.uint64)
    h1 = np.zeros(n, dtype=np.uint64)
    h2 = np.zeros(n, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for i in range(length):
            part = (signed[:, i] << np.int64(8 * (i % 8))).view(np.uint64)
            if i < 8:
                k1 ^= part
            else:
                k2 ^= part
        if length > 8:
            h2 ^= _rotl(k2 * _C2, 33) * _C1
        if length > 0:
            h1 ^= _rotl(k1 * _C1, 31) * _C2
        h1 ^= np.uint64(length)
        h2 ^= np.uint64(length)
        h1 += h2
        h2 += h1
        h1, h2 = _fmix(h1), _fmix(h2)
        h1 += h2
    tok = h1.view(np.int64).copy()
    tok[tok == np.iinfo(np.int64).min] = np.iinfo(np.int64).max
    return tok


def parse_size(value) -> int:
    if isinstance(value, (int, float)):
        return int(value)
    m = re.fullmatch(r"\s*(\d+)\s*(B|KiB|MiB|GiB)\s*", str(value))
    if not m:
        raise ValueError(f"not a size: {value!r}")
    return int(m.group(1)) * UNITS[m.group(2)]


def fanout_threshold(scaling: str) -> tuple:
    """(fanout, threshold) of one scaling parameter."""
    p = scaling.strip().upper()
    if p == "N":
        return 2, 2
    n = int(p[1:])
    if p[0] == "T":
        return n, n
    if p[0] == "L":
        return n, 2
    raise ValueError(scaling)


class Options:
    """A table's UCS options, upstream's defaults where one is absent."""

    def __init__(self, options: dict):
        self.scaling = str(options.get("scaling_parameters", "T4"))
        self.min_size = parse_size(options.get("min_sstable_size",
                                               "100MiB"))
        self.target = parse_size(options.get("target_sstable_size", "1GiB"))
        self.growth = float(options.get("sstable_growth", 0.333))
        self.base = int(options.get("base_shard_count", 4))
        self.max_threshold = int(options.get("max_threshold", 32))

    def level_params(self, level: int) -> tuple:
        parts = [p for p in self.scaling.split(",") if p.strip()]
        return fanout_threshold(parts[min(level, len(parts) - 1)])


def density(size: int, first: int, last: int) -> float:
    return size / max((last - first) / RING_SPAN, MIN_COVERAGE)


def level(opts: Options, dens: float) -> int:
    ceiling = float(opts.min_size) * opts.level_params(0)[0]
    n = 0
    while dens >= ceiling:
        n += 1
        ceiling *= opts.level_params(n)[0]
    return n


def largest_overlap(spans: list) -> list:
    """Indices of the largest set of [first, last] spans that share one
    token (the lowest such token on ties)."""
    best = []
    for token in sorted({first for first, _last in spans}):
        here = [i for i, (a, b) in enumerate(spans) if a <= token <= b]
        if len(here) > len(best):
            best = here
    return best


def select(opts: Options, sstables: list) -> list | None:
    """sstables: (size, first, last) each. The indices the strategy must
    compact next, or None: the lowest level whose largest overlap set
    reaches its threshold."""
    by_level: dict = {}
    for i, (size, first, last) in enumerate(sstables):
        by_level.setdefault(level(opts, density(size, first, last)),
                            []).append(i)
    for lvl in sorted(by_level):
        members = by_level[lvl]
        hit = largest_overlap([sstables[i][1:] for i in members])
        if len(hit) >= opts.level_params(lvl)[1]:
            return [members[k] for k in hit][:opts.max_threshold]
    return None


def num_shards(opts: Options, dens: float) -> int:
    count = dens / opts.min_size
    if count < opts.base:
        low_bit = opts.base & -opts.base
        return min(1 << (max(int(count), 1).bit_length() - 1), low_bit)
    ratio = dens / (opts.target * opts.base)
    e = math.floor(math.log2(ratio) * (1 - opts.growth) + 0.5)
    return opts.base * (1 << max(e, 0))


def boundaries(n: int) -> list:
    """The first token of each of n even shards of the ring."""
    return [RING_MIN + i * RING_SPAN // n for i in range(n)]


def shard_of(tokens, bounds: list) -> np.ndarray:
    """The shard each token falls in."""
    return np.searchsorted(np.asarray(bounds, dtype=np.int64),
                           np.asarray(tokens, dtype=np.int64),
                           side="right") - 1


def plan(opts: Options, inputs: list) -> dict:
    """What a compaction of `inputs` ((size, first, last) each) writes:
    its density, the outputs' level and shard boundaries."""
    size = sum(s for s, _f, _l in inputs)
    dens = density(size, min(f for _s, f, _l in inputs),
                   max(last for _s, _f, last in inputs))
    return {"density": dens, "level": level(opts, dens),
            "boundaries": boundaries(num_shards(opts, dens))}

