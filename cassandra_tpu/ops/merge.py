"""Device merge/reconcile kernel — the TPU form of the compaction pipeline.

The reference merges k sorted SSTable scanners through a binary heap one row
at a time (utils/MergeIterator.java:23, CompactionIterator.java:90). The
TPU formulation: concatenate the runs' identity lanes, sort, then compute
winners / deletion shadowing / purge as masks with segmented scans
(lax.associative_scan). Everything is uint32 lanes — 64-bit quantities
travel as (hi, lo) pairs and compare pairwise — so the kernel maps directly
onto TPU vector units with no 64-bit emulation.

Sorting strategy (the load-bearing TPU decision): XLA's TPU sort compile
time explodes with the number of operands (a 2-operand sort compiles in
seconds; an 18-operand variadic sort takes tens of minutes), while warm
runs are fast. So the lexicographic sort is an LSD radix composition:
16 passes of ONE reused jitted (key, perm) stable sort, least-significant
lane first. One small program compiles once; the passes chain on-device
with no host synchronisation.

Tie-breaks beyond (identity, timestamp) — tombstone-beats-data and
larger-value-wins at equal timestamps (db/rows/Cells.java:68) — are
resolved on the host for the rare flagged runs, exactly, with full value
bytes.

Outputs are a permutation + keep mask; the host applies them to the
variable-length payload with numpy gathers (storage/cellbatch.py).
Shapes are padded to buckets so programs are traced once per bucket size.
"""
from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np

from ..schema import COL_PARTITION_DEL, COL_ROW_DEL
from ..storage.cellbatch import (DEATH_FLAGS, FLAG_COMPLEX_DEL, FLAG_COUNTER,
                                 FLAG_EXPIRING, FLAG_PARTITION_DEL,
                                 FLAG_RANGE_BOUND, FLAG_ROW_DEL,
                                 FLAG_TOMBSTONE, CellBatch,
                                 apply_counter_sums, sum_counter_runs)
from ..utils.logonce import warn_once

_log = logging.getLogger(__name__)

_U32_MAX = jnp.uint32(0xFFFFFFFF)


def _le_pair(ah, al, bh, bl):
    """(ah,al) <= (bh,bl) as unsigned 64-bit pairs."""
    return (ah < bh) | ((ah == bh) & (al <= bl))


def _lt_pair(ah, al, bh, bl):
    return (ah < bh) | ((ah == bh) & (al < bl))


def _seg_carry_pair(vh, vl, is_start):
    """Forward-fill the (vh, vl) value from each segment start across the
    segment: positions where is_start is True supply the value, others
    inherit the most recent start's value."""

    def combine(a, b):
        ah, al, a_s = a
        bh, bl, b_s = b
        h = jnp.where(b_s, bh, ah)
        l = jnp.where(b_s, bl, al)
        return h, l, a_s | b_s

    h, l, _ = jax.lax.associative_scan(combine, (vh, vl, is_start))
    return h, l


# ------------------------------------------------------------------- sort --

@jax.jit
def _lsd_pass(key: jnp.ndarray, perm: jnp.ndarray) -> jnp.ndarray:
    """One stable radix pass: reorder perm by key[perm]. Chained from the
    least-significant sort lane to the most significant, this composes a
    full lexicographic sort (stability carries the lower lanes' order)."""
    k = key[perm]
    _, new_perm = jax.lax.sort((k, perm), num_keys=1, is_stable=True)
    return new_perm


# registry-instrumented (service/profiling.py): eager host calls are
# timed under "merge.lsd_pass"; calls from inside an enclosing trace
# (_resident_program, shard_map bodies) pass through untimed — the
# outer program's dispatch owns those
from ..service.profiling import GLOBAL as _kprof_registry  # noqa: E402

_lsd_pass = _kprof_registry.wrap("merge.lsd_pass", _lsd_pass)


def _sort_keys(operands) -> list:
    """Most-significant first: validity, identity lanes, ~ts."""
    lanes = operands["lanes"]
    K = lanes.shape[1]
    keys = [operands["valid"]]
    keys += [lanes[:, k] for k in range(K)]
    keys += [_U32_MAX - operands["ts_h"], _U32_MAX - operands["ts_l"]]
    return keys


def _traced_sort_perm(operands) -> jnp.ndarray:
    """LSD composition. Works eagerly (each _lsd_pass hits the one cached
    jit program; dispatches pipeline without host sync) and under an
    enclosing jit/shard_map (nested jit inlines)."""
    keys = _sort_keys(operands)
    N = keys[0].shape[0]
    perm = jnp.arange(N, dtype=jnp.int32)
    for key in reversed(keys):
        perm = _lsd_pass(jnp.asarray(key), perm)
    return perm


device_sort_perm = _traced_sort_perm


# -------------------------------------------------------------- reconcile --

def unpack_masks(packed: np.ndarray):
    """(keep, ambiguous, expired, shadowed) from the kernel's packed uint8
    lane — the single definition of the bit layout."""
    return ((packed & 1).astype(bool), (packed & 2).astype(bool),
            (packed & 4).astype(bool), (packed & 8).astype(bool))


def _reconcile_core(lanes, ts_h, ts_l, valid, ldt, expiring, is_cd,
                    death, purge_h, purge_l, now, gc_before, perm):
    """Reconcile over a sort permutation; all arrays UNSORTED (gathered
    through perm here). Returns ONE packed uint8 mask array aligned to
    SORTED order (bit0=keep, bit1=ambiguous, bit2=expired, bit3=shadowed —
    decode with unpack_masks). One small transfer instead of four bools.

    ambiguous marks records whose (identity, ts) equal the previous sorted
    record — the host picks the winner there with death/value tie-break
    rules (the device sort does not order by them)."""
    lanes = lanes[perm]
    N, K = lanes.shape
    g = lambda a: a[perm]
    ts_h, ts_l = g(ts_h), g(ts_l)
    valid = g(valid) == 0
    ldt = g(ldt)
    expiring = g(expiring) == 1
    is_cd = g(is_cd) == 1
    purge_h, purge_l = g(purge_h), g(purge_l)
    death = g(death) == 1

    # ---- boundaries
    prev = jnp.concatenate([jnp.full((1, K), 0xFFFFFFFF, dtype=jnp.uint32),
                            lanes[:-1]], axis=0)
    diff = lanes != prev
    first = jnp.zeros(N, dtype=bool).at[0].set(True)
    part_new = first | diff[:, :4].any(axis=1)
    row_new = part_new | diff[:, 4:K - 3].any(axis=1)
    col_new = row_new | diff[:, K - 3]
    cell_new = col_new | diff[:, K - 2:].any(axis=1)

    col = lanes[:, K - 3]
    winner = cell_new & valid

    # ---- deletion shadowing
    is_pd = col == COL_PARTITION_DEL
    is_rd = col == COL_ROW_DEL
    zero = jnp.uint32(0)
    pd_h = jnp.where(part_new & is_pd, ts_h, zero)
    pd_l = jnp.where(part_new & is_pd, ts_l, zero)
    pd_h, pd_l = _seg_carry_pair(pd_h, pd_l, part_new)
    rd_h = jnp.where(row_new & is_rd, ts_h, zero)
    rd_l = jnp.where(row_new & is_rd, ts_l, zero)
    rd_h, rd_l = _seg_carry_pair(rd_h, rd_l, row_new)
    use_pd = _lt_pair(rd_h, rd_l, pd_h, pd_l)
    del_h = jnp.where(use_pd, pd_h, rd_h)
    del_l = jnp.where(use_pd, pd_l, rd_l)
    cd_h = jnp.where(col_new & is_cd, ts_h, zero)
    cd_l = jnp.where(col_new & is_cd, ts_l, zero)
    cd_h, cd_l = _seg_carry_pair(cd_h, cd_l, col_new)
    use_cd = _lt_pair(del_h, del_l, cd_h, cd_l)
    cdel_h = jnp.where(use_cd, cd_h, del_h)
    cdel_l = jnp.where(use_cd, cd_l, del_l)

    plain = ~is_pd & ~is_rd & ~is_cd
    shadowed = jnp.where(
        plain, _le_pair(ts_h, ts_l, cdel_h, cdel_l),
        jnp.where(is_rd, _le_pair(ts_h, ts_l, pd_h, pd_l),
                  jnp.where(is_cd, _le_pair(ts_h, ts_l, del_h, del_l),
                            False)))

    # ---- TTL expiry + purge (named_scope: op names in a profiler
    # trace, metadata only)
    with jax.named_scope("purge"):
        expired = expiring & (ldt <= now)
        death_eff = death | expired
        purgeable = _lt_pair(ts_h, ts_l, purge_h, purge_l)
        purged = death_eff & (ldt < gc_before) & purgeable

    keep = winner & ~shadowed & ~purged

    # ---- ties the device didn't order: same identity AND same ts
    same_ts = (ts_h == prev_eq(ts_h)) & (ts_l == prev_eq(ts_l))
    ambiguous = (~cell_new) & same_ts & valid

    # pack the four masks into ONE uint8 lane: a single (and much smaller)
    # device->host transfer instead of four bool arrays
    packed = (keep.astype(jnp.uint8)
              | (ambiguous.astype(jnp.uint8) << 1)
              | (expired.astype(jnp.uint8) << 2)
              | (shadowed.astype(jnp.uint8) << 3))
    return packed


@jax.jit
def reconcile_kernel(operands, perm):
    """Dict-operand form (driver entry / shard_map body)."""
    return _reconcile_core(
        operands["lanes"], operands["ts_h"], operands["ts_l"],
        operands["valid"], operands["ldt"], operands["expiring"],
        operands["cdel"], operands["death"], operands["purge_h"],
        operands["purge_l"], operands["now"], operands["gc_before"], perm)


# dual-use like _lsd_pass: host entry ("merge.reconcile") or traced body
reconcile_kernel = _kprof_registry.wrap("merge.reconcile",
                                        reconcile_kernel)


def merge_reconcile_kernel(operands):
    """Jittable single-call form (driver entry / shard_map body): traced
    sort composition + reconcile. Returns (perm, packed_masks) where
    packed bit0=keep, bit1=ambiguous, bit2=expired, bit3=shadowed."""
    perm = _traced_sort_perm(operands)
    packed = reconcile_kernel(operands, perm)
    return perm, packed


def prev_eq(a):
    """a shifted by one (first element compares unequal)."""
    return jnp.concatenate([jnp.full((1,), ~a[0], dtype=a.dtype), a[:-1]])


# ------------------------------------- compressed key-plane path (v2) -------
#
# The v2 path pushes a compressed key stream instead of the full
# (lanes, meta) arrays, to cut host<->device BYTES PER CELL:
#
#   pk rank    u32   partition identity remapped host-side to its dense
#                    rank among the round's distinct partitions (the 16-byte
#                    token+hash prefix repeats for every cell of a
#                    partition; rank preserves order and equality, which is
#                    all sort/boundary detection needs)
#   row/col/path lanes   only lanes that actually VARY in this round; a
#                    constant lane can neither reorder cells nor create a
#                    boundary, so it travels as one scalar
#   ts planes    u32+u16(+u16)  timestamps split into lo32/mid16/hi16 —
#                    hi16 is constant for any real dataset (range < 2^48)
#                    and travels as a scalar
#   cdel         u8   only when the round contains complex deletions
#
# Purge, TTL expiry and tombstone conversion move to a HOST post-pass:
# they filter the kept set but never change the sort order or the
# shadowing carries, so the device doesn't need ldt/flags/purge_ts at all.
# Typical cost: ~14-18 bytes/cell pushed vs 80 for the v1 packed path.
# Whether that buys anything on a directly attached chip is not measured
# (ROADMAP A3/C2).

_PAD_QUANTUM = 1 << 18   # above 256K cells: pad to 256K multiples
                         # (<=12% padding, few program shapes)


def _plane_pad(n: int) -> int:
    """Padded round size: power-of-two buckets below the quantum (a 10K
    round must not pay a 256K-row transfer), 256K multiples above."""
    if n <= _PAD_QUANTUM:
        b = 1024
        while b < n:
            b <<= 1
        return b
    return -(-n // _PAD_QUANTUM) * _PAD_QUANTUM


def _partition_ranks(batches: list[CellBatch]) -> np.ndarray:
    """Dense rank of each cell's 16-byte partition prefix among the
    round's distinct partitions. Each input run is sorted, so per-run
    distinct prefixes come from boundary diffs; the global order is the
    union (np.unique of the per-run boundary sets, not of all cells)."""
    run_uniques = []
    run_counts = []
    for b in batches:
        l4 = np.ascontiguousarray(b.lanes[:, :4].astype(">u4"))
        keys = l4.view("S16").ravel()
        new = np.ones(len(b), dtype=bool)
        new[1:] = keys[1:] != keys[:-1]
        starts = np.flatnonzero(new)
        run_uniques.append(keys[starts])
        run_counts.append(np.diff(np.append(starts, len(b))))
    all_u = np.unique(np.concatenate(run_uniques))
    parts = []
    for uniq, counts in zip(run_uniques, run_counts):
        ranks = np.searchsorted(all_u, uniq).astype(np.uint32)
        parts.append(np.repeat(ranks, counts))
    return np.concatenate(parts)


def _plane_pack_v2(cat: CellBatch, batches: list[CellBatch]):
    """Build the compressed plane dict + static config for the device
    program. Returns (planes, cfg) or None when the layout can't encode
    this round (ts range >= 2^48 with varying hi16 is still encodable —
    only a rank overflow bails)."""
    n = len(cat)
    N = _plane_pad(n)
    K = cat.n_lanes
    ranks = _partition_ranks(batches)
    if n and int(ranks.max()) >= 0xFFFFFF00:
        return None   # rank must stay below the padding sentinel
    rank_plane = np.full(N, 0xFFFFFFFF, dtype=np.uint32)
    rank_plane[:n] = ranks

    # varying non-partition lanes, classified by boundary group. When
    # every composite fits the prefix lanes, the ckh hash lanes (K-5,
    # K-4) are redundant with the prefix (prefix-free encodings) and are
    # not pushed — 8 bytes/cell of incompressible hash saved.
    skip = {K - 5, K - 4} if cat.ck_fits_prefix else set()
    row_idx, col_idx, path_idx = [], [], []
    for k in range(4, K):
        if k in skip:
            continue
        col_vals = cat.lanes[:, k]
        if int(col_vals.min()) == int(col_vals.max()):
            continue
        if k < K - 3:
            row_idx.append(k)
        elif k == K - 3:
            col_idx.append(k)
        else:
            path_idx.append(k)
    lane_planes = []
    for k in row_idx + col_idx + path_idx:
        p = np.full(N, 0xFFFFFFFF, dtype=np.uint32)
        p[:n] = cat.lanes[:, k]
        lane_planes.append(p)
    col_const = int(cat.lanes[0, K - 3]) if not col_idx and n else 0

    with np.errstate(over="ignore"):
        uts = cat.ts.astype(np.uint64) ^ np.uint64(1 << 63)
    ts_lo = np.zeros(N, dtype=np.uint32)
    ts_lo[:n] = (uts & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    mid = ((uts >> np.uint64(32)) & np.uint64(0xFFFF)).astype(np.uint16)
    hi = (uts >> np.uint64(48)).astype(np.uint16)
    ts_mid = np.zeros(N, dtype=np.uint16)
    ts_mid[:n] = mid
    hi_varies = bool(n) and int(hi.min()) != int(hi.max())
    ts_hi = None
    hi_const = int(hi[0]) if n else 0
    if hi_varies:
        ts_hi = np.zeros(N, dtype=np.uint16)
        ts_hi[:n] = hi

    cdel_any = bool(((cat.flags & FLAG_COMPLEX_DEL) != 0).any())
    cdel = None
    if cdel_any:
        cdel = np.zeros(N, dtype=np.uint8)
        cdel[:n] = ((cat.flags & FLAG_COMPLEX_DEL) != 0).astype(np.uint8)

    planes = {"rank": rank_plane, "ts_lo": ts_lo, "ts_mid": ts_mid,
              "hi_const": np.uint32(hi_const),
              "col_const": np.uint32(col_const)}
    for i, p in enumerate(lane_planes):
        planes[f"lane{i}"] = p
    if ts_hi is not None:
        planes["ts_hi"] = ts_hi
    if cdel is not None:
        planes["cdel"] = cdel
    cfg = (len(row_idx), len(col_idx), len(path_idx),
           ts_hi is not None, cdel is not None)
    return planes, cfg


def _plane_pass(key, perm):
    """One ascending LSD pass over a plane of any unsigned dtype, through
    the ONE nested-jit _lsd_pass on a u32 key: XLA then compiles a single
    sort and calls it per pass. A sort inlined per key dtype cost the TPU
    compiler ~30 s EACH at 2^19 cells (CHANGES.md PR 21); widening is
    order-preserving."""
    return _lsd_pass(key.astype(jnp.uint32), perm)


def _plane_lsd_sort(planes, cfg):
    n_row, n_col, n_path, has_hi, has_cdel = cfg
    N = planes["rank"].shape[0]
    perm = jnp.arange(N, dtype=jnp.int32)

    asc = _plane_pass

    def desc(key, perm):
        flipped = jnp.array(np.iinfo(key.dtype.name).max, key.dtype) - key
        return _plane_pass(flipped, perm)

    # least-significant first: ~ts_lo, ~ts_mid, [~ts_hi], path lanes,
    # col lane, row lanes (reversed), rank. Padding rows carry rank
    # 0xFFFFFFFF and sort to the tail; stability keeps input order on ties.
    perm = desc(planes["ts_lo"], perm)
    perm = desc(planes["ts_mid"], perm)
    if has_hi:
        perm = desc(planes["ts_hi"], perm)
    n_lanes = n_row + n_col + n_path
    for i in reversed(range(n_lanes)):
        perm = asc(planes[f"lane{i}"], perm)
    perm = asc(planes["rank"], perm)
    return perm


def _plane_reconcile(planes, cfg, perm):
    n_row, n_col, n_path, has_hi, has_cdel = cfg
    rank = planes["rank"][perm]
    N = rank.shape[0]
    valid = rank != jnp.uint32(0xFFFFFFFF)
    first = jnp.zeros(N, dtype=bool).at[0].set(True)

    def diff(a):
        prev = jnp.concatenate([jnp.full((1,), ~a[0], dtype=a.dtype),
                                a[:-1]])
        return a != prev

    part_new = first | diff(rank)
    row_new = part_new
    for i in range(n_row):
        row_new = row_new | diff(planes[f"lane{i}"][perm])
    if n_col:
        col_lane = planes[f"lane{n_row}"][perm]
        col_new = row_new | diff(col_lane)
    else:
        col_lane = jnp.broadcast_to(planes["col_const"], (N,))
        col_new = row_new
    cell_new = col_new
    for i in range(n_row + n_col, n_row + n_col + n_path):
        cell_new = cell_new | diff(planes[f"lane{i}"][perm])

    hi = planes["ts_hi"][perm].astype(jnp.uint32) if has_hi \
        else jnp.broadcast_to(planes["hi_const"], (N,))
    ts_h = (hi << 16) | planes["ts_mid"][perm].astype(jnp.uint32)
    ts_l = planes["ts_lo"][perm]
    is_cd = planes["cdel"][perm] == 1 if has_cdel \
        else jnp.zeros(N, dtype=bool)

    winner = cell_new & valid
    is_pd = col_lane == COL_PARTITION_DEL
    is_rd = col_lane == COL_ROW_DEL
    zero = jnp.uint32(0)
    pd_h = jnp.where(part_new & is_pd, ts_h, zero)
    pd_l = jnp.where(part_new & is_pd, ts_l, zero)
    pd_h, pd_l = _seg_carry_pair(pd_h, pd_l, part_new)
    rd_h = jnp.where(row_new & is_rd, ts_h, zero)
    rd_l = jnp.where(row_new & is_rd, ts_l, zero)
    rd_h, rd_l = _seg_carry_pair(rd_h, rd_l, row_new)
    use_pd = _lt_pair(rd_h, rd_l, pd_h, pd_l)
    del_h = jnp.where(use_pd, pd_h, rd_h)
    del_l = jnp.where(use_pd, pd_l, rd_l)
    cd_h = jnp.where(col_new & is_cd, ts_h, zero)
    cd_l = jnp.where(col_new & is_cd, ts_l, zero)
    cd_h, cd_l = _seg_carry_pair(cd_h, cd_l, col_new)
    use_cd = _lt_pair(del_h, del_l, cd_h, cd_l)
    cdel_h = jnp.where(use_cd, cd_h, del_h)
    cdel_l = jnp.where(use_cd, cd_l, del_l)

    plain = ~is_pd & ~is_rd & ~is_cd
    shadowed = jnp.where(
        plain, _le_pair(ts_h, ts_l, cdel_h, cdel_l),
        jnp.where(is_rd, _le_pair(ts_h, ts_l, pd_h, pd_l),
                  jnp.where(is_cd, _le_pair(ts_h, ts_l, del_h, del_l),
                            False)))

    keep0 = winner & ~shadowed
    same_ts = (ts_h == prev_eq(ts_h)) & (ts_l == prev_eq(ts_l))
    ambiguous = (~cell_new) & same_ts & valid
    packed = (keep0.astype(jnp.uint32)
              | (ambiguous.astype(jnp.uint32) << 1)
              | (shadowed.astype(jnp.uint32) << 3))
    return (packed << 24) | perm.astype(jnp.uint32)


from functools import partial as _partial


@_partial(jax.jit, static_argnames=("cfg",))
def _plane_program(planes, cfg):
    """One dispatch: LSD sort over the compressed planes + reconcile.
    Returns (masks << 24) | perm as uint32 (requires N < 2^24)."""
    perm = _plane_lsd_sort(planes, cfg)
    return _plane_reconcile(planes, cfg, perm)


# ------------------------------------- truncated-key fast path (v3) ---------
#
# The common compaction round has NO deletions of any kind — just live and
# TTL'd cells from sorted runs. For it the device only has to (a) find the
# merged order and (b) pick newest-version winners; TTL expiry, purge and
# exact tie-breaks are host post-passes that need data the device never
# sees. That permits two big cuts in bytes-per-cell over the v2 planes:
#
#  push  every plane shrinks to the narrowest dtype its VALUE RANGE needs
#        (bias by min): partition rank u16 for <65534 distinct partitions,
#        clustering lanes u8/u16 when their spread fits, and the timestamp
#        truncated to its top bits (uts >> 24, then range-shrunk) — cells
#        of the SAME identity whose truncated stamps collide are flagged
#        ambiguous and ordered exactly on the host (it has full ts).
#  pull  1 byte/cell: the source-run id (4 bits) + keep/ambiguous bits.
#        Each input run is sorted, and the device sort is stable over keys
#        that are order-isomorphic to the true keys, so within a run the
#        output preserves input order — the host reconstructs the full
#        permutation from run bases + per-run occurrence counting instead
#        of pulling a 4-byte perm lane.
#
# Reference semantics carried: newest-wins then Cells.resolveRegular
# (db/rows/Cells.java:79) — the host resolver orders collision runs by
# exact (ts, expiring-or-tombstone, tombstone, localDeletionTime, value).

TS_TRUNC_SHIFT = 24
_FAST_EXCLUDED = (DEATH_FLAGS | FLAG_COMPLEX_DEL | FLAG_RANGE_BOUND
                  | FLAG_COUNTER)


def _shrunk(vals: np.ndarray, n: int, N: int, reserve_sentinel: bool):
    """Bias vals by min and cast to the narrowest uint dtype that holds the
    range (reserving the dtype max as padding sentinel when asked).
    Returns (plane, dtype_name, sentinel_value) or None if > u32 needed."""
    vmin = int(vals.min()) if n else 0
    rng = (int(vals.max()) - vmin) if n else 0
    slack = 1 if reserve_sentinel else 0
    for dt, top in ((np.uint8, 0xFF), (np.uint16, 0xFFFF),
                    (np.uint32, 0xFFFFFFFF)):
        if rng <= top - slack:
            plane = np.full(N, top if reserve_sentinel else 0, dtype=dt)
            plane[:n] = (vals - vmin).astype(dt)
            return plane, np.dtype(dt).name, top
    return None


def _plane_pack_fast(cat: CellBatch, batches: list[CellBatch]):
    """Build the v3 truncated-key planes. Returns (planes, cfg, meta) or
    None when this round doesn't qualify (unsorted runs, any deletion/
    counter/range-bound flag, >15 runs, rank overflow)."""
    n = len(cat)
    k = len(batches)
    if k > 15 or not all(getattr(b, "sorted", False) for b in batches):
        return None
    if (cat.flags & _FAST_EXCLUDED).any():
        return None
    N = _plane_pad(n)
    K = cat.n_lanes

    ranks = _partition_ranks(batches)
    r = _shrunk(ranks, n, N, reserve_sentinel=True)
    if r is None:
        return None
    rank_plane, rank_dt, _sent = r

    skip = {K - 5, K - 4} if cat.ck_fits_prefix else set()
    lane_planes, lane_dts = [], []
    for kk in range(4, K):
        if kk in skip:
            continue
        col_vals = cat.lanes[:, kk]
        if n and int(col_vals.min()) == int(col_vals.max()):
            continue
        s = _shrunk(col_vals, n, N, reserve_sentinel=False)
        plane, dt, _ = s
        lane_planes.append(plane)
        lane_dts.append(dt)

    # truncated timestamp, DESC via host-side flip (device sorts asc only)
    with np.errstate(over="ignore"):
        uts = cat.ts.astype(np.uint64) ^ np.uint64(1 << 63)
    q = uts >> np.uint64(TS_TRUNC_SHIFT)
    qmin = int(q.min()) if n else 0
    qr = q - np.uint64(qmin)
    qrange = int(qr.max()) if n else 0
    q_planes, q_dts = [], []
    if qrange > 0xFFFFFFFF:
        hi = (qr >> np.uint64(32)).astype(np.uint32)
        # flip before shrink for desc order (shrink re-biases by min,
        # which preserves the flipped ascending order)
        fh = hi.max() - hi if n else hi
        ph, dth, _ = _shrunk(fh, n, N, False)
        q_planes.append(ph)
        q_dts.append(dth)
        lo = (qr & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        flo = np.uint32(0xFFFFFFFF) - lo
        pl = np.zeros(N, dtype=np.uint32)
        pl[:n] = flo
        q_planes.append(pl)
        q_dts.append("uint32")
    else:
        qv = qr.astype(np.uint64)
        fq = (np.uint64(qrange) - qv).astype(np.uint32)
        pq, dtq, _ = _shrunk(fq, n, N, False)
        q_planes.append(pq)
        q_dts.append(dtq)

    offs = np.zeros(k + 1, dtype=np.int32)
    offs[1:] = np.cumsum([len(b) for b in batches])
    # ONE transfer per round: all planes + the run-offset table serialized
    # into a single u8 buffer (each device_put pays a fixed dispatch
    # latency). The device program re-slices by the static cfg layout.
    parts = [rank_plane] + lane_planes + q_planes
    buf = np.concatenate([np.ascontiguousarray(p).view(np.uint8).ravel()
                          for p in parts]
                         + [offs.astype("<i4").view(np.uint8)])
    cfg = (rank_dt, tuple(lane_dts), tuple(q_dts), k)
    meta = {"n": n, "k": k,
            "bases": offs[:-1].astype(np.int64),
            "counts": np.diff(offs).astype(np.int64)}
    return buf, cfg, meta


@_partial(jax.jit, static_argnames=("cfg",))
def _plane_program_fast(buf, cfg):
    """v3 device program: LSD sort over truncated planes, then emit ONE u8
    per cell: bits 0-3 source-run id, bit4 keep (newest winner), bit5
    ambiguous (same identity, same truncated ts as predecessor).
    `buf` is the single packed u8 transfer from _plane_pack_fast; plane
    slices/dtypes are recovered via the static cfg layout (bitcast on the
    minor axis — both host and TPU are little-endian)."""
    rank_dt, lane_dts, q_dts, k = cfg
    dts = [rank_dt] + list(lane_dts) + list(q_dts)
    cell_bytes = sum(np.dtype(d).itemsize for d in dts)
    N = (buf.shape[0] - 4 * (k + 1)) // cell_bytes

    def plane_at(off, dt):
        isz = np.dtype(dt).itemsize
        if isz == 1:
            return jax.lax.slice(buf, (off,), (off + N,))
        # assemble each word from strided byte gathers. The direct
        # form — reshape to (N, isz), bitcast — splits a 2- or 4-wide
        # minor dimension, which the TPU compiler unrolls: this
        # program took it 258 s at 2^19 cells (CHANGES.md PR 21)
        at = jnp.arange(N, dtype=jnp.int32) * isz + off
        word = buf[at].astype(dt)
        for b in range(1, isz):
            word = word | (buf[at + b].astype(dt) << (8 * b))
        return word

    planes = {}
    off = 0
    names = (["rank"] + [f"lane{i}" for i in range(len(lane_dts))]
             + [f"q{i}" for i in range(len(q_dts))])
    for name, dt in zip(names, dts):
        planes[name] = plane_at(off, dt)
        off += N * np.dtype(dt).itemsize
    offsets = jax.lax.bitcast_convert_type(
        jax.lax.slice(buf, (off,), (off + 4 * (k + 1),)).reshape(k + 1, 4),
        jnp.int32)
    perm = jnp.arange(N, dtype=jnp.int32)
    asc = _plane_pass

    # least-significant first: q planes are pre-flipped (asc == ts desc),
    # minor q plane last pushed... order: q_lo is LEAST significant
    n_lanes = len(lane_dts)
    n_q = len(q_dts)
    for i in reversed(range(n_q)):
        perm = asc(planes[f"q{i}"], perm)
    for i in reversed(range(n_lanes)):
        perm = asc(planes[f"lane{i}"], perm)
    perm = asc(planes["rank"], perm)

    rank_s = planes["rank"][perm]
    sentinel = jnp.array(np.iinfo(np.dtype(rank_dt)).max, rank_s.dtype)
    valid = rank_s != sentinel
    first = jnp.zeros(N, dtype=bool).at[0].set(True)

    def diff(a):
        prev = jnp.concatenate([jnp.full((1,), ~a[0], dtype=a.dtype),
                                a[:-1]])
        return a != prev

    cell_new = first | diff(rank_s)
    for i in range(n_lanes):
        cell_new = cell_new | diff(planes[f"lane{i}"][perm])
    same_q = jnp.ones(N, dtype=bool)
    for i in range(n_q):
        same_q = same_q & ~diff(planes[f"q{i}"][perm])

    keep = cell_new & valid
    amb = (~cell_new) & same_q & valid
    src = (jnp.searchsorted(offsets, perm, side="right") - 1).astype(
        jnp.uint8)
    return (src | (keep.astype(jnp.uint8) << 4)
            | (amb.astype(jnp.uint8) << 5))


# ----------------------------------------------------------------- wrapper --

def _bucket(n: int) -> int:
    """Pad to power-of-two buckets >= 1024 so jit compiles once per bucket
    (the persistent compilation cache, utils/compile_cache.py, amortises
    the per-bucket compiles across runs)."""
    b = 1024
    while b < n:
        b <<= 1
    return b


def build_operands(cat: CellBatch, gc_before: int = 0, now: int = 0,
                   purgeable_ts_fn=None, bucket: int | None = None) -> dict:
    """Pack a CellBatch into the kernel's padded uint32 operand arrays."""
    n = len(cat)
    N = bucket or _bucket(n)
    K = cat.n_lanes

    lanes = np.full((N, K), 0xFFFFFFFF, dtype=np.uint32)
    lanes[:n] = cat.lanes
    valid = np.ones(N, dtype=np.uint32)
    valid[:n] = 0
    with np.errstate(over="ignore"):
        uts = cat.ts.astype(np.uint64) ^ np.uint64(1 << 63)
    ts_h = np.zeros(N, dtype=np.uint32)
    ts_l = np.zeros(N, dtype=np.uint32)
    ts_h[:n] = (uts >> np.uint64(32)).astype(np.uint32)
    ts_l[:n] = (uts & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    death = np.zeros(N, dtype=np.uint32)
    death[:n] = (cat.flags & DEATH_FLAGS) != 0
    cdel = np.zeros(N, dtype=np.uint32)
    cdel[:n] = (cat.flags & FLAG_COMPLEX_DEL) != 0
    ldt = np.zeros(N, dtype=np.int32)
    ldt[:n] = cat.ldt
    expiring = np.zeros(N, dtype=np.uint32)
    expiring[:n] = (cat.flags & FLAG_EXPIRING) != 0

    if purgeable_ts_fn is not None:
        pts = purgeable_ts_fn(cat).astype(np.int64)
        with np.errstate(over="ignore"):
            upts = pts.astype(np.uint64) ^ np.uint64(1 << 63)
        purge_h = np.zeros(N, dtype=np.uint32)
        purge_l = np.zeros(N, dtype=np.uint32)
        purge_h[:n] = (upts >> np.uint64(32)).astype(np.uint32)
        purge_l[:n] = (upts & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    else:
        purge_h = np.full(N, 0xFFFFFFFF, dtype=np.uint32)
        purge_l = np.full(N, 0xFFFFFFFF, dtype=np.uint32)

    return {
        "lanes": jnp.asarray(lanes), "valid": jnp.asarray(valid),
        "ts_h": jnp.asarray(ts_h), "ts_l": jnp.asarray(ts_l),
        "death": jnp.asarray(death),
        "cdel": jnp.asarray(cdel),
        "ldt": jnp.asarray(ldt), "expiring": jnp.asarray(expiring),
        "purge_h": jnp.asarray(purge_h), "purge_l": jnp.asarray(purge_l),
        "gc_before": jnp.int32(gc_before), "now": jnp.int32(now),
    }


class DeviceMergeHandle:
    """An in-flight device merge round. `submit_merge` packs + dispatches
    (returns while transfers/compute are queued asynchronously);
    `collect_merge` blocks on the device result and runs the host
    post-passes. Keeping >=2 rounds in flight overlaps the device with
    host decode/gather/write — the pipelining the reference gets
    from the kernel writeback cache (CompactionTask.java:207 hot loop)."""

    __slots__ = ("mode", "result", "cat", "n", "fut", "meta", "cfg",
                 "gc_before", "now", "purgeable_ts_fn", "prof", "kernel")


def _host_round(h: DeviceMergeHandle, batches: list[CellBatch],
                why: str) -> DeviceMergeHandle:
    """A round the device layouts cannot encode: merged synchronously by
    the numpy spec, and COUNTED — a device-engine compaction that quietly
    ran on the host is a misread benchmark."""
    from ..service.metrics import GLOBAL as _METRICS
    from ..storage.cellbatch import merge_sorted
    _METRICS.incr("compaction.device_host_rounds")
    warn_once(_log, f"merge.host_round.{why}",
              "device merge round (%d cells) ran on the host: %s",
              h.n, why)
    h.mode = "done"
    h.result = merge_sorted(batches, h.gc_before, h.now, h.purgeable_ts_fn)
    return h


def submit_merge(batches: list[CellBatch], gc_before: int = 0,
                 now: int = 0, purgeable_ts_fn=None,
                 prof: dict | None = None,
                 device=None) -> DeviceMergeHandle:
    """Pack one merge round and dispatch it to the device (async). Rounds
    that can't run on-device (range tombstones, huge partitions) compute
    synchronously on the host instead (_host_round — counted).

    device: an explicit jax.Device to commit the operands to (the mesh
    compaction path places shard s's round on mesh device s); None =
    the default device."""
    import time as _time

    h = DeviceMergeHandle()
    h.gc_before, h.now = gc_before, now
    h.purgeable_ts_fn = purgeable_ts_fn
    h.prof = prof
    cat = CellBatch.concat(batches)
    h.cat = cat
    h.n = len(cat)
    if h.n == 0:
        h.mode, h.result = "done", cat
        return h
    t1 = _time.perf_counter()
    if ((cat.flags & FLAG_RANGE_BOUND) != 0).any():
        # range tombstone coverage is evaluated host-side on full
        # composites — numpy spec path
        return _host_round(h, batches, "range tombstone bounds")
    from ..service.profiling import GLOBAL as _kprof
    fast = _plane_pack_fast(cat, batches)
    if fast is not None:
        buf, cfg, meta = fast
        t2 = _time.perf_counter()
        buf_d = jax.device_put(buf, device)
        h.fut = _plane_program_fast(buf_d, cfg)
        # jit compiles synchronously inside the dispatch call: the first
        # call per (kernel, padded-shape, cfg) IS the compile — the
        # profiler splits compile vs warm dispatch on exactly that key
        # jit compiles per device too: the lane's device is part of
        # the key, or lanes 2..n's compiles read as warm dispatches
        if _kprof.record_dispatch("merge.plane_fast",
                                  (int(buf.shape[0]), cfg,
                                   getattr(device, "id", None)),
                                  _time.perf_counter() - t2):
            _kprof.maybe_record_cost("merge.plane_fast",
                                     _plane_program_fast, (buf_d, cfg))
        h.mode, h.meta, h.cfg = "fast", meta, cfg
        h.kernel = "merge.plane_fast"
        if prof is not None:
            prof["pack"] = prof.get("pack", 0.0) + (t2 - t1)
        return h
    if _plane_pad(h.n) >= (1 << 24):
        # the v2 packed perm layout holds 24 bits — a single >16M-cell
        # round overflows it
        return _host_round(h, batches, "round exceeds the 24-bit perm")
    packed_v2 = _plane_pack_v2(cat, batches)
    if packed_v2 is None:
        return _host_round(h, batches, "partition rank overflow")
    planes, cfg = packed_v2
    t2 = _time.perf_counter()
    planes_d = {k: jax.device_put(v, device) for k, v in planes.items()}
    h.fut = _plane_program(planes_d, cfg)
    if _kprof.record_dispatch("merge.plane_v2",
                              (int(planes["rank"].shape[0]), cfg,
                               getattr(device, "id", None)),
                              _time.perf_counter() - t2):
        _kprof.maybe_record_cost("merge.plane_v2", _plane_program,
                                 (planes_d, cfg))
    h.mode, h.cfg = "v2", cfg
    h.kernel = "merge.plane_v2"
    if prof is not None:
        prof["pack"] = prof.get("pack", 0.0) + (t2 - t1)
    return h


def collect_merge(h: DeviceMergeHandle) -> CellBatch:
    """Block on a submitted round and run the host post-passes: TTL
    expiry, purge, exact tie-breaks, payload gather."""
    import time as _time

    if h.mode == "done":
        return h.result
    cat, n, prof = h.cat, h.n, h.prof
    t0 = _time.perf_counter()
    pts = h.purgeable_ts_fn(cat).astype(np.int64) \
        if h.purgeable_ts_fn is not None else None
    t1 = _time.perf_counter()
    combined = np.asarray(h.fut)
    t2 = _time.perf_counter()
    from ..service.profiling import GLOBAL as _kprof
    _kprof.record_execute(h.kernel, t2 - t1)

    if h.mode == "fast":
        bits = combined[:n]
        src = bits & 0x0F
        keep = (bits & 0x10) != 0
        ambiguous = (bits & 0x20) != 0
        shadowed = np.zeros(n, dtype=bool)
        # permutation reconstruction: each run is sorted and the device
        # sort is stable, so sorted positions of run r enumerate r's cells
        # in input order
        meta = h.meta
        perm = np.empty(n, dtype=np.int64)
        for r in range(meta["k"]):
            pos = np.flatnonzero(src == r)
            if len(pos) != meta["counts"][r]:
                raise RuntimeError(
                    "device merge src-count mismatch (unsorted input run?)")
            perm[pos] = meta["bases"][r] + np.arange(len(pos),
                                                     dtype=np.int64)
    else:
        perm = (combined & 0x00FFFFFF).astype(np.int64)[:n]
        bits8 = (combined >> 24).astype(np.uint8)[:n]
        keep, ambiguous, _, shadowed = unpack_masks(bits8)

    # host post-pass: TTL expiry, purge and tie-breaks don't affect sort
    # order or shadow carries, so they never went to the device
    flags_s = cat.flags[perm]
    ldt_s = cat.ldt[perm]
    ts_s = cat.ts[perm]
    expired = ((flags_s & FLAG_EXPIRING) != 0) & (ldt_s <= h.now)
    death_eff = ((flags_s & DEATH_FLAGS) != 0) | expired
    pts_sorted = pts[perm] if pts is not None else None
    purgeable = np.ones(n, dtype=bool) if pts_sorted is None \
        else ts_s < pts_sorted
    purged = death_eff & (ldt_s < h.gc_before) & purgeable
    keep &= ~purged
    if ambiguous.any():
        host_tiebreak(cat, perm, keep, ambiguous, shadowed,
                      expired, h.gc_before, pts_sorted,
                      order_by_ts=(h.mode == "fast"))

    out = finalize_merged(cat, perm, keep, expired, shadowed)
    t3 = _time.perf_counter()
    if prof is not None:
        prof["purge_fn"] = prof.get("purge_fn", 0.0) + (t1 - t0)
        prof["device"] = prof.get("device", 0.0) + (t2 - t1)
        prof["gather"] = prof.get("gather", 0.0) + (t3 - t2)
    return out


def merge_sorted_device(batches: list[CellBatch], gc_before: int = 0,
                        now: int = 0, purgeable_ts_fn=None,
                        prof: dict | None = None) -> CellBatch:
    """Drop-in equivalent of storage.cellbatch.merge_sorted running the
    sort/reconcile on the default JAX device. `prof` (optional) accumulates
    per-phase wall seconds: pack / purge_fn / device / gather."""
    return collect_merge(submit_merge(batches, gc_before, now,
                                      purgeable_ts_fn, prof))


def finalize_merged(cat: CellBatch, perm_real: np.ndarray,
                    keep: np.ndarray, expired: np.ndarray,
                    shadowed: np.ndarray) -> CellBatch:
    """Materialize the merged output from kernel masks: gather kept cells
    in sorted order, sum counter runs, convert expired-TTL winners to
    tombstones. Shared by the single-device and mesh-sharded paths."""
    kept_sorted_pos = np.flatnonzero(keep)
    out = cat.apply_permutation(perm_real[kept_sorted_pos])
    out.sorted = True
    if ((cat.flags & FLAG_COUNTER) != 0).any():
        # counter columns reconcile by summation (host pass, as in the
        # numpy path; counter tables are the uncommon case)
        s = cat.apply_permutation(perm_real)
        sums = sum_counter_runs(s, keep, shadowed)
        out = apply_counter_sums(out, kept_sorted_pos, sums)
    converted = expired[kept_sorted_pos]
    if converted.any():
        out.flags[converted] |= FLAG_TOMBSTONE
        out = out.drop_values(converted)
    return out


def host_tiebreak(cat: CellBatch, perm_real: np.ndarray, keep: np.ndarray,
                  amb: np.ndarray, shadowed: np.ndarray,
                  expired: np.ndarray, gc_before: int,
                  pts_sorted: np.ndarray | None,
                  order_by_ts: bool = False) -> None:
    """Resolve equal-(identity, ts) runs with exact Cells.resolveRegular
    rules (db/rows/Cells.java:79, CASSANDRA-14592): expiring-or-tombstone
    beats live, pure tombstone beats expiring, larger localDeletionTime,
    larger value bytes, then first-seen. Mutates `keep` in place. Arrays
    are in SORTED order; perm_real maps sorted position -> index into
    `cat`. Shared by the single-device and the mesh-sharded paths.

    order_by_ts: the truncated-key fast path marks runs whose TRUNCATED
    stamps collide — exact timestamps may differ inside a run, so the
    winner key leads with the full ts before the resolveRegular ranking."""
    if not amb.any():
        return
    n = len(perm_real)
    flags_sorted = cat.flags[perm_real]
    death_orig = (flags_sorted & DEATH_FLAGS) != 0
    # rank-grade tombstone: STATIC isTombstone (death, no ttl) so the
    # rank survives expired->tombstone conversion (CASSANDRA-14592);
    # must mirror CellBatch._pure_death_lane and merge.cpp beats()
    pure_death = death_orig & ((flags_sorted & FLAG_EXPIRING) == 0)
    eot = death_orig | ((flags_sorted & FLAG_EXPIRING) != 0)
    death_eff = death_orig | expired
    ldt_sorted = cat.ldt[perm_real]
    ts_sorted = cat.ts[perm_real]
    lanes_sorted = cat.lanes[perm_real]
    cell_new = np.ones(n, dtype=bool)
    if n > 1:
        cell_new[1:] = (lanes_sorted[1:] != lanes_sorted[:-1]).any(axis=1)

    def orig_value(i):
        j = perm_real[i]
        return cat.payload[cat.val_start[j]:cat.off[j + 1]].tobytes()

    idxs = np.flatnonzero(amb)
    prev_i = -2
    runs = []
    for i in idxs:
        if i != prev_i + 1:
            runs.append([i - 1, i])
        else:
            runs[-1][1] = i
        prev_i = i
    for lo, hi in runs:
        if lo < 0 or not cell_new[lo]:
            continue  # run of older duplicates below the winner
        if order_by_ts:
            best = max(range(lo, hi + 1),
                       key=lambda i: (int(ts_sorted[i]), bool(eot[i]),
                                      bool(pure_death[i]),
                                      int(ldt_sorted[i]), orig_value(i)))
        else:
            best = max(range(lo, hi + 1),
                       key=lambda i: (bool(eot[i]), bool(pure_death[i]),
                                      int(ldt_sorted[i]), orig_value(i)))
        keep[lo:hi + 1] = False
        purgeable = pts_sorted is None or ts_sorted[best] < pts_sorted[best]
        purged = bool(death_eff[best]) and ldt_sorted[best] < gc_before \
            and purgeable
        keep[best] = not (shadowed[best] or purged)
