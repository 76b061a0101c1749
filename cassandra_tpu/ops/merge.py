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
passes of ONE (key, perm) stable sort, least-significant lane first, one
per key — validity, the identity lanes, ~ts: 16 at 13 lanes. The passes
chain on-device with no host synchronisation. A round runs only the
passes whose key VARIES among its valid cells: a stable sort by a
constant key moves nothing, and a table with no clustering column and no
collection holds 0 in eight of its thirteen lanes. The program asks each
key on the device (a masked min/max), skips the pass under `lax.cond`,
and returns how many it ran; no host round trip, no static mask, no
second compiled shape.

Tie-breaks beyond (identity, timestamp) — tombstone-beats-data and
larger-value-wins at equal timestamps (db/rows/Cells.java:68) — are
resolved on the host for the rare flagged runs, exactly, with full value
bytes.

Columns travel as ROWS (the second load-bearing TPU decision): a gather
on this chip is paid per INDEX, not per byte — at 2^19 indices a one-lane
gather from HBM takes 5.3 ms, a 13-wide row gather 4.1 and a 26-wide one
5.8; with twenty gathers outside the passes a round took 194.3 ms, with
two it takes 44.9 (PERF.md §6, PR 36) — so nothing outside the passes
gathers a single lane. The per-cell columns are stacked beside `lanes`
into ONE uint32 matrix, its rows are gathered once through the sort's
permutation, and reconcile works on column slices of the result
(`gather_rows`, `reconcile_columns`). int32 columns ride by bitcast, uint8
and bool widened and narrowed again, and reconcile's four yes/no columns
share one word, which keeps a round of 2^20 x 13 within the 24 words a
row gather moves at full speed there. A gather is exact.

Outputs are a permutation + keep mask; the host applies them to the
variable-length payload with numpy gathers (storage/cellbatch.py).
Shapes are padded to buckets so programs are traced once per bucket size.

This module holds the kernel's PARTS. The one jitted program compaction
dispatches, and the submit/collect pair around it, live in
ops/device_write.py (`_resident_program`, kernel name `merge.resident`),
on one chip and on every mesh lane of a sharded compaction.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..schema import COL_PARTITION_DEL, COL_ROW_DEL
from ..storage.cellbatch import (DEATH_FLAGS, FLAG_COMPLEX_DEL, FLAG_COUNTER,
                                 FLAG_EXPIRING, FLAG_TOMBSTONE, CellBatch,
                                 apply_counter_sums, sum_counter_runs)

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


def _sort_keys(operands) -> list:
    """Most-significant first: validity, identity lanes, ~ts."""
    lanes = operands["lanes"]
    K = lanes.shape[1]
    keys = [operands["valid"]]
    keys += [lanes[:, k] for k in range(K)]
    keys += [_U32_MAX - operands["ts_h"], _U32_MAX - operands["ts_l"]]
    return keys


def n_sort_keys(n_lanes: int) -> int:
    """How many keys _sort_keys yields — the passes a round can run."""
    return n_lanes + 3


def _traced_sort_perm(operands):
    """LSD composition over the keys that VARY in this round: a stable
    pass by a key that is the same in every valid cell leaves the valid
    cells where they are, so the program asks each key (one masked
    min/max reduction, on the device, from its own input) and runs the
    pass only where the answer is yes. Padding rows are masked out of
    the question — they carry 0xFFFFFFFF lanes and ts 0, so unmasked
    every key of a padded round would vary — and need not sit at the
    end. `valid` itself is always sorted: it is what moves the padding
    behind the cells.

    Returns (perm, passes_run); perm is the array the unconditional
    passes give, element for element."""
    keys = _sort_keys(operands)
    live = operands["valid"] == 0
    perm = jnp.arange(keys[0].shape[0], dtype=jnp.int32)
    ran = jnp.int32(1)
    for key in reversed(keys[1:]):
        key = jnp.asarray(key)
        varies = (jnp.max(jnp.where(live, key, jnp.uint32(0)))
                  > jnp.min(jnp.where(live, key, _U32_MAX)))
        perm = jax.lax.cond(varies, _lsd_pass, lambda _k, p: p, key, perm)
        ran += varies
    return _lsd_pass(jnp.asarray(keys[0]), perm), ran


# -------------------------------------------------------------- reconcile --

def unpack_masks(packed: np.ndarray):
    """(keep, ambiguous, expired, shadowed) from the kernel's packed uint8
    lane — the single definition of the bit layout."""
    return ((packed & 1).astype(bool), (packed & 2).astype(bool),
            (packed & 4).astype(bool), (packed & 8).astype(bool))


# what reconcile reads of a cell beside `lanes`: five whole words ...
RECONCILE_WORDS = ("ts_h", "ts_l", "ldt", "purge_h", "purge_l")
# ... and four yes/no answers, each an operand column of 0s and 1s and
# the value that means yes; they travel as the low bits of ONE word
_PREDICATES = {"live": ("valid", 0), "expiring": ("expiring", 1),
               "is_cd": ("cdel", 1), "death": ("death", 1)}


def _to_u32(a):
    """A column of at most 32 bits as uint32 words, exactly: int32 by
    bitcast, uint8 and bool widened."""
    if a.dtype == jnp.int32:
        return jax.lax.bitcast_convert_type(a, jnp.uint32)
    return a.astype(jnp.uint32)


def _from_u32(w, dtype):
    """_to_u32's inverse."""
    if dtype == jnp.int32:
        return jax.lax.bitcast_convert_type(w, jnp.int32)
    if dtype == jnp.bool_:
        return w != 0
    return w.astype(dtype)    # uint8: only the zeros it was widened by go


def gather_rows(cols: dict, idx) -> dict:
    """{name: a[idx]} for every column of `cols` — (N,) or (N, k), at
    most 32 bits an element — with ONE gather: the columns are stacked
    side by side into one uint32 matrix, its ROWS are gathered, and the
    columns are sliced back out in their own dtypes. A gather on the TPU
    is paid per index, not per byte (the module header has the figures),
    so every column after the first travels free — up to a size found on
    the chip, not from bytes: at 2^20 rows a 24-wide matrix gathers in
    9.3 ms and a 25-wide one in 39.6 (PERF.md §7). A gather is exact: the
    same elements as the separate gathers give."""
    wide = [_to_u32(a).reshape(a.shape[0], -1) for a in cols.values()]
    rows = jnp.concatenate(wide, axis=1)[idx]
    out, at = {}, 0
    for (name, a), w in zip(cols.items(), wide):
        part = rows[:, at:at + w.shape[1]]
        out[name] = _from_u32(part.reshape(idx.shape + a.shape[1:]), a.dtype)
        at += w.shape[1]
    return out


def reconcile_sorted(s, now, gc_before):
    """Reconcile over columns ALREADY in sorted order (`s`: what
    reconcile_columns returns). Returns ONE packed uint8 mask array
    aligned to SORTED order (bit0=keep, bit1=ambiguous, bit2=expired,
    bit3=shadowed — decode with unpack_masks). One small transfer
    instead of four bools.

    ambiguous marks records whose (identity, ts) equal the previous sorted
    record — the host picks the winner there with death/value tie-break
    rules (the device sort does not order by them)."""
    lanes = s["lanes"]
    N, K = lanes.shape
    ts_h, ts_l, ldt = s["ts_h"], s["ts_l"], s["ldt"]
    purge_h, purge_l = s["purge_h"], s["purge_l"]
    valid, expiring = s["live"], s["expiring"]
    is_cd, death = s["is_cd"], s["death"]

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


def reconcile_columns(operands, perm, also=()) -> dict:
    """In sorted order — one row gather through `perm` (gather_rows) —
    `lanes`, RECONCILE_WORDS, the columns named in `also`, and the four
    predicates as bools under _PREDICATES' names. The predicates share a
    word: at 13 lanes with the write lane's four columns the matrix is
    23 wide, within the 24 a round of 2^20 gathers at full speed."""
    cols = {k: operands[k]
            for k in ("lanes",) + RECONCILE_WORDS + tuple(also)}
    cols["bits"] = sum(
        (operands[column] == yes).astype(jnp.uint32) << i
        for i, (column, yes) in enumerate(_PREDICATES.values()))
    s = gather_rows(cols, perm)
    bits = s.pop("bits")
    for i, name in enumerate(_PREDICATES):
        s[name] = ((bits >> i) & 1) != 0
    return s


def prev_eq(a):
    """a shifted by one (first element compares unequal)."""
    return jnp.concatenate([jnp.full((1,), ~a[0], dtype=a.dtype), a[:-1]])


# ------------------------------------- operands + host materialisation --

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
    """Pack a CellBatch into the kernel's padded uint32 operand arrays,
    host (numpy) arrays: the caller puts them on its device."""
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
        "lanes": lanes, "valid": valid, "ts_h": ts_h, "ts_l": ts_l,
        "death": death, "cdel": cdel, "ldt": ldt, "expiring": expiring,
        "purge_h": purge_h, "purge_l": purge_l,
        "gc_before": np.int32(gc_before), "now": np.int32(now),
    }


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
                  pts_sorted: np.ndarray | None) -> None:
    """Resolve equal-(identity, ts) runs with exact Cells.resolveRegular
    rules (db/rows/Cells.java:79, CASSANDRA-14592): expiring-or-tombstone
    beats live, pure tombstone beats expiring, larger localDeletionTime,
    larger value bytes, then first-seen. Mutates `keep` in place. Arrays
    are in SORTED order; perm_real maps sorted position -> index into
    `cat`. Shared by the single-device and the mesh-sharded paths."""
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
        best = max(range(lo, hi + 1),
                   key=lambda i: (bool(eot[i]), bool(pure_death[i]),
                                  int(ldt_sorted[i]), orig_value(i)))
        keep[lo:hi + 1] = False
        purgeable = pts_sorted is None or ts_sorted[best] < pts_sorted[best]
        purged = bool(death_eff[best]) and ldt_sorted[best] < gc_before \
            and purgeable
        keep[best] = not (shadowed[best] or purged)
