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

Outputs are a permutation + keep mask; the host applies them to the
variable-length payload with numpy gathers (storage/cellbatch.py).
Shapes are padded to buckets so programs are traced once per bucket size.

This module holds the kernel's PARTS. The one jitted program compaction
dispatches, and the submit/collect pair around it, live in
ops/device_write.py (`_resident_program`, kernel name `merge.resident`);
`merge_reconcile_kernel` is the bare sort + reconcile form the driver
entry and the parallel/mesh.py shard_map bodies trace.
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
    end (a mesh shard's valid rows start anywhere). `valid` itself is
    always sorted: it is what moves the padding behind the cells.

    Works eagerly and under an enclosing jit/shard_map (nested jit
    inlines).

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
    perm, _ = _traced_sort_perm(operands)
    packed = reconcile_kernel(operands, perm)
    return perm, packed


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
