"""Device-resident compaction rounds: merge → purge → segment-cut →
serialize without bouncing cell columns through the host.

LUDA (PAPERS.md, arxiv 2004.03054) gets its GPU-LSM win by keeping cell
data accelerator-resident across decode → merge → pack instead of
round-tripping the host per stage. This module is that mode for the
device merge engine: one fused program per round runs the LSD sort, the
reconcile/purge masks AND the kept-cell compaction (stable partition +
column gather) on the device. Outside the sort's passes the program
holds TWO gathers, both of rows (ops/merge.py `gather_rows`: a gather is
paid per index — 5.3 ms a lane, 5.8 ms a 26-wide row at 2^19 — and the
twenty this program once held were 156 of a round's 194 ms): every column
reconcile or the serializer reads, through the sort's permutation; then
the serialize-side columns, that permutation and the expired bit, through
the kept-first order. So the CellBatch's fixed-width columns
(lanes / ts / ldt / ttl / flags / frame offsets) never come back to the
host as columns. They stay resident in a device-side pending buffer
across rounds; segment cuts slice them on-device; and a second fused
kernel serializes each full segment's META block (including the "ce"
ts-delta pre-transform, format.py) byte-identically to the host
serializer (storage/sstable/writer.py build_meta_block). The host
receives only the FINISHED blocks the compress pool consumes — the
META bytes and the row-major LANES matrix `segment_pack` wants — plus
the variable-length payload, which never went to the device (ragged
bytes gather through the native C++ path, storage/cellbatch.py).

This is the ONE jitted merge program compaction dispatches
(`_resident_program`, kernel name `merge.resident`), and this module owns
the submit/collect pair around it: `submit_merge_resident` dispatches a
round, `collect_merge_resident` hands the write lane a DeviceRound, and
`materialize_round` hands back a host CellBatch from the same program's
permutation and masks — what the mesh lanes (compaction/task.py
`_mesh_produce`, one device per lane) and `merge_sorted_device` take.

Byte identity with the serial host path is absolute, not statistical:
rounds the device cannot reproduce exactly leave the resident lane per
ROUND (each counted, `compaction.device_resident_fallback`) —

  * equal-(identity, ts) duplicate runs (the device sort does not order
    the Cells.resolveRegular tie-break lanes; the host resolves them
    with full values): `materialize_round` on the program's own outputs,
  * counter cells / range-tombstone bounds (host-only reconcile) and
    frames past the u32 offset lanes, which the program cannot encode:
    the numpy spec, `cellbatch.merge_sorted`, merges the round
    (`_host_round`; also counted `compaction.device_host_rounds`),

and `scripts/check_compaction_ab.py`'s device legs pin the whole-file
sha256 equality. The scalar count of the first condition is computed in
the same fused program, so the decision costs one tiny transfer.

Kept expired-TTL cells stay resident: the tombstone conversion is column
arithmetic (the value is the tail of a cell's frame, so `flags8 |=
FLAG_TOMBSTONE` and frame length = header length drop it), done in the
program; the host's payload gather then copies the shortened frames
(counted, `compaction.device_expired_converted`).
"""
from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np

from ..storage.cellbatch import (DEATH_FLAGS, FLAG_COUNTER,
                                 FLAG_RANGE_BOUND, FLAG_TOMBSTONE, CellBatch,
                                 merge_sorted)
from ..service.metrics import GLOBAL as _METRICS
from ..service.profiling import GLOBAL as _kprof
from ..utils import pipeline_ledger
from ..utils.logonce import warn_once
from . import device_compress
from . import merge as dmerge

_log = logging.getLogger(__name__)

_U32 = jnp.uint32
_BIAS_H = 0x80000000  # high u32 word of the 2^63 timestamp bias

# pipeline `merge`/`resident`: the host side of a resident round — busy =
# concat + operand pack + dispatch + payload gather, stall = blocked on
# the device result (docs/observability.md, span catalogue)
_LED_RESIDENT = pipeline_ledger.ledger("merge").stage("resident")


# ------------------------------------------------------------- operands --

def build_resident_operands(cat: CellBatch, gc_before: int, now: int,
                            purgeable_ts_fn):
    """The kernel operands (merge.build_operands) extended with the
    serialize-side columns: full flags byte, ttl, u32 frame lengths and
    value offsets. Returns (operands, pts_host) or None when a frame
    exceeds the u32 lanes (the host path raises its loud error
    instead)."""
    n = len(cat)
    N = dmerge._bucket(n)
    lens64 = cat.off[1:] - cat.off[:-1]
    vrel64 = cat.val_start - cat.off[:-1]
    if n and (int(lens64.max()) >= 1 << 32
              or int(vrel64.max()) >= 1 << 32):
        return None
    pts_host = None
    if purgeable_ts_fn is not None:
        pts_host = purgeable_ts_fn(cat).astype(np.int64)
        fn = lambda _c: pts_host
    else:
        fn = None
    operands = dmerge.build_operands(cat, gc_before=gc_before, now=now,
                                     purgeable_ts_fn=fn, bucket=N)
    flags8 = np.zeros(N, dtype=np.uint8)
    flags8[:n] = cat.flags
    ttl = np.zeros(N, dtype=np.int32)
    ttl[:n] = cat.ttl
    fl = np.zeros(N, dtype=np.uint32)
    fl[:n] = lens64.astype(np.uint32)
    vr = np.zeros(N, dtype=np.uint32)
    vr[:n] = vrel64.astype(np.uint32)
    operands["flags8"] = jnp.asarray(flags8)
    operands["ttl"] = jnp.asarray(ttl)
    operands["fl"] = jnp.asarray(fl)
    operands["vr"] = jnp.asarray(vr)
    return operands, pts_host


RESIDENT_COLS = ("lanes", "ts_h", "ts_l", "ldt", "ttl", "flags8",
                 "fl", "vr")
# those of them reconcile does not read: they ride its row gather
_SERIALIZE_ONLY = tuple(k for k in RESIDENT_COLS
                        if k not in ("lanes",) + dmerge.RECONCILE_WORDS)


@jax.jit
def _resident_program(operands):
    """One dispatch: LSD sort (only the passes whose key varies in this
    round), reconcile+purge, kept-cell compaction, column gather and the
    expired -> tombstone conversion — the merged round stays on the
    device, in output order, kept cells first.
    Returns (n_keep, n_amb, n_exp_kept, n_passes, perm_out, cols, perm,
    packed); n_passes is how many sort passes the round ran, and the last
    two feed the host fallback when n_amb demands it."""
    # named_scope: metadata only (op names in a profiler trace), the
    # program and its bytes are unchanged
    with jax.named_scope("sort"):
        perm, n_passes = dmerge.device_sort_perm(operands)
    with jax.named_scope("reconcile"):   # its purge stage names itself
        # ONE row gather through perm carries what reconcile reads AND
        # the serialize-side columns the next stage takes from it
        s = dmerge.reconcile_columns(operands, perm, also=_SERIALIZE_ONLY)
        packed = dmerge.reconcile_sorted(s, operands["now"],
                                         operands["gc_before"])
    with jax.named_scope("compact"):
        keep = (packed & 1) != 0
        amb = (packed & 2) != 0
        expired = (packed & 4) != 0
        n_keep = jnp.sum(keep).astype(jnp.int32)
        n_amb = jnp.sum(amb).astype(jnp.int32)
        n_exp_kept = jnp.sum(expired & keep).astype(jnp.int32)
        N = keep.shape[0]
        # stable partition: kept cells to the front, SORTED ORDER
        # preserved (stability) — the device-side analog of
        # np.flatnonzero(keep)
        _, ord_ = jax.lax.sort(
            (jnp.where(keep, jnp.uint32(0), jnp.uint32(1)),
             jnp.arange(N, dtype=jnp.int32)), num_keys=1, is_stable=True)
        # the second and last row gather: the sorted columns, perm and
        # the expired bit through ord_ (s[ord_] is operands[perm[ord_]])
        cols = dmerge.gather_rows(
            {**{k: s[k] for k in RESIDENT_COLS},
             "perm": perm, "expired": expired}, ord_)
        perm_out, exp_out = cols.pop("perm"), cols.pop("expired")
    with jax.named_scope("convert"):
        # an expired cell that is kept becomes a tombstone without its
        # value (finalize_merged's flags |= FLAG_TOMBSTONE + drop_values):
        # the value is the frame's tail, so the frame shrinks to its
        # header; ldt and ttl stay
        cols["flags8"] = jnp.where(
            exp_out, cols["flags8"] | jnp.uint8(FLAG_TOMBSTONE),
            cols["flags8"])
        cols["fl"] = jnp.where(exp_out, cols["vr"], cols["fl"])
    return n_keep, n_amb, n_exp_kept, n_passes, perm_out, cols, perm, packed


# ------------------------------------------------------ serialize kernel --

@jax.jit
def _meta_block_kernel(ts_h, ts_l, ldt, ttl, flags8, fl, vr):
    """Fused META-block serialize for one FULL segment: the "ce"
    ts-delta pre-transform + the 25 B/cell section layout emitted as
    one u8 buffer, plus the segment's stats reductions — all in a
    single device program, byte-identical to the host
    build_meta_block (pinned by test).

    ts planes arrive BIASED (uts = ts + 2^63 mod 2^64, the sort form);
    bias cancels in differences, so the wraparound deltas of the u32
    pairs ARE the i64 deltas, and cell 0's absolute stamp is its uts
    minus the bias — one XOR on the high word."""
    n = ts_h.shape[0]
    with jax.named_scope("timestamps"):
        prev_h = jnp.concatenate(
            [jnp.full((1,), _BIAS_H, dtype=jnp.uint32), ts_h[:-1]])
        prev_l = jnp.concatenate(
            [jnp.zeros(1, dtype=jnp.uint32), ts_l[:-1]])
        d_l = ts_l - prev_l
        borrow = (ts_l < prev_l).astype(jnp.uint32)
        d_h = ts_h - prev_h - borrow

    def le_bytes(words, per_cell):
        """Little-endian bytes of `per_cell` u32 word planes per cell,
        interleaved cell by cell. Written as a gather over a byte iota
        — byte j is a shift of word j>>2 — because the direct form
        (bitcast to (n, 4) u8, then flatten) merges a 4-wide minor
        dimension, which the TPU compiler unrolls: 18 s of compile per
        section at 65,536 cells against 1 s for this (CHANGES.md
        PR 21). Same bytes."""
        j = jnp.arange(4 * per_cell * n, dtype=jnp.int32)
        w = j >> 2
        cell = w // per_cell
        word = words[0][cell]
        for k in range(1, per_cell):
            word = jnp.where(w % per_cell == k, words[k][cell], word)
        shift = (j & 3).astype(jnp.uint32) << 3
        return ((word >> shift) & jnp.uint32(0xFF)).astype(jnp.uint8)

    def u32_bytes(a):
        return le_bytes(
            [jax.lax.bitcast_convert_type(a, jnp.uint32)], 1)

    with jax.named_scope("timestamps"):
        ts_bytes = le_bytes([d_l, d_h], 2)
    with jax.named_scope("lengths"):
        meta = jnp.concatenate([
            ts_bytes, u32_bytes(ldt), u32_bytes(ttl), flags8,
            u32_bytes(fl), u32_bytes(vr)])

    # stats reductions (biased-pair lexicographic min/max for ts)
    with jax.named_scope("stats"):
        max_h = jnp.max(ts_h)
        max_l = jnp.max(jnp.where(ts_h == max_h, ts_l, jnp.uint32(0)))
        min_h = jnp.min(ts_h)
        min_l = jnp.min(jnp.where(ts_h == min_h, ts_l,
                                  _U32(0xFFFFFFFF)))
        tombs = jnp.sum((flags8 & jnp.uint8(DEATH_FLAGS)) != 0)
        stats = (min_h, min_l, max_h, max_l,
                 jnp.min(ldt), jnp.max(ldt), tombs)
    return meta, stats


def _uts_pair_to_i64(h: int, l: int) -> int:
    return int(np.int64(np.uint64((int(h) << 32) | int(l))
                        ^ np.uint64(1 << 63)))


# --------------------------------------------------------------- rounds --

class DeviceRound:
    """One merged round whose fixed-width columns live on the device
    (padded; `n` is the kept length). The payload side — the only
    ragged data — stays host-resident: gathering variable-length frames
    is exactly what the native C++ gather does well and what device
    memory layouts do badly."""

    __slots__ = ("n", "cols", "payload", "off", "val_start", "pk_map",
                 "ck_fits_prefix")

    def __init__(self, n, cols, payload, off, val_start, pk_map,
                 ck_fits_prefix):
        self.n = n
        self.cols = cols
        self.payload = payload
        self.off = off
        self.val_start = val_start
        self.pk_map = pk_map
        self.ck_fits_prefix = ck_fits_prefix

    def __len__(self) -> int:
        return self.n


class ResidentHandle:
    """An in-flight round: mode "resident" (the program was dispatched,
    `out` holds its outputs) or "done" (`result` already is the merged
    host CellBatch: an empty round, or one the numpy spec merged)."""

    __slots__ = ("mode", "result", "cat", "n", "out", "pts",
                 "gc_before", "now", "prof")


def _resident_fallback(n: int, why: str) -> None:
    """A round that leaves the resident lane for the host
    materialization: same bytes, but the device did not do the work —
    counted, and its cause logged once."""
    _METRICS.incr("compaction.device_resident_fallback")
    warn_once(_log, f"resident.fallback.{why}",
              "device-resident round (%d cells) materialized on the "
              "host: %s", n, why)


def _host_round(h: ResidentHandle, batches: list[CellBatch],
                purgeable_ts_fn, why: str) -> ResidentHandle:
    """A round the program cannot encode: merged synchronously by the
    numpy spec, and COUNTED twice over — it left the resident lane, and
    the device did none of its merge. A device-engine compaction that
    quietly ran on the host is a misread benchmark."""
    _resident_fallback(h.n, why)
    _METRICS.incr("compaction.device_host_rounds")
    warn_once(_log, f"merge.host_round.{why}",
              "device merge round (%d cells) ran on the host: %s",
              h.n, why)
    h.mode = "done"
    h.result = merge_sorted(batches, h.gc_before, h.now, purgeable_ts_fn)
    return h


# test seam: {round_seq: seconds} delay applied at collect time BEFORE
# the device result is consumed — reverses the completion order of
# in-flight rounds (tests/test_device_resident.py); None in production.
_TEST_COLLECT_DELAY = None
_collect_seq = 0


def _note_passes(sp, h: ResidentHandle) -> None:
    """Read how many sort passes a FINISHED round ran (one more scalar of
    the pull that waited for it) into the wait span — items = the passes
    the sort has, cells = the passes this round ran — and the counters."""
    sp.items = dmerge.n_sort_keys(h.cat.n_lanes)
    sp.cells = int(h.out[3])
    _METRICS.incr("merge.resident.passes_run", sp.cells)
    _METRICS.incr("merge.resident.passes_skipped", sp.items - sp.cells)


def submit_merge_resident(batches: list[CellBatch], gc_before: int = 0,
                          now: int = 0, purgeable_ts_fn=None,
                          prof: dict | None = None,
                          device=None) -> ResidentHandle:
    """Dispatch one device-resident round (async). Rounds the program
    cannot encode (counters, range bounds, oversized frames) are merged
    here by the numpy spec instead (_host_round — counted).

    device: an explicit jax.Device to commit the operands to (the mesh
    compaction path places shard s's round on mesh device s); None =
    the default device."""
    h = ResidentHandle()
    h.gc_before, h.now, h.prof = gc_before, now, prof
    with _LED_RESIDENT.busy("merge.resident.concat") as sp:
        cat = CellBatch.concat(batches)
        sp.cells = len(cat)
    h.cat, h.n = cat, len(cat)
    if h.n == 0:
        h.mode, h.result = "done", cat
        return h
    if ((cat.flags & (FLAG_RANGE_BOUND | FLAG_COUNTER)) != 0).any():
        # range tombstone coverage is evaluated on full composites and
        # counters reconcile by summation: host-only passes
        return _host_round(h, batches, purgeable_ts_fn,
                           "counters or range tombstone bounds")
    with _LED_RESIDENT.busy("merge.resident.pack", prof=prof, key="pack",
                            cells=h.n) as sp:
        built = build_resident_operands(cat, gc_before, now,
                                        purgeable_ts_fn)
        if built is not None:
            operands, h.pts = built
            if device is not None:
                operands = {k: jax.device_put(v, device)
                            for k, v in operands.items()}
            # items = the padded cell count the program runs at
            # (_bucket), nbytes = what the round pushes to the device
            sp.items = int(operands["lanes"].shape[0])
            sp.nbytes = sum(int(v.nbytes) for v in operands.values())
    if built is None:   # >= 4 GiB frame: let the host path fail loudly
        return _host_round(h, batches, purgeable_ts_fn,
                           "frame exceeds the u32 offset lane")
    with _LED_RESIDENT.busy("merge.resident.dispatch") as sp:
        h.out = _resident_program(operands)
    # jit compiles synchronously inside the dispatch call, per shape AND
    # per device: the lane's device is part of the key, or lanes 2..n's
    # compiles read as warm dispatches
    if _kprof.record_dispatch(
            "merge.resident",
            (int(operands["lanes"].shape[0]),
             int(operands["lanes"].shape[1]),
             getattr(device, "id", None)), sp.seconds):
        _kprof.maybe_record_cost("merge.resident", _resident_program,
                                 (operands,))
    h.mode = "resident"
    return h


def collect_merge_resident(h: ResidentHandle):
    """Block on a resident round. Returns a DeviceRound (columns still
    on device) for rounds the device reproduced exactly, else a host
    CellBatch computed through the pinned byte-identical fallback."""
    import time as _time

    global _collect_seq
    if _TEST_COLLECT_DELAY is not None:
        _time.sleep(_TEST_COLLECT_DELAY.get(_collect_seq, 0.0))
    _collect_seq += 1
    if h.mode == "done":
        return promote_round(h.result)
    cat, prof = h.cat, h.prof
    n_keep_d, n_amb_d, n_exp_d, _, perm_out_d, cols = h.out[:6]
    with _LED_RESIDENT.stall("merge.resident.wait", prof=prof,
                             key="device") as sp:
        n_keep = int(n_keep_d)      # blocks until the program finishes
        n_amb = int(n_amb_d)
        n_exp_kept = int(n_exp_d)
        _note_passes(sp, h)
    _kprof.record_execute("merge.resident", sp.seconds)

    # cells = the cells the round kept, items = the cells it read
    with _LED_RESIDENT.busy("merge.resident.gather", prof=prof,
                            key="gather", cells=n_keep, items=h.n):
        if n_amb:
            # exact-resolution round: equal-(identity, ts) runs need the
            # host's full-value tie-break
            _resident_fallback(h.n, "equal-(identity, ts) ties")
            return promote_round(_materialize(h))

        # resident round: pull ONLY the kept permutation (the payload
        # gather's index vector) — the columns stay on the device
        perm_kept = np.asarray(perm_out_d).astype(np.int64)[:n_keep]
        lens = None
        if n_exp_kept:
            # the program converted kept expired cells: their frames are
            # the headers now, and the converted length column says so
            _METRICS.incr("compaction.device_expired_converted",
                          n_exp_kept)
            lens = np.asarray(cols["fl"]).astype(np.int64)[:n_keep]
        payload, off, val_start = _gather_payload(cat, perm_kept, lens)
        return DeviceRound(n_keep, cols, payload, off, val_start,
                           dict(cat.pk_map), cat.ck_fits_prefix)


def _materialize(h: ResidentHandle) -> CellBatch:
    """The merged round as a host CellBatch, from a FINISHED program's
    full permutation and packed masks: exact tie-breaks with full values,
    then the payload gather and expired -> tombstone conversion."""
    cat, n = h.cat, h.n
    perm_d, packed_d = h.out[-2:]
    perm = np.asarray(perm_d).astype(np.int64)[:n]
    keep, amb, expired, shadowed = dmerge.unpack_masks(
        np.asarray(packed_d)[:n])
    pts_sorted = h.pts[perm] if h.pts is not None else None
    if amb.any():
        dmerge.host_tiebreak(cat, perm, keep, amb, shadowed, expired,
                             h.gc_before, pts_sorted)
    return dmerge.finalize_merged(cat, perm, keep, expired, shadowed)


def materialize_round(h: ResidentHandle) -> CellBatch:
    """Block on a submitted round and return it as a host CellBatch —
    the collect of callers with no write lane: the mesh lanes (their
    shards drain through the host writer in token order) and
    merge_sorted_device. The kept-cell compaction and column gather the
    program also did go unused here; that is the price of one program."""
    if h.mode == "done":
        return h.result
    with _LED_RESIDENT.stall("merge.resident.wait", prof=h.prof,
                             key="device") as sp:
        jax.block_until_ready(h.out[-2:])
        _note_passes(sp, h)
    _kprof.record_execute("merge.resident", sp.seconds)
    with _LED_RESIDENT.busy("merge.resident.gather", prof=h.prof,
                            key="gather", cells=h.n):
        return _materialize(h)


def merge_sorted_device(batches: list[CellBatch], gc_before: int = 0,
                        now: int = 0, purgeable_ts_fn=None,
                        prof: dict | None = None) -> CellBatch:
    """Drop-in equivalent of storage.cellbatch.merge_sorted running the
    sort/reconcile on the default JAX device. `prof` (optional)
    accumulates per-phase wall seconds: pack / device / gather."""
    return materialize_round(submit_merge_resident(
        batches, gc_before, now, purgeable_ts_fn, prof))


def promote_round(batch: CellBatch) -> DeviceRound:
    """Lift a host-materialized round (fallback rounds: ties, counters,
    range bounds) onto the device so the write lane consumes ONE
    ordered stream — interleaving host appends with device-pending
    cells would cut segments out of order. Values are
    copied verbatim, so the serialized bytes are identical to feeding
    the batch through the host writer."""
    n = len(batch)
    lens64 = batch.off[1:] - batch.off[:-1]
    vrel64 = batch.val_start - batch.off[:-1]
    if n and (int(lens64.max()) >= 1 << 32
              or int(vrel64.max()) >= 1 << 32):
        # mirror the host serializer's loud failure (writer._cut_segment)
        raise ValueError(
            f"cell frame exceeds the u32 offset lane "
            f"(max frame {int(lens64.max())} bytes)")
    with np.errstate(over="ignore"):
        uts = batch.ts.astype(np.uint64) ^ np.uint64(1 << 63)
    cols = {
        "lanes": jnp.asarray(np.ascontiguousarray(batch.lanes)),
        "ts_h": jnp.asarray((uts >> np.uint64(32)).astype(np.uint32)),
        "ts_l": jnp.asarray((uts & np.uint64(0xFFFFFFFF))
                            .astype(np.uint32)),
        "ldt": jnp.asarray(batch.ldt.astype(np.int32, copy=False)),
        "ttl": jnp.asarray(batch.ttl.astype(np.int32, copy=False)),
        "flags8": jnp.asarray(batch.flags.astype(np.uint8, copy=False)),
        "fl": jnp.asarray(lens64.astype(np.uint32)),
        "vr": jnp.asarray(vrel64.astype(np.uint32)),
    }
    return DeviceRound(n, cols, np.asarray(batch.payload),
                       np.asarray(batch.off, dtype=np.int64),
                       np.asarray(batch.val_start, dtype=np.int64),
                       dict(batch.pk_map), batch.ck_fits_prefix)


def _gather_payload(cat: CellBatch, perm: np.ndarray,
                    lens: np.ndarray | None = None):
    """Host-side ragged payload gather (the one part of the round that
    never went to the device) — same native path apply_permutation
    uses, without touching the fixed-width columns. lens: the bytes to
    copy from the head of each gathered frame where that is not the
    whole frame (cells the program converted to tombstones keep their
    header and lose their value)."""
    from ..storage.cellbatch import _native_gather
    n = len(perm)
    starts = cat.off[:-1][perm]
    if lens is None:
        lens = (cat.off[1:] - cat.off[:-1])[perm]
    new_off = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lens, out=new_off[1:])
    total = int(new_off[-1])
    if total:
        payload = _native_gather(cat.payload, cat.off, perm, new_off)
        if payload is None:
            pos_in_cell = np.arange(total, dtype=np.int64) - \
                np.repeat(new_off[:-1], lens)
            payload = cat.payload[np.repeat(starts, lens) + pos_in_cell]
    else:
        payload = np.zeros(0, dtype=np.uint8)
    val_start = new_off[:-1] + (cat.val_start - cat.off[:-1])[perm]
    return payload, new_off, val_start


# ----------------------------------------------------------- write lane --

class DeviceWriteLane:
    """The device-resident write stage: accumulates rounds' columns in
    a device pending buffer, cuts segments on-device, serializes each
    full segment's META block with the fused kernel and hands the
    writer only finished blocks (writer._emit_segment — the exact tail
    the host path runs after its own serialize). The final partial
    segment assembles through the host build_meta_block on pulled
    column slices: one segment per output, and bit-equality with the
    kernel is the pinned contract, not an optimization target."""

    def __init__(self, writer):
        from ..storage.sstable.format import SEGMENT_CELLS
        self.writer = writer
        self.seg_cells = writer.segment_cells or SEGMENT_CELLS
        self.cols: dict | None = None     # device pending columns
        self.pending = 0                  # valid cells in self.cols
        self.payloads: list = []          # (payload, off, val_start)
        self.payload_cells = 0
        self.pk_map: dict = {}

    def append(self, r: DeviceRound) -> None:
        w = self.writer
        with w._span("serialize", "busy", "write.lane.append",
                     key="serialize", cells=r.n) as sp:
            if w.K is None:
                w.K = int(r.cols["lanes"].shape[1])
            w._ck_fits = w._ck_fits and r.ck_fits_prefix
            take = {k: v[:r.n] for k, v in r.cols.items()}
            sp.items = len(take)       # eager programs dispatched
            if self.cols is None or self.pending == 0:
                self.cols = take
            else:
                self.cols = {k: jnp.concatenate(
                    [self.cols[k][:self.pending], take[k]])
                    for k in RESIDENT_COLS}
                sp.items += 2 * len(RESIDENT_COLS)
            self.pending += r.n
            self.payloads.append((r.payload, r.off, r.val_start))
            self.payload_cells += r.n
            for k, v in r.pk_map.items():
                self.pk_map[k] = v
        while self.pending >= self.seg_cells:
            self._cut(self.seg_cells)

    def flush(self) -> None:
        """Cut everything left (the final partial segment) — the
        device-mode analog of finish()'s pending drain; call before
        writer.finish()/roll."""
        while self.pending >= self.seg_cells:
            self._cut(self.seg_cells)
        if self.pending:
            self._cut(self.pending)

    # ------------------------------------------------------------ internals

    def _take_payload(self, n: int):
        """Pop n cells' worth of payload frames (host side), mirroring
        SSTableWriter._take's slicing."""
        outs, got = [], 0
        while got < n:
            payload, off, val_start = self.payloads[0]
            avail = len(off) - 1
            need = n - got
            if avail <= need:
                outs.append((payload, off, val_start))
                self.payloads.pop(0)
                got += avail
            else:
                base = int(off[need])
                outs.append((payload[:base], off[:need + 1],
                             val_start[:need]))
                self.payloads[0] = (payload[base:], off[need:] - base,
                                    val_start[need:] - base)
                got = n
        self.payload_cells -= n
        if len(outs) == 1:
            payload, off, _vs = outs[0]
            return np.ascontiguousarray(payload[:int(off[-1])])
        return np.concatenate([payload[:int(off[-1])]
                               for payload, off, _vs in outs])

    def _cut(self, n: int) -> None:
        w = self.writer
        span = pipeline_ledger.span
        full = n == self.seg_cells
        # one `serialize` span per segment; its parts are children that
        # bill nothing themselves (ring + trace only)
        with w._span("serialize", "busy", "write.lane.cut",
                     key="serialize", cells=n):
            with span("write.lane.cut.slice",
                      items=2 * len(RESIDENT_COLS)):
                seg = {k: self.cols[k][:n] for k in RESIDENT_COLS}
                self.cols = {k: self.cols[k][n:] for k in RESIDENT_COLS}
                self.pending -= n
            with span("write.lane.cut.pull_lanes") as sp:
                lanes_np = np.ascontiguousarray(np.asarray(seg["lanes"]))
                sp.nbytes = lanes_np.nbytes
            if full:
                # full segment: the fused kernel serializes + reduces
                # stats in one device program; the host sees finished
                # bytes
                kargs = (seg["ts_h"], seg["ts_l"], seg["ldt"], seg["ttl"],
                         seg["flags8"], seg["fl"], seg["vr"])
                with span("write.lane.cut.kernel_dispatch") as sp:
                    meta_d, st = _meta_block_kernel(*kargs)
                if _kprof.record_dispatch("write.serialize", (n,),
                                          sp.seconds):
                    _kprof.maybe_record_cost(
                        "write.serialize", _meta_block_kernel, kargs)
                with span("write.lane.cut.kernel_pull") as sp:
                    meta = np.asarray(meta_d)   # blocks on the kernel
                    stats = (_uts_pair_to_i64(st[0], st[1]),
                             _uts_pair_to_i64(st[2], st[3]),
                             int(st[4]), int(st[5]), int(st[6]))
                    sp.nbytes = meta.nbytes + 4 * len(st)
                _kprof.record_execute("write.serialize", sp.seconds)
            else:
                # final partial segment: host assembly through the one
                # shared META builder (byte-identical layout by
                # definition)
                from ..storage.sstable.writer import build_meta_block
                with span("write.lane.cut.host_meta"):
                    h = np.asarray(seg["ts_h"]).astype(np.uint64)
                    l = np.asarray(seg["ts_l"]).astype(np.uint64)
                    ts = ((h << np.uint64(32)) | l) ^ np.uint64(1 << 63)
                    ts = ts.astype(np.int64)
                    ldt = np.asarray(seg["ldt"])
                    ttl = np.asarray(seg["ttl"])
                    flags = np.asarray(seg["flags8"])
                    meta = build_meta_block(
                        ts, ldt, ttl, flags,
                        np.asarray(seg["fl"]).astype("<u4"),
                        np.asarray(seg["vr"]).astype("<u4"))
                    stats = (int(ts.min()), int(ts.max()),
                             int(ldt.min()), int(ldt.max()),
                             int(((flags & DEATH_FLAGS) != 0).sum()))
            with span("write.lane.cut.payload") as sp:
                payload_np = self._take_payload(n)
                sp.nbytes = payload_np.nbytes
        dc_state = None
        if full and w._device_compress_now():
            # second fused program: lane shuffle + order check + the
            # policy match scans; the host keeps only the LZ4 wire
            # emission (O(sequences)) and the pwrite pump
            with w._span("compress", "busy", "write.lane.device_compress",
                         key="compress", cells=n):
                dc_state = self._device_compress(n, meta_d, seg["lanes"])
        device_pack = None
        if dc_state is not None:
            planes_np, scans = dc_state

            def device_pack(attempt, maxlen, _m=meta, _p=planes_np,
                            _s=scans, _pl=payload_np):
                return device_compress.pack_device_segment(
                    _m, _p, _s, _pl, attempt, maxlen)
        w._emit_segment(n, meta, lanes_np, payload_np, self.pk_map,
                        stats, device_pack=device_pack)

    @staticmethod
    def _device_compress(n: int, meta_d, lanes_d):
        """(planes, scans) of one full segment compressed on the device,
        or None when the kernel failed and the host compress leg takes
        the segment (counted; output bytes identical either way)."""
        span = pipeline_ledger.span
        try:
            with span("write.lane.device_compress.dispatch") as sp:
                planes_d, mbl, mbd, lbl, lbd, order_ok = \
                    device_compress.segment_scan_kernel(meta_d, lanes_d)
            if _kprof.record_dispatch("write.compress", (n,), sp.seconds):
                _kprof.maybe_record_cost(
                    "write.compress",
                    device_compress.segment_scan_kernel,
                    (meta_d, lanes_d))
            with span("write.lane.device_compress.pull") as sp:
                ok = bool(order_ok)
                planes_np = np.asarray(planes_d)
                scans = ((np.asarray(mbl), np.asarray(mbd)),
                         (np.asarray(lbl), np.asarray(lbd)))
            _kprof.record_execute("write.compress", sp.seconds)
        except Exception as e:
            from ..service.metrics import GLOBAL as _METRICS
            _METRICS.incr("compaction.device_compress_fallback")
            warn_once(_log, "write.compress.fallback",
                      "device compress kernel failed, host "
                      "compress leg takes the segment: %r", e)
            return None
        if not ok:
            raise ValueError("appended cells out of order")
        return planes_np, scans
