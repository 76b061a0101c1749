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
host as columns. The write lane (`DeviceWriteLane`) runs two more
jitted programs, and nothing eager: per round `_lane_append` lays the
round behind the device-resident carry of cells earlier rounds left
over and cuts the full segments out of the pair; per full segment
`_meta_block_kernel` computes what its META block needs computed — the
"ce" ts-delta pre-transform (format.py) as u32 word planes, and the
segment's stats — and lays out no bytes. The offsets are traced, so the
shapes depend on the round's bucket, the lanes and the segment size,
never on how many cells a round kept. The host receives, in one pull a
segment, the kernel's word planes and stats, the segment's flags bytes
and the row-major LANES matrix `segment_pack` wants, and writes the
META block through the one layout definition
(storage/sstable/writer.py lay_meta_block, which the host serializer's
build_meta_block writes through too) — plus the variable-length
payload, which never went to the device (ragged bytes gather through
the native C++ path, storage/cellbatch.py).

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
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..storage.cellbatch import (DEATH_FLAGS, FLAG_COUNTER,
                                 FLAG_RANGE_BOUND, FLAG_TOMBSTONE, CellBatch,
                                 merge_sorted)
from ..storage.sstable.writer import build_meta_block, lay_meta_block
from ..service.metrics import GLOBAL as _METRICS
from ..service.profiling import GLOBAL as _kprof
from ..service.profiling import device_id
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


def _device_tag(dev: int) -> str:
    """The last component of a per-device counter: `dev<id>`."""
    return f"dev{dev}"


# ------------------------------------------------------------- operands --

def build_resident_operands(cat: CellBatch, gc_before: int, now: int,
                            purgeable_ts_fn, min_bucket: int = 0,
                            device=None):
    """The kernel operands (merge.build_operands) extended with the
    serialize-side columns: full flags byte, ttl, u32 frame lengths and
    value offsets, padded to the round's bucket and to at least
    min_bucket cells, pushed to `device` in one transfer (None = jax's
    default device). Returns (operands, pts_host) or None when a frame
    exceeds the u32 lanes (the host path raises its loud error
    instead)."""
    n = len(cat)
    N = max(dmerge._bucket(n), min_bucket)
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
    operands.update(flags8=flags8, ttl=ttl, fl=fl, vr=vr)
    return jax.device_put(operands, device), pts_host


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
        perm, n_passes = dmerge._traced_sort_perm(operands)
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
def _lane_append(carry, cols, rem, n):
    """The write lane's one program a round. `carry` holds the cells
    left over from earlier rounds, right-aligned: its last `rem` rows
    (rem < seg, its row count). `cols` is the round, kept cells first
    and padded to its bucket N. Behind the carry the round's first `n`
    cells continue the stream, so the pair holds (rem + n) // seg full
    segments from row seg - rem on.

    Returns (segments, carry): (seg - 1 + N) // seg column sets of seg
    rows each, the j-th starting at row seg - rem + j·seg (the ones past
    the full count are never read), and the next carry, whose last
    (rem + n) mod seg rows are what is left. Offsets are traced, so the
    shapes depend on N, the lanes and seg alone."""
    seg = carry["lanes"].shape[0]
    N = cols["lanes"].shape[0]
    both = {k: jnp.concatenate([carry[k], cols[k]])
            for k in RESIDENT_COLS}

    def window(start):
        return {k: jax.lax.dynamic_slice_in_dim(v, start, seg)
                for k, v in both.items()}

    segments = [window(seg - rem + j * seg)
                for j in range((seg - 1 + N) // seg)]
    return segments, window(n)


# the columns `_meta_block_kernel` reads, in its argument order
_META_COLS = ("ts_h", "ts_l", "ldt", "ttl", "flags8", "fl", "vr")


@jax.jit
def _meta_block_kernel(ts_h, ts_l, ldt, ttl, flags8, fl, vr):
    """The device's part of one FULL segment's META block: the "ce"
    ts-delta pre-transform and the segment's stats reductions, in one
    program. It lays out no bytes. Returns (words, stats):

      words  ONE u32 vector, six segment-long planes end to end — the
             delta's low and high words, ldt, ttl, frame lengths, value
             offsets (`_meta_from_words` reads it back);
      stats  the seven stats words (`_segment_stats` reads them).

    The host writes the block from these planes and the segment's flags
    byte plane (which needs no arithmetic, so it is pulled from the
    segment's columns) through the one layout definition
    (storage/sstable/writer.py lay_meta_block): byte identity with the
    host serializer holds by construction, and is pinned by test. The
    bytes are not laid out here: interleaving them on the device takes
    a per-byte gather, which the chip pays per index (≈ 15 ms a
    65,536-cell segment on a v5e), for what the host does as one copy.

    ts planes arrive BIASED (uts = ts + 2^63 mod 2^64, the sort form);
    bias cancels in differences, so the wraparound deltas of the u32
    pairs ARE the i64 deltas, and cell 0's absolute stamp is its uts
    minus the bias — one XOR on the high word."""
    with jax.named_scope("timestamps"):
        prev_h = jnp.concatenate(
            [jnp.full((1,), _BIAS_H, dtype=jnp.uint32), ts_h[:-1]])
        prev_l = jnp.concatenate(
            [jnp.zeros(1, dtype=jnp.uint32), ts_l[:-1]])
        d_l = ts_l - prev_l
        borrow = (ts_l < prev_l).astype(jnp.uint32)
        d_h = ts_h - prev_h - borrow
    with jax.named_scope("lengths"):
        # a 1-D concatenate at multiples of the segment: no minor
        # dimension to merge, and one vector is one transfer
        words = jnp.concatenate([
            d_l, d_h, jax.lax.bitcast_convert_type(ldt, jnp.uint32),
            jax.lax.bitcast_convert_type(ttl, jnp.uint32), fl, vr])

    # stats reductions (biased-pair lexicographic min/max for ts)
    with jax.named_scope("stats"):
        max_h = jnp.max(ts_h)
        max_l = jnp.max(jnp.where(ts_h == max_h, ts_l, jnp.uint32(0)))
        min_h = jnp.min(ts_h)
        min_l = jnp.min(jnp.where(ts_h == min_h, ts_l,
                                  _U32(0xFFFFFFFF)))
        tombs = jnp.sum((flags8 & jnp.uint8(DEATH_FLAGS)) != 0)
        stats = jnp.stack([
            min_h, min_l, max_h, max_l,
            jax.lax.bitcast_convert_type(jnp.min(ldt), jnp.uint32),
            jax.lax.bitcast_convert_type(jnp.max(ldt), jnp.uint32),
            tombs.astype(jnp.uint32)])
    return words, stats


def _meta_from_words(words: np.ndarray, flags8: np.ndarray) -> np.ndarray:
    """A full segment's META block, laid out on the host from the
    kernel's pulled word planes and the segment's flags bytes."""
    n = len(flags8)
    lo, hi, ldt, ttl, fl, vr = (words[k * n:(k + 1) * n] for k in range(6))
    return lay_meta_block(lo, hi, ldt, ttl, flags8, fl, vr)


def _uts_pair_to_i64(h: int, l: int) -> int:
    return int(np.int64(np.uint64((int(h) << 32) | int(l))
                        ^ np.uint64(1 << 63)))


def _segment_stats(st: np.ndarray) -> tuple:
    """The kernel's pulled stats vector as the writer's (min ts, max ts,
    min ldt, max ldt, tombstones)."""
    ldt = st[4:6].view(np.int32)
    return (_uts_pair_to_i64(st[0], st[1]), _uts_pair_to_i64(st[2], st[3]),
            int(ldt[0]), int(ldt[1]), int(st[6]))


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
    """An in-flight round: mode "resident" (the program was dispatched
    on device id `dev`, `out` holds its outputs) or "done" (`result`
    already is the merged host CellBatch: an empty round, or one the
    numpy spec merged)."""

    __slots__ = ("mode", "result", "cat", "n", "out", "pts",
                 "gc_before", "now", "prof", "device", "dev")


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


def _wait(h: ResidentHandle) -> tuple[int, int, int]:
    """Block on a dispatched round's program — span `merge.resident.wait`,
    items = the passes the sort has, cells = the passes this round ran,
    the two pass counters, and the registry's execute time. Returns the
    round's (n_keep, n_amb, n_exp_kept); the one pull of its four count
    scalars is the wait."""
    with _LED_RESIDENT.stall("merge.resident.wait", prof=h.prof,
                             key="device") as sp:
        n_keep, n_amb, n_exp_kept, n_run = (
            int(x) for x in jax.device_get(h.out[:4]))
        sp.items = dmerge.n_sort_keys(h.cat.n_lanes)
        sp.cells = n_run
        _METRICS.incr("merge.resident.passes_run", sp.cells)
        _METRICS.incr("merge.resident.passes_skipped", sp.items - sp.cells)
    _kprof.record_execute("merge.resident", sp.seconds)
    return n_keep, n_amb, n_exp_kept


def submit_merge_resident(batches: list[CellBatch], gc_before: int = 0,
                          now: int = 0, purgeable_ts_fn=None,
                          prof: dict | None = None,
                          device=None, min_bucket: int = 0) -> ResidentHandle:
    """Dispatch one device-resident round (async). Rounds the program
    cannot encode (counters, range bounds, oversized frames) are merged
    here by the numpy spec instead (_host_round — counted).

    min_bucket: the least padded shape the program runs at (a partial
    round then shares the full rounds' compiled shapes).

    device: an explicit jax.Device to commit the operands to (a pinned
    engine's device, or mesh device s for shard s's round); None = the
    default device. Each dispatched round counts under
    `merge.resident.rounds.dev<id>`, the id of the device it ran on."""
    h = ResidentHandle()
    h.gc_before, h.now, h.prof, h.device = gc_before, now, prof, device
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
                                        purgeable_ts_fn, min_bucket, device)
        if built is not None:
            operands, h.pts = built
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
    # per device: the device is part of the key, or a second device's
    # compiles read as warm dispatches
    dev = h.dev = device_id(device)
    _METRICS.incr(f"merge.resident.rounds.{_device_tag(dev)}")
    if _kprof.record_dispatch(
            "merge.resident",
            (int(operands["lanes"].shape[0]),
             int(operands["lanes"].shape[1]), dev), sp.seconds, dev):
        _kprof.maybe_record_cost("merge.resident", _resident_program,
                                 (operands,))
    h.mode = "resident"
    return h


def collect_merge_resident(h: ResidentHandle):
    """Block on a resident round. Returns a DeviceRound (columns still
    on device) for rounds the device reproduced exactly, else a host
    CellBatch computed through the pinned byte-identical fallback."""
    global _collect_seq
    if _TEST_COLLECT_DELAY is not None:
        time.sleep(_TEST_COLLECT_DELAY.get(_collect_seq, 0.0))
    _collect_seq += 1
    if h.mode == "done":
        return promote_round(h.result, h.device)
    cat, prof = h.cat, h.prof
    perm_out_d, cols = h.out[4:6]
    n_keep, n_amb, n_exp_kept = _wait(h)

    # cells = the cells the round kept, items = the cells it read
    with _LED_RESIDENT.busy("merge.resident.gather", prof=prof,
                            key="gather", cells=n_keep, items=h.n):
        if n_amb:
            # exact-resolution round: equal-(identity, ts) runs need the
            # host's full-value tie-break
            _resident_fallback(h.n, "equal-(identity, ts) ties")
            return promote_round(_materialize(h), h.device)

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
    _wait(h)
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


def promote_round(batch: CellBatch, device=None) -> DeviceRound:
    """Lift a host-materialized round (fallback rounds: ties, counters,
    range bounds) onto the device (`device`, None = the default one:
    where the round's program ran) so the write lane consumes ONE
    ordered stream — interleaving host appends with device-pending
    cells would cut segments out of order. Values are
    copied verbatim, so the serialized bytes are identical to feeding
    the batch through the host writer. The columns are padded to the
    round's bucket (merge._bucket), as the program's own are, so the
    lane compiles per bucket and not per length."""
    n = len(batch)
    N = dmerge._bucket(n)
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

    def padded(a, dtype):
        out = np.zeros((N,) + a.shape[1:], dtype=dtype)
        out[:n] = a
        return jax.device_put(out, device)
    cols = {
        "lanes": padded(batch.lanes, np.uint32),
        "ts_h": padded(uts >> np.uint64(32), np.uint32),
        "ts_l": padded(uts & np.uint64(0xFFFFFFFF), np.uint32),
        "ldt": padded(batch.ldt, np.int32),
        "ttl": padded(batch.ttl, np.int32),
        "flags8": padded(batch.flags, np.uint8),
        "fl": padded(lens64, np.uint32),
        "vr": padded(vrel64, np.uint32),
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
    """The device-resident write stage. A round's columns never come back
    to the host as columns, and no program runs at a shape the data
    chooses: each round is ONE dispatch of `_lane_append` (registry
    `write.append`), which lays the round behind the carry — the cells
    earlier rounds left over, fewer than a segment, kept on the device —
    and returns every full segment's columns and the next carry. Each
    full segment is then ONE dispatch of `_meta_block_kernel`
    (`write.serialize`, the ts deltas as word planes + stats), all of a
    round's dispatched before the first is pulled, and ONE pull of its
    words, stats, flags and row-major lanes; the host lays out the META
    block from them (`_meta_from_words`) and the writer gets finished
    blocks (writer._emit_segment — the exact tail the host path runs
    after its own serialize). The final partial segment is the carry
    itself, pulled whole and trimmed on the host, and its block is
    built by the host build_meta_block. Both go through the one section
    writer, lay_meta_block: one layout, so byte identity with the host
    serializer holds by construction (and is pinned by test).
    Counters: `write.lane.dispatches` (one a round plus one a full
    segment; and per device, `.dev<id>`) and `write.lane.segments`
    (every segment cut). `device`: the jax device the lane's carry and
    programs are committed to, its rounds' own (None = the default)."""

    def __init__(self, writer, device=None):
        from ..storage.sstable.format import SEGMENT_CELLS
        self.writer = writer
        self.device = device
        self.dev = device_id(device)
        self.dispatches = 0               # programs this lane dispatched
        self.seg_cells = writer.segment_cells or SEGMENT_CELLS
        self.carry: dict | None = None    # device columns, seg_cells rows
        self.pending = 0                  # cells at the carry's end
        self.payloads: list = []          # (payload, off, val_start)
        self.payload_cells = 0
        self.pk_map: dict = {}

    def append(self, r: DeviceRound) -> None:
        w, seg = self.writer, self.seg_cells
        with w._span("serialize", "busy", "write.lane.append",
                     key="serialize", cells=r.n) as sp:
            N, K = (int(d) for d in r.cols["lanes"].shape)
            if w.K is None:
                w.K = K
            w._ck_fits = w._ck_fits and r.ck_fits_prefix
            if self.carry is None:
                # an empty carry shaped like the round's columns: a plain
                # transfer, no program
                self.carry = {k: jax.device_put(
                    np.zeros((seg,) + v.shape[1:], dtype=v.dtype),
                    self.device)
                    for k, v in r.cols.items()}
            args = (self.carry, r.cols, np.int32(self.pending),
                    np.int32(r.n))
            t0 = time.perf_counter()
            segments, self.carry = _lane_append(*args)
            if _kprof.record_dispatch("write.append",
                                      (N, K, seg, self.dev),
                                      time.perf_counter() - t0, self.dev):
                _kprof.maybe_record_cost("write.append", _lane_append,
                                         args)
            full, self.pending = divmod(self.pending + r.n, seg)
            cuts = [(s, self._dispatch_cut(s)) for s in segments[:full]]
            # items = programs dispatched: this round's, one per full
            # segment it completed
            sp.items = 1 + full
            self.dispatches += sp.items
            _METRICS.incr("write.lane.dispatches", sp.items)
            _METRICS.incr(f"write.lane.dispatches.{_device_tag(self.dev)}",
                          sp.items)
        # the host's side of the round: its payload frames queued, its
        # partition keys merged (one C-level update: a round of 2^19
        # cells brings ≈ 100,000 keys)
        with w._span("serialize", "busy", "write.lane.keys",
                     key="serialize", cells=r.n) as sp:
            self.payloads.append((r.payload, r.off, r.val_start))
            self.payload_cells += r.n
            self.pk_map.update(r.pk_map)
            sp.items = len(r.pk_map)
        for s, out in cuts:
            self._cut(seg, s, out)

    def flush(self) -> None:
        """Cut what is left (the final partial segment) — the
        device-mode analog of finish()'s pending drain; call before
        writer.finish()/roll."""
        if self.pending:
            self._cut(self.pending)
            self.pending = 0

    def _dispatch_cut(self, s: dict):
        """Dispatch one full segment's serialize (async)."""
        kargs = tuple(s[k] for k in _META_COLS)
        t0 = time.perf_counter()
        out = _meta_block_kernel(*kargs)
        if _kprof.record_dispatch("write.serialize",
                                  (int(kargs[0].shape[0]), self.dev),
                                  time.perf_counter() - t0, self.dev):
            _kprof.maybe_record_cost("write.serialize", _meta_block_kernel,
                                     kargs)
        return out

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

    def _cut(self, n: int, seg: dict | None = None, out=None) -> None:
        """Hand one segment to the writer: a full one (`seg`, its device
        columns, and `out`, its dispatched serialize) or, without them,
        the final partial one — the last n rows of the carry."""
        w = self.writer
        span = pipeline_ledger.span
        full = out is not None
        _METRICS.incr("write.lane.segments")
        # one `serialize` span per segment; its parts are children that
        # bill nothing themselves (ring + trace only)
        with w._span("serialize", "busy", "write.lane.cut",
                     key="serialize", cells=n):
            # the handoff: the program was dispatched at append time
            with span("write.lane.cut.slice"):
                if full:
                    words_d, stats_d = out
                    pull = (words_d, stats_d, seg["flags8"], seg["lanes"])
                else:
                    pull = self.carry
            # the cut's one pull: the kernel's word planes and stats with
            # the segment's flags and lanes (it blocks on the kernel), or
            # the carry
            with span("write.lane.cut.kernel_pull") as sp:
                host = jax.device_get(pull)
                if full:
                    words, st, flags8, lanes_np = host
                    # the chip hands a matrix back in its own layout
                    # (column-major); the writer reads rows from memory
                    lanes_np = np.ascontiguousarray(lanes_np)
                sp.nbytes = sum(v.nbytes
                                for v in jax.tree_util.tree_leaves(host))
            if full:
                _kprof.record_execute("write.serialize", sp.seconds)
            # the block laid out on the host, full segment or final
            # partial alike, through the one META layout
            with span("write.lane.cut.host_meta"):
                if full:
                    meta = _meta_from_words(words, flags8)
                    stats = _segment_stats(st)
                else:
                    c = {k: v[len(v) - n:] for k, v in host.items()}
                    lanes_np = np.ascontiguousarray(c["lanes"])
                    h = c["ts_h"].astype(np.uint64)
                    l = c["ts_l"].astype(np.uint64)
                    ts = ((h << np.uint64(32)) | l) ^ np.uint64(1 << 63)
                    ts = ts.astype(np.int64)
                    ldt, flags = c["ldt"], c["flags8"]
                    meta = build_meta_block(
                        ts, ldt, c["ttl"], flags,
                        c["fl"].astype("<u4"), c["vr"].astype("<u4"))
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
                dc_state = self._device_compress(n, meta, seg["lanes"],
                                                 self.device)
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
    def _device_compress(n: int, meta: np.ndarray, lanes_d, device=None):
        """(planes, scans) of one full segment compressed on the device,
        or None when the kernel failed and the host compress leg takes
        the segment (counted; output bytes identical either way). The
        META block the host laid out goes back to the device for it:
        the main lane never holds the block's bytes there."""
        span = pipeline_ledger.span
        try:
            meta_d = jax.device_put(meta, device)
            with span("write.lane.device_compress.dispatch") as sp:
                planes_d, mbl, mbd, lbl, lbd, order_ok = \
                    device_compress.segment_scan_kernel(meta_d, lanes_d)
            dev = device_id(device)
            if _kprof.record_dispatch("write.compress", (n, dev),
                                      sp.seconds, dev):
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
