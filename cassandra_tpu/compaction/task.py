"""CompactionTask: the streaming device-merge rewrite of N sstables.

Reference counterpart: db/compaction/CompactionTask.java:114 (runMayThrow;
the hot loop :207-225 `while (ci.hasNext()) writer.append(ci.next())`),
CompactionIterator.java:90 (merge + purge pipeline) and
CompactionController.java:55 (purgeability from overlapping sources).

Formulation: instead of a row-at-a-time heap, each round buffers one
batch per input run, finds the safe merge boundary (min of the runs'
buffered maxima), merges everything below it in ONE engine call, and
hands the result to a pipelined writer thread (compression + file I/O
overlap the next round's decode + merge). Three interchangeable,
bit-identical merge engines:

  device  ops/device_write.py — the TPU program (ops/merge.py's LSD radix
          sort + segmented-scan reconcile, then kept-cell compaction);
          big rounds amortise link latency.
  native  ops/native/merge.cpp — C++ k-way streaming merge with inline
          reconcile (the CompactionIterator formulation in native code);
          wins when the accelerator link is bandwidth-bound.
  numpy   storage/cellbatch.py — the executable spec.
"""
from __future__ import annotations

import bisect
import collections
import logging
import threading
import time

import numpy as np

from ..ops.device_write import (DeviceWriteLane, collect_merge_resident,
                                materialize_round, submit_merge_resident)
from ..ops.merge import _bucket
from ..service.metrics import GLOBAL as _METRICS
from ..storage import cellbatch as cb
from ..storage.lifecycle import LifecycleTransaction
from ..storage.sstable import Descriptor, SSTableReader, SSTableWriter
from ..utils import gil_probe, murmur3, pipeline_ledger, timeutil

_log = logging.getLogger(__name__)


def _lane_keys(batch: cb.CellBatch) -> np.ndarray:
    """Rows as fixed-width byte strings (lexicographic == lane order)."""
    K = batch.n_lanes
    return np.ascontiguousarray(batch.lanes.astype(">u4")).view(
        f"S{4 * K}").ravel()


def _full_key(batch: cb.CellBatch, i: int) -> bytes:
    """Row i's lane key as exactly 4*K bytes. numpy S-dtype strips trailing
    NUL bytes; comparisons re-pad, but PREFIX SLICING must not see a
    shortened string — always pad before [:16]."""
    K = batch.n_lanes
    return bytes(_lane_keys(batch)[i]).ljust(4 * K, b"\x00")


def _cut_range(lo: int, hi: int, cuts: list) -> list:
    """The token range (lo, hi] split at each cut point inside it."""
    out = []
    for c in cuts:
        if lo < c < hi:
            out.append((lo, c))
            lo = c
    out.append((lo, hi))
    return out


def _own_pk_map(batch: cb.CellBatch) -> dict[bytes, bytes]:
    """The entries of batch.pk_map for the partitions batch holds (a
    sorted batch: one lookup per partition run)."""
    lane4 = np.ascontiguousarray(batch.lanes[:, :4].astype(">u4"))
    new = np.ones(len(lane4), dtype=bool)
    new[1:] = (lane4[1:] != lane4[:-1]).any(axis=1)
    keys = lane4[new].view("V16").ravel().tolist()
    return dict(zip(keys, map(batch.pk_map.__getitem__, keys)))


class _Cursor:
    """Buffered scanner over one input sstable.

    Merge rounds are PARTITION-ALIGNED: deletion markers sort at the start
    of their partition/row, so reconcile is only correct when a round sees
    whole partitions (the reference's CompactionIterator merges per
    partition for the same reason). A partition larger than one segment is
    buffered whole — acceptable for round 1; the reference streams within
    partitions via its row index.

    (A background decode-prefetch thread was tried here early on and
    measured a net LOSS: the serial compress leg monopolized the GIL's
    contended windows, so the extra decode thread only fought pack/
    gather for them. With the compress leg on the GIL-releasing worker
    pool that contention is gone, and CompactionTask.decode_ahead now
    runs exactly that prefetch — the task's helper thread fills these
    buffers between rounds via fill_to, never concurrently with the
    round's own cursor access.)"""

    def __init__(self, reader: SSTableReader, prof: dict | None = None):
        self._it = reader.scanner()
        self.prof = prof
        # pipeline ledger `compaction`/`decode` stage: every fetch is
        # one `compaction.decode.fetch` span, which bills the SAME
        # seconds to the profile and to the stage's busy seconds
        self.led = pipeline_ledger.ledger("compaction").stage("decode")
        # which phase bucket _fetch bills: the decode-ahead thread bills
        # its overlapped fills to 'decode_ahead' so 'io_decode' keeps
        # meaning time the MERGE thread stalled waiting on decode
        self.prof_key = "io_decode"
        self.bufs: list[cb.CellBatch] = []
        self.exhausted = False
        self._fetch()

    def _fetch(self) -> bool:
        with self.led.busy("compaction.decode.fetch", prof=self.prof,
                           key=self.prof_key) as sp:
            try:
                b = next(self._it)
            except StopIteration:
                self.exhausted = True
                return False
            self.bufs.append(b)
            sp.cells = len(b)
            sp.nbytes = b.payload.nbytes + b.lanes.nbytes
            self.led.add_items(1, sp.nbytes)
            return True

    @property
    def has_data(self) -> bool:
        return bool(self.bufs)

    @property
    def buffered_cells(self) -> int:
        return sum(len(b) for b in self.bufs)

    def fill_to(self, n_cells: int) -> None:
        """Buffer segments until ~n_cells are held (or input exhausted).
        Large rounds amortise the per-round device dispatch latency."""
        while not self.exhausted and self.buffered_cells < n_cells:
            if not self._fetch():
                return

    def first_key(self) -> bytes:
        return self.bufs[0].lanes[0].astype(">u4").tobytes()

    def last_key(self) -> bytes:
        return _full_key(self.bufs[-1], -1)

    def extend_past_partition(self, prefix16: bytes) -> None:
        """Buffer more segments until the buffered data no longer ENDS
        inside the given partition (or the input is exhausted). Segments
        accumulate in a list — concat happens once, at slice time."""
        while self.bufs and self.last_key()[:16] == prefix16:
            if not self._fetch():
                return

    def split_at(self, boundary: bytes) -> cb.CellBatch | None:
        """Take cells with key <= boundary from the buffer; refill when the
        whole buffer is consumed."""
        if not self.bufs:
            return None
        buf = self.bufs[0] if len(self.bufs) == 1 \
            else cb.CellBatch.concat(self.bufs)
        buf.sorted = True
        keys = _lane_keys(buf)
        idx = int(np.searchsorted(keys, np.bytes_(boundary), side="right"))
        if idx == 0:
            self.bufs = [buf]
            return None
        if idx >= len(buf):
            self.bufs = []
            self._fetch()
            return buf
        head = buf.slice_range(0, idx)
        tail = buf.slice_range(idx, len(buf))
        if len(tail.pk_map) > len(tail):
            # more keys than cells: the map holds partitions already
            # consumed (a joined buffer's holds every partition of the
            # buffers it joined). Kept, it would grow with all the input
            # read so far, and each round's concats walk it in Python
            tail.pk_map = _own_pk_map(tail)
        self.bufs = [tail]
        return head


class CompactionController:
    """Purge decisions: a tombstone may only be dropped if no source
    OUTSIDE the compaction could still hold older shadowed data for its
    partition (CompactionController.java:61-121 maxPurgeableTimestamp).

    The overlap set is re-read per batch — a flush landing mid-compaction
    produces a new sstable (and the construction-time memtable is checked
    too), so concurrently-written older-timestamp data can never be purged
    against (the reference refreshes overlaps once a minute for the same
    reason)."""

    def __init__(self, cfs, compacting: list[SSTableReader]):
        self.cfs = cfs
        self.compacting_gens = {r.desc.generation for r in compacting}
        self.memtable_at_start = cfs.memtable

    def _overlapping(self) -> list[SSTableReader]:
        return [s for s in self.cfs.live_sstables()
                if s.desc.generation not in self.compacting_gens]

    @staticmethod
    def fully_expired(cfs, candidates) -> list[SSTableReader]:
        """The candidates that can be deleted without a rewrite
        (CompactionController.getFullyExpiredSSTables): every cell is
        past gc grace — `max_ldt < gc_before`; a live cell without a
        TTL carries NO_DELETION_TIME and keeps max_ldt above any
        gc_before, so an sstable of TTL'd cells that all ran out
        qualifies whether or not a compaction ever rewrote them as
        tombstones — and nothing that stays could hold data its cells
        shadow: no live sstable that may hold live data, and no
        candidate that itself has to stay, with a cell as old as the
        candidate's newest inside its token span. Candidates do not
        block one another. A non-empty memtable blocks every drop: the
        purge guard consults it, and dropping against a hot memtable
        could rewrite the sstable unchanged and re-select it forever."""
        if not cfs.memtable.is_empty:
            return []
        gc_before = timeutil.now_seconds() - \
            cfs.table.params.gc_grace_seconds

        def expired(s) -> bool:
            return s.max_ldt is not None and s.max_ldt < gc_before \
                and s.max_ts is not None

        stay = [o for o in cfs.live_sstables() if not expired(o)]
        out = []
        for s in sorted((c for c in candidates if expired(c)),
                        key=lambda c: c.max_ts, reverse=True):
            if any(o.min_ts is not None and o.min_ts <= s.max_ts
                   and o.min_token() <= s.max_token()
                   and s.min_token() <= o.max_token() for o in stay):
                stay.append(s)
            else:
                out.append(s)
        return out

    def purgeable_ts_fn(self, batch: cb.CellBatch) -> np.ndarray:
        """Per cell, the oldest timestamp a source outside the
        compaction may hold for its partition (+inf where none may).
        Each distinct partition key of the probed runs is hashed once
        and every outside sstable's bloom filter is asked about all of
        them in one call: the runs of one partition that several input
        cursors bring share their key."""
        n = len(batch)
        imax = np.iinfo(np.int64).max
        out = np.full(n, imax, dtype=np.int64)
        overlapping = self._overlapping()
        mems = {id(m): m for m in (self.memtable_at_start,
                                   self.cfs.memtable)}.values()
        mems = [m for m in mems if not m.is_empty]
        if not overlapping and not mems:
            return out
        with pipeline_ledger.span("compaction.purge.probe") as sp:
            # the answer is read only under a death flag or an expired
            # TTL (reconcile's `purged`, in every engine): only the
            # partition runs that hold such a cell are probed
            mask = (batch.flags & (cb.DEATH_FLAGS | cb.FLAG_EXPIRING)) != 0
            if not mask.any():
                return out
            # run boundaries lane by lane: a strided (n, 4) compare
            # costs several times as much
            lanes = batch.lanes
            new = lanes[1:, 0] != lanes[:-1, 0]
            for k in range(1, 4):
                new |= lanes[1:, k] != lanes[:-1, k]
            starts = np.concatenate(([0], np.flatnonzero(new) + 1))
            probed = np.flatnonzero(np.logical_or.reduceat(mask, starts))
            sp.cells, sp.items = len(starts), len(probed)
            ids = lanes[starts[probed], :4].astype(">u4")
            uniq, key_of_run = np.unique(ids.view("V16").ravel(),
                                         return_inverse=True)
            keys = [batch.pk_map[k] for k in uniq.tolist()]
            h1, h2 = murmur3.hash128_batch(keys)
            _METRICS.incr("compaction.purge.keys_hashed", len(keys))
            lo = np.full(len(keys), imax, dtype=np.int64)
            for src in overlapping:
                if src.min_ts is None:
                    continue
                hit = src.might_contain_hashes(h1, h2)
                _METRICS.incr("compaction.purge.filter_probes")
                lo = np.where(hit, np.minimum(lo, src.min_ts), lo)
            if mems:
                # memtable data is never purged against
                held = np.fromiter((any(m.contains(pk) for m in mems)
                                    for pk in keys), dtype=bool,
                                   count=len(keys))
                lo = np.where(held, np.minimum(lo, 0), lo)
            per_run = np.full(len(starts), imax, dtype=np.int64)
            per_run[probed] = lo[key_of_run]
            return np.repeat(per_run, np.diff(starts, append=n))


def tpu_backend() -> bool:
    """The backend probe of the engine choice: is jax's default backend
    a TPU? Called only for a compaction that passed the size floor and
    the metadata check, so a store that never compacts anything large
    never initialises a jax backend for it. Tests replace it (the
    `backend_probe=` argument, or this module attribute)."""
    import jax
    return jax.default_backend() == "tpu"


# cell kinds whose rounds the resident program hands to the host
# (ops/device_write.py submit_merge_resident; ROADMAP B2): range
# tombstone bounds and counters, by construction
_HOST_ROUND_FLAGS = cb.FLAG_RANGE_BOUND | cb.FLAG_COUNTER


def host_engine() -> str:
    """What an unnamed engine has always resolved to: the C++ merge when
    the library loads, else numpy."""
    from ..ops import host_merge
    return "native" if host_merge.available() else "numpy"


def choose_engine(inputs, backend_probe=None) -> tuple[str, str]:
    """(engine, why) for a compaction nobody named an engine for, from
    what the code can observe — no setting (ROADMAP B1, C1):

      size      fewer input cells than DEVICE_MIN_CELLS: the host engine.
                A served node compacts its system keyspace and freshly
                flushed slivers all day; each would pad to a program
                shape nobody compiled (tens of seconds on a TPU) to
                save milliseconds.
      metadata  the inputs' Statistics (`cell_flags`, the OR of their
                cells' flags) show range tombstone bounds or counters
                — or do not say: the resident program would send those
                rounds to the host anyway. TTLs stay: the program
                converts a kept expired cell itself.
      backend   the probe last, and only for what passed the others:
                `device` on a TPU, else the host engine."""
    host = host_engine()
    if sum(r.n_cells for r in inputs) < CompactionTask.DEVICE_MIN_CELLS:
        return host, "below the device size floor"
    flags = [getattr(r, "cell_flags", None) for r in inputs]
    if any(f is None for f in flags):
        return host, "an input's statistics do not record its cell kinds"
    if any(f & _HOST_ROUND_FLAGS for f in flags):
        return host, "inputs hold range tombstones or counters"
    if not (backend_probe or tpu_backend)():
        return host, "no TPU backend"
    return "device", "TPU backend, resident-encodable inputs"


class CompactionTask:
    # cells merged per round. Device rounds target just under 2^19 cells:
    # big enough to amortise dispatch latency, small enough that rounds
    # pipeline (submit round N+1 while N's result is in flight, so
    # transfers overlap host decode/gather/write), and sized so the
    # padded program shape is almost always exactly 2^19 — one compiled
    # program, warm after the first round. Not tuned on the attached
    # chip yet (ROADMAP A3).
    ROUND_CELLS_DEVICE = (1 << 19) - (1 << 15)
    PIPELINE_DEPTH = 3
    # the host engines want SMALL rounds: per-round cost is near zero and
    # many rounds let the pipelined writer thread overlap compression +
    # file I/O with the next round's decode + merge.
    ROUND_CELLS_HOST = 1 << 17
    # the engine choice's size floor (choose_engine): one full device
    # round. Below it a compaction is a single partial round, padded to
    # a power-of-two shape of its own (ops/merge._bucket) — a compile of
    # tens of seconds on a TPU for work the native engine finishes in
    # under a second; at or above it the rounds are the 2^19 / 2^20
    # shapes every large compaction shares.
    DEVICE_MIN_CELLS = ROUND_CELLS_DEVICE

    def __init__(self, cfs, inputs: list[SSTableReader],
                 max_output_bytes: int | None = None,
                 level: int = 0, use_device: bool | None = None,
                 round_cells: int | None = None,
                 engine: str | None = None,
                 limiter=None, progress=None,
                 pipelined_io: bool = True,
                 compress_pool=None,
                 decode_ahead: bool | None = None,
                 mesh_devices: int | None = None,
                 device_compress: bool | None = None,
                 drop_only: bool = False,
                 backend_probe=None,
                 shard_boundaries: list | None = None):
        """engine: 'device' (TPU kernel), 'native' (C++ streaming merge),
        'numpy' (reference path). All three are tested bit-identical.
        An explicit engine= wins; else use_device=True means 'device'
        and use_device=False 'numpy'. With neither given (every task a
        strategy builds, so every served compaction) the task chooses
        itself, choose_engine(): 'device' when jax's default backend is
        a TPU, the inputs hold at least DEVICE_MIN_CELLS cells and their
        statistics show nothing the resident program sends to the host
        (range tombstones, counters); otherwise 'native' when the
        library is available, else 'numpy' (a failed g++ build is
        logged by ops/native/build.py) — which is what None resolved to
        before, and still does on any backend but a TPU.
        `engine_chosen` says whether the task chose and `engine_why`
        why; a chosen engine is counted at execute()
        (compaction.engine_chosen.<engine>). backend_probe: the probe
        in tpu_backend()'s place (tests).

        shard_boundaries: the first token of each output shard, sorted
        (UCS's fixed token-space split, strategies.py): the task writes
        one sstable per shard that holds data, rolling to the next
        writer between rounds. A round is never cut across a boundary,
        so every engine writes the same split; without boundaries the
        rounds are cut exactly as they always were. On the device a
        sharded task's partial rounds (capped at a shard's end, or the
        input's last) run at the full rounds' padded shape (the whole
        compaction's, where that is smaller), so which shapes a
        compaction compiles does not depend on where its data falls.
        The mesh fan-out cuts its token ranges at the boundaries too
        and writes the same sstables.

        limiter: a utils.ratelimit.RateLimiter debited per round with the
        round's share of on-disk input bytes (compaction_throughput).
        progress: a compaction.executor.CompactionProgress the task
        updates as it runs (nodetool compactionstats / the
        compactions_in_progress virtual table).
        pipelined_io: thread the output's disk writes behind the
        compress stage (SSTableWriter threaded_io) — the write leg of
        the decode→merge→pack→compress→io_write pipeline. Output bytes
        are identical either way; disable to keep everything on two
        threads.
        compress_pool: the compressor-worker pool for the writers'
        parallel-compress leg. None (default) = the shared process
        pool sized by compaction_compressor_threads; 0 = keep the
        serial compress thread; a compress_pool.CompressorPool pins an
        explicit pool (bench sweeps, tests). Output bytes identical for
        every choice.
        decode_ahead: prefetch-decode round k+1's input segments on a
        helper thread while round k merges and the pool compresses —
        profitable now that the compress leg no longer contends for
        the GIL (an earlier prefetch attempt lost to exactly that, see
        _Cursor). None = inherit the owning ENGINE's hot-reloadable
        `compaction_decode_ahead` knob (default on), re-read EVERY
        ROUND so a mid-compaction flip stops or restarts the prefetch
        thread at the next round boundary; an explicit True/False pins
        it for this task. Host engines under pipelined_io only — the
        device engine keeps its own submit/collect pipelining.
        mesh_devices: the mesh execution mode (docs/multichip.md) —
        the compaction is token-range sharded by count-weighted
        boundaries planned from the input sstables' partition indexes
        and the per-shard decode->merge fans across N mesh lanes
        (engine='device': each shard's kernel committed to its own
        jax device; host engines: one GIL-releasing worker thread per
        lane). Shard results drain IN TOKEN ORDER through the same
        compress-pool/threaded-io writer, so output bytes are
        identical to the serial path for every N (token-range shard
        order IS identity-lane order — no reshuffle). None = inherit
        the `compaction_mesh_devices` knob (parallel/fanout.py);
        0 = force serial. Mesh lanes place shard s on jax device s mod
        N whether or not the owning engine is pinned to a device.
        device_compress: device-side block compression for the
        device-resident lane's full segments (ops/device_compress.py)
        — the fused policy-scan kernel compresses META + lanes on the
        device and the host io thread becomes a pwrite pump. None =
        inherit the engine's hot-reloadable `compaction_device_compress`
        knob (default OFF since PR 27: on a v5e the lane's host-side LZ4
        emission took 234 s for a compaction the host pool does in
        12 s), re-read by the writer PER SEGMENT (a mid-compaction flip
        moves the work at the next segment boundary); True/False pins
        it. Output bytes are identical for every choice — the native
        packer runs the same deterministic policy encoder.
        """
        self.cfs = cfs
        self.inputs = inputs
        # the owning engine's jax device (config `device_ordinal`, bound
        # at engine start): the serial round loop's rounds and its write
        # lane are committed to it. None = jax's default device
        self.device = getattr(cfs, "jax_device", None)
        self.max_output_bytes = max_output_bytes
        self.shard_boundaries = sorted(shard_boundaries) \
            if shard_boundaries else None
        self.level = level
        self.use_device = bool(use_device)
        self.limiter = limiter
        self.progress = progress
        self.pipelined_io = pipelined_io
        self.engine_chosen = engine is None and use_device is None
        self.engine_why = "named by the caller"
        if engine is None:
            if use_device:
                engine = "device"
            elif use_device is False:
                engine = "numpy"
            else:
                engine, self.engine_why = choose_engine(inputs,
                                                        backend_probe)
        self.engine = engine
        if compress_pool is None:
            from ..storage.sstable.compress_pool import get_pool
            self.compress_pool = get_pool() if pipelined_io else None
        elif isinstance(compress_pool, int):
            if compress_pool != 0:
                # a worker COUNT belongs on the knob or an explicit
                # CompressorPool — silently running serial instead
                # would be an invisible perf misconfiguration
                raise ValueError(
                    "compress_pool takes a CompressorPool, None (shared "
                    "pool) or 0 (serial compress); to pin a worker "
                    "count pass CompressorPool(n)")
            self.compress_pool = None      # 0: serial compress
        else:
            self.compress_pool = compress_pool
        # tri-state: None = knob-inherited (resolved per round by
        # _decode_ahead_enabled), True/False = pinned for this task
        self.decode_ahead = decode_ahead
        self.mesh_devices = mesh_devices
        # tri-state like decode_ahead: None = inherit the owning
        # engine's hot-reloadable `compaction_device_compress` knob
        # (re-read PER SEGMENT by the writer), True/False = pinned for
        # this task (AB legs / bench sweeps). Only consulted by the
        # device-resident write lane; output bytes identical always.
        self.device_compress = device_compress
        self.round_cells = round_cells or (
            self.ROUND_CELLS_DEVICE if self.engine == "device"
            else self.ROUND_CELLS_HOST)
        # a sharded task pads every device round to the full round's
        # shape, or to the whole compaction's where that is smaller
        self.min_bucket = min(
            _bucket(self.round_cells),
            _bucket(sum(r.n_cells for r in inputs))) \
            if self.shard_boundaries else 0
        # drop_only: the selecting strategy asserts every input is
        # fully expired and safe to delete without a rewrite (TWCS
        # expired drop, CompactionController.fully_expired). execute()
        # re-verifies the guard against the CURRENT live set/memtable
        # and falls back to the normal merge (which purges correctly)
        # if anything changed between selection and execution.
        self.drop_only = bool(drop_only)
        # per-phase wall seconds, accumulated across rounds (published by
        # bench.py -- the breakdown the perf work navigates by)
        self.profile: dict = {}

    def _shard_cap(self, cursors: list, n_lanes: int):
        """(shard, cap) of the round the cursors' smallest buffered key
        starts: the index of the shard it falls in and the largest lane
        key of that shard's last token, None for the last shard. (None,
        None) for a task without boundaries."""
        bounds = self.shard_boundaries
        if not bounds:
            return None, None
        first_key = min(c.first_key() for c in cursors)
        token = int.from_bytes(first_key[:8], "big") - (1 << 63)
        shard = max(bisect.bisect_right(bounds, token) - 1, 0)
        if shard + 1 >= len(bounds):
            return shard, None
        last = bounds[shard + 1] - 1 + (1 << 63)
        return shard, last.to_bytes(8, "big") + b"\xff" * (4 * n_lanes - 8)

    def _effective_mesh_devices(self) -> int:
        """The mesh width this task runs at: the explicit mesh_devices=
        argument wins; None inherits the owning ENGINE's hot-reloadable
        `compaction_mesh_devices` knob via the store (0 = serial) —
        never a co-hosted engine's — falling back to the process demand
        for standalone stores."""
        if self.mesh_devices is not None:
            return max(int(self.mesh_devices), 0)
        fn = getattr(self.cfs, "mesh_devices_fn", None)
        if fn is not None:
            return max(int(fn()), 0)
        from ..parallel import fanout
        return fanout.mesh_devices()

    def _decode_ahead_enabled(self) -> bool:
        """Whether the decode-ahead prefetch should be running RIGHT
        NOW: the explicit decode_ahead= argument wins; None inherits
        the owning engine's hot-reloadable `compaction_decode_ahead`
        knob via the store (never a co-hosted engine's), defaulting on
        for standalone stores. The serial round loop re-reads this
        every round, so a mid-compaction knob flip stops or restarts
        the helper thread at the next round boundary — round
        boundaries and output bytes are identical either way (the
        pf_done handshake guarantees it)."""
        if self.decode_ahead is not None:
            return bool(self.decode_ahead)
        if not self.pipelined_io or self.engine == "device":
            return False
        fn = getattr(self.cfs, "decode_ahead_fn", None)
        return bool(fn()) if fn is not None else True

    def _device_compress_gate(self):
        """The writer's per-segment device-compress gate: False when
        this task has no device-resident lane; a pinned bool when
        device_compress= was explicit; else the owning store's
        hot-reloadable `compaction_device_compress` closure (never a
        co-hosted engine's), falling back to the config default for
        standalone stores. The writer re-reads a callable gate per
        segment, so mid-compaction knob flips land on segment
        boundaries."""
        if self.engine != "device":
            return False
        if self.device_compress is not None:
            return bool(self.device_compress)
        fn = getattr(self.cfs, "device_compress_fn", None)
        if fn is not None:
            return fn
        from ..config import Config
        return lambda: bool(Config().compaction_device_compress)

    def _engine_merge_fn(self, prof: dict | None,
                         defer_gather: bool = False):
        """The host-merge closure for this task's engine — the ONE place
        the native/numpy dispatch lives, shared by the serial round loop
        and the mesh lanes so the two paths can never diverge on merge
        semantics. Returns None for the device engine (its rounds go
        through submit/collect). prof: where the native merge bills its
        phase timings — run() passes the task profile, the mesh lanes
        pass a per-shard dict (folded under a lock; concurrent lanes
        must not race on the shared profile). defer_gather: the serial
        round loop defers the native merge's output gather to the
        writer thread (host_merge.LazyMergedBatch) so it overlaps the
        next round's decode + merge; mesh lanes keep it in-lane (their
        parallelism already covers it)."""
        if self.engine == "device":
            return None
        if self.engine == "native":
            from ..ops.host_merge import merge_sorted_native

            def merge_fn(slices, **kw):
                return merge_sorted_native(slices, prof=prof,
                                           defer_gather=defer_gather,
                                           **kw)
            return merge_fn
        return cb.merge_sorted

    # in-flight shard window beyond the mesh width: one extra so the
    # drain thread always has a completed shard to feed the writer
    # while every lane computes
    MESH_WINDOW_SLACK = 1

    def _mesh_produce(self, n_devices: int, wq, controller,
                      gc_before: int, now: int, werr,
                      bytes_per_cell: float) -> bool:
        """Mesh execution mode: token-range shard the whole rewrite by
        count-weighted boundaries planned from the input sstables'
        partition indexes, fan per-shard decode->merge across
        n_devices mesh lanes, and drain the merged shards IN TOKEN
        ORDER into the writer queue. Token-range shard order is
        identity-lane order, so the drained stream — and therefore
        every output byte — is identical to the serial round loop.
        bytes_per_cell: run()'s on-disk byte/cell ratio (throttle +
        progress accounting). Returns False (caller runs the serial
        path) when the inputs expose no index samples to plan from."""
        from ..parallel import fanout as fanout_mod
        from ..parallel.boundaries import (boundaries_from_indexes,
                                           boundaries_to_ranges,
                                           record_shard_metrics)

        prof = self.profile
        cfs = self.cfs
        progress = self.progress
        t_plan = time.perf_counter()
        cells_read = sum(r.n_cells for r in self.inputs)
        # shard count: at least one per lane, sized so a shard is about
        # one serial round (bounded memory per in-flight shard)
        n_shards = max(n_devices, -(-cells_read // self.round_cells))
        n_shards = min(int(n_shards), 4096)
        bounds = boundaries_from_indexes(self.inputs, n_shards)
        if bounds is None:
            return False
        ranges = boundaries_to_ranges(bounds, n_shards)
        # a sharded task's output shards: every range lies inside one
        # (cut at each boundary's last token below it) and is tagged with
        # it, so the writer rolls where the serial round loop rolls
        tags = [None] * len(ranges)
        if self.shard_boundaries:
            cuts = [b - 1 for b in self.shard_boundaries[1:]]
            ranges = [piece for lo, hi in ranges
                      for piece in _cut_range(lo, hi, cuts)]
            tags = [max(bisect.bisect_right(self.shard_boundaries, hi) - 1,
                        0) for _lo, hi in ranges]
            n_shards = len(ranges)
        # exact per-shard INPUT cells from the partition directories
        # (throttle + progress accounting in on-disk byte terms)
        shard_in_cells = np.zeros(n_shards, dtype=np.int64)
        signed_bounds = np.array([hi for (_lo, hi) in ranges[:-1]],
                                 dtype=np.int64)
        for r in self.inputs:
            if r.n_partitions == 0:
                continue
            part_cells = np.diff(np.append(r._part_cell0, r.n_cells))
            ps = np.searchsorted(signed_bounds, r.partition_tokens,
                                 side="left")
            np.add.at(shard_in_cells, ps, part_cells)
        prof["mesh_plan"] = prof.get("mesh_plan", 0.0) \
            + (time.perf_counter() - t_plan)

        devices = None
        if self.engine == "device":
            import jax
            devs = jax.devices()
            if n_devices > len(devs):
                # lanes still overlap host decode/merge, but they SHARE
                # devices — never silently: a 4-lane mesh on one
                # visible chip is one chip's worth of device work
                _log.warning(
                    "mesh_devices=%d but jax sees %d device(s): mesh "
                    "lanes share devices", n_devices, len(devs))
            devices = [devs[i % len(devs)] for i in range(n_devices)]
        # which jax device each lane's programs were committed to (None
        # for the host engines) — chip_smoke.py --chips 4 reads it
        self.mesh_lane_devices = devices

        def merge_shard(slices, shard_prof):
            # the same per-engine dispatch run() uses — one source of
            # merge semantics for both paths (byte identity depends on
            # it); only the prof sink differs (per-shard, lock-folded)
            fn = self._engine_merge_fn(shard_prof)
            return fn(slices, gc_before=gc_before, now=now,
                      purgeable_ts_fn=controller.purgeable_ts_fn)

        import queue as _queue

        from ..service import tracing
        from ..utils import pipeline_ledger

        mesh_led = pipeline_ledger.ledger("mesh")
        led_decode = mesh_led.stage("decode")
        led_merge = mesh_led.stage("merge")
        # shard dispatch/completion under the active trace session (the
        # thread driving the compaction; lanes have no contextvar)
        trace_st = tracing.active()

        slots: list = [None] * n_shards
        evs = [threading.Event() for _ in range(n_shards)]
        errs: list = [None] * n_shards
        walls = [0.0] * n_shards
        busy = [0.0] * n_shards
        decoded_cells = [0] * n_shards
        stop = threading.Event()
        # plain Semaphore: a worker that bails between claim and acquire
        # during an abort may leave the drain's release unmatched —
        # harmless here, but BoundedSemaphore would raise and mask the
        # real error
        sem = threading.Semaphore(n_devices + self.MESH_WINDOW_SLACK)
        shard_q: _queue.Queue = _queue.Queue()
        for s in range(n_shards):
            shard_q.put(s)
        prof_lock = threading.Lock()
        self._mesh_completion_order: list[int] = []
        # merged-but-undrained shards: the mesh pipeline's inbound
        # queue to the writer drain (high-water = how far lanes ran
        # ahead of the token-order drain)
        ready_count = [0]

        def run_shard(s: int) -> None:
            shard_prof: dict = {}
            try:
                delay = fanout_mod._TEST_SHARD_DELAY
                if delay:
                    time.sleep(delay.get(s, 0.0))
                if trace_st is not None:
                    trace_st.add(f"Mesh shard {s} dispatched "
                                 f"({int(shard_in_cells[s])} cell(s))")
                if self.limiter is not None:
                    # stop cuts the throttle sleep short AND refunds the
                    # debit: an aborted task's debt must not throttle
                    # the re-planned replacement
                    t_thr = time.perf_counter()
                    self.limiter.acquire(
                        int(shard_in_cells[s] * bytes_per_cell),
                        cancel=stop)
                    # throttle sleeps are decode-stage stalls in the
                    # ledger (paid before the lane touches data)
                    led_decode.add_stall(time.perf_counter() - t_thr)
                if stop.is_set():   # abort: drop the shard, exit fast
                    return
                lo, hi = ranges[s]
                t0 = time.perf_counter()
                slices = []
                for r in self.inputs:
                    if stop.is_set():
                        return
                    w = r.scan_tokens(lo, hi)
                    if w is not None and len(w):
                        slices.append(w)
                t1 = time.perf_counter()
                shard_prof["mesh_decode"] = t1 - t0
                decoded_cells[s] = sum(len(x) for x in slices)
                merged = None
                if slices and not stop.is_set():
                    if devices is not None:
                        # the serial loop's program, one device per
                        # lane; shards drain as host CellBatches
                        h = submit_merge_resident(
                            slices, gc_before=gc_before, now=now,
                            purgeable_ts_fn=controller.purgeable_ts_fn,
                            device=devices[s % n_devices])
                        if h.mode == "resident":
                            with prof_lock:
                                self.rounds_by_device[h.dev] += 1
                        merged = materialize_round(h)
                    else:
                        merged = merge_shard(slices, shard_prof)
                walls[s] = time.perf_counter() - t1
                shard_prof["mesh_merge"] = walls[s]
                # busy = decode + merge, throttle sleeps excluded: the
                # lane-exclusive work an overlap measure sums
                busy[s] = time.perf_counter() - t0
                slots[s] = merged
                # per-stage ledger accounting (the same numbers the
                # shard_prof folds into the task profile, accumulated
                # process-wide under pipeline `mesh`)
                led_decode.add_busy(shard_prof.get("mesh_decode", 0.0))
                led_decode.add_items(
                    1, int(shard_in_cells[s] * bytes_per_cell))
                led_merge.add_busy(walls[s])
                led_merge.add_items(decoded_cells[s])
                if trace_st is not None:
                    trace_st.add(f"Mesh shard {s} complete "
                                 f"({decoded_cells[s]} cell(s) merged)")
            except BaseException as e:
                errs[s] = e
                stop.set()
            finally:
                with prof_lock:
                    for k, v in shard_prof.items():
                        prof[k] = prof.get(k, 0.0) + v
                    self._mesh_completion_order.append(s)
                    ready_count[0] += 1
                    led_merge.note_queue(ready_count[0])
                evs[s].set()

        def work_loop() -> None:
            while not stop.is_set():
                try:
                    s = shard_q.get_nowait()
                except _queue.Empty:
                    return
                acquired = False
                while not stop.is_set():
                    if sem.acquire(timeout=0.1):
                        acquired = True
                        break
                if not acquired:   # stopping: settle the shard's event
                    evs[s].set()
                    return
                run_shard(s)

        # daemon: lanes only read inputs and merge in memory (the
        # writer owns every on-disk mutation), so a straggler must not
        # block process exit after an abort already abandoned it
        workers = [threading.Thread(target=work_loop,
                                    name=f"compact-mesh-{i}",
                                    daemon=True)
                   for i in range(min(n_devices, n_shards))]
        t_fan = time.perf_counter()
        for t in workers:
            t.start()
        try:
            for s in range(n_shards):
                if werr:     # writer died: fail fast
                    break
                abort = getattr(cfs, "compaction_abort", None)
                if (abort is not None and abort.is_set()) or \
                        (progress is not None and progress.stop_requested):
                    raise RuntimeError(
                        "compaction stopped by operator request")
                evs[s].wait()
                if errs[s] is not None:
                    raise errs[s]
                merged = slots[s]
                slots[s] = None
                with prof_lock:
                    ready_count[0] -= 1
                sem.release()
                if progress is not None:
                    progress.set_phase("merge")
                    progress.add_read(
                        int(shard_in_cells[s] * bytes_per_cell))
                if merged is not None and len(merged):
                    wq.put((tags[s], merged))
        finally:
            stop.set()
            for t in workers:
                t.join(timeout=30.0)
        record_shard_metrics(decoded_cells, walls)
        # per-shard forensics for bench.py / the multichip entry:
        # sum(busy)/produce_seconds > 1 proves the lanes actually
        # overlapped (busy is lane-EXCLUSIVE decode+merge work; a
        # 1-lane run measures ~1 by construction), the cell spread is
        # the planner's balance
        self.mesh_shard_walls = walls
        self.mesh_shard_busy = busy
        self.mesh_produce_seconds = time.perf_counter() - t_fan
        self.mesh_shard_cells = decoded_cells
        return True

    def _handle_corrupt_input(self, exc: BaseException) -> None:
        """Corruption surfacing mid-compaction aborts ONLY this task
        (the lifecycle txn already rolled back); route the failing
        input through the store's disk failure policy so best_effort
        quarantines it and the strategy re-plans without it
        (CompactionManager re-selects after the quarantine)."""
        from ..storage.sstable.reader import CorruptSSTableError
        if not isinstance(exc, CorruptSSTableError):
            return
        failures = getattr(self.cfs, "failures", None)
        if failures is None:
            return
        bad = None
        if exc.descriptor is not None:
            bad = next((r for r in self.inputs
                        if r.desc == exc.descriptor), None)
        path = bad.desc.path("Data.db") if bad is not None else ""
        policy = failures.handle_corruption(exc, path)
        if policy == "best_effort" and bad is not None:
            self.cfs.quarantine_sstable(bad, exc)

    def _drop_safe(self) -> bool:
        """Re-verify the fully-expired drop guard at EXECUTE time (the
        selecting strategy checked at selection; a flush or an
        out-of-order write may have landed since): the rule the
        strategy selected by, against the CURRENT live set and memtable
        (dropping must not resurrect anything the inputs shadow)."""
        safe = CompactionController.fully_expired(self.cfs, self.inputs)
        return len(safe) == len(self.inputs)

    def _execute_drop(self) -> dict:
        """Rewrite-free expired drop: obsolete the inputs in one
        lifecycle txn and swap them out of the live view — no decode,
        no merge, no output writer. Zero compacted bytes land on the
        amplification counters: that IS the point of the drop."""
        cfs = self.cfs
        t0 = time.time()
        cells_read = sum(r.n_cells for r in self.inputs)
        with pipeline_ledger.span(
                "compaction.drop", items=len(self.inputs),
                cells=cells_read,
                nbytes=sum(r.data_size for r in self.inputs)):
            txn = LifecycleTransaction(cfs.directory)
            for r in self.inputs:
                txn.track_obsolete(r.desc.generation)
            txn.commit()
            cfs.tracker.replace(self.inputs, [])
            if cfs.row_cache is not None:
                cfs.row_cache.clear()
            for r in self.inputs:
                r.release()
            if getattr(cfs, "index_listeners", ()):
                cfs.sstables_changed(self.inputs, [])
        stats = {
            "inputs": len(self.inputs), "outputs": 0,
            "bytes_read": 0, "bytes_written": 0,
            "cells_read": cells_read, "cells_written": 0,
            "seconds": time.time() - t0,
            "read_mib_s": 0.0, "write_mib_s": 0.0,
            "dropped": True,
        }
        rec = getattr(cfs, "record_compaction", None)
        if rec is not None:
            rec(stats)
        elif cfs.compaction_history is not None:
            cfs.compaction_history.append(stats)
        return stats

    def execute(self) -> dict:
        """Run the compaction; returns stats (reference logs these at
        CompactionTask.java:252-266)."""
        if self.drop_only and self._drop_safe():
            return self._execute_drop()
        if self.engine_chosen:
            _METRICS.incr(f"compaction.engine_chosen.{self.engine}")
        if self.progress is not None:
            self.progress.engine = self.engine
        # the root span: every span of this task, on whichever thread,
        # carries its id (docs/observability.md, span catalogue)
        with pipeline_ledger.span(
                "compaction.task", task=pipeline_ledger.new_task_id(),
                cells=sum(r.n_cells for r in self.inputs),
                nbytes=sum(r.data_size for r in self.inputs)) as root:
            # the GIL probe beats for the length of the task: a bare
            # store compacts with no engine open (utils/gil_probe.py)
            gil_probe.GLOBAL.set_demand(id(self), True)
            try:
                return self._execute(root.task)
            finally:
                gil_probe.GLOBAL.set_demand(id(self), False)

    def _execute(self, task_id: int) -> dict:
        cfs = self.cfs
        table = cfs.table
        t0 = time.time()
        gc_before = timeutil.now_seconds() - table.params.gc_grace_seconds
        now = timeutil.now_seconds()
        controller = CompactionController(cfs, self.inputs)
        prof = self.profile
        # merge.resident rounds and write-lane programs by the id of the
        # device they ran on (the stats' `rounds_by_device`,
        # `lane_dispatches_by_device`)
        self.rounds_by_device = collections.Counter()
        self.lane_dispatches_by_device = collections.Counter()
        # pipeline `compaction` (docs/observability.md): `decode` —
        # cursor fetches (inline AND decode-ahead) bill busy, the merge
        # thread's prefetch waits stall, the prefetch thread's parked
        # time idle, queue_hwm = segments decoded ahead of the merge;
        # `writeq` — the merge thread blocked on the full write queue
        # bills stall, compact-w parked on the empty one idle
        led = pipeline_ledger.ledger("compaction")
        led_decode, led_wq = led.stage("decode"), led.stage("writeq")
        # None for the device engine: its rounds go through
        # submit/collect. The serial loop defers the output gather to
        # the writer thread (it drains the wq FIFO on one thread, so
        # materialization order — and output bytes — are unchanged).
        merge_fn = self._engine_merge_fn(prof, defer_gather=True)

        txn = LifecycleTransaction(cfs.directory)
        writers: list[SSTableWriter] = []
        new_readers: list[SSTableReader] = []
        bytes_read = sum(r.data_size for r in self.inputs)
        cells_read = sum(r.n_cells for r in self.inputs)
        cells_written = 0

        def new_writer() -> SSTableWriter:
            gen = cfs.next_generation()
            desc = Descriptor(cfs.directory, gen)
            txn.track_new(gen)
            w = SSTableWriter(desc, table,
                              estimated_partitions=max(
                                  sum(r.n_partitions for r in self.inputs), 16),
                              prof=prof, threaded_io=self.pipelined_io,
                              compress_pool=self.compress_pool,
                              metrics_group="compaction",
                              device_compress=self._device_compress_gate())
            w.level = self.level
            # outputs carry the MINIMUM repairedAt of the inputs
            # (CompactionTask.getMinRepairedAt): mixing repaired with
            # unrepaired demotes to unrepaired, never promotes
            w.repaired_at = min(r.repaired_at for r in self.inputs)
            writers.append(w)
            return w

        # pipelined write stage: compression + file I/O run on a worker
        # thread (ctypes FFI and FileIO release the GIL) while the main
        # thread decodes and merges the next round — the reference gets
        # the same overlap from the kernel's writeback cache; here it is
        # explicit. Queue depth 2 bounds buffered memory.
        import queue

        wq: queue.Queue = queue.Queue(maxsize=2)
        werr: list[BaseException] = []
        # credited: bytes of the CURRENT writer already added to
        # progress — in parallel-compress mode data_offset() trails
        # appends, so finish()'s pool drain must credit the tail too.
        # resident: device-resident rounds flow as DeviceRound objects
        # through a DeviceWriteLane instead of writer.append ("lane").
        # shard: the output shard of the rounds the writer is on
        wstate = {"writer": None, "cells": 0, "credited": 0,
                  "resident": False, "lane": None, "shard": None}

        progress = self.progress

        def wq_put(item):
            with led_wq.stall("compaction.writeq.put_wait", prof=prof,
                              key="writeq_put_wait"):
                wq.put(item)

        def flush_lane():
            lane = wstate["lane"]
            if lane is not None:
                lane.flush()
                wstate["lane"] = None
                self.lane_dispatches_by_device[lane.dev] += lane.dispatches

        def roll():
            # finish the writer and open the next: the lane's pending
            # partial flushes into the finishing writer first — exactly
            # the cells finish() would cut from host pending
            w = wstate["writer"]
            flush_lane()
            w.finish()
            if progress is not None:
                progress.add_written(w.data_offset() - wstate["credited"])
            new_readers.append(SSTableReader(w.desc, table))
            wstate["writer"] = new_writer()
            wstate["credited"] = 0
            return w

        def write_loop():
            # pack/compress stage of the pipeline: writer.append cuts
            # segments, serializes their blocks and (parallel-compress
            # mode) fans them out to the compressor pool, whose results
            # re-sequence through the writer's ordered completion queue
            # onto its I/O thread — the stages decode+merge / pack /
            # compress-pool / io_write all overlap. In device-resident
            # mode the rounds arrive as DeviceRound column sets and the
            # segment cut + META serialize happen ON DEVICE through the
            # write lane; the writer sees only finished blocks. Phase
            # timings land in prof as 'serialize', 'compress' and
            # 'io_write'. Progress + the output-size cut-over read the
            # writer's PUBLISHED offset (data_offset()), never private
            # state another thread is mutating.
            try:
                while True:
                    with led_wq.idle("compaction.writeq.get_wait",
                                     prof=prof, key="writeq_get_wait"):
                        item = wq.get()
                    if item is None:
                        # the sentinel is already consumed: a raise out
                        # of the lane flush must land in werr and
                        # RETURN (the generic except below drains the
                        # queue waiting for a sentinel that will never
                        # come — the producer already sent it)
                        try:
                            flush_lane()
                        except BaseException as e:
                            werr.append(e)
                        return
                    shard, merged = item
                    if shard != wstate["shard"]:
                        if wstate["shard"] is not None:
                            # the next shard's first round: its cells
                            # go to an sstable of their own
                            with pipeline_ledger.span(
                                    "compaction.shard.roll",
                                    items=wstate["shard"]) as sp:
                                sp.nbytes = roll().data_offset()
                            _METRICS.incr("compaction.shard_rolls")
                        wstate["shard"] = shard
                    if hasattr(merged, "materialize"):
                        # deferred native-merge gather: runs HERE, on
                        # the writer thread, overlapping the producer's
                        # next round (host_merge.LazyMergedBatch)
                        merged = merged.materialize()
                    w = wstate["writer"]
                    if wstate["resident"]:
                        lane = wstate["lane"]
                        if lane is None:
                            lane = wstate["lane"] = DeviceWriteLane(
                                w, self.device)
                        lane.append(merged)
                    else:
                        w.append(merged)
                    if progress is not None:
                        off = w.data_offset()
                        progress.add_written(off - wstate["credited"])
                        wstate["credited"] = off
                    wstate["cells"] += len(merged)
                    if self.max_output_bytes and \
                            wstate["writer"].data_offset() >= \
                            self.max_output_bytes:
                        # roll the output (MaxSSTableSizeWriter role).
                        # In parallel mode the published offset trails
                        # in-flight segments, so the roll lands late by
                        # a bounded amount — finish() drains the pool
                        # (and the drained tail is credited below).
                        roll()
            except BaseException as e:   # surfaced after join
                werr.append(e)
                wstate["lane"] = None
                while True:              # drain so the producer never blocks
                    if wq.get() is None:
                        return

        # device engine: keep rounds in flight (async dispatch) so the
        # accelerator link overlaps host decode + gather + write
        from collections import deque

        pending: deque = deque()

        def collect_oldest():
            shard, h = pending.popleft()
            merged = collect_merge_resident(h)
            if len(merged):
                wq_put((shard, merged))

        # throttle + progress work in on-disk byte terms: each round
        # consumed cells are mapped back to their share of the input
        # files' bytes, so compaction_throughput limits disk read rate
        # (the reference debits its limiter per scanned partition) and
        # progress.bytes_read converges on total_bytes exactly
        bytes_per_cell = bytes_read / max(cells_read, 1)

        # decode-ahead stage (LUDA's overlap of decode k+1 with merge k):
        # a helper thread refills the cursors' segment buffers while the
        # merge engine reconciles the current round and the pool
        # compresses its output. Strictly handshaked — the helper only
        # touches cursors between pf_done.clear() and pf_done.set(), and
        # the main loop waits on pf_done before every cursor access — so
        # round boundaries (and output bytes) are identical either way.
        pf_q = None
        pf_thread = None
        pf_done = threading.Event()
        pf_done.set()
        pf_err: list[BaseException] = []

        def prefetch_loop():
            while True:
                # parked between prefetches
                with led_decode.idle("compaction.decode.park"):
                    per = pf_q.get()
                if per is None:
                    return
                try:
                    for c in cursors:
                        if not c.exhausted:
                            c.prof_key = "decode_ahead"
                            try:
                                c.fill_to(per)
                            finally:
                                c.prof_key = "io_decode"
                except BaseException as e:   # surfaced next round
                    pf_err.append(e)
                finally:
                    # prefetch-queue high water: segments buffered
                    # ahead of the merge (how far decode ran ahead)
                    led_decode.note_queue(
                        sum(len(c.bufs) for c in cursors))
                    pf_done.set()

        def in_task(fn):
            # the task's helper threads: their spans carry its id
            with pipeline_ledger.task_scope(task_id):
                fn()

        def stop_prefetch():
            if pf_thread is not None:
                pf_q.put(None)
                pf_thread.join(timeout=30.0)

        wthread = None
        try:
            if progress is not None:
                progress.set_phase("decode")
            wstate["writer"] = new_writer()
            wthread = threading.Thread(
                target=in_task, args=(write_loop,), name="compact-w")
            wthread.start()
            # mesh execution mode: shard the rewrite by token range and
            # fan decode+merge across the mesh lanes; the serial round
            # loop below is skipped (its cursor list stays empty). Falls
            # back to the serial path when no boundaries can be planned.
            mesh_done = False
            mesh_n = self._effective_mesh_devices()
            if mesh_n >= 1:
                if progress is not None:
                    progress.set_phase("mesh_plan")
                mesh_done = self._mesh_produce(mesh_n, wq, controller,
                                               gc_before, now, werr,
                                               bytes_per_cell)
            # device-resident rounds only make sense for the serial
            # device round loop: mesh shards drain host CellBatches
            # through the unchanged writer (token-order contract)
            wstate["resident"] = self.engine == "device" and not mesh_done
            cursors = [] if mesh_done \
                else [_Cursor(r, prof) for r in self.inputs]
            # the decode-ahead thread starts (and stops, and restarts)
            # from the knob check at the top of each round — see below
            while True:
                if werr:       # writer died: fail fast, don't keep merging
                    break
                abort = getattr(cfs, "compaction_abort", None)
                if (abort is not None and abort.is_set()) or \
                        (progress is not None and progress.stop_requested):
                    # nodetool stop: cooperative cancel between rounds
                    # (per-task via the progress handle under the
                    # executor; the legacy shared event covers tasks
                    # driven without one); the lifecycle txn below never
                    # commits, so the partial output rolls back on the
                    # crash-safe path
                    raise RuntimeError(
                        "compaction stopped by operator request")
                # cursors are shared with the decode-ahead helper: wait
                # out any in-flight prefetch before touching them (the
                # wait is the merge thread BLOCKED ON decode — the
                # ledger bills it as a decode-stage stall)
                if pf_thread is not None:
                    with led_decode.stall("compaction.decode.wait"):
                        pf_done.wait()
                if pf_err:
                    raise pf_err[0]
                # hot-reloadable `compaction_decode_ahead`: re-resolved
                # every round, so a mid-compaction flip OFF retires the
                # helper thread here (the prefetch in flight already
                # handshook out above) and a flip ON starts it — round
                # boundaries, and therefore output bytes, are identical
                # under any flip sequence
                if not mesh_done:
                    want_da = self._decode_ahead_enabled()
                    if pf_thread is not None and not want_da:
                        stop_prefetch()
                        pf_thread = None
                    elif pf_thread is None and want_da:
                        pf_q = queue.Queue()
                        pf_thread = threading.Thread(
                            target=in_task, args=(prefetch_loop,),
                            name="compact-prefetch", daemon=True)
                        pf_thread.start()
                active = [c for c in cursors if c.has_data]
                if not active:
                    break
                # buffer a full round's worth per cursor first, THEN find
                # the partition-aligned boundary: the minimal buffered-
                # through key, extended so no cursor's buffer ends INSIDE
                # that key's partition; merge everything up to the
                # partition end (full key width padded with 0xFF). A
                # sharded task caps the cut at the last token of the
                # shard the round starts in (`items` = 1 on such a cut)
                per_cursor = max(self.round_cells // len(active), 1)
                with pipeline_ledger.span("compaction.round.cut") as sp:
                    for c in active:
                        c.fill_to(per_cursor)
                    prefix16 = min(c.last_key() for c in active)[:16]
                    K = self.inputs[0].K
                    boundary = prefix16 + b"\xff" * (4 * K - 16)
                    shard, cap = self._shard_cap(active, K)
                    if cap is not None and boundary > cap:
                        # every buffer reaches past the shard's end,
                        # so none ends inside a partition below it
                        boundary = cap
                        sp.items = 1
                        _METRICS.incr("compaction.rounds_cut_at_shard")
                    else:
                        for c in cursors:
                            c.extend_past_partition(prefix16)
                    slices = []
                    for c in cursors:
                        s = c.split_at(boundary)
                        if s is not None and len(s):
                            slices.append(s)
                    sp.cells = sum(len(s) for s in slices)
                if not slices:
                    continue
                if pf_thread is not None and \
                        any(not c.exhausted for c in cursors):
                    # round k's inputs are sliced off: decode round
                    # k+1's segments while k merges + compresses
                    pf_done.clear()
                    pf_q.put(per_cursor)
                round_bytes = int(sum(len(s) for s in slices)
                                  * bytes_per_cell)
                if progress is not None:
                    progress.set_phase("merge")
                    progress.add_read(round_bytes)
                if self.limiter is not None:
                    self.limiter.acquire(round_bytes)
                if self.engine == "device":
                    h = submit_merge_resident(
                        slices, gc_before=gc_before, now=now,
                        purgeable_ts_fn=controller.purgeable_ts_fn,
                        prof=prof, device=self.device,
                        min_bucket=self.min_bucket)
                    if h.mode == "resident":
                        self.rounds_by_device[h.dev] += 1
                    pending.append((shard, h))
                    while len(pending) >= self.PIPELINE_DEPTH:
                        collect_oldest()
                else:
                    merged = merge_fn(slices, gc_before=gc_before, now=now,
                                      purgeable_ts_fn=controller.purgeable_ts_fn)
                    if len(merged):
                        wq_put((shard, merged))
            stop_prefetch()
            pf_thread = None
            while pending:
                collect_oldest()
            with led_wq.stall("compaction.writeq.drain", prof=prof,
                              key="writeq_put_wait"):
                wq.put(None)
                wthread.join()
            if werr:
                raise werr[0]
            cells_written = wstate["cells"]
            writer = wstate["writer"]
            if progress is not None:
                progress.set_phase("seal")
            with led.stage("seal").busy("compaction.seal", prof=prof,
                                        key="seal"):
                writer.finish()
            if progress is not None:
                # the final pool drain's tail (write_loop is joined,
                # so "credited" is stable here)
                progress.add_written(
                    writer.data_offset() - wstate["credited"])
            with pipeline_ledger.span("compaction.commit", prof=prof,
                                      key="commit"):
                new_readers.append(SSTableReader(writer.desc, table))
                for r in self.inputs:
                    txn.track_obsolete(r.desc.generation)
                # empty outputs (everything purged) die in the same txn
                live_new = []
                for r in new_readers:
                    if r.n_cells > 0:
                        live_new.append(r)
                    else:
                        r.close()
                        txn.track_obsolete(r.desc.generation)
                # COMMIT first (a failure here must roll back cleanly while the
                # tracker still serves the inputs), then swap the live view;
                # input files may already be unlinked but their open fds keep
                # serving in-flight reads. Inputs are RELEASED, not closed
                # (reference SSTableReader ref-counting, utils/concurrent/Ref).
                txn.commit()
                cfs.tracker.replace(self.inputs, live_new)
                if cfs.row_cache is not None:
                    # compaction-generation change: the read fast lane pins
                    # cached merges to the sstable set they were computed
                    # from (storage/row_cache.py invalidation contract)
                    cfs.row_cache.clear()
                for r in self.inputs:
                    r.release()
                if getattr(cfs, "index_build_fn", None) is not None:
                    # eager attached-index components for the outputs, so
                    # the first indexed query after compaction never pays
                    # the build storm (build_eager never raises)
                    for r in live_new:
                        cfs.index_build_fn(r)
                if getattr(cfs, "index_listeners", ()):
                    cfs.sstables_changed(self.inputs, live_new)
        except BaseException as exc:
            pending.clear()
            stop_prefetch()
            if wthread is not None and wthread.is_alive():
                # blocking put is safe: the consumer is either processing
                # or draining toward the sentinel — put_nowait could drop
                # the sentinel on a full queue and leave the thread stuck
                wq.put(None)
                wthread.join(timeout=30.0)
            for w in writers:
                try:
                    w.abort()
                except Exception:
                    pass
            for r in new_readers:
                r.close()
            txn.abort()   # no-op if the COMMIT record already landed
            self._handle_corrupt_input(exc)
            raise

        dt = time.time() - t0
        if prof:
            # per-phase wall seconds aggregate process-wide: the
            # system_views.device_profile vtable and bench.py's
            # kernel_profile section read them alongside kernel stats
            from ..service.profiling import GLOBAL as kprof
            kprof.add_phases(prof)
        bytes_written = sum(r.data_size for r in new_readers)
        stats = {
            "inputs": len(self.inputs),
            "outputs": len([r for r in new_readers if r.n_cells > 0]),
            "bytes_read": bytes_read,
            "bytes_written": bytes_written,
            "cells_read": cells_read,
            "cells_written": cells_written,
            "seconds": dt,
            "read_mib_s": bytes_read / dt / 2**20 if dt > 0 else 0,
            "write_mib_s": bytes_written / dt / 2**20 if dt > 0 else 0,
            # what merged, and whether the task chose it itself
            "engine": self.engine,
            "engine_chosen": self.engine_chosen,
            # where its device programs ran: {device id: calls}
            "rounds_by_device": dict(self.rounds_by_device),
            "lane_dispatches_by_device": dict(self.lane_dispatches_by_device),
        }
        # history ring + amplification counters in one locked fold
        # (storage/table.py record_compaction: the append shares a
        # lock with the capacity-knob swap, and the byte totals also
        # land on the monotonic counters that survive ring eviction);
        # bare test doubles without the method keep the raw append
        rec = getattr(cfs, "record_compaction", None)
        if rec is not None:
            rec(stats)
        elif cfs.compaction_history is not None:
            cfs.compaction_history.append(stats)
        return stats
