"""Device-side LZ4 block compression for the compaction write path.

LUDA's endgame (PAPERS.md, arxiv 2004.03054): compaction blocks leave
the accelerator already compressed and the host io thread is reduced
to a pwrite pump. The precondition is determinism — every
check_compaction_ab.py leg must stay byte-identical for any pool size
× device on/off — so the native encoder (ops/native/codec.cpp
`lz4_compress`) is a fixed POLICY, not a heuristic: at each visited
position take the longest forward run over the DISTANCES candidate
set (ties → smallest distance), accept iff ≥ MINMATCH, else advance
one byte. A hash-table matcher's output depends on probe/insertion
order, which a data-parallel scan cannot replay; the policy's argmax
is order-free and maps to one vectorized shifted-equality pass per
candidate distance — a single fused jax program over a full
segment's device-resident blocks (lane shuffle + order check + both
match scans).

The LZ4 wire emission (greedy parse + token stream) is inherently
sequential but cheap — O(emitted sequences), not O(bytes × distances)
— so it runs host-side from the pulled (best_len, best_d) arrays.

Three implementations, one contract:
  native  lz4_compress (codec.cpp)          — host CompressorPool legs
  numpy   match_scan_np + emit_block        — reference; payload block
  jax     segment_scan_kernel + emit_block  — device META/lane blocks
Byte equality across all three is pinned by tests/test_device_compress
and the check_compaction_ab.py `device_compress*` legs.
"""
from __future__ import annotations

import zlib

import numpy as np
import jax
import jax.numpy as jnp

MINMATCH = 4

# Must stay identical to LZ4_DIST in ops/native/codec.cpp: all short
# lags 1..64 (columnar 25-byte META strides, shuffled lane byte-planes,
# periodic text) plus power-of-two long lags up to the format's 64KiB
# window. Ascending order is load-bearing: ties resolve to the
# SMALLEST distance.
DISTANCES = tuple(range(1, 65)) + (128, 256, 512, 1024, 2048, 4096,
                                   8192, 16384, 32768)


# ------------------------------------------------------------- scans -----

def match_scan_np(src: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Reference policy match scan: for every position, the longest
    forward run over DISTANCES (ties → smallest d). Runs shorter than
    MINMATCH may appear in best_len; the parse ignores them, so the
    native encoder's 4-byte prefilter and this full scan emit the same
    sequences."""
    src = np.asarray(src, dtype=np.uint8).reshape(-1)
    n = src.size
    best_len = np.zeros(n, dtype=np.int64)
    best_d = np.zeros(n, dtype=np.int64)
    idx = np.arange(n, dtype=np.int64)
    for d in DISTANCES:
        if d >= n:
            break
        e = src[d:] == src[:-d]
        nxt = np.where(e, n, idx[d:])
        nxt = np.minimum.accumulate(nxt[::-1])[::-1]
        run = nxt - idx[d:]
        bl = best_len[d:]
        upd = run > bl
        bl[upd] = run[upd]
        best_d[d:][upd] = d
    return best_len, best_d


def _policy_scan(src, n):
    """Traced body of the policy scan; one shifted-equality pass +
    reversed cummin per candidate distance. The distances run as ONE
    lax.scan body with a dynamic shift, not 73 unrolled copies: the
    unrolled program took the TPU compiler 104 s and 443 MB of code
    for one segment (CHANGES.md PR 21), the loop compiles in seconds.
    Ascending order keeps ties on the smallest distance."""
    idx = jnp.arange(n, dtype=jnp.int32)
    zero = jnp.zeros((n,), dtype=jnp.int32)
    dists = [d for d in DISTANCES if d < n]
    if not dists:
        return zero, zero

    def step(best, d):
        best_len, best_d = best
        # roll: prev[i] = src[i - d] wherever i >= d
        e = (idx >= d) & (src == jnp.roll(src, d))
        nxt = jnp.where(e, jnp.int32(n), idx)
        nxt = jax.lax.cummin(nxt, axis=0, reverse=True)
        run = nxt - idx
        upd = run > best_len
        return (jnp.where(upd, run, best_len),
                jnp.where(upd, d, best_d)), None

    best, _ = jax.lax.scan(step, (zero, zero),
                           jnp.asarray(dists, dtype=jnp.int32))
    return best


@jax.jit
def _scan_kernel(src):
    return _policy_scan(src, src.shape[0])


@jax.jit
def segment_scan_kernel(meta_u8, lanes_u32):
    """The fused device program for one full segment: lane shuffle to
    byte planes (segment_pack's byte_transpose, via the LE u32→u8
    bitcast), the u32-lexicographic order check, and the policy match
    scan over both compressible device-resident blocks. Returns
    (planes, meta_best_len, meta_best_d, lane_best_len, lane_best_d,
    order_ok)."""
    n, k = lanes_u32.shape
    # (n, k, 4) bytes -> plane (lane, byte) major, cell minor. One
    # transpose; merging the 4-wide minor dimension first (reshape to
    # (n, 4k), then .T) is what the TPU compiler is slow to compile
    planes = jax.lax.bitcast_convert_type(lanes_u32, jnp.uint8)
    planes = jnp.transpose(planes, (1, 2, 0)).reshape(-1)
    a = lanes_u32[:-1]
    b = lanes_u32[1:]
    neq = a != b
    firstc = jnp.argmax(neq, axis=1)
    rows = jnp.arange(n - 1)
    bad = neq.any(axis=1) & (b[rows, firstc] < a[rows, firstc])
    order_ok = ~bad.any()
    mbl, mbd = _policy_scan(meta_u8, meta_u8.shape[0])
    lbl, lbd = _policy_scan(planes, planes.shape[0])
    return planes, mbl, mbd, lbl, lbd, order_ok


# ---------------------------------------------------------- emission -----

def emit_block(src, best_len, best_d, cap: int):
    """LZ4 block-format emission from policy match arrays. Returns the
    compressed bytes, or None when the output would overrun `cap` —
    including the native encoder's slightly conservative per-sequence
    `need` bound, replicated exactly so the compress-vs-raw decision
    lands on the same side at the boundary."""
    src = np.asarray(src, dtype=np.uint8).reshape(-1)
    n = src.size
    if n == 0:
        return b"\x00" if cap >= 1 else None
    mem = src.tobytes()
    out = bytearray()
    pos = 0
    anchor = 0
    mf = n - 12
    if mf > 0:
        bl = np.asarray(best_len, dtype=np.int64)[:mf]
        bd = np.asarray(best_d, dtype=np.int64)[:mf]
        cand = np.flatnonzero(bl >= MINMATCH)
        while True:
            j = int(np.searchsorted(cand, pos))
            if j >= cand.size:
                break
            p = int(cand[j])
            m = int(bl[p])
            # clamp to the literal tail; p < n-12 keeps m >= MINMATCH
            if m > n - 5 - p:
                m = n - 5 - p
            d = int(bd[p])
            lit = p - anchor
            ml = m - MINMATCH
            need = 1 + lit // 255 + 1 + lit + 2 + ml // 255 + 1
            if len(out) + need > cap:
                return None
            out.append(((15 if lit >= 15 else lit) << 4)
                       | (15 if ml >= 15 else ml))
            if lit >= 15:
                l = lit - 15
                while l >= 255:
                    out.append(255)
                    l -= 255
                out.append(l)
            out += mem[anchor:p]
            out.append(d & 0xFF)
            out.append(d >> 8)
            if ml >= 15:
                l = ml - 15
                while l >= 255:
                    out.append(255)
                    l -= 255
                out.append(l)
            pos = p + m
            anchor = pos
    lit = n - anchor
    need = 1 + lit // 255 + 1 + lit
    if len(out) + need > cap:
        return None
    out.append((15 if lit >= 15 else lit) << 4)
    if lit >= 15:
        l = lit - 15
        while l >= 255:
            out.append(255)
            l -= 255
        out.append(l)
    out += mem[anchor:]
    return bytes(out)


def compress_np(data, cap: int | None = None):
    """Full numpy reference: scan + emit. Equals the native
    lz4_compress byte-for-byte (tests pin this)."""
    src = np.frombuffer(bytes(data), dtype=np.uint8)
    if cap is None:
        cap = src.size + src.size // 255 + 16
    bl, bd = match_scan_np(src)
    return emit_block(src, bl, bd, cap)


def compress_jax(data, cap: int | None = None):
    """Device scan + host emit (test entry; production goes through
    segment_scan_kernel so the whole segment is one program)."""
    src = np.frombuffer(bytes(data), dtype=np.uint8)
    if cap is None:
        cap = src.size + src.size // 255 + 16
    if src.size == 0:
        return emit_block(src, src, src, cap)
    bl, bd = _scan_kernel(jnp.asarray(src))
    return emit_block(src, np.asarray(bl), np.asarray(bd), cap)


# ------------------------------------------------------ segment pack -----

def pack_device_segment(meta, planes, scans, payload, attempt,
                        max_compressed_length: int):
    """segment_pack's compress-or-raw placement, replicated from device
    scan results: returns (total, sizes, crcs, parts) where parts are
    the stored bytes of the (META, lanes, payload) blocks in order.
    `planes` is the lane block already shuffled to byte planes (its
    stored form); `scans` carries the device (best_len, best_d) pairs
    for META and planes, and the payload block — host memory — scans
    through the numpy reference on demand. The compress-vs-raw rule is
    segment_pack's verbatim: compressed iff the emission fits
    cap = min(srcLen, max_compressed_length) AND is shorter than both
    bounds."""
    maxlen = int(max_compressed_length)
    blocks = ((meta, scans[0]), (planes, scans[1]), (payload, None))
    parts, sizes, crcs = [], [], []
    for (blk, scan), att in zip(blocks, attempt):
        raw = np.asarray(blk, dtype=np.uint8).reshape(-1)
        stored = None
        if att:
            cap = min(raw.size, maxlen)
            if scan is None:
                scan = match_scan_np(raw)
            c = emit_block(raw, scan[0], scan[1], cap)
            if c is not None and len(c) < raw.size and len(c) < maxlen:
                stored = c
        if stored is None:
            stored = raw.tobytes()
        parts.append(stored)
        sizes.append(len(stored))
        crcs.append(zlib.crc32(stored))
    return sum(sizes), sizes, crcs, parts
