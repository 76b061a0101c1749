"""Substrate tests: murmur3 (against known Cassandra token vectors),
varint round-trips, byte-comparable order properties, bloom filter."""
import random
import struct

import numpy as np
import pytest

from cassandra_tpu.utils import bloom, bytecomp, murmur3, varint


def test_murmur3_reference_vectors():
    # Cross-check scalar impl against the canonical smhasher vectors
    # (all-ASCII keys, where Cassandra's sign-extended tail == canonical):
    # murmur3 x64_128("hello", seed=0) h1 is well known.
    h1, h2 = murmur3.hash128(b"hello")
    assert (h1, h2) == (0xCBD8A7B341BD9B02, 0x5B1E906A48AE1D19)
    h1, h2 = murmur3.hash128(b"hello, world")
    assert (h1, h2) == (0x342FAC623A5EBC8E, 0x4CDCBC079642414D)
    h1, h2 = murmur3.hash128(b"The quick brown fox jumps over the lazy dog.")
    assert (h1, h2) == (0xCD99481F9EE902C9, 0x695DA1A38987B6E7)


def _java_tail_oracle(data: bytes) -> tuple[int, int]:
    """Independent slow model of the Java-signed-byte murmur3 variant used
    by Murmur3Partitioner (murmur3 x64/128 is public domain; the quirk is
    sign-extended tail bytes, MurmurHash.java:216-232)."""
    M = (1 << 64) - 1

    def rotl(x, r):
        return ((x << r) | (x >> (64 - r))) & M

    def fmix(k):
        k ^= k >> 33
        k = k * 0xFF51AFD7ED558CCD & M
        k ^= k >> 33
        k = k * 0xC4CEB9FE1A85EC53 & M
        k ^= k >> 33
        return k

    h1 = h2 = 0
    c1, c2 = 0x87C37B91114253D5, 0x4CF5AD432745937F
    nb = len(data) // 16
    for i in range(nb):
        k1 = int.from_bytes(data[i * 16: i * 16 + 8], "little")
        k2 = int.from_bytes(data[i * 16 + 8: i * 16 + 16], "little")
        k1 = rotl(k1 * c1 & M, 31) * c2 & M
        h1 = ((rotl(h1 ^ k1, 27) + h2) * 5 + 0x52DCE729) & M
        k2 = rotl(k2 * c2 & M, 33) * c1 & M
        h2 = ((rotl(h2 ^ k2, 31) + h1) * 5 + 0x38495AB5) & M
    tail = data[nb * 16:]
    signed = [b - 256 if b >= 128 else b for b in tail]
    k1 = k2 = 0
    if len(tail) >= 9:
        for i in range(8, len(tail)):
            k2 ^= (signed[i] << (8 * (i - 8))) & M
        h2 ^= rotl(k2 * c2 & M, 33) * c1 & M
    if tail:
        for i in range(min(8, len(tail))):
            k1 ^= (signed[i] << (8 * i)) & M
        h1 ^= rotl(k1 * c1 & M, 31) * c2 & M
    h1 ^= len(data)
    h2 ^= len(data)
    h1 = (h1 + h2) & M
    h2 = (h2 + h1) & M
    h1 = fmix(h1)
    h2 = fmix(h2)
    h1 = (h1 + h2) & M
    h2 = (h2 + h1) & M
    return h1, h2


def test_murmur3_java_signed_tail():
    rng = random.Random(11)
    keys = [b"\x80", b"\xff" * 15, b"\x80" * 9, bytes(range(200, 216)) + b"\xfe\x80"]
    keys += [bytes(rng.randrange(128, 256) for _ in range(n)) for n in range(1, 40)]
    for k in keys:
        assert murmur3.hash128(k) == _java_tail_oracle(k), k


def test_murmur3_batch_matches_scalar():
    rng = random.Random(42)
    keys = [bytes(rng.randrange(256) for _ in range(rng.randrange(0, 70)))
            for _ in range(300)]
    h1b, h2b = murmur3.hash128_batch(keys)
    for i, k in enumerate(keys):
        h1, h2 = murmur3.hash128(k)
        assert (int(h1b[i]), int(h2b[i])) == (h1, h2), f"key {i} len {len(k)}"


def test_tokens_batch():
    keys = [str(i).encode() for i in range(100)]
    toks = murmur3.tokens_of(keys)
    for i, k in enumerate(keys):
        assert int(toks[i]) == murmur3.token_of(k)


def test_varint_roundtrip():
    vals = [0, 1, 127, 128, 255, 256, 2**14, 2**21 - 1, 2**35, 2**56 + 17,
            2**63 - 1, 2**64 - 1]
    for v in vals:
        out = bytearray()
        varint.write_unsigned_vint(v, out)
        got, pos = varint.read_unsigned_vint(out, 0)
        assert got == v and pos == len(out), v
    for v in [0, -1, 1, -2**31, 2**31, -2**62, 2**62]:
        out = bytearray()
        varint.write_signed_vint(v, out)
        got, pos = varint.read_signed_vint(out, 0)
        assert got == v and pos == len(out), v


def test_varint_ordering_of_length():
    # single byte for < 128
    out = bytearray(); varint.write_unsigned_vint(127, out)
    assert len(out) == 1
    out = bytearray(); varint.write_unsigned_vint(128, out)
    assert len(out) == 2


def _sorted_check(pairs):
    """pairs: list of (value, encoding); assert encoding order == value order."""
    by_val = sorted(pairs, key=lambda p: p[0])
    by_enc = sorted(pairs, key=lambda p: p[1])
    assert [p[0] for p in by_val] == [p[0] for p in by_enc]


def test_bytecomp_int_order():
    rng = random.Random(7)
    vals = [rng.randrange(-2**63, 2**63) for _ in range(200)] + [0, 1, -1, 2**63 - 1, -2**63]
    _sorted_check([(v, bytecomp.encode_int(v, 8)) for v in vals])
    for v in vals:
        assert bytecomp.decode_int(bytecomp.encode_int(v, 8), 8) == v


def test_bytecomp_float_order():
    rng = random.Random(9)
    vals = [rng.uniform(-1e10, 1e10) for _ in range(200)] + [0.0, -0.0, 1.5, -1.5, 1e-300, -1e-300, float("inf"), float("-inf")]
    uniq = sorted(set(vals))
    _sorted_check([(v, bytecomp.encode_float(v)) for v in uniq])
    for v in uniq:
        assert bytecomp.decode_float(bytecomp.encode_float(v)) == v


def test_bytecomp_varint_order():
    vals = [0, 1, -1, 255, -255, 2**100, -2**100, 12345678901234567890,
            -12345678901234567890, 7, -7]
    _sorted_check([(v, bytecomp.encode_varint(v)) for v in vals])
    for v in vals:
        assert bytecomp.decode_varint(bytecomp.encode_varint(v)) == v


def test_composite_order_asc():
    rng = random.Random(3)
    tuples = []
    for _ in range(300):
        t = (bytes(rng.randrange(256) for _ in range(rng.randrange(0, 6))),
             bytes(rng.randrange(256) for _ in range(rng.randrange(0, 6))))
        tuples.append(t)
    tuples = sorted(set(tuples))
    _sorted_check([(t, bytecomp.encode_composite(list(t))) for t in tuples])
    for t in tuples:
        assert tuple(bytecomp.decode_composite(
            bytecomp.encode_composite(list(t)), 2)) == t


def test_composite_order_desc():
    vals = sorted({bytes([b]) * n for b in (0, 1, 127, 255) for n in (0, 1, 2, 3)})
    pairs = [((v,), bytecomp.encode_composite([v], [True])) for v in vals]
    # descending: encoding order must be REVERSE of value order
    by_val = sorted(pairs, key=lambda p: p[0], reverse=True)
    by_enc = sorted(pairs, key=lambda p: p[1])
    assert [p[0] for p in by_val] == [p[0] for p in by_enc]
    for v in vals:
        assert bytecomp.decode_composite(
            bytecomp.encode_composite([v], [True]), 1, [True]) == [v]


def test_composite_mixed_asc_desc():
    items = [(a, b) for a in (b"a", b"b") for b in (b"x", b"y", b"z")]
    enc = {t: bytecomp.encode_composite(list(t), [False, True]) for t in items}
    order = sorted(items, key=lambda t: enc[t])
    # expect a ASC then b DESC
    expected = sorted(items, key=lambda t: (t[0], [255 - c for c in t[1]]))
    assert order == expected


def test_bloom_filter():
    bf = bloom.BloomFilter.create(1000, 0.01)
    keys = [f"key-{i}".encode() for i in range(1000)]
    bf.add_batch(keys)
    assert bf.might_contain_batch(keys).all()
    other = [f"other-{i}".encode() for i in range(2000)]
    fp = int(np.sum(bf.might_contain_batch(other)))
    assert fp < 100  # ~1% target
    data = bf.serialize()
    bf2 = bloom.BloomFilter.deserialize(data)
    assert bf2.might_contain_batch(keys).all()


@pytest.mark.parametrize("bits,k", [(64, 1), (640, 3), (1024, 7),
                                    bloom.optimal_params(100, 0.01)])
def test_bloom_might_contain_hashes_is_might_contain(bits, k):
    """Keys hashed once by the caller get, from filters of any bits and
    k, the answer each filter gives the key itself: members and
    non-members, false positives included."""
    rng = random.Random(bits * 31 + k)
    keys = [bytes(rng.randrange(256) for _ in range(rng.choice(
        (0, 1, 15, 16, 17, 33, 185)))) for _ in range(300)]
    bf = bloom.BloomFilter(bits, k)
    bf.add_batch(keys[:60])
    h1, h2 = murmur3.hash128_batch(keys)
    got = bf.might_contain_hashes(h1, h2)
    assert got.dtype == bool
    assert got.tolist() == [bf.might_contain(key) for key in keys]
    assert got[:60].all() and not got.all()
    assert bf.might_contain_hashes(*murmur3.hash128_batch([])).shape == (0,)


@pytest.mark.parametrize("batch", [1 << 18, 7])
def test_bloom_add_blob_is_add_by_add(batch, monkeypatch):
    """Keys stored back to back, hashed in bounded batches, set the bits
    the scalar hash sets one key at a time — whatever the batch size."""
    monkeypatch.setattr(bloom.BloomFilter, "_BATCH", batch)
    rng = random.Random(5)
    keys = [bytes(rng.randrange(256) for _ in range(rng.choice(
        (0, 1, 9, 15, 16, 17, 23, 32, 33, 50)))) for _ in range(40)]
    bf = bloom.BloomFilter.create(100)
    bf.add_blob(b"".join(keys), np.array([len(k) for k in keys]))
    want = 0
    for key in keys:
        h1, h2 = murmur3.hash128(key)
        for j in range(bf.k):
            want |= 1 << (((h1 + j * h2) & (2**64 - 1)) % bf.bits)
    assert bf.words.tobytes() == want.to_bytes(bf.bits // 8, "little")
    bf.add_blob(b"", np.zeros(0, dtype=np.int64))      # no key: no change
    assert bf.words.tobytes() == want.to_bytes(bf.bits // 8, "little")
