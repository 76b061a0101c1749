"""The main-path device programs, compiled for the chip they are meant for.

Every other test compiles these programs with XLA's CPU backend. Here the
TPU's own compiler compiles them, at the sizes the served path uses, for a
DESCRIBED v5e:2x2 — a chip that is not attached (on-chip-measurement guide,
section 2). A compile that passes says the chip's compiler accepts the
program and that it fits the device's memory; it says nothing about results
or times. `chip_smoke.py` is the run on the real chip.

Rules this file keeps (several xdist workers import it, and only one process
may hold the TPU library): the topology is described inside a module-scoped,
non-autouse fixture — never at import, in a skipif or in parametrize — and
everything built from it is built in a fixture or a test. The persistent
compile cache is off around the compiles: an entry written for a described
chip cannot be read back without one. x64 stays off, as in production.
"""
import time

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

ROUND = 1 << 19          # one ROUND_CELLS_DEVICE round, padded (task.py)
SEGMENT = 1 << 16        # storage/sstable/format.py SEGMENT_CELLS
LANES = 13               # (id int, c int, v blob): lanes_for_table
META_BYTES = 25          # writer.lay_meta_block bytes per cell
META_WORDS = 6           # u32 planes write.serialize returns per cell
ANN_ROWS, ANN_DIM = 100_000, 128

# (program, compile seconds, generated code bytes, temp bytes) — printed
# with `pytest -s`; CHANGES.md's compile-rehearsal table comes from it
REPORT: list = []
# what the rehearsal printed at PR 36's parent, shown beside the new
# figures: (compile seconds, code MB, temp MB) with the twenty separate
# gathers outside the passes (eighteen of them one-lane)
BEFORE = {"merge.resident": (36.1, 171.6, 36.1),
          # the serialize while it laid out the block's bytes itself
          "write.serialize": (1.4, 1.2, 2.5)}
# merge.resident's temporaries at 2^19 x 13 as the compiler counts them:
# 24.1 MB with the two stacked matrices 23 and 22 words wide (PR 36's
# step 0, here and on the chip's host; the parent 36.1). At 26 words the
# same program took 73.8 MB and, at 2^20, a row gather four times slower:
# a stacked matrix wider than 24 words passes this.
RESIDENT_TEMP_LIMIT_MB = 48


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()
    for row in REPORT:
        before = BEFORE.get(row[0])
        print("tpu-compile %-24s %6.1f s  code %5.1f MB  temp %6.1f MB"
              % row + ("  (was %.1f s, %.1f MB, %.1f MB)" % before
                       if before else ""))


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(name, jitted, *args, **kwargs):
    """AOT-compile the jit itself (the registry wrapper is a host-side
    timer around it) and record what the compiler reports."""
    fn = jitted if hasattr(jitted, "lower") else jitted.__wrapped__
    t0 = time.perf_counter()
    compiled = fn.lower(*args, **kwargs).compile()
    dt = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    REPORT.append((name, dt, mem.generated_code_size_in_bytes / 2**20,
                   mem.temp_size_in_bytes / 2**20))
    return compiled


def _resident_operands(sh, n=ROUND, k=LANES):
    """ops/device_write.build_resident_operands' shapes."""
    def a(dt, *tail):
        return jax.ShapeDtypeStruct((n,) + tail, dt, sharding=sh)
    u32, i32 = jnp.uint32, jnp.int32
    scalar = jax.ShapeDtypeStruct((), i32, sharding=sh)
    return {"lanes": a(u32, k), "valid": a(u32), "ts_h": a(u32),
            "ts_l": a(u32), "death": a(u32), "cdel": a(u32),
            "ldt": a(i32), "expiring": a(u32), "purge_h": a(u32),
            "purge_l": a(u32), "gc_before": scalar, "now": scalar,
            "flags8": a(jnp.uint8), "ttl": a(i32), "fl": a(u32),
            "vr": a(u32)}


def _compile_resident(name, sh):
    from cassandra_tpu.ops.device_write import _resident_program
    return _compile(name, _resident_program, _resident_operands(sh))


def test_lsd_pass_round(one_chip):
    from cassandra_tpu.ops.merge import _lsd_pass
    key = jax.ShapeDtypeStruct((ROUND,), jnp.uint32, sharding=one_chip)
    perm = jax.ShapeDtypeStruct((ROUND,), jnp.int32, sharding=one_chip)
    _compile("_lsd_pass", _lsd_pass, key, perm)


def test_resident_round(one_chip):
    compiled = _compile_resident("merge.resident", one_chip)
    # every pass but `valid`'s sits under its own skip
    assert compiled.as_text().count(" conditional(") == LANES + 2
    n_passes = compiled.out_info[3]
    assert n_passes.shape == () and n_passes.dtype == jnp.int32
    # the columns travel as rows of two stacked matrices: what those cost
    temp_mb = compiled.memory_analysis().temp_size_in_bytes / 2**20
    assert temp_mb < RESIDENT_TEMP_LIMIT_MB, temp_mb


def test_meta_block_segment(one_chip):
    from cassandra_tpu.ops.device_write import _meta_block_kernel

    def a(dt):
        return jax.ShapeDtypeStruct((SEGMENT,), dt, sharding=one_chip)
    u32, i32 = jnp.uint32, jnp.int32
    compiled = _compile("write.serialize", _meta_block_kernel, a(u32),
                        a(u32), a(i32), a(i32), a(jnp.uint8), a(u32),
                        a(u32))
    words, stats = compiled.out_info
    # the block's six word planes (its flags bytes come from the
    # segment's columns), laid out by the host
    assert words.shape == (SEGMENT * META_WORDS,) and stats.shape == (7,)
    assert words.dtype == jnp.uint32
    # the program computes words and lays out no bytes: a gather here
    # is paid per index on the chip (≈ 15 ms a segment for a per-byte
    # layout on a v5e)
    hlo = compiled.as_text()
    assert "gather(" not in hlo and "iota(" not in hlo


@pytest.mark.parametrize("bucket", [ROUND, 2 * ROUND])
def test_lane_append_round(one_chip, bucket):
    """The write lane's per-round program at the two buckets a full
    round runs at: the carry and the round in, every full segment the
    pair can hold and the next carry out."""
    from cassandra_tpu.ops.device_write import RESIDENT_COLS, _lane_append
    dtypes = {"lanes": jnp.uint32, "ts_h": jnp.uint32, "ts_l": jnp.uint32,
              "ldt": jnp.int32, "ttl": jnp.int32, "flags8": jnp.uint8,
              "fl": jnp.uint32, "vr": jnp.uint32}

    def cols(n):
        return {k: jax.ShapeDtypeStruct(
            (n, LANES) if k == "lanes" else (n,), dtypes[k],
            sharding=one_chip) for k in RESIDENT_COLS}
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = _compile(f"write.append@{bucket >> 10}k", _lane_append,
                        cols(SEGMENT), cols(bucket), scalar, scalar)
    segments, carry = compiled.out_info
    assert len(segments) == (SEGMENT - 1 + bucket) // SEGMENT
    assert carry["lanes"].shape == (SEGMENT, LANES)


@pytest.mark.slow   # 51 s: the TPU compiler spends 35 s on the reverse
# cummin at META's 1.6 M bytes (5 s at the lane block's 3.4 M); the file
# must stay near three minutes on one worker. chip_smoke.py runs it.
def test_compress_segment(one_chip):
    from cassandra_tpu.ops.device_compress import segment_scan_kernel
    meta = jax.ShapeDtypeStruct((SEGMENT * META_BYTES,), jnp.uint8,
                                sharding=one_chip)
    lanes = jax.ShapeDtypeStruct((SEGMENT, LANES), jnp.uint32,
                                 sharding=one_chip)
    _compile("write.compress", segment_scan_kernel, meta, lanes)


def test_scan_kernels_segment(one_chip):
    from cassandra_tpu.ops.device_scan import _kernels
    kernels, fold = _kernels()

    def a(n, dt=jnp.uint32):
        return jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)
    _compile("scan.mask_lt", kernels["lt"],
             a(SEGMENT), a(SEGMENT), a(1), a(1))
    _compile("scan.mask_in", kernels["in"],
             a(SEGMENT), a(SEGMENT), a(8), a(8))
    _compile("scan.fold", fold, a(SEGMENT), a(SEGMENT),
             a(SEGMENT, jnp.bool_))


def test_ann_topk(one_chip):
    from cassandra_tpu.index.manager import ann_program
    m = jax.ShapeDtypeStruct((ANN_ROWS, ANN_DIM), jnp.float32,
                             sharding=one_chip)
    q = jax.ShapeDtypeStruct((ANN_DIM,), jnp.float32, sharding=one_chip)
    _compile("index.ann", ann_program(), m, q, k=10, similarity="cosine")


def test_ann_live_programs_at_the_streaming_cells_size(one_chip):
    """The masked score + top-k, the delta put and the mask update that a
    vector index under writes runs, at msturing_stream.ann_ingest's size:
    2,000,000 resident rows and the delta bucket the index sizes for them
    (index/manager.py _grow)."""
    from cassandra_tpu.index.manager import (PUT_ROWS, UNSET_SLOTS,
                                             _live_programs)
    rows, dim, cap = 2_000_000, 100, 1 << 15
    progs = _live_programs()

    def a(*shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _compile("index.ann.live", progs["live"], a(rows, dim),
             a(rows, dt=jnp.bool_), a(cap, dim), a(cap, dt=jnp.bool_),
             a(dim), k=10, similarity="euclidean")
    _compile("index.ann.delta_put", progs["delta_put"], a(cap, dim),
             a(cap, dt=jnp.bool_), a(PUT_ROWS, dt=jnp.int32),
             a(PUT_ROWS, dim))
    _compile("index.ann.unset", progs["unset"], a(rows, dt=jnp.bool_),
             a(UNSET_SLOTS, dt=jnp.int32))


def test_resident_round_on_a_lane_device(topo):
    """A mesh lane commits its shard's round to its own device
    (compaction/task.py `_mesh_produce`): the last of four lanes compiles
    the one-chip program with its operands on device 3."""
    d = topo.devices[3]
    compiled = _compile_resident("merge.resident@3",
                                 SingleDeviceSharding(d))
    (ops,), _ = compiled.input_shardings
    assert all(sh.device_set == {d} for sh in ops.values())
    assert compiled.as_text().count(" conditional(") == LANES + 2
