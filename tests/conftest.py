"""Test configuration: the suite runs on the CPU with an 8-device virtual
mesh, so sharding tests need no hardware (mirrors the reference's in-JVM
dtest approach of simulating a cluster in one process; see SURVEY.md
section 4). The chip is reached through `chip_smoke.py`, never through
pytest; tests/test_tpu_compile.py compiles for a described chip.

x64 stays OFF, as in production (noded, bench.py, chip_smoke.py): the
device programs are written for 32-bit lanes."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_sstable_caches():
    """The chunk cache and the key cache are process-global and keyed by
    (directory, generation): an sstable's identity for the life of a node.
    Since PR 33 pytest removes a passing test's tmp_path at once
    (pytest.ini: the disk filled otherwise) and hands its numbered name to
    the next test, so two tests can hold different sstables under one
    directory and generation. Each test starts with both caches empty."""
    from cassandra_tpu.storage import chunk_cache, key_cache
    chunk_cache.GLOBAL.clear()
    key_cache.GLOBAL.clear()


@pytest.fixture(autouse=True)
def _no_probe_left_by_an_earlier_test():
    """The GIL probe (utils/gil_probe.py) beats while any engine of the
    process is open, and writes into the one span ring. A test that left
    an engine open would leave it beating into the rings of the tests
    after it, which count records: each test starts with no demand."""
    from cassandra_tpu.utils import gil_probe
    for owner in list(gil_probe.GLOBAL._demands):
        gil_probe.GLOBAL.set_demand(owner, False)
