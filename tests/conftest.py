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
