"""chip_smoke.py's control flow, guarded by tier-1.

The script proves the device path on a TPU and refuses to run anywhere
else, so its phases would otherwise rot unseen between chip runs. Here
they run at a tiny size on the CPU through `chip_smoke.run(...,
check_platform=False)` — the entry that skips the platform check and
nothing else — plus the two ways the script must FAIL: a device program
that fell back to the host, and the driver's own invocation on a machine
with no chip."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def cache_config_restored():
    """run() points jax's persistent compile cache at the checkout, as
    every entry point does; the worker's later tests get their setting
    back."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


def _lines(capsys) -> list:
    return [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]


@pytest.mark.parametrize("chips,phases", [
    (1, ["startup", "wire", "compact", "scan", "ann", "summary"]),
    (4, ["startup", "mesh_compact", "sharded_merge", "summary"]),
])
def test_phases_pass_at_tiny_size(smoke, capsys, cache_config_restored,
                                  chips, phases):
    rc = smoke.run(seed=3, chips=chips, sizes=smoke.TINY,
                   check_platform=False)
    lines = _lines(capsys)
    assert rc == 0, lines[-1]
    assert [ln["phase"] for ln in lines[:-1]] == phases
    assert all(ln["ok"] for ln in lines)
    # the contract line: these keys, nothing more
    last = lines[-1]
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["count"] >= chips
    by = {ln["phase"]: ln for ln in lines[:-1]}
    assert by["startup"]["x64"] is False
    if chips == 1:
        c = by["compact"]
        assert c["device_cold"]["engine"] == "device"
        assert c["numpy"]["engine"] == "numpy"
        assert c["host_rounds"] == 0 and c["device_rounds"] >= 1
        assert c["full_segments"] >= 1      # serialize + compress ran
        assert c["programs_cold"]["write.compress"]["calls"] \
            == c["full_segments"]
        assert c["programs_warm"]["merge.resident"]["compiles"] == 0
        assert by["scan"]["device_gate_on"]["host_segments"] == 0
        assert by["scan"]["device_gate_off"]["device_segments"] == 0
    else:
        assert len(set(by["mesh_compact"]["lanes_on_devices"])) == 4
        assert by["sharded_merge"]["psum_stats"][0] \
            == by["sharded_merge"]["cells_kept"]


def test_a_fallback_to_the_host_fails_the_smoke(smoke, capsys, monkeypatch,
                                                cache_config_restored):
    """The scan mask kernel raises; the lane falls back per segment and
    still answers correctly — which is exactly what the smoke must not
    let pass."""
    from cassandra_tpu.ops import device_scan

    def boom(keys, pred):
        raise RuntimeError("injected kernel failure")
    monkeypatch.setattr(device_scan, "mask_device", boom)
    rc = smoke.run(seed=3, chips=1, sizes=smoke.TINY, check_platform=False)
    lines = _lines(capsys)
    assert rc != 0
    assert lines[-1]["phase"] == "scan" and lines[-1]["ok"] is False
    assert "host" in lines[-1]["error"]
    assert not any(set(ln) == {"ok", "device"} for ln in lines)


def test_driver_invocation_without_a_chip_exits_nonzero():
    """`python3 chip_smoke.py` as the driver runs it, on this machine:
    non-zero, and no contract line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    out = [json.loads(line) for line in p.stdout.splitlines()
           if line.startswith("{")]
    assert out and out[-1]["ok"] is False and "tpu" in out[-1]["error"]
    assert not any(ln.get("ok") and "device" in ln and "phase" not in ln
                   for ln in out)
