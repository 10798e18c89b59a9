"""chip_smoke.py's phases, rehearsed off the chip at toy size.

The script itself accepts nothing but a TPU. Here its phases are called as
functions with the ``test`` preset, the flash kernel under the Pallas
interpreter and ``cpu`` named as the platform to expect, so that wrong paths,
arguments and control flow are found without chip time: every check the chip
run makes (budgets, demotion and re-promotion, loss agreement with the
reference, solo-equals-batched token ids, sharding across four devices) runs
here on small shapes. What it cannot show is that the kernels compile for the
chip (tests/test_chip_compile.py) or any time.
"""

import importlib.util
import os
import subprocess
import sys

import pytest

import ray_memory_management_tpu as rmt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMMON = dict(expect_platform="cpu", seed=0)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # workers import it by name
    spec.loader.exec_module(mod)
    yield mod
    sys.modules.pop("chip_smoke", None)


def test_device_plane_phase(smoke):
    out = smoke.device_plane_phase(
        rmt, n_arrays=4, array_bytes=4 * smoke.MIB,
        capacity_bytes=14 * smoke.MIB, timeout_s=120, **COMMON)
    assert out["after_puts"]["demotions"] == 1
    assert out["after_repromotion"]["demotions"] == 2
    assert out["after_donation"]["store_pinned_bytes"] == 8 * smoke.MIB
    assert out["cross_process_read"]["value_exact"]


def test_train_phase(smoke):
    out = smoke.train_phase(
        rmt, preset="test", batch=4, seq=64, steps=5,
        attention="flash-interpret", timeout_s=300, **COMMON)
    assert len(out["losses"]) == 5 and out["losses"][-1] < out["losses"][0]


def test_serve_phase(smoke):
    out = smoke.serve_phase(
        rmt, preset="test", prompt_len=32, budgets=[16, 4] * 4,
        max_new_tokens=16, timeout_s=300, **COMMON)
    assert out["solo_equals_batched"] and out["kv_peak_pinned_bytes"] > 0
    assert out["compilations_by_the_solo_repeat"] == 0


def test_sharded_train_phase_on_four_virtual_devices(smoke, monkeypatch):
    # the leased worker inherits the flag: four CPU devices stand in for
    # the four chips of the lease
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")
    out = smoke.sharded_train_phase(
        rmt, preset="test", batch=4, seq=64, steps=2,
        attention="flash-interpret", timeout_s=300, **COMMON)
    assert out["device"]["count"] == 4
    assert out["param_min_device_set"] == 4 and out["allreduce_exact"]


def test_command_line_refuses_a_machine_without_a_chip():
    """``python chip_smoke.py`` where jax finds no TPU: another exit code
    than 0, a last line with ok false that names the platform found, and
    no phase carried on on the CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    import json

    assert proc.returncode != 0
    verdict = json.loads(proc.stdout.strip().splitlines()[-1])
    assert verdict["ok"] is False
    assert "platform 'cpu'" in verdict["error"]
    assert '"phase": "device_plane"' not in proc.stdout
