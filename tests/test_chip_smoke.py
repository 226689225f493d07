"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and its
phases pass at a tiny size with the kernels in interpret mode, so the script
the chip runs does not rot between chip runs."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Job(depth="resnet4", num_classes=4, image_size=8, clients=4,
                      samples_per_client=32, batch_size=16)


def test_refuses_to_run_without_a_tpu():
    res = subprocess.run(
        [sys.executable, "chip_smoke.py"], capture_output=True, text=True,
        cwd=ROOT, timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
    assert "no TPU" in res.stderr


def test_kernel_phase_tiny():
    res = chip_smoke.kernel_phase(TINY, interpret=True)
    assert 0 < res["trained_blocks"] < res["blocks"]
    assert res["frozen_exact"]


def test_rounds_phase_tiny(capsys):
    history = chip_smoke.rounds_phase(TINY, chip_smoke.CompileClock())
    assert [h["phase"] for h in history] == ["warmup", "partial", "partial"]
    assert [h["group"] for h in history] == [-1, 0, 1]
    assert all(h["seconds"] > 0 for h in history)
    assert "compile_s=" in capsys.readouterr().out


def test_engines_phase_tiny():
    d = chip_smoke.engines_phase(TINY)
    assert d["update_rel_l2"] <= chip_smoke.ENGINE_RTOL


def test_check_fails_loudly():
    with pytest.raises(SystemExit, match="FAILED"):
        chip_smoke.check(False, "boom")


_FOUR_DEVICE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
sys.path.insert(0, ".")
import chip_smoke
job = chip_smoke.Job(depth="resnet4", num_classes=4, image_size=8, clients=8,
                     samples_per_client=16, batch_size=16)
print(json.dumps(chip_smoke.four_chip_phase(job)))
"""


def test_four_chip_phase_on_four_host_devices():
    res = subprocess.run(
        [sys.executable, "-c", _FOUR_DEVICE_SCRIPT], capture_output=True,
        text=True, cwd=ROOT, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-3000:]
    assert "0:2 clients, 1:2 clients, 2:2 clients, 3:2 clients" in res.stdout
    d = json.loads(res.stdout.strip().splitlines()[-1])
    assert d["update_rel_l2"] <= chip_smoke.ENGINE_RTOL
