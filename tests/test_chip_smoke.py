"""``chip_smoke.py`` on the CPU: its tiny-shape rehearsals pass every check,
and without a TPU it fails and prints no result line."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


def _run(*args, devices=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    if devices:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run(
        [sys.executable, str(SCRIPT), *args], env=env, capture_output=True,
        text=True, timeout=600,
    )


def test_fails_without_a_tpu():
    out = _run()
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


@pytest.mark.parametrize("chips", [1, 4])
def test_rehearsal_passes_every_check(chips):
    out = _run("--rehearse", "--chips", str(chips), devices=chips)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert "[FAIL]" not in out.stdout
    assert out.stdout.count("[ok]") >= 4
    assert "rehearsal passed" in out.stdout
    assert '"ok"' not in out.stdout
