"""``chip_smoke.py`` refuses to report a result without a TPU, and outside
a checkout of the repo."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

SMOKE = Path(__file__).resolve().parents[1] / "chip_smoke.py"


def _run(script: Path, cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(script)], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def _no_result(proc: subprocess.CompletedProcess) -> bool:
    return proc.returncode != 0 and '"ok"' not in proc.stdout


def test_smoke_fails_without_tpu(tmp_path):
    proc = _run(SMOKE, tmp_path)
    assert _no_result(proc), proc.stdout
    assert "no TPU" in proc.stderr


def test_smoke_fails_outside_a_checkout(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SMOKE, alone)
    proc = _run(alone, tmp_path)
    assert _no_result(proc), proc.stdout
