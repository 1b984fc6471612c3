"""manual_sp loss/grad parity with the baseline stack (§Perf H3 it6)."""
import os
import subprocess
import sys
from pathlib import Path

import pytest


@pytest.mark.slow
def test_manual_sp_multidevice():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    r = subprocess.run(
        [sys.executable, str(Path(__file__).parent / "manual_sp_check.py")],
        capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "ALL MANUAL_SP CHECKS PASSED" in r.stdout
