"""EP all-to-all MoE (§Perf H2): numerics in a forced-8-device subprocess."""
import os
import subprocess
import sys
from pathlib import Path

import pytest


@pytest.mark.slow
def test_ep_moe_multidevice():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    r = subprocess.run(
        [sys.executable, str(Path(__file__).parent / "ep_moe_check.py")],
        capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "ALL EP MOE CHECKS PASSED" in r.stdout
