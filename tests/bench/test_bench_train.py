"""The training driver at a tiny size on four forced CPU devices, in a
child process (the devices are forced before JAX starts): a sound run is
correct; the fp8 control, each fault planted in the reference, and each
fault planted in the program underneath the window are not."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = Path(__file__).parent / "train_checks.py"


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, str(SCRIPT)], capture_output=True,
                          text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rows = [json.loads(x) for x in proc.stdout.splitlines()
            if x.startswith("{")]
    return {r["case"]: r for r in rows}


def test_a_sound_training_run_is_correct(cases):
    assert cases["sound"]["correct"]
    assert cases["sound"]["steps"] >= 1


@pytest.mark.parametrize("case", ["reference_control",
                                  "reference_half_batch",
                                  "reference_no_exchange"])
def test_control_and_faults_in_the_reference_fail(cases, case):
    assert not cases[case]["correct"], cases[case]["readings"]


def test_the_control_goes_through_the_runs_own_checks(cases):
    """The driver's ``control_checks``: the control's reading of each
    compared number beside the run's own limit."""
    sound, control = cases["sound"], cases["reference_control"]
    got = {c["name"]: c for c in control["checks"]}
    assert set(got) == set(sound["readings"])
    for name, check in got.items():
        assert check["value"] == control["readings"][name]
    assert {n: c["limit"] for n, c in got.items()} == sound["limits"]


@pytest.mark.parametrize("case", ["unchanged", "half_batch", "no_exchange"])
def test_faults_under_the_window_fail(cases, case):
    assert not cases[case]["correct"], cases[case]["readings"]


def test_a_state_left_unchanged_reads_one(cases):
    """The first moment stays zero and no parameter moves: the first
    gradient and the change each read 1 off."""
    r = cases["unchanged"]["readings"]
    assert r["grad_norm_gap"] == pytest.approx(1.0)
    assert r["change_norm_gap"] == pytest.approx(1.0)
