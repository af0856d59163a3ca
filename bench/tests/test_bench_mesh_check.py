"""``correct`` for the four-chip mesh cell, on four virtual CPU devices in a
process of their own: the sound program passes; a state left unchanged and
the gradient exchange between chips left out both fail."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = Path(__file__).with_name("mesh_faults.py")


@pytest.fixture(scope="module")
def verdicts():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, str(SCRIPT)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_four_virtual_devices(verdicts):
    assert verdicts["devices"] == 4


def test_sound_mesh_program_is_correct(verdicts):
    assert verdicts["sound"] is True


@pytest.mark.parametrize("fault", ["unchanged_state", "no_exchange"])
def test_mesh_fault_is_not_correct(verdicts, fault):
    assert verdicts[fault] is False
