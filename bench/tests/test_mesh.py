"""The four-chip training cell on four virtual CPU devices
(``mesh_rehearsal.py``): a sound run is correct, and each fault the cell
can have, and the fp8 control, come out not correct under its limits."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CASES = {"sound": True, "state_unchanged": False, "half_batch": False,
         "no_exchange": False, "fp8_control": False}


@pytest.mark.parametrize("case", list(CASES))
def test_mesh_rehearsal(case):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    run = subprocess.run([sys.executable, "-m", "bench.tests.mesh_rehearsal",
                          case], cwd=ROOT, env=env, capture_output=True,
                         text=True, timeout=900)
    assert run.returncode == 0, run.stderr[-4000:]
    out = json.loads(run.stdout.splitlines()[-1])
    assert out["correct"] is CASES[case], out["checks"]
