"""The mesh cell on four host devices: a sound run is correct, and the
check fails a run with the exchange between chips left out."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.parametrize("fault,correct", [(None, True),
                                           ("no_exchange", False)])
def test_mesh_cell(fault, correct, tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "mesh_run.py"), str(tmp_path)]
        + ([fault] if fault else []),
        capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["device"]["count"] == 4
    assert result["correct"] is correct, (result["checks"], p.stderr[-1500:])
