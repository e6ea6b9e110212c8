"""One run of the mesh cell at the CPU size on four host devices, with
an optional planted fault (faults.py); prints the result as JSON.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python bench/tests/mesh_run.py OUT_DIR [FAULT]

The device count must be forced before JAX starts, so the test runs this
file in a subprocess.
"""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [HERE, BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import pytest  # noqa: E402

import faults  # noqa: E402
import harness  # noqa: E402
import tiny  # noqa: E402

CELL = "r18-multi-mesh4.paper-b512"


def main(out_dir, fault=None):
    with pytest.MonkeyPatch.context() as mp:
        tiny.shrink(mp, out_dir, traffic={"vehicles_per_round": 4})
        if fault:
            faults.plant(mp, fault)
        result = harness.run_cell(CELL, 11, 0.1, False,
                                  t0=time.perf_counter())
    print(json.dumps(result))


if __name__ == "__main__":
    main(*sys.argv[1:])
