"""The control at a CPU size: the reference computed in bfloat16, put in
the program's place, fails the cell's check. The chip readings of the
same script at each cell's own size set the limits' upper ends. The
images stay 32x32 here, and handover's batch 64: with smaller ones each
step's update is larger against bfloat16's resolution and the control
reads lower than at the cells' own sizes."""
import jax
import pytest

import control
import harness
import tiny


@pytest.mark.parametrize("cell,vehicles,batch", [
    ("r18-single.paper-b256", 2, 16),
    ("r18-handover.q8-b256", 2, 64),
    ("r18-multi-mesh4.paper-b512", 4, 16)])
def test_bf16_control_fails_the_check(cell, vehicles, batch, monkeypatch,
                                      tmp_path):
    tiny.shrink(monkeypatch, tmp_path,
                traffic={"vehicles_per_round": vehicles},
                cfg={"image_shape": [32, 32, 3], "batch_size": batch})
    got = control.readings(cell, 5, ["bf16"], jax.devices()[:1])["bf16"]
    limits = harness.cell(harness.benchmark(), cell)["limits"]
    assert got["schedule_gap"] == 0.0
    assert any(got[k] > v for k, v in limits.items()), got
