"""The check fails a run of the handover cell with int8 uploads with each planted fault."""
import time

import pytest

import faults
import harness
import tiny

CELL = "r18-handover.q8-b256"


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_planted_fault_is_not_correct(fault, monkeypatch, tmp_path):
    tiny.shrink(monkeypatch, tmp_path)
    faults.plant(monkeypatch, fault)
    result = harness.run_cell(CELL, 7, 0.1, False, t0=time.perf_counter())
    assert result["correct"] is False, result["checks"]
