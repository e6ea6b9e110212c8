"""A whole run at a CPU size, and the runs that must print no result."""
import json
import os
import shutil
import subprocess
import sys

import harness
import tiny

CELL = "r18-single.paper-b256"
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "checks"]


def _run_py(cwd, env=None):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed", "3",
         "--seconds", "1", "--trace", "0"], cwd=cwd, capture_output=True,
        text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})


def test_no_chip_exits_nonzero_with_no_result():
    p = _run_py(harness.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero_with_no_result(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = _run_py(tmp_path, env={"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_last_line_holds_the_contract_keys(monkeypatch, tmp_path, capsys):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.environ.get("JAX_COMPILATION_CACHE_DIR", ""))
    tiny.shrink(monkeypatch, tmp_path)
    import run
    run.main(["--workload", CELL, "--seed", str(2**33 + 5), "--seconds",
              "0.1", "--trace", "0"])
    out, err = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    assert list(result) == RESULT_KEYS
    assert result["correct"] is True, err
    assert result["attempted"] == 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"round_ms", "peak_hbm_gib", "setup_s"}
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    limits = harness.cell(harness.benchmark(), CELL)["limits"]
    assert list(result["checks"]) == list(limits)
    tail = err.strip().splitlines()[-len(limits):]
    for line, (name, v) in zip(tail, result["checks"].items()):
        assert set(v) == {"value", "limit"} and v["limit"] == limits[name]
        assert line == f"{name}: {v['value']!r} (limit {v['limit']!r})"
    assert result["checks"]["schedule_gap"]["value"] == 0.0
    chunks = json.load(open(tmp_path / CELL / f"chunks-{2**33 + 5}.json"))
    assert len(chunks["publish"]) == 1 and chunks["rounds"] == 1
