"""BENCHMARK.json and the files it names: every name resolves to its
file, by name, and the file keeps the benchmark contract's limits."""
import json
import os
import re

import pytest

import harness

BENCH = harness.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024


def test_names_units_and_whys():
    entries = (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
               + BENCH["per_layer"])
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names)), group
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                    and "\t" not in e[key], (e["name"], key)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_is_found_by_name(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert conf["file"] == f"bench/configs/{conf['name']}.json"
    cfg = harness.load_json(os.path.join(harness.ROOT, conf["file"]))
    assert cfg["name"] == conf["name"] and cfg["source"] == conf["source"]
    assert cfg["reduced"] == conf["reduced"]
    for key in conf["reduced"]:
        assert key in cfg and NAME.match(key)
        assert not key.endswith(("_dim", "_rank")) and "width" not in key
    assert os.path.exists(os.path.join(harness.BENCH, "flops",
                                       f"{cfg['flops']}.py"))
    assert any(w["config"] == conf["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_arch_names_a_backbone_and_a_program_config(conf):
    from repro.configs.base import get_config
    cfg = harness.load_json(os.path.join(harness.ROOT, conf["file"]))
    arch = cfg["model"]["arch"]
    assert os.path.isfile(os.path.join(harness.BENCH, "models",
                                       f"{arch}.py"))
    assert get_config(arch).name == arch


@pytest.mark.parametrize("name", WORKLOADS)
def test_cell_resolves_traffic_limits_and_metrics(name):
    c = harness.cell(BENCH, name)
    w = c["workload"]
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] in (1, 4)
    t = c["traffic"]
    assert t["vehicles_per_round"] >= 1 and t["chunk_rounds"] >= 1
    assert set(c["limits"]) <= {"loss1_gap", "grad_gap", "change_gap",
                                "schedule_gap"}
    assert c["limits"]["schedule_gap"] == 0
    e2e = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c["per_layer"]
    for m in c["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
        assert m["moves"] in e2e


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_bounds():
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert {m["name"] for m in BENCH["end_to_end"]} == {
        "round_ms", "peak_hbm_gib", "setup_s"}
    for m in BENCH["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert set(m["workloads"]) <= set(WORKLOADS)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_window_is_whole_chunks():
    for name in WORKLOADS:
        t = harness.cell(BENCH, name)["traffic"]
        for seconds in (1, BENCH["run_seconds"], 51):
            n = harness.window_rounds(seconds, t)
            assert n >= t["chunk_rounds"] and n % t["chunk_rounds"] == 0


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit, match="unknown workload"):
        harness.cell(BENCH, "no-such-cell")


def test_paths_hold_only_the_benchmark():
    for p in BENCH["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and ".." not in p
    for word in BENCH["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        assert any(word.startswith(p + "/") for p in BENCH["paths"])
    json.dumps(BENCH)
