"""The trace reductions: op classes from HLO instruction text, and the
per-layer readers on a small trace recorded on one TPU v5e."""
import gzip
import json
import os

import pytest

import harness
import tracing

HERE = os.path.dirname(os.path.abspath(__file__))

# instruction texts as the TPU's "XLA Ops" line names them (shapes cut)
CONV_FUSION = ("%multiply_reduce_fusion.417 = (f32[1280,64]{0,1:T(8,128)}, "
               "f32[1280,32,32,64]{0,3,2,1:T(8,128)}) fusion(f32[1280,32,32,"
               "64]{0,3,2,1:T(8,128)} %get-tuple-element.4779, f32[3,3,64,64]"
               "{3,2,1,0:T(8,128)} %get-tuple-element.5607), kind=kOutput, "
               "calls=%fused_computation.110.clone.clone")
LOOP_FUSION = ("%fusion.1903 = (bf16[5,256,32,32,64]{1,0,4,3,2:T(8,128)(2,1)}"
               ", f32[5,256,32,32,64]{1,0,4,3,2:T(8,128)}) fusion(f32[5,256,"
               "32,32,64]{1,0,4,3,2:T(8,128)} %reshape.6088), kind=kLoop, "
               "calls=%fused_computation.21.clone.clone")
Q8_ENCODE = ("%closed_call.456 = (s8[5,11507712]{1,0:T(8,128)(4,1)S(1)}, "
             "f32[5619,5,8]{2,1,0:T(8,128)}, f32[5,11507712]{1,0:T(8,128)}) "
             "custom-call(f32[5,11507712]{1,0:T(8,128)} %constant_dynamic-"
             "update-slice_fusion.361, f32[5,11507712]{1,0:T(8,128)} "
             "%pad.1362), custom_call_target=\"tpu_custom_call\", "
             "operand_layout_constraints={f32[5,11507712]{1,0}, "
             "f32[5,11507712]{1,0}}, frontend_attributes={kernel_metadata={}}")
WAGG = ("%custom-call.7 = f32[11507712]{0:T(1024)} custom-call(f32[5,11507712]"
        "{1,0:T(8,128)} %concatenate.1, f32[5]{0:T(128)} %w.2, f32[5]{0:T(128)}"
        " %mask.3), custom_call_target=\"tpu_custom_call\"")
ALL_GATHER = ("%all-gather-start.2 = (f32[2,128]{1,0}, f32[8,128]{1,0}) "
              "all-gather-start(f32[2,128]{1,0} %p), dimensions={0}")
HLO = """HloModule jit_wrapped

%fused_computation.110.clone.clone (param_0: f32[1280,32,32,64]) -> f32[1280,64] {
  %param_0 = f32[1280,32,32,64]{0,3,2,1} parameter(0)
  %convolution.3 = f32[1280,32,32,64]{0,3,2,1} convolution(%param_0, %param_1), window={size=3x3 pad=1_1x1_1}
}

%fused_computation.21.clone.clone (param_0.1: f32[5,256,32,32,64]) -> bf16[5,256,32,32,64] {
  %param_0.1 = f32[5,256,32,32,64]{1,0,4,3,2} parameter(0)
  %convert.9 = bf16[5,256,32,32,64]{1,0,4,3,2} convert(%param_0.1)
}

%outer (p: f32[2]) -> f32[2] {
  %fusion.5 = f32[2]{0} fusion(%p), kind=kOutput, calls=%fused_computation.110.clone.clone
}
"""


def test_conv_computations_follow_calls():
    assert tracing.conv_computations(HLO) == {
        "fused_computation.110.clone.clone", "outer"}


@pytest.mark.parametrize("text,name,cls,nbytes", [
    (CONV_FUSION, "multiply_reduce_fusion.417", "conv", 0),
    (LOOP_FUSION, "fusion.1903", "xla", 0),
    (Q8_ENCODE, "closed_call.456", "q8_encode",
     5 * 11507712 * (1 + 4 + 4 + 4) + 5619 * 5 * 8 * 4),
    (WAGG, "custom-call.7", "wagg", 11507712 * 4 * 6 + 5 * 4 * 2),
    (ALL_GATHER, "all-gather-start.2", "collective", 0),
])
def test_classify(text, name, cls, nbytes):
    assert tracing.classify(text, tracing.conv_computations(HLO)) == (
        name, cls, nbytes)


def test_leaves_drop_enclosing_events():
    ops = [["while", 0, 100, "xla", 0], ["a", 10, 20, "conv", 0],
           ["b", 40, 10, "xla", 0], ["c", 120, 5, "xla", 0]]
    assert [o[0] for o in tracing._leaves(ops)] == ["a", "b", "c"]


def test_idle_gaps_and_busy():
    trace = {"window": [0, 100], "publish": [55], "devices": [
        {"name": "d", "modules": [], "ops": [
            ["a", 10, 20, "conv", 0], ["b", 25, 15, "xla", 0],
            ["c", 60, 30, "xla", 0], ["late", 95, 10, "xla", 0]]}]}
    d = trace["devices"][0]
    assert tracing.busy_ns(trace, d) == 30 + 30
    assert tracing.idle_gaps(trace, d) == [[0, 10], [40, 60], [90, 100]]


RECORDED = sorted(f for f in os.listdir(os.path.join(HERE, "data"))
                  if f.startswith("trace_"))


def _recorded(name):
    with gzip.open(os.path.join(HERE, "data", name), "rt") as f:
        return json.load(f)


def _ctx(rec):
    cfg = harness.load_json(os.path.join(
        harness.BENCH, "configs", f"{rec['config']}.json"))
    traffic = harness.load_json(os.path.join(
        harness.BENCH, "traffic", f"{rec['traffic']}.json"))
    return harness.Context(rec["trace"], cfg, traffic, rec["rounds"],
                           harness.peaks_for("TPU v5 lite"), rec["chips"])


@pytest.mark.parametrize("name", RECORDED)
def test_recorded_trace_readers(name):
    rec = _recorded(name)
    ctx = _ctx(rec)
    got = {m: harness.metric_reader(m)(ctx) for m in rec["metrics"]}
    assert got == pytest.approx(rec["metrics"], rel=1e-9)
    assert 0 < ctx.busy_s() <= ctx.window_s()
    for m, v in got.items():
        assert v is not None and v > 0, m
        if m.endswith("_roofline") or "mfu" in m:
            assert v <= 100, m
    assert len(ctx.trace["devices"]) == rec["chips"]
    b = ctx.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert any(g[0].startswith("chunk boundary") for g in b["idle_gaps"])
    assert all(0 < g[1] <= ctx.window_s() for g in b["idle_gaps"])


@pytest.mark.parametrize("name", RECORDED)
def test_reader_finds_nothing_and_returns_none(name):
    rec = _recorded(name)
    ctx = _ctx(rec)
    classes = {o[3] for d in ctx.trace["devices"] for o in d["ops"]}
    for reader, cls in (("q8_roofline", "q8_encode"),
                        ("wagg_roofline", "wagg"),
                        ("collective_ms", "collective")):
        if cls not in classes:
            assert harness.metric_reader(reader)(ctx) is None
