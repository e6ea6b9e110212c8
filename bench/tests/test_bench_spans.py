"""The program's spans and scopes (spans.py) and the readers built on
them, on synthetic inputs and on traces recorded on one TPU v5e."""
import gzip
import json
import os

import pytest

import harness
import spans
import tracing

HERE = os.path.dirname(os.path.abspath(__file__))
NEW = ("plan_ms", "carry_ms", "fetch_wait_ms", "client_ms", "batch_norm_ms",
       "codec_ms", "aggregate_ms")

HLO = """HloModule jit_wrapped

%fused_computation.3 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %mul.2 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(wrapped)/while/body/vmap(client_step)/jvp(batch_norm)/mul" stack_frame_id=3}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.7 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(wrapped)/while/body/transpose(vmap(client_step))/transpose(jvp(batch_norm))/mul" stack_frame_id=3}
  %copy.1 = f32[8]{0} copy(%fusion.7), metadata={op_name="jit(wrapped)/while/body/a[1][0][\\'params\\']"}
  %wagg.1 = f32[8]{0} custom-call(%copy.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(wrapped)/while/body/region_sync/aggregate/wagg/pallas_call" stack_frame_id=9}, backend_config={"x":1}
  ROOT %add.9 = f32[8]{0} add(%wagg.1, %a)
}
"""


def test_read_scopes_maps_instructions_to_op_names():
    assert spans.read_scopes(HLO) == {
        "mul.2": "jit(wrapped)/while/body/vmap(client_step)/jvp(batch_norm)/mul",
        "fusion.7": "jit(wrapped)/while/body/transpose(vmap(client_step))/"
                    "transpose(jvp(batch_norm))/mul",
        "copy.1": "jit(wrapped)/while/body/a[1][0][\\'params\\']",
        "wagg.1": "jit(wrapped)/while/body/region_sync/aggregate/wagg/"
                  "pallas_call"}


@pytest.mark.parametrize("op_name,scopes,hit", [
    ("jit(f)/while/body/vmap(client_step)/jvp(batch_norm)/mul",
     ("batch_norm",), True),
    ("jit(f)/transpose(vmap(client_step))/transpose(jvp(batch_norm))/add",
     ("batch_norm",), True),
    ("jit(f)/transpose(vmap(client_step))/mul", ("client_step",), True),
    ("jit(f)/vmap(blur)/pad", ("blur", "client_step"), True),
    ("jit(f)/region_sync/aggregate/jit(row_weighted_sum)/mul",
     ("region_sync",), True),
    ("jit(f)/body/mul;jit(f)/codec/add", ("codec",), True),
    ("jit(f)/batch_norm_stats/mul", ("batch_norm",), False),
    ("jit(f)/vmap(client_step)/mul", ("aggregate", "codec"), False),
    ("jit(batch_norm)", ("batch_norm",), True),
    ("", ("blur",), False),
])
def test_under_matches_a_segment_or_its_wrapped_form(op_name, scopes, hit):
    assert spans.under(op_name, *scopes) is hit


class Ctx:
    """The reader context over a synthetic trace (one round module per
    chunk on each device)."""

    def __init__(self, trace, rounds, chips=1):
        self.tr, self.trace, self.rounds, self.chips = tracing, trace, \
            rounds, chips

    round_ops = harness.Context.round_ops


def _chunk(step, start, kids, rounds=1):
    """A chunk span starting at `start` and its children, laid end to end:
    kids = [(name, dur_ns), ...]."""
    out, t = [], start
    for name, dur in kids:
        out.append([name, t, dur, step, {"round": step}])
        t += dur
    return [[spans.CHUNK, start, t - start, step, {"rounds": rounds}]] + out


def _span_trace():
    sp = [["campaign.data_stack", 0, 50, None, {"bytes": 8}]]
    sp += _chunk(0, 100, [("campaign.plan", 10), ("campaign.carry", 2),
                          ("campaign.dispatch", 3), ("campaign.fetch", 80),
                          ("campaign.state", 4)])
    sp += _chunk(1, 300, [("campaign.plan", 12), ("campaign.carry", 2),
                          ("campaign.dispatch", 3), ("campaign.fetch", 900),
                          ("campaign.state", 6)])        # a stall in fetch
    sp += _chunk(2, 1300, [("campaign.plan", 30), ("campaign.carry", 4),
                           ("campaign.dispatch", 6), ("campaign.fetch", 160),
                           ("campaign.state", 8)], rounds=2)
    sp += _chunk(3, 1600, [("campaign.plan", 99)])       # ends past the window
    return {"window": [0, 1650], "publish": [], "spans": sp,
            "scopes": {}, "devices": [{"name": "d", "ops": [], "modules": []}]}


@pytest.mark.parametrize("names,want", [
    (("campaign.plan",), 12 / 1e6),
    (("campaign.carry", "campaign.state"), 6 / 1e6),
    (("campaign.fetch",), 80 / 1e6),
])
def test_chunk_ms_takes_the_median_per_round(names, want):
    """Chunks 0 and 1 have one round, chunk 2 two; chunk 3 leaves the
    window. The stall in chunk 1's fetch does not move the median."""
    assert spans.chunk_ms(Ctx(_span_trace(), 4), *names) == \
        pytest.approx(want)


def test_idle_gaps_name_the_innermost_span():
    trace = _span_trace()
    trace["devices"][0]["ops"] = [["a", 0, 120, "xla", 0],
                                  ["b", 130, 1180, "xla", 0],
                                  ["c", 1340, 310, "xla", 0]]
    assert spans.idle_gaps(Ctx(trace, 4)) == [
        ["campaign.plan", 30e-9], ["campaign.fetch", 10e-9]]
    trace["devices"][0]["ops"] = [["a", 0, 1210, "xla", 0],
                                  ["c", 1240, 410, "xla", 0]]
    assert spans.idle_gaps(Ctx(trace, 4)) == [[None, 30e-9]]


def _scope_trace():
    mods = [["jit_wrapped(1)", 0, 100], ["jit_wrapped(1)", 200, 100],
            ["jit_other(2)", 150, 10]]
    ops = [["fusion.7", 10, 30, "conv", 0], ["mul.2", 40, 5, "xla", 0],
           ["wagg.1", 60, 20, "wagg", 64], ["copy.1", 85, 5, "xla", 0],
           ["fusion.7", 210, 30, "conv", 0], ["wagg.1", 260, 20, "wagg", 64],
           ["mul.2", 151, 5, "xla", 0]]               # outside the program
    return {"window": [0, 400], "publish": [], "spans": [],
            "scopes": spans.read_scopes(HLO),
            "devices": [{"name": "d0", "ops": ops, "modules": mods},
                        {"name": "d1", "ops": ops, "modules": mods}]}


@pytest.mark.parametrize("scopes,want_ns", [
    (("batch_norm",), 30 + 5 + 30),
    (("blur", "client_step"), 30 + 5 + 30),
    (("aggregate", "region_sync"), 20 + 20),
])
def test_scope_ms_sums_the_round_program_per_round_and_chip(scopes, want_ns):
    got = spans.scope_ms(Ctx(_scope_trace(), rounds=2, chips=2), *scopes)
    assert got == pytest.approx(want_ns / 2 / 1e6)


def test_scope_ms_is_none_without_a_match():
    assert spans.scope_ms(Ctx(_scope_trace(), rounds=2), "codec") is None
    trace = _scope_trace()
    trace["scopes"] = {}
    assert spans.scope_ms(Ctx(trace, rounds=2), "batch_norm") is None


def _profile(logdir, step):
    """A profile with the harness's window marker around one chunk."""
    import jax
    with tracing.capture(logdir):
        with jax.profiler.TraceAnnotation(tracing.WINDOW):
            with jax.profiler.StepTraceAnnotation(spans.CHUNK, step_num=step,
                                                  rounds=1):
                with jax.profiler.TraceAnnotation("campaign.plan",
                                                  round=step, rounds=1):
                    pass


def test_attach_reads_the_run_whose_window_is_parsed(monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "OUT", str(tmp_path))
    cell = tmp_path / "some-cell"
    for seed in (5, 6):
        _profile(str(cell / f"trace-{seed}"), step=seed)
        with gzip.open(cell / f"round-{seed}.hlo.gz", "wt") as f:
            f.write(HLO)
    window, got = spans.read_spans(str(cell / "trace-5"))
    assert [s[0] for s in got] == [spans.CHUNK, "campaign.plan"]
    assert got[0][3] == got[1][3] == 5 and got[0][4] == {"rounds": 1}
    trace = spans.attach(Ctx({"window": window}, 1))
    assert trace["spans"] == got
    assert trace["scopes"] == spans.read_scopes(HLO)
    # a window no run wrote: a program without spans, as the readers see it
    other = spans.attach(Ctx({"window": [0, 1]}, 1))
    assert other["spans"] == [] and other["scopes"] == {}


RECORDED = sorted(f for f in os.listdir(os.path.join(HERE, "data"))
                  if f.startswith("trace_"))
WITH_SPANS = [f for f in RECORDED if f.startswith("trace_spans_")]


def _ctx(name):
    with gzip.open(os.path.join(HERE, "data", name), "rt") as f:
        rec = json.load(f)
    cfg = harness.load_json(os.path.join(
        harness.BENCH, "configs", f"{rec['config']}.json"))
    traffic = harness.load_json(os.path.join(
        harness.BENCH, "traffic", f"{rec['traffic']}.json"))
    return rec, harness.Context(rec["trace"], cfg, traffic, rec["rounds"],
                                harness.peaks_for("TPU v5 lite"),
                                rec["chips"])


@pytest.mark.parametrize("name", sorted(set(RECORDED) - set(WITH_SPANS)))
def test_new_readers_are_silent_on_a_program_without_spans(
        name, monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "OUT", str(tmp_path))
    _, ctx = _ctx(name)
    for m in NEW:
        assert harness.metric_reader(m)(ctx) is None, m


@pytest.mark.parametrize("name", WITH_SPANS)
def test_recorded_spans_account_for_each_chunk(name):
    """Each chunk's children cover at least 95% of it, and every idle gap
    of the device over 10 ms lies inside a program span."""
    rec, ctx = _ctx(name)
    sp = ctx.trace["spans"]
    chunks = [s for s in sp if s[0] == spans.CHUNK]
    assert len(chunks) == rec["rounds"]
    for c in chunks:
        kids = [s for s in sp if s[0] != spans.CHUNK and s[3] == c[3]]
        assert sum(s[2] for s in kids) >= 0.95 * c[2]
    gaps = spans.idle_gaps(ctx, top=100)
    assert all(g[0] is not None for g in gaps if g[1] > 0.01), gaps
    for m in NEW:
        if m in rec["metrics"]:
            assert harness.metric_reader(m)(ctx) == pytest.approx(
                rec["metrics"][m], rel=1e-9)
