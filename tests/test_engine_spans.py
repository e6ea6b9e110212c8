"""The campaign engine's host spans and the round program's named scopes.

`run_campaign` writes `jax.profiler` host spans around each piece of a
chunk, and the shared functions of the round body open `jax.named_scope`s
that land in the compiled program's `op_name` metadata. The benchmark
(bench/spans.py) reads both by name; these tests hold the program to the
names it pins, at the engine tests' micro size.
"""
import functools
import glob
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import engine
from repro.core.scenario import Scenario, run_campaign

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "bench"))
import spans  # noqa: E402

_RS = np.random.RandomState(0)
DATA = [_RS.rand(6, 4, 4, 3).astype(np.float32) for _ in range(8)]
TINY = dict(data=DATA, n_vehicles=8, vehicles_per_round=3, batch_size=2,
            rounds=6, local_iters=1, lr=0.4, seed=11)
CASES = {
    "single": dict(topology="single"),
    "handover": dict(topology="handover", codec="delta_int8",
                     topology_kwargs={"n_rsus": 2, "rsu_range": 200.0,
                                      "round_duration": 50.0,
                                      "sync_every": 2}),
}
CHILDREN = ("campaign.plan", "campaign.carry", "campaign.dispatch",
            "campaign.fetch", "campaign.state", "campaign.publish",
            "campaign.checkpoint")
# the scopes each case's round program must hold
SCOPED = {"single": ("blur", "client_step", "batch_norm", "aggregate"),
          "handover": ("blur", "client_step", "batch_norm", "codec",
                       "aggregate", "region_sync")}


@functools.lru_cache(maxsize=None)
def _scenario(case):
    return Scenario(**TINY, **CASES[case])


def _events(logdir):
    """[(line, name, start_ns, end_ns, stats)] of the host planes."""
    path = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.append((line.name, e.name, e.start_ns,
                                e.start_ns + e.duration_ns,
                                {k: v for k, v in e.stats}))
    return out


@pytest.mark.parametrize("case", sorted(CASES))
def test_campaign_spans_nest_per_chunk(case, tmp_path):
    sc = _scenario(case)
    state = sc.init_state()
    opts = jax.profiler.ProfileOptions()     # as the benchmark traces
    opts.python_tracer_level, opts.host_tracer_level = 0, 1
    with jax.profiler.trace(str(tmp_path / "trace"), profiler_options=opts):
        run_campaign(sc, state, rounds=2, mode="scan", checkpoint_every=1,
                     checkpoint_dir=str(tmp_path / "ckpt"),
                     publish=lambda rnd, tree: None)
    evs = [e for e in _events(str(tmp_path / "trace"))
           if e[1].startswith("campaign.")]
    assert {e[1] for e in evs} == set(spans.SPANS)
    assert [e[1] for e in evs].count("campaign.data_stack") == 1
    assert "bytes" in next(e for e in evs
                           if e[1] == "campaign.data_stack")[4]
    chunks = sorted((e for e in evs if e[1] == spans.CHUNK),
                    key=lambda e: e[2])
    assert [c[4]["step_num"] for c in chunks] == [0, 1]
    assert all(c[4]["rounds"] == 1 for c in chunks)
    for line, _, lo, hi, stats in chunks:
        kids = [e for e in evs if e[1] in CHILDREN
                and e[4].get("round") == stats["step_num"]]
        assert sorted(k[1] for k in kids) == sorted(CHILDREN)
        for k in kids:
            assert k[0] == line and lo <= k[2] and k[3] <= hi, k
        plan = next(k for k in kids if k[1] == "campaign.plan")
        assert plan[4]["rounds"] == 1 and plan[4]["upload_bytes"] > 0
        assert next(k for k in kids if k[1] == "campaign.fetch")[4][
            "bytes"] == 3 * 4          # one round's f32 losses, 3 clients
    # the benchmark's reader sees the same spans
    _, got = spans.read_spans(str(tmp_path / "trace"))
    assert sorted(s[0] for s in got) == sorted(e[1] for e in evs)


@functools.lru_cache(maxsize=None)
def _round_program_text(case):
    sc = _scenario(case)
    state = sc.init_state()
    xs_list = engine._plan_chunk(state, sc, 1)[0]
    xs = jax.tree.map(lambda *ls: jnp.stack(ls), *xs_list)
    return engine.campaign_callables(sc)["scan"].lower(
        engine._data_stack(sc), engine._carry_of(state, sc),
        xs).compile().as_text()


@pytest.mark.parametrize("case", sorted(CASES))
def test_round_program_carries_named_scopes(case):
    names = spans.read_scopes(_round_program_text(case)).values()
    for scope in SCOPED[case]:
        assert any(spans.under(n, scope) for n in names), scope
    assert any(re.search(r"/transpose\(jvp\(batch_norm\)\)/", n)
               for n in names)
    absent = set(spans.SCOPES) - set(SCOPED[case])
    assert not any(spans.under(n, *absent) for n in names)
