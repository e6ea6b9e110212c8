"""Campaign engine (core/engine.py) acceptance tests.

The compiled campaign's contract has three layers, each tested here:

  1. schedule — every pre-drawn quantity (cohort ids, batch indices,
     velocities, lr, key chain, host-RNG successor, handover positions/
     weights/sync decisions, every record field except the loss) is
     BITWISE identical to the eager `run` loop;
  2. reuse boundaries — the engine's batch construction and client step
     are the legacy functions, verified bitwise against the legacy
     cohort path on concrete arrays;
  3. within-mode determinism — for a fixed mode, any chunking and any
     save/restore split replays the campaign bit for bit, losses and
     model trees included (scan(a)+scan(b) == scan(a+b); the jit mode
     replays one identical program).

Cross-engine/cross-mode MODEL values agree only to float tolerance
(XLA fuses the round body differently from the op-by-op eager path —
see the engine module docstring), so no test compares losses or trees
ACROSS engines; the schedule layer plus the reuse-boundary layer pin
semantic equivalence instead.

Uses a micro payload (4x4 images, cohorts of 3) so each engine program
compiles in seconds; `ENGINE_TINY` is deliberately NOT the test_state
tiny-world (32x32 compiles are ~2 min per program on CI CPUs).
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.guards import assert_compile_bounds, track_compiles
from repro.checkpoint.store import restore_state, save_state
from repro.core import engine
from repro.core.clients import CLIENT_UPDATES, raw_local_step
from repro.core.engine import (check_campaign_supported, compile_counts,
                               resolve_mode, run_campaign)
from repro.core.scenario import Scenario, run
from repro.core.state import FLState, pack_host_rng, unpack_host_rng
from repro.core.topology import MultiRSU

_RS = np.random.RandomState(0)
DATA = [_RS.rand(6, 4, 4, 3).astype(np.float32) for _ in range(8)]

ENGINE_TINY = dict(data=DATA, n_vehicles=8, vehicles_per_round=3,
                   batch_size=2, rounds=6, local_iters=1, lr=0.4, seed=11)

CASES = {
    "single": dict(topology="single"),
    "multi": dict(topology="multi", topology_kwargs={"n_rsus": 2}),
    "handover": dict(topology="handover",
                     topology_kwargs={"n_rsus": 2, "rsu_range": 200.0,
                                      "round_duration": 50.0,
                                      "sync_every": 2}),
}


def _scenario(case: str, **over) -> Scenario:
    kw = {**ENGINE_TINY, **CASES[case]}
    if "topology_kwargs" in over:
        kw["topology_kwargs"] = {**kw.get("topology_kwargs", {}),
                                 **over.pop("topology_kwargs")}
    kw.update(over)
    return Scenario(**kw)


# memoized reference runs — compiled programs are shared through the
# engine's callable cache, these just avoid re-executing rounds per test
@functools.lru_cache(maxsize=None)
def _eager6(case):
    return run(_scenario(case), rounds=6)


@functools.lru_cache(maxsize=None)
def _jit6(case):
    return run_campaign(_scenario(case), rounds=6, mode="jit")


def _assert_states_identical(s1: FLState, s2: FLState):
    l1, l2 = jax.tree.leaves(s1.to_tree()), jax.tree.leaves(s2.to_tree())
    assert len(l1) == len(l2)
    for a, b in zip(l1, l2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert s1.round == s2.round


def _sans_loss(rec):
    return {k: v for k, v in rec.items() if k != "loss"}


# --------------------------------------------------------------------------
# layer 1: schedule bitwise vs eager
# --------------------------------------------------------------------------

@pytest.mark.parametrize("case", sorted(CASES))
def test_schedule_and_records_match_eager(case):
    """Every record field except the loss, the RNG successor states and
    (for handover) the motion/accumulator state match the eager loop
    bit for bit."""
    st_e, hist_e = _eager6(case)
    st_c, hist_c = _jit6(case)
    assert len(hist_c) == len(hist_e) == 6
    for a, b in zip(hist_e, hist_c):
        assert _sans_loss(a) == _sans_loss(b)
        assert isinstance(b["loss"], float) and np.isfinite(b["loss"])
    np.testing.assert_array_equal(np.asarray(st_e.key), np.asarray(st_c.key))
    for k in st_e.host_rng:
        np.testing.assert_array_equal(np.asarray(st_e.host_rng[k]),
                                      np.asarray(st_c.host_rng[k]))
    assert st_c.round == st_e.round == 6
    if case == "handover":
        for k in ("positions", "blur_sum", "upload_count"):
            np.testing.assert_array_equal(np.asarray(st_e.topo[k]),
                                          np.asarray(st_c.topo[k]))


@pytest.mark.parametrize("case", ["single", "handover"])
@pytest.mark.parametrize("seed", [0, 3, 7])
def test_plan_is_chunking_invariant(case, seed):
    """Property: planning a campaign in one chunk or in pieces yields the
    SAME schedule arrays, records and RNG successors — the invariant
    that makes checkpoint_every (which re-plans per chunk) bit-exact."""
    sc = _scenario(case, seed=seed)

    def plan(chunks):
        state = sc.init_state()
        xs_all, recs_all = [], []
        for k in chunks:
            xs, recs, key, rng, topo_host = engine._plan_chunk(state, sc, k)
            xs_all += xs
            recs_all += recs
            topo = state.topo
            if topo_host:
                topo = {**topo, **topo_host}
            state = state.replace(key=key, host_rng=pack_host_rng(rng),
                                  round=state.round + k, topo=topo)
        return xs_all, recs_all, state

    xs1, recs1, end1 = plan([6])
    xs2, recs2, end2 = plan([2, 2, 2])
    assert recs1 == recs2
    for row1, row2 in zip(xs1, xs2):
        for a, b in zip(row1, row2):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(end1.key), np.asarray(end2.key))
    for k in end1.host_rng:
        np.testing.assert_array_equal(np.asarray(end1.host_rng[k]),
                                      np.asarray(end2.host_rng[k]))
    if case == "handover":
        np.testing.assert_array_equal(end1.topo["positions"],
                                      end2.topo["positions"])


# --------------------------------------------------------------------------
# layer 2: reuse boundaries bitwise vs the legacy cohort path
# --------------------------------------------------------------------------

def test_batches_and_client_step_match_legacy():
    """The engine's batch construction and client step ARE the legacy
    ones: on concrete arrays (outside the fused body) both produce
    bitwise-identical batches, losses and client trees."""
    from repro.core.topology import _client_images

    sc = _scenario("single")
    state = sc.init_state()
    xs_list, _, _, _, _ = engine._plan_chunk(state, sc, 1)
    ids, idx, cks, velocities, blur, lr = xs_list[0]

    # batch construction: stacked gather + vmapped blur == per-client
    # eager slicing + blur
    dstack = engine._data_stack(sc)
    batches = engine._client_batches(dstack, ids, idx, velocities, sc)
    legacy = np.stack([
        np.asarray(_client_images(sc, int(c), np.asarray(idx)[i],
                                  velocities[i]))
        for i, c in enumerate(np.asarray(ids))])
    np.testing.assert_array_equal(np.asarray(batches), legacy)

    # client step: jit(vmap(raw_local_step)) == the legacy cohort path
    cohort, _ = CLIENT_UPDATES["dtssl"].run_cohort(
        sc.cfg, state.global_tree, None, batches, list(cks), lr,
        parallel=True)
    step = jax.jit(jax.vmap(raw_local_step(sc.cfg),
                            in_axes=(None, 0, 0, None)))
    trees, losses = step(state.global_tree, batches, cks, lr)
    np.testing.assert_array_equal(np.asarray(losses),
                                  np.asarray(cohort.losses))
    for a, b in zip(jax.tree.leaves(trees), jax.tree.leaves(cohort.trees)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------
# layer 3: within-mode bit-exactness (chunking + save/restore)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("case", sorted(CASES))
def test_jit_resume_bit_exact(case, tmp_path):
    """mode="jit": 6 rounds straight == 6 rounds with checkpoint_every=3
    == restore the round-3 checkpoint + 3 more rounds, bit for bit, and
    the whole campaign compiles exactly ONE round program."""
    sc = _scenario(case)
    st6, hist6 = _jit6(case)

    st_ck, hist_ck = run_campaign(sc, rounds=6, mode="jit",
                                  checkpoint_every=3,
                                  checkpoint_dir=str(tmp_path))
    _assert_states_identical(st6, st_ck)
    assert hist_ck == hist6

    restored = restore_state(os.path.join(tmp_path, "round_000003"), sc)
    assert restored.round == 3
    st_b, hist_b = run_campaign(sc, restored, rounds=3, mode="jit")
    _assert_states_identical(st6, st_b)
    assert hist_ck[:3] + hist_b == hist6
    assert compile_counts(sc)["jit_round"] == 1


@pytest.mark.parametrize("case", ["single", "handover"])
def test_scan_chunks_compose(case):
    """mode="scan": scan(3)+scan(3) == scan(6) bit for bit (losses, model
    trees, full FLState), with <= 2 compiled scan programs (one per
    distinct chunk length)."""
    sc = _scenario(case)
    st6, hist6 = run_campaign(sc, rounds=6, mode="scan")
    st_a, hist_a = run_campaign(sc, rounds=3, mode="scan")
    st_b, hist_b = run_campaign(sc, st_a, rounds=3, mode="scan")
    _assert_states_identical(st6, st_b)
    assert hist_a + hist_b == hist6
    # same schedule as the jit mode (the plan is mode-independent)
    assert [_sans_loss(r) for r in hist6] == \
        [_sans_loss(r) for r in _jit6(case)[1]]
    assert compile_counts(sc)["scan"] <= 2


# --------------------------------------------------------------------------
# handover chunk boundary: the per-RSU tuple crosses it as the state holds it
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _handover4(mode, codec):
    """4 handover rounds through run_campaign in chunks of one round."""
    sc = _scenario("handover", codec=codec)
    return run_campaign(sc, rounds=4, mode=mode, publish_every=1)


def _stacked_boundary_campaign(sc, mode, rounds):
    """Reference for `_handover4`: the same plan, chunking and round body,
    with the RSU models stacked per leaf before each chunk and sliced per
    leaf after it, outside the compiled program."""
    body = engine._build_handover_body(sc)
    if mode == "scan":
        step = jax.jit(lambda ds, c, xs: jax.lax.scan(
            lambda cc, x: body(ds, cc, x), c, xs))
    else:
        step = jax.jit(body)
    codec = engine._round_codec(sc.cfg)
    stateful = codec is not None and codec.stateful
    dstack = engine._data_stack(sc)
    state = sc.init_state()
    for _ in range(rounds):
        xs_list, _, key, rng, topo_host = engine._plan_chunk(state, sc, 1)
        rstack = jax.tree.map(lambda *ls: jnp.stack(ls),
                              *state.topo["rsu_models"])
        carry = (state.global_tree, rstack)
        if stateful:
            carry += (state.comms["ef"],)
        if mode == "scan":
            xs = jax.tree.map(lambda *ls: jnp.stack(ls), *xs_list)
        else:
            xs = xs_list[0]
        carry, _ = step(dstack, carry, xs)
        gtree, rstack = carry[:2]
        rsu_models = tuple(jax.tree.map(lambda x: x[r], rstack)
                           for r in range(sc.topology.n_rsus))
        state = state.replace(
            global_tree=gtree, key=key, host_rng=pack_host_rng(rng),
            round=state.round + 1,
            topo={**topo_host, "rsu_models": rsu_models},
            comms={"ef": carry[2]} if stateful else state.comms)
    return state


@pytest.mark.parametrize("codec", ["identity", "delta_int8"])
@pytest.mark.parametrize("mode", ["scan", "jit"])
def test_handover_tuple_carry_matches_stacked_boundary(mode, codec):
    """The compiled callables take and return the per-RSU tuple and stack
    it only inside the program: after 4 one-round chunks the RSU models,
    the global model and the error-feedback residual equal a campaign
    that stacks and slices the models per leaf around each chunk, bit for
    bit (the change adds copies only)."""
    sc = _scenario("handover", codec=codec)
    st, _ = _handover4(mode, codec)
    ref = _stacked_boundary_campaign(sc, mode, 4)
    assert isinstance(st.topo["rsu_models"], tuple)
    assert len(st.topo["rsu_models"]) == sc.topology.n_rsus
    for a, b in zip(jax.tree.leaves((st.topo["rsu_models"], st.global_tree)),
                    jax.tree.leaves((ref.topo["rsu_models"],
                                     ref.global_tree))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if codec == "delta_int8":
        np.testing.assert_array_equal(np.asarray(st.comms["ef"]),
                                      np.asarray(ref.comms["ef"]))
    else:
        assert st.comms == ref.comms
    for k in ("positions", "blur_sum", "upload_count"):
        np.testing.assert_array_equal(st.topo[k], ref.topo[k])


@pytest.mark.parametrize("mode", ["scan", "jit"])
def test_handover_carry_is_the_state_tuple(mode):
    """Crossing a chunk boundary costs no device op: the carry holds the
    very arrays of the state (the RSU models' tuple and the residual),
    and a warm campaign compiles nothing (no per-shape eager stack or
    slice program). The codec scenario is this test's and the parity
    test's alone, so its compile counts are this campaign's."""
    sc = _scenario("handover", codec="delta_int8")
    st, _ = _handover4(mode, "delta_int8")
    carry = engine._carry_of(st, sc)
    assert carry[0] is st.global_tree
    assert carry[1] is st.topo["rsu_models"]
    assert carry[2] is st.comms["ef"]
    for got, want in zip(jax.tree.leaves(carry[1]),
                         jax.tree.leaves(st.topo["rsu_models"])):
        assert got is want
    with track_compiles() as tracker:
        st2, _ = run_campaign(sc, rounds=4, mode=mode, publish_every=1)
    assert tracker.backend_compiles == 0
    _assert_states_identical(st, st2)
    assert_compile_bounds(compile_counts(sc))


def test_log_every_formats_from_chunk_history(capsys):
    """log_every on the compiled path prints the SAME line format as the
    eager loop, assembled from the once-per-chunk fetched history — and
    logging does not perturb the campaign."""
    sc = _scenario("single")
    st_plain, hist = run_campaign(sc, rounds=4, mode="jit")
    capsys.readouterr()
    st_log, hist_log = run_campaign(sc, rounds=4, mode="jit", log_every=2)
    lines = capsys.readouterr().out.splitlines()
    want = [f"[round {r['round']:4d}] loss={r['loss']:.4f} "
            f"lr={r['lr']:.4f}" for r in hist if r["round"] % 2 == 0]
    assert lines == want
    assert hist_log == hist
    _assert_states_identical(st_plain, st_log)

    # the eager loop prints byte-identical lines for ITS history rows
    capsys.readouterr()
    _, hist_e = run(sc, rounds=4, log_every=2)
    lines_e = capsys.readouterr().out.splitlines()
    want_e = [f"[round {r['round']:4d}] loss={r['loss']:.4f} "
              f"lr={r['lr']:.4f}" for r in hist_e if r["round"] % 2 == 0]
    assert lines_e == want_e


# --------------------------------------------------------------------------
# guard rails
# --------------------------------------------------------------------------

def test_round_body_no_implicit_transfers():
    """The steady-state campaign dispatch is transfer-clean: with
    ``transfer_guard=True`` the fused round body runs under
    ``jax.transfer_guard("disallow")`` (via ``analysis.guards``), so any
    implicit host<->device movement inside the round loop raises. The
    only sanctioned transfer is the explicit once-per-chunk
    ``jax.device_get`` history fetch. The program is warmed first —
    lowering's constant uploads are outside the guarded window by
    design — and the guard must not perturb the campaign: the guarded
    run replays the unguarded one bit for bit."""
    st_ref, hist_ref = _jit6("single")
    st, hist = run_campaign(_scenario("single"), rounds=6, mode="jit",
                            transfer_guard=True)
    _assert_states_identical(st, st_ref)
    assert hist == hist_ref

    # the guard itself is live, not a no-op: an implicit transfer inside
    # the same context manager the engine uses does raise
    from repro.analysis.guards import no_implicit_transfers
    with pytest.raises(Exception, match="[Dd]isallow"):
        with no_implicit_transfers():
            jax.jit(lambda v: v + 1)(np.ones(3))  # numpy leaks into jit


def test_unsupported_configs_fail_fast():
    with pytest.raises(ValueError, match="sequential"):
        check_campaign_supported(
            Scenario(**{**ENGINE_TINY, "topology": "single",
                        "client": "fedco", "aggregator": "fedavg",
                        "queue_len": 16}))
    sc_mesh = _scenario("multi")
    # constructed directly: Scenario.validate would already reject the
    # collective on a 1-device box, before the engine check runs
    sc_mesh.topology = MultiRSU(n_rsus=2, mesh_aggregate=True)
    with pytest.raises(ValueError, match="mesh_aggregate"):
        check_campaign_supported(sc_mesh)

    class CustomTopo(MultiRSU):
        pass

    sc = _scenario("single")
    sc.topology = CustomTopo(n_rsus=2)
    with pytest.raises(ValueError, match="built-in"):
        check_campaign_supported(sc)

    with pytest.raises(ValueError, match="mode"):
        resolve_mode("eager")
    with pytest.raises(ValueError, match="checkpoint_dir"):
        run_campaign(_scenario("single"), rounds=1, checkpoint_every=1)
    with pytest.raises(ValueError, match=">= 1"):
        run_campaign(_scenario("single"), rounds=1, checkpoint_every=0,
                     checkpoint_dir="/tmp/x")


def test_checkpoint_refuses_other_topology_params(tmp_path):
    """The store fingerprint includes topology.signature() params: a
    handover checkpoint taken under sync_every=2 must not resume under
    sync_every=3 (same shapes — only the schedule differs)."""
    sc2 = _scenario("handover")
    state = sc2.init_state()
    path = save_state(os.path.join(tmp_path, "ck"), state, sc2)
    sc3 = _scenario("handover", topology_kwargs={"sync_every": 3})
    with pytest.raises(ValueError, match="topology_params"):
        restore_state(path, sc3)
    back = restore_state(path, sc2)
    _assert_states_identical(state, back)


def test_auto_mode_resolution():
    want = "jit" if jax.default_backend() == "cpu" else "scan"
    assert resolve_mode("auto") == want
    assert resolve_mode("jit") == "jit"
    assert resolve_mode("scan") == "scan"
