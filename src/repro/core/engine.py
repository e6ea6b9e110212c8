"""Whole-campaign compilation — many rounds per XLA dispatch.

The eager loop (`run_round`, core/topology.py) re-enters Python every
round: host-RNG draws, eager blur/aggregation dispatches, a per-round
`device_get`. All of that is *training-independent* — cohort ids and
batch indices come from the MT19937 host stream, velocities/keys from
the jax PRNG chain, the LR from a pure function of the round index, and
(for handover) motion, grouping, upload weights and sync decisions are
functions of those draws alone. So a campaign of K rounds factors into

  plan    — replay the EXACT eager draw sequence K rounds ahead into
            device schedule arrays (ids, batch indices, client keys,
            velocities, blur, lr, and for handover: download RSU per
            client, zero-padded upload-weight matrices, sync flags +
            level-2 weights). The plan consumes the host RNG and jax
            key chain identically to the eager loop, bit for bit —
            the draw helpers (`_cohort_plan`, `_batch_indices`,
            `HandoverMultiRSU.plan_round`) are shared verbatim.
  execute — a single jitted round body applied K times, either as a
            python loop over one compiled program (mode="jit") or as
            `jax.lax.scan` chunks (mode="scan"). History (per-client
            losses) streams out device-side and is fetched ONCE per
            chunk; records are assembled on host afterwards.

Two modes because of a backend asymmetry: `lax.scan` lowers to a while
loop, and XLA-CPU pessimizes convolutions inside while loops (~25x;
the same issue keeps `local_iters` python-unrolled in core/clients.py).
On CPU the scan EXECUTES slower than the eager loop; a python loop over
one fully-jitted round keeps the fusion win without the while loop.
mode="auto" therefore picks "jit" on the CPU backend and "scan"
elsewhere. Both modes are chunk-composable bit for bit:

  * "jit" applies the SAME compiled program round by round, so any
    pause/checkpoint/resume split replays identical programs;
  * "scan" chunks compose exactly — scan(a)+scan(b) == scan(a+b) and
    K x scan(1) == scan(K), verified leafwise in tests/test_engine.py
    (the carry crosses chunk boundaries as device values, and
    `optimization_barrier` pinch points keep XLA from fusing across
    the aggregation boundary differently per chunk length).

Versus the eager loop, the ENTIRE schedule (cohort ids, batch indices,
velocities, blur levels, LR, key chain, host-RNG successor state,
positions, upload weights, sync decisions — every record field except
the loss) is bitwise-identical. The fused round body itself reassociates
the client-step/aggregation arithmetic, so model trees and losses agree
only to float tolerance across engines (and across the two modes) —
this is inherent to XLA, not a looseness of this module: even the
UNCHANGED legacy step evaluated eagerly vs jitted differs in its f32
loss, and SSL training chaotically compounds such deltas over rounds
(tiny-batch BatchNorm amplifies them further at toy sizes). The
enforceable contract is therefore: schedule bitwise vs eager, the
client step itself bitwise vs the legacy jitted cohort step, and
EVERYTHING bitwise WITHIN a mode — any chunking, any save/restore
split. tests/test_engine.py enforces each layer.

Compile bound: one program per (mode, topology, shape) — mode="jit"
compiles exactly one round body per campaign; mode="scan" one program
per distinct chunk length (<= 2 for a fixed checkpoint cadence: the
body chunk + the remainder). The handover topology needs NO extra
programs and no eager fallback: instead of per-download-group cohorts
(whose sizes change with vehicle motion), the compiled body gathers
each client's init model from a stacked per-RSU model
(`rsu_stack[down[i]]`) and applies uploads as zero-padded weight
matrices under `where`-gated sync — regrouping changes DATA, never
shapes, so one program covers every round regime. The stack lives only
inside the program: both callables take and return the per-RSU models
as the tuple `FLState.topo["rsu_models"]` holds, stack it once on
entry and unstack it once on exit (in-program copies), so crossing a
chunk boundary costs the host no per-leaf device op.
"""
from __future__ import annotations

import contextlib
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.comms.codecs import CODECS, tree_nbytes
from repro.core import aggregation as agg
from repro.core.clients import raw_local_step
from repro.core.cohort import CohortBatch
from repro.core.hierarchical import aggregate_hierarchical
from repro.core.mobility import apply_motion_blur
from repro.core.state import FLState, pack_host_rng, unpack_host_rng
from repro.core.topology import (HandoverMultiRSU, MultiRSU, SingleRSU,
                                 _batch_indices, _cohort_plan)

MODES = ("auto", "jit", "scan")


# --------------------------------------------------------------------------
# support checks
# --------------------------------------------------------------------------

def check_campaign_supported(scenario) -> None:
    """Fail fast (before any compile) on configs the compiled engine
    cannot express."""
    cfg, topo = scenario.cfg, scenario.topology
    if cfg.client != "dtssl":
        raise ValueError(
            "run_campaign compiles the whole round into one traced body, "
            "which requires a stateless, vmappable client update; "
            f"client={cfg.client!r} is sequential (FedCo threads a MoCo "
            "key-encoder/queue through the cohort). Use the eager "
            "run()/run_round() loop for it.")
    if type(topo) is MultiRSU:
        # resolves the cohort mesh the compiled body will trace with —
        # raises the actionable mesh_aggregate errors (uneven cohorts,
        # missing devices) before any compile
        topo.resolve_mesh(cfg)
    if type(topo) not in (SingleRSU, MultiRSU, HandoverMultiRSU):
        raise ValueError(
            f"run_campaign supports the built-in topologies "
            f"(single/multi/handover); got {type(topo).__name__}. "
            "Custom topologies run through the eager run() loop.")


def resolve_mode(mode: str) -> str:
    """auto -> "jit" on the CPU backend (scan's while loop pessimizes
    convolutions there), "scan" on accelerators."""
    if mode not in MODES:
        raise ValueError(f"mode {mode!r} not in {MODES}")
    if mode != "auto":
        return mode
    return "jit" if jax.default_backend() == "cpu" else "scan"


# --------------------------------------------------------------------------
# schedule planning (replays the eager draw sequence, bit for bit)
# --------------------------------------------------------------------------

def _data_stack(scenario):
    """Per-client data as one device array (n_clients, maxlen, ...);
    rows are zero-padded to the longest client but padding is never
    indexed (batch indices are drawn against each client's true
    length, exactly like the eager path)."""
    data = scenario.data
    sample = np.asarray(data[0])
    maxlen = max(len(d) for d in data)
    stack = np.zeros((len(data), maxlen) + sample.shape[1:], sample.dtype)
    for c, d in enumerate(data):
        stack[c, : len(d)] = d
    return jnp.asarray(stack)


def _plan_cohort_chunk(state, scenario, k: int):
    """Schedule for k single/multi rounds. Returns (xs_list, recs, key,
    rng) with the host RNG and jax key advanced exactly as k eager
    rounds would advance them."""
    cfg, mob, topo = scenario.cfg, scenario.mobility, scenario.topology
    rng = unpack_host_rng(state.host_rng)
    key = state.key
    if type(topo) is MultiRSU:
        assign = np.arange(cfg.vehicles_per_round) % topo.n_rsus
        # analysis: allow=host-sync-cast -- assign is host numpy
        rsu_sizes = [int((assign == r).sum()) for r in range(topo.n_rsus)
                     if (assign == r).any()]
    xs_list, recs = [], []
    for i in range(k):
        rnd = state.round + i
        ids, velocities, lr, key, cks = _cohort_plan(rng, key, rnd, scenario)
        idx = np.stack([_batch_indices(rng, len(scenario.data[c]), cfg)
                        for c in ids])
        blur = mob.blur_level(velocities)
        # analysis: allow=retrace-fresh-array -- the once-per-round
        # schedule upload: fresh host draws become device xs here
        xs_list.append((jnp.asarray(ids.astype(np.int32)),
                        jnp.asarray(idx.astype(np.int32)),
                        jnp.stack(cks), velocities, blur, lr))
        # analysis: sanctioned-sync -- plan-time record build: one
        # O(cohort) fetch per planned round, off the compiled path
        rec = {"round": rnd, "loss": None,
               "velocities": np.asarray(velocities).tolist(),
               "lr": float(lr), "topology": topo.name}
        if type(topo) is MultiRSU:
            rec["rsu_sizes"] = list(rsu_sizes)
        recs.append(rec)
    return xs_list, recs, key, rng


def _plan_handover_chunk(state, scenario, k: int):
    """Schedule for k handover rounds: replays `plan_round` (the SAME
    code the eager round executes) and packs each plan into device
    arrays. Returns (xs_list, recs, key, rng, topo_host) where
    topo_host carries the advanced positions/accumulators."""
    topo = scenario.topology
    R = topo.n_rsus
    n = scenario.cfg.vehicles_per_round
    rng = unpack_host_rng(state.host_rng)
    key = state.key
    # analysis: allow=host-sync-fetch -- handover topo state is host
    # numpy (positions/accumulators); copies keep planning pure
    positions = np.asarray(state.topo["positions"])
    # analysis: allow=host-sync-fetch -- host accumulator copy
    blur_sum = np.array(state.topo["blur_sum"], np.float64)
    # analysis: allow=host-sync-fetch -- host accumulator copy
    upload_count = np.array(state.topo["upload_count"], np.float64)
    xs_list, recs = [], []
    for i in range(k):
        rnd = state.round + i
        plan = topo.plan_round(rng, key, rnd, positions, blur_sum,
                               upload_count, scenario)
        key = plan["key"]
        positions = plan["positions"]
        blur_sum, upload_count = plan["blur_sum"], plan["upload_count"]
        wmat = np.zeros((R, n), np.float32)
        has_up = np.zeros((R,), bool)
        for rsu, sel, w in plan["uploads"]:
            wmat[rsu, sel] = w
            has_up[rsu] = True
        sync_w = (plan["sync_W"] if plan["synced"]
                  else np.zeros((R,), np.float64)).astype(np.float32)
        # analysis: allow=retrace-fresh-array -- the once-per-round
        # schedule upload (handover plan arrays become device xs)
        xs_list.append((jnp.asarray(plan["ids"].astype(np.int32)),
                        jnp.asarray(plan["idx"].astype(np.int32)),
                        jnp.stack(plan["cks"]), plan["velocities"],
                        plan["lr"],
                        jnp.asarray(plan["down"].astype(np.int32)),
                        jnp.asarray(wmat), jnp.asarray(has_up),
                        jnp.asarray(bool(plan["synced"])),
                        jnp.asarray(sync_w)))
        # analysis: sanctioned-sync -- plan-time record build;
        # stale/velocities are host plan arrays
        recs.append({"round": rnd, "loss": None,
                     "velocities": np.asarray(plan["velocities"]).tolist(),
                     "lr": float(plan["lr"]), "topology": topo.name,
                     "rsu_sizes": plan["upload_sizes"],
                     "n_handovers": int(plan["stale"].sum()),
                     "synced": plan["synced"]})
    topo_host = {"positions": positions, "blur_sum": blur_sum,
                 "upload_count": upload_count}
    return xs_list, recs, key, rng, topo_host


# --------------------------------------------------------------------------
# round bodies (one per topology family)
# --------------------------------------------------------------------------

def _round_codec(cfg):
    """The codec the compiled bodies thread, or None for identity (the
    no-op stage costs nothing to skip at trace time). A stateful codec
    grows the carry by its error-feedback residual — still ONE traced
    program per campaign (`compile_counts`): the codec is part of the
    cfg in the callable cache key, and its ops trace into the same
    round body."""
    return None if cfg.codec == "identity" else CODECS[cfg.codec]


def _client_batches(dstack, ids, idx, velocities, scenario):
    batches = dstack[ids[:, None], idx]
    if scenario.blur_images:
        batches = jax.vmap(apply_motion_blur, in_axes=(0, 0, None))(
            batches, velocities, scenario.mobility.camera_const)
    return batches


def _build_cohort_body(scenario):
    """Round body for SingleRSU / MultiRSU: carry = (global_tree,).

    When MultiRSU resolves a multi-device cohort mesh (the default with
    >1 device — see `MultiRSU.resolve_mesh`), the traced body runs the
    client blocks under shard_map and routes the two-level reduction
    through `sharded_hierarchical` — the compiled path and the sharded
    path COMPOSE (shard_map inlines into the jitted round program), one
    program per campaign either way.
    """
    cfg, topo = scenario.cfg, scenario.topology
    local = raw_local_step(cfg)
    mesh = None
    if type(topo) is MultiRSU:
        assign = np.arange(cfg.vehicles_per_round) % topo.n_rsus
        sels = [np.where(assign == r)[0] for r in range(topo.n_rsus)]
        sels = [s for s in sels if s.size]
        count_scaled = topo.count_scaled
        mesh = topo.resolve_mesh(cfg)
        if mesh is not None and mesh.size > 1:
            from jax.sharding import PartitionSpec as P

            from repro.core.hierarchical import sharded_hierarchical
            axes = tuple(a for a in mesh.axis_names
                         if a in ("pod", "data"))
            # rsu-major permutation: client blocks shard in cohort order
            # (losses stream out in cohort order, same as the host body),
            # the reduction sees rsu-major rows
            perm = np.concatenate(sels)
            # analysis: allow=retrace-ctor -- built once per campaign
            # callable, memoized in _CALLABLE_CACHE below
            sh_step = jax.shard_map(
                jax.vmap(local, in_axes=(None, 0, 0, None)), mesh=mesh,
                in_specs=(P(), P(axes), P(axes), P()),
                out_specs=(P(axes), P(axes)), check_vma=False)
        else:
            mesh = None
    aggregator = agg.AGGREGATORS[cfg.aggregator]
    codec = _round_codec(cfg)

    def body(dstack, carry, xs):
        if codec is not None and codec.stateful:
            tree, ef = carry
        else:
            (tree,) = carry
            ef = None
        ids, idx, cks, velocities, blur, lr = xs
        batches = _client_batches(dstack, ids, idx, velocities, scenario)
        if mesh is not None:
            trees, losses = sh_step(tree, batches, cks, lr)
        else:
            trees, losses = jax.vmap(local, in_axes=(None, 0, 0, None))(
                tree, batches, cks, lr)
        trees, losses, blur = jax.lax.optimization_barrier(
            (trees, losses, blur))
        new_ef = None
        if codec is not None:
            # comms tier, in cohort order (EF slot i = cohort position
            # i — identical to the host paths' rows=sel/perm scatter);
            # the aggregation below consumes the RECONSTRUCTED trees
            payload, new_ef = codec.encode(trees, tree, ef)
            trees = codec.decode(payload, tree)
        if mesh is not None:
            new_tree = sharded_hierarchical(
                jax.tree.map(lambda x: x[perm], trees), blur[perm], mesh,
                len(sels), count_scaled=count_scaled,
                reduction=topo.mesh_reduction)
        elif type(topo) is MultiRSU:
            cohorts = [
                CohortBatch.from_stacked(
                    jax.tree.map(lambda x: x[sel], trees), losses[sel]
                ).with_stats(velocities=velocities[sel], blur=blur[sel])
                for sel in sels]
            new_tree = aggregate_hierarchical(cohorts,
                                              count_scaled=count_scaled)
        else:
            cohort = CohortBatch.from_stacked(trees, losses).with_stats(
                velocities=velocities, blur=blur)
            new_tree = aggregator(cohort, cfg)
        if new_ef is not None:
            new_tree, new_ef = jax.lax.optimization_barrier(
                (new_tree, new_ef))
            return (new_tree, new_ef), losses
        new_tree = jax.lax.optimization_barrier(new_tree)
        return (new_tree,), losses

    return body


def _build_handover_body(scenario):
    """Round body for HandoverMultiRSU: carry = (global_tree, rsu_stack)
    where rsu_stack holds the per-RSU models with a leading n_rsus axis.
    The stacked form is internal to the compiled program: the callables
    take and return the per-RSU tuple and convert at their edges
    (`_stack_rsus` / `_unstack_rsus`), so rounds inside a scan chunk keep
    the stacked carry.

    Every download/upload regrouping arrives as DATA (the per-client
    download index, the zero-padded upload-weight matrix, the sync flag
    + level-2 weights), so one compiled program covers every round —
    no bucket regimes, no eager fallback. Zero upload weights contribute
    exact +0.0 terms and `where`-gated sync/keep branches select full
    precomputed alternatives, matching the eager skip/sync semantics.
    """
    cfg, topo = scenario.cfg, scenario.topology
    R = topo.n_rsus
    local = raw_local_step(cfg)
    codec = _round_codec(cfg)

    def body(dstack, carry, xs):
        if codec is not None and codec.stateful:
            gtree, rstack, ef = carry
        else:
            gtree, rstack = carry
            ef = None
        ids, idx, cks, velocities, lr, down, wmat, has_up, sync, sync_w = xs
        batches = _client_batches(dstack, ids, idx, velocities, scenario)
        # each client trains from the model of the RSU covering its
        # round-start position — a gather out of the stacked carry
        init_trees = jax.tree.map(lambda x: x[down], rstack)
        trees, losses = jax.vmap(local, in_axes=(0, 0, 0, None))(
            init_trees, batches, cks, lr)
        trees, losses = jax.lax.optimization_barrier((trees, losses))
        new_ef = None
        if codec is not None:
            # comms tier: each client's delta is against its DOWNLOAD
            # RSU's model (a per-row stacked base), matching the eager
            # handover path's per-group roundtrip
            payload, new_ef = codec.encode(trees, init_trees, ef,
                                           stacked_base=True)
            trees = codec.decode(payload, init_trees, stacked_base=True)
        # uploads: each RSU's new model is a weighted sum over the FULL
        # cohort with zero weights off-group; RSUs without usable
        # uploads keep their model
        ups = [agg._weighted_stacked_sum(trees, wmat[r]) for r in range(R)]
        up_stack = jax.tree.map(lambda *ls: jnp.stack(ls), *ups)

        def keep(old, new):
            sel = has_up.reshape((R,) + (1,) * (old.ndim - 1))
            return jnp.where(sel, new, old)

        rstack = jax.tree.map(keep, rstack, up_stack)
        # region sync: merge with the precomputed level-2 weights when
        # the flag is set, else pass both models through unchanged
        with jax.named_scope("region_sync"):
            merged = agg._weighted_stacked_sum(rstack, sync_w)
            rstack = jax.tree.map(
                lambda r_, m: jnp.where(sync, jnp.broadcast_to(m, r_.shape),
                                        r_),
                rstack, merged)
            gtree = jax.tree.map(lambda g, m: jnp.where(sync, m, g),
                                 gtree, merged)
        if new_ef is not None:
            gtree, rstack, new_ef = jax.lax.optimization_barrier(
                (gtree, rstack, new_ef))
            return (gtree, rstack, new_ef), losses
        gtree, rstack = jax.lax.optimization_barrier((gtree, rstack))
        return (gtree, rstack), losses

    return body


def _stack_rsus(carry):
    """(gtree, rsu_models tuple[, ef]) -> (gtree, rsu_stack[, ef]), traced
    inside the compiled program: one copy of the models, no host op."""
    gtree, rsus, *ef = carry
    return (gtree, jax.tree.map(lambda *ls: jnp.stack(ls), *rsus), *ef)


def _unstack_rsus(carry):
    """Inverse of `_stack_rsus`: the per-RSU tuple that
    `FLState.topo["rsu_models"]` holds."""
    gtree, rstack, *ef = carry
    R = jax.tree.leaves(rstack)[0].shape[0]
    return (gtree, tuple(jax.tree.map(lambda x: x[r], rstack)
                         for r in range(R)), *ef)


def _identity(carry):
    return carry


# --------------------------------------------------------------------------
# compiled-callable cache
# --------------------------------------------------------------------------

_CALLABLE_CACHE: dict = {}


def _campaign_key(scenario):
    return (scenario.cfg,
            tuple(sorted(scenario.topology.signature().items())),
            scenario.mobility, scenario.blur_images,
            agg._resolve_wagg_backend())


def campaign_callables(scenario) -> dict:
    """The jitted round body + scan wrapper for this scenario, cached on
    (cfg, topology signature, mobility, blur flag, wagg backend) — a
    sweep over seeds/rounds reuses one compilation; switching the wagg
    backend retraces. The data stack is an ARGUMENT, so programs
    specialize on shapes only, never on dataset values."""
    key = _campaign_key(scenario)
    got = _CALLABLE_CACHE.get(key)
    if got is None:
        if isinstance(scenario.topology, HandoverMultiRSU):
            body = _build_handover_body(scenario)
            enter, leave = _stack_rsus, _unstack_rsus
        else:
            body = _build_cohort_body(scenario)
            enter = leave = _identity
        # trace counters: jax runs the python function once per trace,
        # and every trace lowers to exactly one XLA program — unlike
        # `fn._cache_size()`, which also counts dispatch-cache re-keys
        # for equivalent inputs (e.g. numpy leaves from a restored
        # checkpoint) that reuse the existing executable
        traces = {"jit_round": 0, "scan": 0}

        def _counted(name, f):
            def wrapped(*a):
                traces[name] += 1
                return f(*a)
            return wrapped

        def _round(ds, c, x):
            c, losses = body(ds, enter(c), x)
            return leave(c), losses

        def _scan(ds, c, xs):
            c, ys = jax.lax.scan(lambda cc, x: body(ds, cc, x), enter(c), xs)
            return leave(c), ys

        got = {
            # analysis: allow=retrace-ctor -- memoized in _CALLABLE_CACHE
            "jit_round": jax.jit(_counted("jit_round", _round)),
            # analysis: allow=retrace-ctor -- memoized in _CALLABLE_CACHE
            "scan": jax.jit(_counted("scan", _scan)),
            "traces": traces,
        }
        _CALLABLE_CACHE[key] = got
    return got


def compile_counts(scenario) -> dict:
    """Traced-program counts for this scenario's engine callables:
    {"jit_round": ..., "scan": ...} (each trace lowers to one XLA
    compile). The campaign contract — benchmarks/round_engine.py
    asserts it — is jit_round <= 1 program per campaign and scan <=
    #distinct chunk lengths (<= 2 for a fixed checkpoint cadence),
    REGARDLESS of topology: handover regrouping is data, not shape."""
    got = _CALLABLE_CACHE.get(_campaign_key(scenario))
    if got is None:
        return {"jit_round": 0, "scan": 0}
    return dict(got["traces"])


def reset_engine_caches() -> None:
    """Drop every cached engine callable (benchmark/test isolation)."""
    _CALLABLE_CACHE.clear()


# --------------------------------------------------------------------------
# campaign driver
# --------------------------------------------------------------------------

def _carry_of(state, scenario):
    codec = _round_codec(scenario.cfg)
    # a stateful codec's error-feedback residual rides in the carry so
    # the compiled chunks thread it exactly like the eager rounds do
    ef = (state.comms["ef"],) if codec is not None and codec.stateful else ()
    if isinstance(scenario.topology, HandoverMultiRSU):
        # the per-RSU tuple as the state holds it: the compiled program
        # stacks it (`_stack_rsus`), the host touches no leaf
        return (state.global_tree, state.topo["rsu_models"]) + ef
    return (state.global_tree,) + ef


def _state_of(carry, state, scenario, key, rng, k, topo_host):
    codec = _round_codec(scenario.cfg)
    comms = state.comms
    if codec is not None and codec.stateful:
        carry, comms = carry[:-1], {"ef": carry[-1]}
    if isinstance(scenario.topology, HandoverMultiRSU):
        gtree, rsu_models = carry
        topo = {"positions": topo_host["positions"],
                "rsu_models": rsu_models,
                "blur_sum": topo_host["blur_sum"],
                "upload_count": topo_host["upload_count"]}
        return state.replace(global_tree=gtree, key=key,
                             host_rng=pack_host_rng(rng),
                             round=state.round + k, topo=topo, comms=comms)
    return state.replace(global_tree=carry[0], key=key,
                         host_rng=pack_host_rng(rng),
                         round=state.round + k, comms=comms)


def _replicated(scenario):
    """A sharding replicated over the cohort mesh the round body
    shard_maps on, or None for a single-device body. The carry and the
    data stack are placed there before every call, so every call sees
    the same input shardings: otherwise the first chunk (single-device
    inputs) and the next (mesh-replicated outputs of the previous one)
    trace and compile the round twice."""
    topo = scenario.topology
    if type(topo) is not MultiRSU:
        return None
    mesh = topo.resolve_mesh(scenario.cfg)
    if mesh is None or mesh.size <= 1:
        return None
    from jax.sharding import NamedSharding, PartitionSpec
    # analysis: allow=retrace-ctor -- once per campaign; the mesh is the
    # lru_cached cohort mesh
    return NamedSharding(mesh, PartitionSpec())


def _plan_chunk(state, scenario, k):
    if isinstance(scenario.topology, HandoverMultiRSU):
        return _plan_handover_chunk(state, scenario, k)
    xs_list, recs, key, rng = _plan_cohort_chunk(state, scenario, k)
    return xs_list, recs, key, rng, {}


def run_campaign(scenario, state: Optional[FLState] = None,
                 rounds: Optional[int] = None, *, mode: str = "auto",
                 checkpoint_every: Optional[int] = None,
                 checkpoint_dir: Optional[str] = None,
                 log_every: int = 0, transfer_guard: bool = False,
                 publish=None, publish_every: int = 0):
    """Run `rounds` rounds (default cfg.rounds) through the compiled
    campaign engine. Returns (final state, history) like `run`, with the
    whole schedule bitwise-identical to the eager loop (losses/models
    agree to float tolerance; see the module docstring).

    mode              "jit" (one compiled round, python loop — the CPU
                      fast path), "scan" (lax.scan chunks — the
                      accelerator path), or "auto" (pick by backend)
    checkpoint_every  chunk size AND checkpoint cadence; resuming from
                      any saved chunk boundary is bit-exact with the
                      uninterrupted campaign (tests/test_engine.py)
    checkpoint_dir    where `save_state` writes round_NNNNNN.npz (+ the
                      scenario fingerprint sidecar); required when
                      checkpoint_every is set
    log_every         print the same "[round N] loss=... lr=..." lines
                      as the eager `run`, but from the ONCE-per-chunk
                      fetched history — logging never adds a per-round
                      host sync to the compiled path
    transfer_guard    wrap the fused-round dispatch (not the host-side
                      planning) in `analysis.guards.no_implicit_transfers`
                      so any implicit host<->device transfer inside the
                      compiled path raises. Steady-state assertion: run
                      one warm-up campaign first — compilation itself
                      uploads constants and would trip the guard
                      (tests/test_engine.py::test_round_body_no_implicit_transfers)
    publish           serving hook: called as ``publish(round, tree)``
                      with the post-chunk ``FLState`` round and global
                      tree (device arrays, untouched) at the SAME
                      once-per-chunk boundary as the history fetch —
                      e.g. ``ModelStore.publish`` from repro.serve.
                      Serving never adds per-round device syncs
                      (tests/test_serve.py pins the compile bounds)
    publish_every     chunk size when only serving cadence matters —
                      like log_every/checkpoint_every but for the
                      publish hook; 0 publishes once per natural chunk

    Under a `jax.profiler` trace the call marks its pieces with host
    spans: `campaign.data_stack` once, and per chunk a `campaign.chunk`
    step (`step_num` = the chunk's first round) holding `campaign.plan`,
    `.carry`, `.dispatch`, `.fetch`, `.state`, `.publish` and
    `.checkpoint`, each with `round` = that step number (README
    §Tracing a campaign).
    """
    check_campaign_supported(scenario)
    mode = resolve_mode(mode)
    if checkpoint_every is not None:
        if checkpoint_every < 1:
            raise ValueError("checkpoint_every must be >= 1")
        if checkpoint_dir is None:
            raise ValueError("checkpoint_every needs checkpoint_dir")
    if state is None:
        state = scenario.init_state()
    total = rounds if rounds is not None else scenario.cfg.rounds
    if publish_every < 0:
        raise ValueError("publish_every must be >= 0")
    chunk = (checkpoint_every or publish_every
             or (log_every if log_every > 0 else total))
    chunk = max(1, min(chunk, total)) if total else 1
    fns = campaign_callables(scenario)
    rep = _replicated(scenario)
    # host spans (jax.profiler): free unless a profiler is collecting;
    # their attributes come from shapes, never from a device sync
    with TraceAnnotation("campaign.data_stack") as span:
        dstack = _data_stack(scenario)
        if rep is not None:
            dstack = jax.device_put(dstack, rep)
        span.set_metadata(bytes=dstack.nbytes)
    history = []
    done = 0
    while done < total:
        k = min(chunk, total - done)
        rnd = state.round
        if transfer_guard:
            from repro.analysis.guards import no_implicit_transfers
            guard = no_implicit_transfers()
        else:
            guard = contextlib.nullcontext()
        # one step per chunk, numbered by its first round; every child
        # span carries the same number as `round`
        with StepTraceAnnotation("campaign.chunk", step_num=rnd, rounds=k):
            with TraceAnnotation("campaign.plan", round=rnd,
                                 rounds=k) as span:
                xs_list, recs, key, rng, topo_host = _plan_chunk(
                    state, scenario, k)
                span.set_metadata(upload_bytes=tree_nbytes(xs_list))
            with TraceAnnotation("campaign.carry", round=rnd):
                carry = _carry_of(state, scenario)
                if rep is not None:
                    carry = jax.device_put(carry, rep)
            with TraceAnnotation("campaign.dispatch", round=rnd), guard:
                if mode == "scan":
                    xs = jax.tree.map(lambda *ls: jnp.stack(ls), *xs_list)
                    carry, ys = fns["scan"](dstack, carry, xs)
                else:
                    ys = []
                    for x in xs_list:
                        carry, losses = fns["jit_round"](dstack, carry, x)
                        ys.append(losses)
                    ys = jnp.stack(ys)
            # the host waits here for the chunk's device work
            with TraceAnnotation("campaign.fetch", round=rnd) as span:
                # ONE host transfer per chunk: the stacked loss history
                # analysis: sanctioned-sync -- the designed once-per-chunk fetch
                losses_h = np.asarray(jax.device_get(ys), np.float64)
                span.set_metadata(bytes=ys.nbytes)
                for i, rec in enumerate(recs):
                    rec["loss"] = float(np.mean(losses_h[i]))
                    history.append(rec)
                    if log_every and rec["round"] % log_every == 0:
                        print(f"[round {rec['round']:4d}] "
                              f"loss={rec['loss']:.4f} lr={rec['lr']:.4f}")
            with TraceAnnotation("campaign.state", round=rnd):
                state = _state_of(carry, state, scenario, key, rng, k,
                                  topo_host)
            if publish is not None:
                with TraceAnnotation("campaign.publish", round=rnd):
                    publish(state.round, state.global_tree)
            done += k
            if checkpoint_every:
                from repro.checkpoint.store import save_state
                with TraceAnnotation("campaign.checkpoint", round=rnd):
                    save_state(os.path.join(checkpoint_dir,
                                            f"round_{state.round:06d}"),
                               state, scenario)
    return state, history
