"""RSU topologies — pure round orchestration over an explicit `FLState`.

The paper's FLSimCo loop (Sec. 4) assumes a single RSU, yet its own
motivation — vehicles at high velocity — means clients cross RSU coverage
boundaries mid-training. This module factors the *shape of a round* into
a `Topology` strategy (DESIGN.md §3). A topology is a *stateless* config
object: everything that changes round to round (positions, per-RSU
models, sync statistics) lives in `FLState.topo`, so

    state, rec = topology.run_round(state, scenario)

is pure — same state in, same state out, nothing mutated.

  SingleRSU         paper-exact Steps 2-4: one RSU, one cohort, one
                    host-side aggregation (any ``AGGREGATORS`` scheme,
                    any ``CLIENT_UPDATES`` algorithm).
  MultiRSU          N RSUs under one regional server. Each RSU trains its
                    cohort as a vmapped batch and aggregates locally
                    (Eq. 11), then the region merges the RSU models —
                    `aggregate_hierarchical` on host, or the
                    `two_stage_weighted_psum` collective when a
                    (pod, data) mesh is available. With n_rsus=1 this
                    reduces exactly to SingleRSU (tests/test_topology.py).
  HandoverMultiRSU  MultiRSU plus vehicle motion: per-RSU models persist
                    across rounds in `FLState.topo`, vehicles hold
                    positions on a circular road and download from the
                    RSU covering their position at round start. Positions
                    advance during local training; a vehicle that ends
                    the round under a different RSU uploads *there* (a
                    handover), and the receiving RSU discounts that stale
                    upload's Eq.-11 weight by `stale_discount`. Every
                    `sync_every` rounds the region merges the RSU models.

Rounds move cohorts between layers as device-resident `CohortBatch`es
(core/cohort.py): the client layer returns its vmapped result stacked,
aggregation consumes the stacked leaves + validity mask directly, and
the per-client payload (losses) crosses to host exactly once per round,
in the single `jax.device_get` that builds the round record (handover
additionally fetches a few SMALL per-round arrays — positions, blur,
per-RSU weights — whose sizes are O(cohort), not O(model)). Handover pads each per-RSU group to a bucketed
(power-of-two) size so its variable-size cohorts run the vmapped path
with a bounded set of compiles, bit-exact with the sequential reference
(tests/test_topology.py). All three topologies funnel their weighted
sums through `core.aggregation._weighted_stacked_sum`, i.e. the fused
Pallas `wagg` kernel on TPU (tree-map fallback off-TPU;
`wagg_backend("interpret")` forces the kernel anywhere).
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.comms.codecs import roundtrip_cohort
from repro.core import aggregation as agg
from repro.core.clients import CLIENT_UPDATES
from repro.core.cohort import CohortBatch, bucket_size
from repro.core.hierarchical import (aggregate_hierarchical,
                                     sharded_hierarchical)
from repro.core.mobility import apply_motion_blur
from repro.core.state import FLConfig, FLState, pack_host_rng, unpack_host_rng


# --------------------------------------------------------------------------
# shared round machinery (host RNG draws in a fixed, documented order)
# --------------------------------------------------------------------------

def _batch_indices(rng, data_len: int, cfg) -> np.ndarray:
    """One client's batch indices, drawn from the *host* RNG stream.

    Fixed batch size across clients (vmapped cohorts need equal shapes);
    small clients sample with replacement. This is the ONE place batch
    indices come from: the eager round path and the compiled campaign
    engine (core/engine.py) both draw through here, which is what makes
    the engine's pre-drawn schedule arrays bitwise-identical to the live
    draws (tests/test_engine.py)."""
    return rng.choice(data_len, size=cfg.batch_size,
                      replace=data_len < cfg.batch_size)


def _client_images(scenario, cid: int, idx, velocity):
    """Materialize one client's batch from pre-drawn indices (consumes
    no RNG — blur is a pure function of the velocity draw)."""
    # analysis: allow=retrace-fresh-array -- the per-round batch upload
    # IS the data path; indices are fresh draws, nothing to cache
    images = jnp.asarray(scenario.data[cid][idx])
    if scenario.blur_images:
        images = apply_motion_blur(images, velocity,
                                   scenario.mobility.camera_const)
    return images


def _client_batch(rng, scenario, cid: int, velocity):
    """Draw + materialize one client's training batch."""
    idx = _batch_indices(rng, len(scenario.data[cid]), scenario.cfg)
    return _client_images(scenario, cid, idx, velocity)


def _draw_batches(rng, scenario, ids, velocities):
    """Batches for a cohort, drawn in `ids` order (the host RNG is a
    sequential stream, so draw order matters for cross-topology
    equivalence — see MultiRSU.run_round)."""
    return jnp.stack([_client_batch(rng, scenario, c, v)
                      for c, v in zip(ids, velocities)])


def _cohort_plan(rng, key, rnd: int, scenario):
    """Training-independent round preamble: cohort ids from the host RNG,
    velocities + per-client keys from the jax chain, LR from the cosine
    schedule. Takes the RNG streams EXPLICITLY (not an FLState) so the
    compiled campaign engine can replay the identical draw sequence K
    rounds ahead of execution. Returns (ids, velocities, lr, key, cks).
    """
    cfg, mob = scenario.cfg, scenario.mobility
    ids = rng.choice(cfg.n_vehicles, size=cfg.vehicles_per_round,
                     replace=False)
    key, kv = jax.random.split(key)
    velocities = mob.sample(kv, len(ids))
    lr = scenario.lr_fn(rnd)
    key, *cks = jax.random.split(key, len(ids) + 1)
    return ids, velocities, lr, key, cks


def _sample_cohort(state, scenario):
    """Round preamble shared by SingleRSU and MultiRSU.

    The draw ORDER (host-RNG cohort ids -> jax velocity key -> per-client
    keys) is load-bearing: the MultiRSU(1) == SingleRSU bit-exactness
    guarantee requires both topologies to consume both RNG streams
    identically, so the sequence lives in exactly one place
    (`_cohort_plan`, also the engine's schedule source).
    Returns (rng, ids, velocities, lr, key, client_keys).
    """
    rng = unpack_host_rng(state.host_rng)
    ids, velocities, lr, key, cks = _cohort_plan(rng, state.key,
                                                 state.round, scenario)
    return rng, ids, velocities, lr, key, cks


def _region_sync_weights(mob, blur_sum, upload_count,
                         count_scaled: bool) -> np.ndarray:
    """Level-2 sync weights (Eq. 11 over per-RSU mean blur since the last
    sync, optionally scaled by upload counts). Training-independent —
    shared by the eager round and the engine's schedule precompute."""
    counts = np.asarray(upload_count, np.float64)
    mean_blur = np.where(
        counts > 0, np.asarray(blur_sum, np.float64) / np.maximum(counts, 1.0),
        float(mob.blur_level(mob.mu)))   # no uploads: prior mean blur
    W = np.asarray(agg.flsimco_weights(jnp.asarray(mean_blur, jnp.float32)))
    if count_scaled:
        W = W * counts
    s = W.sum()
    return W / s if s > 1e-12 else np.full_like(W, 1.0 / len(W))


def _record_fetch(losses, velocities, lr):
    """The one per-round device transfer: fetch the whole record payload
    (losses + velocities + lr) in a single `device_get`. Losses stay
    device-resident inside the `CohortBatch` until here; the mean is
    taken in float64 on host, matching the old per-client `float(loss)`
    record values bit for bit. `device_get` passes host (numpy) inputs
    through untouched, so callers hand over whatever mix the round
    produced — no re-upload, no second sync for the lr scalar.
    """
    # analysis: sanctioned-sync -- the designed once-per-round record fetch
    losses_h, v_h, lr_h = jax.device_get((losses, velocities, lr))
    # analysis: sanctioned-sync -- host-side views of the fetched payload
    return (np.asarray(losses_h, np.float64),
            np.asarray(v_h).tolist(), float(lr_h))


class Topology:
    """Strategy object: owns the structure of one federated round.

    Topologies hold only static configuration (n_rsus, ranges, ...);
    round-to-round state lives in `FLState.topo`, produced by
    `init_state` and threaded through `run_round`.

    validate(cfg)                      fail fast on unsupported configs
    init_state(cfg, mobility,
               global_tree, key)       -> (topo_state dict, new key)
    run_round(state, scenario)         -> (new FLState, round record)
    """

    name = "base"

    def validate(self, cfg: FLConfig) -> None:
        pass

    def signature(self) -> dict:
        """Static topology parameters, JSON-able — part of the checkpoint
        experiment fingerprint (checkpoint/store.py) and the engine's
        compiled-callable cache key (core/engine.py). The name alone is
        not enough: a handover checkpoint taken under n_rsus=2 must not
        resume under n_rsus=3."""
        return {"name": self.name}

    def init_state(self, cfg: FLConfig, mobility, global_tree, key):
        return {}, key

    def run_round(self, state: FLState, scenario, parallel: bool = True):
        raise NotImplementedError


class SingleRSU(Topology):
    """Paper-exact FLSimCo: one RSU aggregating one sampled cohort."""

    name = "single"

    def run_round(self, state: FLState, scenario, parallel: bool = True):
        cfg, mob = scenario.cfg, scenario.mobility
        rng, ids, velocities, lr, key, cks = _sample_cohort(state, scenario)
        client = CLIENT_UPDATES[cfg.client]
        batches = _draw_batches(rng, scenario, ids, velocities)
        cohort, uploads = client.run_cohort(
            cfg, state.global_tree, state.client_state, batches, cks, lr,
            parallel)
        cohort = cohort.with_stats(velocities=velocities,
                                   blur=mob.blur_level(velocities))
        # comms tier: the cohort the RSU aggregates is what survived the
        # V2I link (encode -> decode against the broadcast base model);
        # identity short-circuits, the lossless delta codec is bitwise
        cohort, comms = roundtrip_cohort(cfg, cohort, state.global_tree,
                                         state.comms)
        new_tree = agg.AGGREGATORS[cfg.aggregator](cohort, cfg)
        new_cs = client.finalize(cfg, state.client_state, new_tree, uploads)
        losses, vels, lr_h = _record_fetch(cohort.valid_losses,
                                           cohort.valid_velocities, lr)
        rec = {"round": state.round, "loss": float(np.mean(losses)),
               "velocities": vels,
               "lr": lr_h, "topology": self.name}
        return state.replace(global_tree=new_tree, key=key,
                             host_rng=pack_host_rng(rng),
                             round=state.round + 1,
                             client_state=new_cs, comms=comms), rec


def _require_flsimco(cfg: FLConfig, name: str) -> None:
    if cfg.aggregator != "flsimco":
        raise ValueError(
            f"{name} implements the hierarchical Eq.-11 (blur-weighted) "
            f"extension and requires aggregator='flsimco'; got "
            f"{cfg.aggregator!r}. Run other schemes under SingleRSU.")
    if not cfg.normalize_weights:
        raise ValueError(
            f"{name} always normalizes Eq.-11 weights (DESIGN.md deviation "
            f"#2); normalize_weights=False would break the "
            f"MultiRSU(1) == SingleRSU equivalence. Use SingleRSU for the "
            f"unnormalized literal form.")


class MultiRSU(Topology):
    """N RSUs + regional server, no motion: hierarchical Eq. 11.

    The sampled cohort is dealt round-robin across RSUs; each RSU runs its
    vehicles as one vmapped batch. Aggregation is two-level: Eq.-11 within
    each RSU, then blur-weighted (optionally vehicle-count-scaled) across
    RSU models — `sharded_hierarchical` over a cached (pod=n_rsus, data=d)
    cohort mesh BY DEFAULT whenever >1 device is visible and the cohort
    splits evenly (mesh_aggregate=None auto; the "exact" reduction is
    bit-exact with the host path), `aggregate_hierarchical` on host
    otherwise. mesh_aggregate=True forces the mesh (actionable error when
    infeasible); False pins the host path. On a multi-device mesh the
    whole round shards: client blocks run under shard_map too
    (float-close vs the single-device vmap width — DESIGN.md §Sharded
    cohorts).
    """

    name = "multi"

    def __init__(self, n_rsus: int = 2, count_scaled: bool = True,
                 mesh_aggregate: bool | None = None,
                 mesh_reduction: str = "exact"):
        if n_rsus < 1:
            raise ValueError("n_rsus must be >= 1")
        if mesh_reduction not in ("exact", "psum"):
            raise ValueError(f"mesh_reduction {mesh_reduction!r} not in "
                             f"('exact', 'psum')")
        self.n_rsus = n_rsus
        self.count_scaled = count_scaled
        # None = AUTO (the default): shard whenever >1 device is visible
        # and the cohort splits evenly across RSUs; True forces the mesh
        # path (raising an actionable error when infeasible); False pins
        # the host path.
        self.mesh_aggregate = mesh_aggregate
        self.mesh_reduction = mesh_reduction

    def signature(self) -> dict:
        return {"name": self.name, "n_rsus": self.n_rsus,
                "count_scaled": self.count_scaled,
                "mesh_aggregate": self.mesh_aggregate,
                "mesh_reduction": self.mesh_reduction}

    def resolve_mesh(self, cfg: FLConfig):
        """The cohort mesh this topology's rounds run on, or None for the
        single-device host path. AUTO (mesh_aggregate=None) promotes the
        sharded path to the default whenever >1 device is visible and the
        cohort splits evenly; explicit True raises actionable errors
        (required vs available devices, uneven-cohort hint) instead of
        silently falling back."""
        from repro.launch.mesh import (cohort_axis_divisor, cohort_mesh,
                                       maybe_cohort_mesh)
        if self.mesh_aggregate is False:
            return None
        n = cfg.vehicles_per_round
        if n % self.n_rsus:
            if self.mesh_aggregate:   # explicit True: fail, don't fall back
                raise ValueError(
                    f"mesh_aggregate needs equal per-RSU cohorts: "
                    f"vehicles_per_round={n} not divisible by "
                    f"n_rsus={self.n_rsus} — pick n_rsus dividing the "
                    f"cohort, or mesh_aggregate=None to auto-fall-back")
            return None
        s = n // self.n_rsus
        if self.mesh_aggregate:
            return cohort_mesh(self.n_rsus,
                               cohort_axis_divisor(s, self.n_rsus))
        return maybe_cohort_mesh(self.n_rsus, s)

    def validate(self, cfg: FLConfig) -> None:
        _require_flsimco(cfg, "MultiRSU")
        # fail before any training work, not after the cohort has run
        self.resolve_mesh(cfg)

    def run_round(self, state: FLState, scenario, parallel: bool = True):
        cfg, mob = scenario.cfg, scenario.mobility
        rng, ids, velocities, lr, key, cks = _sample_cohort(state, scenario)
        blur = mob.blur_level(velocities)
        client = CLIENT_UPDATES[cfg.client]
        mesh = self.resolve_mesh(cfg)
        # draw every batch in round order BEFORE partitioning: the host RNG
        # is sequential, so this keeps MultiRSU(1) bit-identical to SingleRSU
        batches = _draw_batches(rng, scenario, ids, velocities)
        assign = np.arange(len(ids)) % self.n_rsus
        sels = [np.where(assign == rsu)[0] for rsu in range(self.n_rsus)]
        sels = [s for s in sels if s.size]
        if (mesh is not None and parallel and mesh.size > 1
                and cfg.client == "dtssl"):
            # fully sharded round: ONE rsu-major cohort, client blocks and
            # the two-level reduction both under shard_map. Client
            # execution vmaps per device block (float-close vs the
            # unsharded vmap width); the aggregation itself is bit-exact
            # with the host path (DESIGN.md §Sharded cohorts).
            perm = np.concatenate(sels)
            cohort, uploads = client.run_cohort(
                cfg, state.global_tree, state.client_state, batches[perm],
                jnp.stack([cks[i] for i in perm]), lr, parallel, mesh=mesh)
            blur_rm = blur[perm]      # blur_level already yields jnp f32
            cohort = cohort.with_stats(velocities=velocities[perm],
                                       blur=blur_rm)
            # codec rows are perm (cohort indices): EF slot = cohort
            # position, identical to the host branch's per-group slots
            cohort, comms = roundtrip_cohort(cfg, cohort,
                                             state.global_tree,
                                             state.comms, rows=perm)
            new_tree = sharded_hierarchical(
                cohort.valid_trees, blur_rm, mesh, len(sels),
                count_scaled=self.count_scaled,
                reduction=self.mesh_reduction)
            sizes = [int(s.size) for s in sels]
            losses = cohort.valid_losses   # already rsu-major
            uploads = list(uploads) if uploads else []
        else:
            comms = state.comms
            cohorts, sizes, uploads = [], [], []
            for sel in sels:
                cohort, ups = client.run_cohort(
                    cfg, state.global_tree, state.client_state,
                    batches[sel], [cks[i] for i in sel], lr, parallel)
                cohort = cohort.with_stats(velocities=velocities[sel],
                                           blur=blur[sel])
                # per-group roundtrip; the codec is row-wise, so group
                # application == full-cohort application (rows=sel keeps
                # EF slots in cohort order, matching the sharded branch)
                cohort, comms = roundtrip_cohort(cfg, cohort,
                                                 state.global_tree,
                                                 comms, rows=sel)
                cohorts.append(cohort)
                sizes.append(int(sel.size))
                if ups:
                    uploads.extend(ups)
            if mesh is not None:
                new_tree = self._mesh_aggregate(cohorts, mesh)
            else:
                new_tree = aggregate_hierarchical(
                    cohorts, count_scaled=self.count_scaled)
            losses = jnp.concatenate([c.valid_losses for c in cohorts])
        new_cs = client.finalize(cfg, state.client_state, new_tree,
                                 uploads or None)
        # losses in RSU order (matching the old list-extend order), one fetch
        losses, vels, lr_h = _record_fetch(losses, velocities, lr)
        rec = {"round": state.round, "loss": float(np.mean(losses)),
               "velocities": vels,
               "lr": lr_h, "topology": self.name, "rsu_sizes": sizes}
        return state.replace(global_tree=new_tree, key=key,
                             host_rng=pack_host_rng(rng),
                             round=state.round + 1,
                             client_state=new_cs, comms=comms), rec

    def _mesh_aggregate(self, cohorts: Sequence[CohortBatch], mesh):
        """Region merge sharded over the cached cohort mesh
        (launch/mesh.py — the old code built a fresh `jax.make_mesh`
        every round). reduction="exact" (default) is bit-exact with
        `aggregate_hierarchical`; "psum" is the blocked
        `two_stage_weighted_psum` collective (documented-float-close)."""
        sizes = {c.n for c in cohorts}
        if len(sizes) != 1:
            raise ValueError("mesh_aggregate needs equal per-RSU cohorts; "
                             f"got sizes {sorted(c.n for c in cohorts)}")
        # rsu-major stacked cohort: concatenate the already-stacked valid
        # leaves — the old list path re-stacked N separate trees here
        stacked = jax.tree.map(lambda *ls: jnp.concatenate(ls),
                               *[c.valid_trees for c in cohorts])
        blur = jnp.concatenate([c.valid_blur.astype(jnp.float32)
                                for c in cohorts])
        return sharded_hierarchical(stacked, blur, mesh, len(cohorts),
                                    count_scaled=self.count_scaled,
                                    reduction=self.mesh_reduction)


class HandoverMultiRSU(Topology):
    """MultiRSU with persistent per-RSU models and vehicle motion.

    Road model: a ring road of length n_rsus * rsu_range; RSU r covers
    [r*rsu_range, (r+1)*rsu_range). Each round every vehicle's position
    advances by v * round_duration (positions wrap), so a participant can
    download from RSU A and — after training — upload to RSU B. Such stale
    uploads get their Eq.-11 weight multiplied by `stale_discount` before
    renormalization. RSUs that receive no uploads keep their model.
    Every `sync_every` rounds the regional server merges the RSU models
    with blur-weighted, upload-count-scaled level-2 weights (accumulated
    since the last sync) and redistributes the merged model.

    Per-RSU cohort sizes change with vehicle positions every round, and
    the vmapped cohort step specializes on its size — naively that is a
    fresh XLA compile per new size, which is why this topology used to be
    stuck on the slow sequential client path. Instead each download group
    is padded to a bucketed size (`cohort.bucket_size`: the next power of
    two), so `parallel=True` (the default) runs every group vmapped with
    at most ceil(log2(vehicles_per_round)) + 1 distinct compiles; the
    padding rows replicate the last valid client, consume no RNG, and are
    masked out of every upload aggregation, making the bucketed path
    bit-exact with the sequential reference (`parallel=False`,
    tests/test_topology.py).

    Per-round vehicle state (positions, per-RSU models, sync statistics)
    lives in `FLState.topo`:

      positions      (n_vehicles,) ring-road positions
      rsu_models     tuple of n_rsus model pytrees
      blur_sum       (n_rsus,) blur accumulated since last sync
      upload_count   (n_rsus,) uploads accumulated since last sync
    """

    name = "handover"

    def __init__(self, n_rsus: int = 2, rsu_range: float = 1000.0,
                 round_duration: float = 20.0, stale_discount: float = 0.5,
                 sync_every: int = 5, count_scaled: bool = True,
                 bucketed: bool = True, mesh_shard: bool = False):
        if n_rsus < 1:
            raise ValueError("n_rsus must be >= 1")
        if not 0.0 <= stale_discount <= 1.0:
            raise ValueError("stale_discount must be in [0, 1]")
        if sync_every < 1:
            raise ValueError("sync_every must be >= 1")
        self.n_rsus = n_rsus
        self.rsu_range = rsu_range
        self.road_length = n_rsus * rsu_range
        self.round_duration = round_duration
        self.stale_discount = stale_discount
        self.sync_every = sync_every
        self.count_scaled = count_scaled
        # bucketed=False runs the vmapped step at each group's EXACT size
        # — a fresh XLA compile for every cohort size vehicle motion
        # produces. Exists so benchmarks/round_engine.py can price the
        # recompile cost bucketing removes; keep the default on.
        self.bucketed = bucketed
        # mesh_shard=True shards each download group's client execution
        # over a (pod=1, data=d) cohort mesh when >1 device is visible;
        # the per-RSU regrouping stays device-side `CohortBatch.take`
        # gathers under the sharding. Opt-in (not auto like MultiRSU):
        # the sharded vmap width differs from the single-device one, so
        # this path is float-close, not bitwise, with the bucketed
        # reference the handover tests pin.
        self.mesh_shard = mesh_shard

    def signature(self) -> dict:
        return {"name": self.name, "n_rsus": self.n_rsus,
                "rsu_range": self.rsu_range,
                "round_duration": self.round_duration,
                "stale_discount": self.stale_discount,
                "sync_every": self.sync_every,
                "count_scaled": self.count_scaled,
                "mesh_shard": self.mesh_shard}

    def validate(self, cfg: FLConfig) -> None:
        _require_flsimco(cfg, "HandoverMultiRSU")
        if cfg.client != "dtssl":
            raise ValueError(
                "HandoverMultiRSU keeps divergent per-RSU models between "
                "syncs, so client algorithms with global server state "
                f"(client={cfg.client!r}) are undefined here; use "
                "client='dtssl' or the SingleRSU/MultiRSU topologies.")

    def init_state(self, cfg: FLConfig, mobility, global_tree, key):
        key, kp = jax.random.split(key)
        positions = np.asarray(mobility.init_positions(
            kp, cfg.n_vehicles, self.road_length))
        return {"positions": positions,
                "rsu_models": tuple([global_tree] * self.n_rsus),
                "blur_sum": np.zeros(self.n_rsus),
                "upload_count": np.zeros(self.n_rsus)}, key

    def rsu_index(self, positions) -> np.ndarray:
        return (np.floor_divide(np.asarray(positions), self.rsu_range)
                .astype(np.int64) % self.n_rsus)

    def plan_round(self, rng, key, rnd: int, positions, blur_sum,
                   upload_count, scenario) -> dict:
        """Everything about one handover round that does NOT depend on
        training results: all RNG draws (in the documented order), the
        download/upload grouping, Eq.-11 upload weights with staleness
        discounts, motion, sync decision + level-2 weights, and the
        accumulator updates. `run_round` executes a plan against the
        models; the campaign engine (core/engine.py) replays K plans
        ahead of time into schedule arrays — one code path for the
        draws is what makes the two bitwise-identical.

        Mutates nothing: takes positions/blur_sum/upload_count by value
        and returns their successors in the plan dict.
        """
        cfg, mob = scenario.cfg, scenario.mobility
        # analysis: allow=host-sync-fetch -- host accumulators (copied
        # by value so the plan mutates nothing; never device-resident)
        blur_sum = np.array(blur_sum, np.float64)
        # analysis: allow=host-sync-fetch -- host accumulator copy
        upload_count = np.array(upload_count, np.float64)
        n = cfg.vehicles_per_round
        ids = rng.choice(cfg.n_vehicles, size=n, replace=False)
        # one velocity draw per vehicle per round, used for both the blur
        # level of the participants' captures and the whole fleet's motion
        key, kv = jax.random.split(key)
        fleet_v = mob.sample(kv, cfg.n_vehicles)
        # analysis: allow=retrace-fresh-array -- once-per-round schedule
        # upload (fresh host draws enter the device here by design)
        velocities = jnp.take(fleet_v, jnp.asarray(ids))
        lr = scenario.lr_fn(rnd)
        key, *cks = jax.random.split(key, n + 1)

        # Step 2 grouping: download from the RSU covering the round-start
        # position; batch indices are drawn in download-group order (the
        # host RNG is sequential) and scattered back to cohort positions
        down = self.rsu_index(positions[ids])
        down_groups = []
        idx = np.empty((n, cfg.batch_size), np.int64)
        for rsu in range(self.n_rsus):
            sel = np.where(down == rsu)[0]
            if sel.size == 0:
                continue
            for i in sel:
                idx[i] = _batch_indices(rng, len(scenario.data[ids[i]]), cfg)
            down_groups.append((rsu, sel))

        # motion during the round: everyone moves, positions wrap
        # analysis: sanctioned-sync -- plan-time fetch of O(fleet)
        # positions; handover grouping is host-side by design
        positions = np.asarray(mob.advance_positions(
            positions, fleet_v, self.round_duration, self.road_length))

        # Step 3-4 grouping: upload to the RSU now covering the vehicle,
        # stale uploads discounted before renormalization
        up = self.rsu_index(positions[ids])
        stale = up != down
        # analysis: sanctioned-sync -- plan-time fetch of O(cohort) blur
        blur = np.asarray(mob.blur_level(velocities))
        upload_sizes, uploads = [], []
        for rsu in range(self.n_rsus):
            sel = np.where(up == rsu)[0]
            upload_sizes.append(int(sel.size))
            if sel.size == 0:
                continue
            # analysis: allow=host-sync-fetch,retrace-fresh-array --
            # Eq.-11 weights on O(group) arrays; f32-on-device is the
            # bit-pinned path (tests), the round trip is the price
            w = np.asarray(agg.flsimco_weights(jnp.asarray(blur[sel])))
            w = w * np.where(stale[sel], self.stale_discount, 1.0)
            s = w.sum()
            if s <= 1e-12:
                # every upload stale with stale_discount=0: no usable
                # uploads — the RSU keeps its model (same as receiving
                # none), rather than handing the discarded uploads full
                # uniform weight
                continue
            uploads.append((rsu, sel, w / s))
            # analysis: allow=host-sync-cast -- blur is host numpy here
            blur_sum[rsu] += float(blur[sel].sum())
            upload_count[rsu] += sel.size

        synced = (rnd + 1) % self.sync_every == 0
        sync_W = None
        if synced:
            sync_W = _region_sync_weights(mob, blur_sum, upload_count,
                                          self.count_scaled)
            blur_sum = np.zeros(self.n_rsus)
            upload_count = np.zeros(self.n_rsus)
        return {"ids": ids, "idx": idx, "velocities": velocities,
                "fleet_v": fleet_v, "lr": lr, "key": key, "cks": cks,
                "down": down, "down_groups": down_groups,
                "positions": positions, "up": up, "stale": stale,
                "blur": blur, "uploads": uploads,
                "upload_sizes": upload_sizes, "synced": synced,
                "sync_W": sync_W, "blur_sum": blur_sum,
                "upload_count": upload_count}

    def run_round(self, state: FLState, scenario, parallel: bool = True):
        cfg = scenario.cfg
        rng = unpack_host_rng(state.host_rng)
        rsu_models = list(state.topo["rsu_models"])
        plan = self.plan_round(rng, state.key, state.round,
                               # analysis: allow=host-sync-fetch --
                               # positions live in host topo state
                               np.asarray(state.topo["positions"]),
                               state.topo["blur_sum"],
                               state.topo["upload_count"], scenario)
        ids, velocities, lr = plan["ids"], plan["velocities"], plan["lr"]
        client = CLIENT_UPDATES[cfg.client]

        # Step 2: each download group runs vmapped (parallel=True, the
        # default), padded to its power-of-two bucket so the set of
        # compiled cohort sizes is bounded; parallel=False is the
        # sequential reference path. Either way the group results stay
        # STACKED in CohortBatches.
        mesh = None
        if self.mesh_shard and parallel:
            from repro.launch.mesh import maybe_cohort_mesh
            mesh = maybe_cohort_mesh(1, bucket_size(cfg.vehicles_per_round))
        comms = state.comms
        group_sel, group_cohorts = [], []
        for rsu, sel in plan["down_groups"]:
            batches = jnp.stack([
                _client_images(scenario, ids[i], plan["idx"][i],
                               velocities[i]) for i in sel])
            cohort, _ = client.run_cohort(
                cfg, rsu_models[rsu], state.client_state, batches,
                [plan["cks"][i] for i in sel], lr, parallel=parallel,
                pad_to=bucket_size(int(sel.size))
                if (parallel and self.bucketed) else None, mesh=mesh)
            # comms tier: each client's delta base is its DOWNLOAD RSU's
            # model (the tree it trained from); rows=sel keeps EF slots
            # in cohort order, matching the engine's per-row gather.
            # Bucketed padding rows are re-padded from the decoded rows.
            cohort, comms = roundtrip_cohort(cfg, cohort, rsu_models[rsu],
                                             comms, rows=sel)
            group_sel.append(sel)
            group_cohorts.append(cohort)
        # one stacked cohort of all n valid clients (padding dropped),
        # rows in download-group order; row_of maps cohort index -> row
        n = cfg.vehicles_per_round
        full = CohortBatch.concat(group_cohorts)
        order = np.concatenate(group_sel)
        row_of = np.empty(n, np.int64)
        row_of[order] = np.arange(n)

        # Step 3-4: upload groups are device-side gathers out of the
        # stacked cohort — the old path unstacked into n host trees and
        # re-stacked per RSU
        for rsu, sel, w in plan["uploads"]:
            sub = full.take(row_of[sel])
            rsu_models[rsu] = agg.cohort_weighted_sum(sub, w)

        new_tree = state.global_tree
        if plan["synced"]:
            with jax.named_scope("region_sync"):
                new_tree = agg._weighted_tree_sum(rsu_models, plan["sync_W"])
            rsu_models = [new_tree] * self.n_rsus
        # between syncs global_tree keeps the last merged model; RSU models
        # stay divergent until sync (region_view() merges on demand without
        # paying an n_rsus-model sum every round)
        losses_g, vels, lr_h = _record_fetch(full.losses, velocities, lr)
        losses = losses_g[row_of]                 # back to cohort order
        rec = {"round": state.round, "loss": float(np.mean(losses)),
               "velocities": vels,
               "lr": lr_h, "topology": self.name,
               "rsu_sizes": plan["upload_sizes"],
               # analysis: allow=host-sync-cast -- plan arrays are host numpy
               "n_handovers": int(plan["stale"].sum()),
               "synced": plan["synced"]}
        topo = {"positions": plan["positions"],
                "rsu_models": tuple(rsu_models),
                "blur_sum": plan["blur_sum"],
                "upload_count": plan["upload_count"]}
        return state.replace(global_tree=new_tree, key=plan["key"],
                             host_rng=pack_host_rng(rng),
                             round=state.round + 1, topo=topo,
                             comms=comms), rec

    def region_view(self, state: FLState):
        """Uniform merge of the current per-RSU models — an evaluation
        snapshot between syncs; does not touch the state."""
        return agg.aggregate_fedavg(list(state.topo["rsu_models"]))


TOPOLOGIES = {
    "single": SingleRSU,
    "multi": MultiRSU,
    "handover": HandoverMultiRSU,
}
