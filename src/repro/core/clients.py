"""Client update algorithms — the ``CLIENT_UPDATES`` registry.

The paper compares two client-side algorithms under the same federated
round structure: FLSimCo's dual-temperature SSL (Sec. 4 Step 2) and the
FedCo MoCo baseline (momentum key encoder + global negative queue). The
old trainer special-cased FedCo by string comparison
(``aggregator == "fedco"`` + a private ``_round_fedco``); here both are
entries in one registry with one signature, so every topology runs any
client algorithm through the same three hooks:

  init_state(cfg, global_tree)           -> client_state pytree (or None)
  run_cohort(cfg, tree, client_state, batches,
             keys, lr, parallel, pad_to) -> (CohortBatch, uploads)
  finalize(cfg, client_state,
           aggregated_tree, uploads)      -> new client_state

`run_cohort` returns a device-resident `CohortBatch` (core/cohort.py):
the vmapped result stays STACKED — no per-client unstacking, no
`float(loss)` device syncs; the topology fetches losses once per round
when it builds the record. `pad_to` pads the cohort to a bucketed size
(replicating the last batch/key; the mask marks the valid prefix) so
variable-size cohorts — the handover topology — reuse a bounded set of
compiled cohort-step sizes instead of recompiling per size.

`uploads` is whatever extra payload the vehicles send besides parameters
(FedCo: the k-value batches the RSU merges into the global queue; DT-SSL:
nothing). Aggregation of the parameter trees themselves is the
topology's job, through the ``AGGREGATORS`` registry — client algorithm
and aggregation scheme are orthogonal axes of a `Scenario`.

Jitted client steps are cached per (hyperparameter tuple), not per
trainer, so seed/aggregator/round-count sweeps reuse one compilation;
`cohort_step_cache_size(cfg)` exposes how many cohort shapes have been
compiled (benchmarks/round_engine.py asserts the bucketing bound).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import ssl
from repro.core.cohort import CohortBatch
from repro.core.dt_loss import dt_loss_matrix, info_nce_loss
from repro.core.state import FLConfig
from repro.models.resnet import resnet_apply
from repro.optim.optimizers import sgd


# --------------------------------------------------------------------------
# per-client local training (ResNet / images)
# --------------------------------------------------------------------------

def _client_loss(tree, cfg: FLConfig, images, key):
    """pi1/pi2 views -> encoder -> DT loss. Returns (loss, new_tree)."""
    k1, k2 = jax.random.split(key)
    v1 = ssl.pi1(k1, images)
    v2 = ssl.pi2(k2, images)
    q, _, tree1 = resnet_apply(tree, v1, train=True)
    k, _, tree2 = resnet_apply(tree1, v2, train=True)
    loss = dt_loss_matrix(q, k, cfg.tau_alpha, cfg.tau_beta)
    return loss, tree2


def make_local_train_step(cfg: FLConfig):
    opt_init, opt_update = sgd(cfg.momentum, cfg.weight_decay)

    def local_train(tree, images, key, lr):
        """cfg.local_iters SGD steps on one client. Returns (tree, loss).

        The iteration loop is a *python* unroll, not lax.scan: XLA-CPU
        pessimizes convolutions inside while-loops (~25x slower measured),
        and local_iters is 1-2 in the paper.
        """
        with jax.named_scope("client_step"):
            opt_state = opt_init(tree["params"])
            losses = []
            for k in jax.random.split(key, cfg.local_iters):
                tree_c = tree

                def loss_fn(params):
                    t = {"params": params, "state": tree_c["state"]}
                    loss, t2 = _client_loss(t, cfg, images, k)
                    return loss, t2["state"]

                (loss, new_state), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(tree_c["params"])
                new_params, opt_state = opt_update(tree_c["params"], grads,
                                                   opt_state, lr)
                tree = {"params": new_params, "state": new_state}
                losses.append(loss)
            return tree, jnp.stack(losses).mean()

    return local_train


def make_moco_local_train_step(cfg: FLConfig):
    """FedCo client: InfoNCE against the (global) queue, EMA key encoder."""
    opt_init, opt_update = sgd(cfg.momentum, cfg.weight_decay)

    def local_train(tree, key_tree, queue, images, key, lr):
        # python unroll (see make_local_train_step for the XLA-CPU rationale)
        with jax.named_scope("client_step"):
            opt_state = opt_init(tree["params"])
            losses, kvec = [], None
            for k in jax.random.split(key, cfg.local_iters):
                k1, k2 = jax.random.split(k)
                v1 = ssl.pi1(k1, images)
                v2 = ssl.pi2(k2, images)
                tree_c, key_tree_c = tree, key_tree

                def loss_fn(params):
                    t = {"params": params, "state": tree_c["state"]}
                    q, _, t2 = resnet_apply(t, v1, train=True)
                    kv, _, _ = resnet_apply(key_tree_c, v2, train=False)
                    kv = jax.lax.stop_gradient(kv)
                    return info_nce_loss(q, kv, queue), (t2["state"], kv)

                (loss, (new_state, kvec)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(tree_c["params"])
                new_params, opt_state = opt_update(tree_c["params"], grads,
                                                   opt_state, lr)
                tree = {"params": new_params, "state": new_state}
                key_tree = {
                    "params": ssl.momentum_update(key_tree_c["params"],
                                                  new_params,
                                                  cfg.moco_momentum),
                    "state": new_state,
                }
                losses.append(loss)
            return tree, key_tree, kvec, jnp.stack(losses).mean()

    return local_train


# --------------------------------------------------------------------------
# shared jit caches (keyed on exactly the fields the step closes over)
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=16)
def _cached_local_steps(local_iters, momentum, weight_decay,
                        tau_alpha, tau_beta):
    f = make_local_train_step(FLConfig(
        local_iters=local_iters, momentum=momentum,
        weight_decay=weight_decay, tau_alpha=tau_alpha, tau_beta=tau_beta))
    # The cohort step vmaps with the init tree UNBATCHED (in_axes=None):
    # every client in a cohort starts the round from the same model, so
    # broadcasting N weight copies (the old form) only forced XLA into
    # batched-weight (grouped) convolutions for ops whose weights are
    # genuinely shared. vmap propagates the batch axis lazily — the
    # first local iteration runs shared-weight, later iterations (whose
    # trees have diverged per client) batched — and the result is
    # bit-exact with the sequential path (tests/test_federation.py).
    return jax.jit(f), jax.jit(jax.vmap(f, in_axes=(None, 0, 0, None)))


def _jitted_local_steps(cfg: FLConfig):
    return _cached_local_steps(cfg.local_iters, cfg.momentum,
                               cfg.weight_decay, cfg.tau_alpha, cfg.tau_beta)


@functools.lru_cache(maxsize=16)
def _cached_sharded_steps(local_iters, momentum, weight_decay,
                          tau_alpha, tau_beta, mesh):
    """The vmapped cohort step under shard_map: each device trains its
    block of the cohort (rows sharded over the mesh's federated axes),
    the init tree and lr replicated. Block width = cohort / devices, so
    vmap batching math runs at a DIFFERENT width than the single-device
    reference — float-close, never bitwise, versus the unsharded vmap
    (DESIGN.md §Sharded cohorts); bitwise-deterministic within the
    sharded mode itself."""
    from jax.sharding import PartitionSpec as P


    f = make_local_train_step(FLConfig(
        local_iters=local_iters, momentum=momentum,
        weight_decay=weight_decay, tau_alpha=tau_alpha, tau_beta=tau_beta))
    axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    vf = jax.vmap(f, in_axes=(None, 0, 0, None))
    return jax.jit(jax.shard_map(
        vf, mesh=mesh,
        in_specs=(P(), P(axes), P(axes), P()),
        out_specs=(P(axes), P(axes)), check_vma=False))


def _jitted_sharded_steps(cfg: FLConfig, mesh):
    return _cached_sharded_steps(cfg.local_iters, cfg.momentum,
                                 cfg.weight_decay, cfg.tau_alpha,
                                 cfg.tau_beta, mesh)


@functools.lru_cache(maxsize=16)
def _cached_raw_step(local_iters, momentum, weight_decay,
                     tau_alpha, tau_beta):
    return make_local_train_step(FLConfig(
        local_iters=local_iters, momentum=momentum,
        weight_decay=weight_decay, tau_alpha=tau_alpha, tau_beta=tau_beta))


def raw_local_step(cfg: FLConfig):
    """The UNJITTED per-client train step for cfg — the campaign engine
    (core/engine.py) vmaps and fuses this inside its own jitted round
    body, so wrapping it in jit here would only nest dispatch layers.
    Cached per hyperparameter tuple so engine callables built for the
    same cfg share one function object."""
    return _cached_raw_step(cfg.local_iters, cfg.momentum,
                            cfg.weight_decay, cfg.tau_alpha, cfg.tau_beta)


@functools.lru_cache(maxsize=16)
def _cached_moco_step(local_iters, momentum, weight_decay, moco_momentum):
    return jax.jit(make_moco_local_train_step(FLConfig(
        local_iters=local_iters, momentum=momentum,
        weight_decay=weight_decay, moco_momentum=moco_momentum)))


def _jitted_moco_step(cfg: FLConfig):
    return _cached_moco_step(cfg.local_iters, cfg.momentum,
                             cfg.weight_decay, cfg.moco_momentum)


def cohort_step_cache_size(cfg: FLConfig) -> int:
    """Number of compiled variants of cfg's VMAPPED cohort step — one per
    distinct (cohort size, batch shape). The handover bucketing policy
    bounds this by ceil(log2(vehicles_per_round)) + 1 per topology
    (benchmarks/round_engine.py reports it)."""
    _, vlocal = _jitted_local_steps(cfg)
    return vlocal._cache_size()


def reset_cohort_step_caches() -> None:
    """Drop every cached/compiled client step (benchmark isolation)."""
    _cached_local_steps.cache_clear()
    _cached_moco_step.cache_clear()
    _cached_raw_step.cache_clear()
    _cached_sharded_steps.cache_clear()


# --------------------------------------------------------------------------
# registry entries
# --------------------------------------------------------------------------

def _pad_cohort_inputs(batches, keys, pad_to: int):
    """Pad stacked batches/keys from n to pad_to rows by replicating the
    last valid row — NO RNG is consumed, so a padded cohort draws exactly
    the same host/jax random streams as an unpadded one. The replicated
    rows train on real (finite) data and are masked out of every
    aggregation downstream."""
    n = batches.shape[0]
    pad = pad_to - n
    if pad < 0:
        raise ValueError(f"pad_to={pad_to} smaller than cohort size {n}")
    if pad == 0:
        return batches, keys
    batches = jnp.concatenate(
        [batches, jnp.broadcast_to(batches[-1:], (pad,) + batches.shape[1:])])
    keys = jnp.concatenate(
        [keys, jnp.broadcast_to(keys[-1:], (pad,) + keys.shape[1:])])
    return batches, keys


class DTSSLClient:
    """FLSimCo Step 2: dual-temperature contrastive SSL. Stateless."""

    name = "dtssl"

    def init_state(self, cfg: FLConfig, global_tree):
        return None

    def run_cohort(self, cfg: FLConfig, tree, client_state, batches, keys,
                   lr, parallel: bool = True, pad_to: int | None = None,
                   mesh=None):
        """Run one cohort of clients from init model `tree`.

        `parallel=True` vmaps the cohort over a stacked tree and returns
        the result STACKED (a `CohortBatch`) — no unstacking, no host
        syncs; `pad_to` additionally pads the cohort to a bucketed size
        so variable-size cohorts share compilations. The sequential path
        is the tested-equivalent reference (tests/test_federation.py,
        tests/test_topology.py).

        `mesh` (a cohort mesh, launch/mesh.py) additionally shards the
        cohort rows over the mesh's federated axes: each device vmaps its
        own block. Pads to a multiple of the mesh extent (replicated last
        row — no RNG consumed, padding masked out downstream), so a
        cohort smaller than the mesh still runs. The block-sharded vmap
        batches at a different width than the single-device reference, so
        this path is float-close, not bitwise, versus `parallel=True`
        without a mesh (DESIGN.md §Sharded cohorts).
        """
        local, vlocal = _jitted_local_steps(cfg)
        n = len(keys)
        if not parallel:
            client_trees, losses = [], []
            for i in range(n):
                t, l = local(tree, batches[i], keys[i], lr)
                client_trees.append(t)
                losses.append(l)
            return CohortBatch.from_list(client_trees, losses), None
        m = n if pad_to is None else pad_to
        keys_arr = keys if hasattr(keys, "shape") else jnp.stack(list(keys))
        if mesh is not None and mesh.size > 1:
            ext = 1
            for a in ("pod", "data"):
                if a in mesh.axis_names:
                    ext *= mesh.shape[a]
            m = -(-m // ext) * ext
            batches, keys_arr = _pad_cohort_inputs(batches, keys_arr, m)
            trees, losses = _jitted_sharded_steps(cfg, mesh)(
                tree, batches, keys_arr, lr)
            return CohortBatch.from_stacked(trees, losses, n=n), None
        batches, keys_arr = _pad_cohort_inputs(batches, keys_arr, m)
        trees, losses = vlocal(tree, batches, keys_arr, lr)
        return CohortBatch.from_stacked(trees, losses, n=n), None

    def finalize(self, cfg: FLConfig, client_state, aggregated_tree, uploads):
        return None


class FedCoClient:
    """FedCo baseline: MoCo with a *global* negative queue.

    Vehicles upload k-values alongside parameters; the RSU merges them
    into the global queue (`ssl.fedco_merge_queues`) and resets the key
    encoder to the aggregated model — exactly the protocol FLSimCo
    criticizes (Sec. 2: mixed-encoder negatives, representation leakage).
    """

    name = "fedco"

    def init_state(self, cfg: FLConfig, global_tree):
        queue = jax.random.normal(
            jax.random.PRNGKey(cfg.seed + 1),
            (cfg.queue_len, cfg.feature_dim))
        queue = queue / jnp.linalg.norm(queue, axis=-1, keepdims=True)
        return {"key_tree": jax.tree.map(jnp.copy, global_tree),
                "queue": queue}

    def run_cohort(self, cfg: FLConfig, tree, client_state, batches, keys,
                   lr, parallel: bool = True, pad_to: int | None = None,
                   mesh=None):
        # mesh accepted (uniform registry signature) and ignored:
        # sequential by design: the MoCo step threads a key-encoder EMA
        # whose updates are not batchable across clients — the result is
        # still stacked into a CohortBatch so aggregation sees one
        # uniform device-resident boundary (losses stay on device)
        moco = _jitted_moco_step(cfg)
        client_trees, losses, kvecs = [], [], []
        for i in range(len(keys)):
            t, _, kv, loss = moco(tree, client_state["key_tree"],
                                  client_state["queue"], batches[i],
                                  keys[i], lr)
            client_trees.append(t)
            losses.append(loss)
            kvecs.append(kv)
        return CohortBatch.from_list(client_trees, losses), kvecs

    def finalize(self, cfg: FLConfig, client_state, aggregated_tree, uploads):
        return {"key_tree": jax.tree.map(jnp.copy, aggregated_tree),
                "queue": ssl.fedco_merge_queues(client_state["queue"],
                                                uploads)}


CLIENT_UPDATES = {
    "dtssl": DTSSLClient(),
    "fedco": FedCoClient(),
}
