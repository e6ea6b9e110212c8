"""Hierarchical (two-level) blur-weighted aggregation — beyond-paper.

The paper has ONE RSU. A deployed vehicular network has many RSUs, each
aggregating its own vehicles, with a regional server (MEC / cloud) merging
the RSU models. Natural extension of Eq. 11:

  level 1 (RSU r):   theta_r = sum_{n in r} w_n theta_n,
                     w_n ∝ (Σ_r L − L_n)   over vehicles at RSU r
  level 2 (region):  theta   = sum_r W_r theta_r,
                     W_r ∝ (Σ L̄ − L̄_r)    over RSU mean blur levels,
                     optionally scaled by each RSU's vehicle count.

This maps 1:1 onto the production mesh: level 1 = weighted psum over
"data", level 2 = weighted psum over "pod" — the two-stage form of the
single collective in launch/steps.py. `hierarchical_equals_flat` shows
when the two coincide (count-scaled level-2 weights + equal blur).

Host-level forms here; the mesh-level two-stage reduce is
`two_stage_weighted_psum`. Equivalence covered by tests/test_hierarchical.py.

Sharded cohorts (DESIGN.md §Sharded cohorts): when the stacked cohort's
leading axis is partitioned over a ("pod", "data") mesh, the weighted
reductions here run under `shard_map`:

* `sharded_cohort_sum` / `sharded_aggregate` — the flat ``AGGREGATORS``
  sum with the cohort rows sharded. The default "gather" reduction
  all-gathers the rows (data movement only — bitwise identity) and
  applies the SAME `_weighted_stacked_sum` dispatch as the single-device
  path, so it is BIT-EXACT with `cohort_weighted_sum` for every scheme
  and backend; the "split" reduction all-to-alls row shards into
  parameter shards and reduces every row locally with the tree backend's
  own row-order sum (`aggregation.row_weighted_sum`) — bit-exact with
  the tree backend while keeping per-device memory at O(m * P /
  devices).
* `sharded_hierarchical` — the two-level Eq. 11 with per-RSU blocks on
  the "pod" axis. reduction="exact" (default) composes per-level
  gathers with the host weight functions — bit-exact with
  `aggregate_hierarchical`; reduction="psum" routes through the
  (blocked) `two_stage_weighted_psum` collective — fewer bytes on the
  wire, documented-float-close (psum reassociates the row sum; the
  existing mesh tests pin atol=1e-5).

A psum of per-shard partial sums is NOT bit-exact versus the
single-device reduction (reassociation), which is why the bit-exact
forms are gathers/all-to-alls rather than "express everything as psum".
tests/multidevice/ enforces each contract under forced 8-device CPU.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import aggregation as agg
from repro.core.aggregation import (SCHEME_WEIGHTS, _weighted_tree_sum,
                                    cohort_weighted_sum, flsimco_weights,
                                    weighted_psum_tree)
from repro.core.cohort import CohortBatch

COHORT_AXES = ("pod", "data")


def _as_cohort(group, blur) -> CohortBatch:
    """Normalize one RSU group to a `CohortBatch`: either it already is
    one (the round engine's stacked path — blur travels inside it), or a
    legacy (list of client trees, blur array) pair that gets stacked."""
    if isinstance(group, CohortBatch):
        return group
    blur = jnp.asarray(blur, jnp.float32)
    return CohortBatch.from_list(
        group, jnp.zeros((len(group),), jnp.float32), blur=blur)


def aggregate_hierarchical(groups: Sequence, blur_groups: Sequence = None,
                           count_scaled: bool = True):
    """groups[r] = the cohort at RSU r — a `CohortBatch` (stacked leaves +
    mask, blur attached) or a legacy list of client trees with
    blur_groups[r] = (N_r,) blur levels. Returns the region-level global
    model. Level-1 weights are computed on each cohort's valid slice, so
    padded (bucketed) cohorts aggregate bit-exactly like unpadded ones."""
    with jax.named_scope("aggregate"):
        cohorts = [_as_cohort(g, None if blur_groups is None else b)
                   for g, b in zip(groups,
                                   blur_groups or [None] * len(groups))]
        rsu_models = []
        rsu_blur = []
        rsu_count = []
        for cohort in cohorts:
            blur = cohort.valid_blur
            rsu_models.append(cohort_weighted_sum(cohort,
                                                  flsimco_weights(blur)))
            rsu_blur.append(blur.mean())
            rsu_count.append(cohort.n)
        W = flsimco_weights(jnp.stack(rsu_blur))
        if count_scaled:
            W = W * _count_scale(tuple(rsu_count))
            W = W / jnp.sum(W)
        return _weighted_tree_sum(rsu_models, W)


@functools.lru_cache(maxsize=128)
def _count_scale(counts) -> jnp.ndarray:
    """Device-resident per-RSU vehicle counts, cached by value: RSU
    geometry repeats every round, so the count vector must not be
    re-uploaded per aggregation call (lint rule retrace-fresh-array).
    Built as a concrete array even when first called inside a jit
    trace: a traced constant cached here would leak into later calls."""
    with jax.ensure_compile_time_eval():
        return jnp.asarray(counts, jnp.float32)


def two_stage_weighted_psum(tree, blur_level, *, rsu_axis="data",
                            region_axis="pod", count_scaled=True,
                            accum_dtype=None):
    """Mesh-level hierarchical Eq. 11: weighted psum over `rsu_axis`, then
    over `region_axis`. Call inside shard_map with both axes bound.

    blur_level: this device's L — a SCALAR when every device holds one
    vehicle (the original one-device-per-vehicle form), or a (b,) BLOCK
    when the cohort axis is blocked over the mesh (b vehicles per device;
    `tree` then carries a leading (b, ...) axis). The blocked form sums
    each device's weighted rows locally and psums the partials — the
    collective moves one model per device instead of b, at the cost of
    reassociating the row sum (documented-float-close versus the host
    path; the bit-exact alternative is `sharded_hierarchical`'s gather
    form). With count-scaled level-2 weights and equal per-RSU cohort
    counts this equals the flat single-psum form.

    accum_dtype: None (default) keeps the existing op sequence — f32
    weighted sums, cast back per level — bit-compatible with the pinned
    mesh tests. A wider dtype (e.g. jnp.float64 under enable_x64) makes
    BOTH weighted reductions accumulate in that dtype, casting back to
    each leaf's dtype only after level 2 — the psum reassociation error
    then shrinks from ~1e-6 to the f32 rounding floor
    (tests/test_hierarchical.py pins the tightened tolerance).
    """
    # analysis: allow=retrace-fresh-array -- traced under shard_map;
    # these constants fold at compile time, nothing runs per call
    L = jnp.asarray(blur_level, jnp.float32)
    blocked = L.ndim > 0
    # level 1: vehicles within the RSU
    tot1 = jax.lax.psum(L.sum() if blocked else L, rsu_axis)
    # analysis: allow=retrace-fresh-array -- traced constants (see above)
    n1 = jax.lax.psum(jnp.asarray(L.size, jnp.float32) if blocked
                      else jnp.ones(()), rsu_axis)
    w1 = (tot1 - L) / jnp.maximum(tot1, 1e-12)
    s1 = jax.lax.psum(w1.sum() if blocked else w1, rsu_axis)
    w1 = jnp.where(s1 > 1e-12, w1 / jnp.maximum(s1, 1e-12), 1.0 / n1)
    ad = None if accum_dtype is None else jnp.dtype(accum_dtype)
    if blocked:
        def red(x):
            if ad is not None:
                return jax.lax.psum(
                    jnp.tensordot(w1.astype(ad), x.astype(ad), axes=1,
                                  precision=jax.lax.Precision.HIGHEST),
                    rsu_axis)
            y = jnp.tensordot(w1, x.astype(jnp.float32), axes=1,
                              precision=jax.lax.Precision.HIGHEST)
            return jax.lax.psum(y, rsu_axis).astype(x.dtype)
        rsu_model = jax.tree.map(red, tree)
    elif ad is not None:
        rsu_model = jax.tree.map(
            lambda x: jax.lax.psum(x.astype(ad) * w1.astype(ad), rsu_axis),
            tree)
    else:
        rsu_model = weighted_psum_tree(tree, w1, rsu_axis)
    # level 2: RSUs within the region. psum over `region_axis` alone sums
    # one representative per pod (the rsu-level quantities are replicated
    # across rsu_axis after the level-1 psum) — no double counting.
    Lbar = tot1 / n1
    tot2 = jax.lax.psum(Lbar, region_axis)
    # analysis: allow=retrace-fresh-array -- traced constant (see above)
    n2 = jax.lax.psum(jnp.ones(()), region_axis)
    w2 = (tot2 - Lbar) / jnp.maximum(tot2, 1e-12)
    if count_scaled:
        w2 = w2 * n1
    s2 = jax.lax.psum(w2, region_axis)
    w2 = jnp.where(s2 > 1e-12, w2 / jnp.maximum(s2, 1e-12), 1.0 / n2)
    if ad is not None:
        # rsu_model is still in accum_dtype; cast back only after the
        # final reduction (target dtypes come from the input leaves)
        out = jax.tree.map(
            lambda x: jax.lax.psum(x * w2.astype(ad), region_axis),
            rsu_model)
        return jax.tree.map(lambda o, x: o.astype(x.dtype), out, tree)
    return weighted_psum_tree(rsu_model, w2, region_axis)


# --------------------------------------------------------------------------
# sharded cohorts: the masked weighted sums under shard_map
# --------------------------------------------------------------------------

def _mesh_extent(mesh) -> int:
    ext = 1
    for a in COHORT_AXES:
        if a in mesh.axis_names:
            ext *= mesh.shape[a]
    return ext


@functools.lru_cache(maxsize=64)
def _flat_gather_fn(mesh, backend: str):
    """shard_map'd masked cohort sum, "gather" reduction: all-gather the
    row shards (pure data movement) and run the SAME
    `_weighted_stacked_sum` dispatch as the single-device path on the
    reassembled cohort — bit-exact by construction, on any backend."""

    def body(blk_trees, w, mask):
        full = jax.tree.map(
            lambda x: jax.lax.all_gather(x, COHORT_AXES, tiled=True),
            blk_trees)
        with agg.wagg_backend(backend):
            return agg._weighted_stacked_sum(full, w, mask)

    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(P(COHORT_AXES), P(), P()),
                                 out_specs=P(), check_vma=False))


@functools.lru_cache(maxsize=64)
def _flat_split_fn(mesh):
    """shard_map'd masked cohort sum, "split" reduction: all-to-all the
    (rows/D, P) row shards into (rows, P/D) parameter shards, then reduce
    ALL rows locally over the parameter slice. `row_weighted_sum` is the
    tree backend's reduction and its per-element arithmetic does not
    depend on the slice's shape, so the result is bit-exact with the
    tree backend while per-device memory stays at O(rows * P / D)."""

    def body(flat_blk, w):
        cols = jax.lax.all_to_all(flat_blk, COHORT_AXES, split_axis=1,
                                  concat_axis=0, tiled=True)
        return agg.row_weighted_sum(w, cols)

    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(P(COHORT_AXES), P()),
                                 out_specs=P(COHORT_AXES), check_vma=False))


def sharded_cohort_sum(cohort: CohortBatch, w_valid, mesh, *,
                       reduction: str = "gather"):
    """`cohort_weighted_sum` with the cohort rows sharded over `mesh`.

    (n,) weights over the valid rows, zero-padded to the (possibly
    re-padded) cohort size; rows shard P(("pod", "data")). Bit-exact with
    the single-device `cohort_weighted_sum` — "gather" on every backend,
    "split" versus the tree backend (test-enforced in
    tests/multidevice/). Cohorts whose padded size does not divide the
    mesh extent are re-padded first (`CohortBatch.pad_to` — replicated
    finite rows, zero weights, exact +0.0 terms), so a cohort SMALLER
    than the mesh still works: whole shards of padding reduce to
    nothing.
    """
    if reduction not in ("gather", "split"):
        raise ValueError(f"reduction {reduction!r} not in "
                         f"('gather', 'split')")
    ext = _mesh_extent(mesh)
    m = -(-cohort.size // ext) * ext
    cohort = cohort.pad_to(m)
    w = cohort.padded_weights(w_valid)
    if reduction == "gather":
        fn = _flat_gather_fn(mesh, agg._resolve_wagg_backend())
        return fn(cohort.trees, w, cohort.mask)
    # split: ravel the stacked leaves to one (m, P) f32 matrix (the same
    # layout wagg_stacked kernels consume), pad P to a multiple of the
    # mesh extent, reduce, unravel
    w = w * cohort.mask               # mask is float32 by construction
    leaves = jax.tree.leaves(cohort.trees)
    flat = jnp.concatenate(
        [l.reshape(m, -1).astype(jnp.float32) for l in leaves], axis=1)
    P_total = flat.shape[1]
    pad = (-P_total) % ext
    if pad:
        # analysis: allow=retrace-fresh-array -- device-side zero pad;
        # its width follows the cohort, there is no constant to hoist
        flat = jnp.concatenate(
            [flat, jnp.zeros((m, pad), jnp.float32)], axis=1)
    out = _flat_split_fn(mesh)(flat, w)[:P_total]
    from repro.kernels.ops import _unravel_like
    return _unravel_like(out, jax.tree.map(lambda x: x[0], cohort.trees))


def sharded_aggregate(cohort: CohortBatch, cfg, mesh, *,
                      scheme: str = None, reduction: str = "gather"):
    """``AGGREGATORS[scheme]`` with the reduction sharded over `mesh`.

    The weights come from the SAME ``SCHEME_WEIGHTS`` entry the
    single-device dispatch uses, computed on the replicated valid slice
    (`cohort.valid_blur` is (n,) — tiny), so the sharded result is
    bit-exact with `AGGREGATORS[scheme](cohort, cfg)` for all five
    schemes (acceptance-tested under forced 8-device CPU).
    """
    scheme = cfg.aggregator if scheme is None else scheme
    w = SCHEME_WEIGHTS[scheme](cohort, cfg)
    return sharded_cohort_sum(cohort, w, mesh, reduction=reduction)


@functools.lru_cache(maxsize=64)
def _hier_exact_fn(mesh, backend: str):
    """shard_map'd two-level Eq. 11, gather form: level 1 gathers each
    RSU's rows over "data" and reduces with the host dispatch; level 2
    gathers the per-RSU models over "pod" and reduces with the host
    dispatch. Both weight vectors arrive replicated (computed outside by
    the host weight functions), so every arithmetic op matches
    `aggregate_hierarchical` bit for bit."""

    def body(blk_trees, w1_blk, W2):
        blk = jax.tree.map(
            lambda x: jax.lax.all_gather(x, "data", tiled=True), blk_trees)
        w1 = jax.lax.all_gather(w1_blk, "data", tiled=True)
        with agg.wagg_backend(backend):
            rsu_model = agg._weighted_stacked_sum(blk, w1)
            stack = jax.tree.map(
                lambda x: jax.lax.all_gather(x, "pod"), rsu_model)
            return agg._weighted_stacked_sum(stack, W2)

    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(P(COHORT_AXES), P(COHORT_AXES), P()),
                                 out_specs=P(), check_vma=False))


@functools.lru_cache(maxsize=64)
def _hier_psum_fn(mesh, count_scaled: bool, accum_name: str = None):
    accum_dtype = None if accum_name is None else jnp.dtype(accum_name)

    def body(blk_trees, blur_blk):
        return two_stage_weighted_psum(blk_trees, blur_blk,
                                       count_scaled=count_scaled,
                                       accum_dtype=accum_dtype)

    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(P(COHORT_AXES), P(COHORT_AXES)),
                                 out_specs=P(), check_vma=False))


def sharded_hierarchical(stacked_trees, blur, mesh, n_rsus: int, *,
                         count_scaled: bool = True,
                         reduction: str = "exact",
                         accum_dtype=None):
    """Two-level Eq. 11 over an RSU-MAJOR stacked cohort sharded on
    `mesh` (pod=n_rsus, data=d with d | per-RSU size).

    stacked_trees: every leaf (n_rsus * s, ...), RSU r's vehicles in rows
    [r*s, (r+1)*s); blur: (n_rsus * s,) matching. reduction="exact"
    (default) computes both weight levels with the host functions on the
    replicated blur and reduces via gathers — bit-exact with
    `aggregate_hierarchical` on the same cohorts; reduction="psum" is the
    blocked `two_stage_weighted_psum` collective — one model per device
    on the wire, float-close (atol~1e-5). accum_dtype widens the psum
    reduction's accumulator (see `two_stage_weighted_psum`); it has no
    effect on the already-bit-exact "exact" reduction.
    """
    if reduction not in ("exact", "psum"):
        raise ValueError(f"reduction {reduction!r} not in ('exact', 'psum')")
    R = n_rsus
    m = int(jnp.shape(blur)[0])
    if m % R:
        raise ValueError(f"rsu-major cohort of {m} rows not divisible by "
                         f"n_rsus={R}")
    s = m // R
    with jax.named_scope("aggregate"):
        if reduction == "psum":
            accum_name = None if accum_dtype is None \
                else jnp.dtype(accum_dtype).name
            # analysis: allow=retrace-fresh-array -- f32 normalization at
            # the aggregation boundary (no-op when blur is already jnp f32)
            return _hier_psum_fn(mesh, count_scaled, accum_name)(
                stacked_trees, jnp.asarray(blur, jnp.float32))
        # weights exactly as aggregate_hierarchical computes them: per-RSU
        # level-1 weights on each (s,) blur block, level-2 on the stacked
        # block means (count-scaled) — all on replicated (tiny) arrays
        # analysis: allow=retrace-fresh-array -- f32 normalization at the
        # aggregation boundary (no-op when blur is already jnp f32)
        blur = jnp.asarray(blur, jnp.float32)
        blocks = [blur[r * s:(r + 1) * s] for r in range(R)]
        w1 = jnp.concatenate([flsimco_weights(b) for b in blocks])
        W2 = flsimco_weights(jnp.stack([b.mean() for b in blocks]))
        if count_scaled:
            # cached: same values as jnp.full((R,), s) but not rebuilt
            # per call
            W2 = W2 * _count_scale((s,) * R)
            W2 = W2 / jnp.sum(W2)
        fn = _hier_exact_fn(mesh, agg._resolve_wagg_backend())
        return fn(stacked_trees, w1, W2)


def reset_sharded_caches() -> None:
    """Drop every cached shard_map'd aggregation callable (test/benchmark
    isolation — mirrors `engine.reset_engine_caches`)."""
    _flat_gather_fn.cache_clear()
    _flat_split_fn.cache_clear()
    _hier_exact_fn.cache_clear()
    _hier_psum_fn.cache_clear()
    _count_scale.cache_clear()
