"""Model aggregation schemes — the paper's core contribution (Eq. 11).

Two implementations of each scheme:

* **host-level** (`aggregate`): takes a list of client parameter pytrees —
  the faithful cross-device FL simulation used by the paper-repro examples
  and benchmarks.
* **mesh-level** (`weighted_psum_tree`): each client cohort lives on a
  slice of the (pod, data) mesh axes and aggregation is a single weighted
  all-reduce — the TPU-native production form used by launch/steps.py.
  Equivalence of the two is covered by tests/test_aggregation.py.

Registry (``AGGREGATORS``, the names ``FLConfig.aggregator`` accepts).
Every entry has the uniform dispatch signature

    aggregate(cohort: CohortBatch, cfg) -> tree

where `cohort` carries the STACKED client trees, the validity mask of a
bucketed (padded) cohort, and device-resident blur/velocities
(core/cohort.py) — so topologies route Step 4 through the registry with
zero per-scheme branching and zero unstack/restack churn; the underlying
``aggregate_*`` functions keep their minimal list-based signatures for
direct use.

  flsimco  — blur-weighted (Eq. 11), weight_n ∝ (ΣL − L_n)/ΣL — the paper
  fedavg   — baseline1: uniform average (McMahan et al.)
  discard  — baseline2: drop clients above cfg.blur_threshold, then fedavg
  softmax  — beyond-paper: w ∝ softmax(−L/T), scale-free in N
  inverse  — beyond-paper: w ∝ 1/(L+eps), inverse-variance-flavored

(The paper's baseline3, FedCo, is not an aggregation scheme but a client
*algorithm* — FedAvg parameters + a global negative queue — and lives in
the ``CLIENT_UPDATES`` registry, core/clients.py. ``aggregator="fedco"``
is accepted as a legacy alias that FLConfig normalizes to
``client="fedco", aggregator="fedavg"``.)

Host-side weighted sums route through the fused Pallas kernel
(kernels/wagg.py) on TPU — one HBM pass over the stacked cohort tensor
(with the validity mask applied in-kernel) instead of N tree-map passes —
and fall back to the jnp tree-map path off-TPU.
``wagg_backend("interpret")`` forces the kernel in interpret mode (used by
tests/test_topology.py to exercise the kernel path on CPU).
"""
from __future__ import annotations

import contextlib
from typing import Sequence

import jax
import jax.numpy as jnp


# Backend for host-side weighted tree sums:
#   auto      — fused Pallas kernel on TPU, jnp tree-map elsewhere
#   fused     — force the compiled Pallas kernel (TPU)
#   interpret — force the Pallas kernel in interpret mode (any backend)
#   tree      — force the jnp tree-map path
_WAGG_BACKENDS = ("auto", "fused", "interpret", "tree")
_wagg_backend = "auto"


def set_wagg_backend(mode: str) -> str:
    """Select the weighted-sum backend; returns the previous mode."""
    # analysis: allow=purity-global-mutation -- the one deliberate
    # process-wide switch (scoped form: wagg_backend() below)
    global _wagg_backend
    if mode not in _WAGG_BACKENDS:
        raise ValueError(f"wagg backend {mode!r} not in {_WAGG_BACKENDS}")
    prev, _wagg_backend = _wagg_backend, mode
    return prev


@contextlib.contextmanager
def wagg_backend(mode: str):
    """Scoped `set_wagg_backend` (tests force 'interpret' through this)."""
    prev = set_wagg_backend(mode)
    try:
        yield
    finally:
        set_wagg_backend(prev)


def _resolve_wagg_backend() -> str:
    if _wagg_backend != "auto":
        return _wagg_backend
    from repro.kernels.ops import on_tpu  # deferred: keep core import-light
    return "fused" if on_tpu() else "tree"


def _weighted_stacked_sum(stacked, weights, mask=None) -> object:
    """sum_m w_m * tree[m] over the leading cohort axis of a STACKED tree.

    Every host-side aggregation scheme funnels through here, so this is
    the single dispatch point between the fused kernel and the tree-map
    reference path. `mask` (m,) zeroes padding rows of a bucketed cohort
    (w*1.0 == w and w*0.0 == 0.0, so a masked padded sum is bit-exact
    versus the unpadded sum over the valid prefix).
    """
    with jax.named_scope("aggregate"):
        # analysis: allow=retrace-fresh-array -- f32 normalization at the
        # aggregation boundary (no-op for device weights, traced in jit)
        weights = jnp.asarray(weights, jnp.float32)
        backend = _resolve_wagg_backend()
        if backend != "tree":
            from repro.kernels import ops as _kops  # deferred: keep core import-light
            return _kops.wagg_stacked(stacked, weights, mask=mask,
                                      interpret=(backend == "interpret"))

        if mask is not None:
            # analysis: allow=retrace-fresh-array -- same normalization
            weights = weights * jnp.asarray(mask, jnp.float32)

        return jax.tree.map(
            lambda leaf: row_weighted_sum(weights, leaf).astype(leaf.dtype),
            stacked)


# rows summed per unrolled block of `row_weighted_sum`
_ROW_BLOCK = 64


@jax.jit
def row_weighted_sum(weights, x) -> jnp.ndarray:
    """sum_m weights[m] * x[m] over x's leading axis, in f32, as a chain
    of elementwise multiply-adds in row order.

    Every output element runs the same arithmetic, acc <- acc + w_m x_m
    for m = 0, 1, ..., whatever x's trailing shape or layout, so a
    per-leaf sum and a sum over a column slice of the raveled cohort
    (the sharded "split" reduction, core/hierarchical.py) agree bit for
    bit (tests/multidevice/). Neither a dot nor a reduce does: XLA picks
    a dot's accumulation order by operand shape and a reduce's by
    layout, and on TPU an f32 dot rounds its inputs to bf16 unless
    asked for full precision. Cohorts of more than `_ROW_BLOCK` rows
    loop over unrolled blocks (same order, bounded program size).
    Jitted so that eager callers reuse one compiled program per shape.
    """
    def chain(acc, w, rows, n):
        for i in range(n):
            acc = acc + w[i] * rows[i].astype(jnp.float32)
        return acc

    # a zero XLA cannot fold away: after a literal 0 the first step
    # would read w0 x0 + w1 x1, and a compiler that fuses multiply-adds
    # may fuse either product; 0 * w0 leaves one product per step
    # analysis: allow=retrace-fresh-array -- traced accumulator init
    acc = jnp.zeros(x.shape[1:], jnp.float32) * weights[0]
    m = x.shape[0]
    blocks = m // _ROW_BLOCK
    if blocks > 1:
        def block(b, acc):
            lo = b * _ROW_BLOCK
            return chain(acc,
                         jax.lax.dynamic_slice_in_dim(weights, lo, _ROW_BLOCK),
                         jax.lax.dynamic_slice_in_dim(x, lo, _ROW_BLOCK),
                         _ROW_BLOCK)
        acc = jax.lax.fori_loop(0, blocks, block, acc)
        done = blocks * _ROW_BLOCK
    else:
        done = 0
    return chain(acc, weights[done:], x[done:], m - done)


def _weighted_tree_sum(trees: Sequence, weights) -> object:
    """sum_n w_n * tree_n over a LIST of pytrees (legacy boundary): one
    stack, then the stacked dispatch above."""
    stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *trees)
    return _weighted_stacked_sum(stacked, weights)


def cohort_weighted_sum(cohort, w_valid) -> object:
    """Weighted sum of a `CohortBatch`: (n,) weights over the valid rows,
    zero-padded to the bucketed size, applied to the stacked trees with
    the cohort's validity mask — no unstack/restack anywhere."""
    return _weighted_stacked_sum(cohort.trees, cohort.padded_weights(w_valid),
                                 mask=cohort.mask)


def flsimco_weights(blur_levels, normalize: bool = True):
    """Eq. (11) weights: w_n = (ΣL − L_n) / ΣL   [/ (N−1) when normalized].

    The literal equation's weights sum to N−1; `normalize=True` (default)
    rescales them to sum to 1, the only reading under which the paper's
    multi-vehicle experiments converge (DESIGN.md deviation #2).
    """
    L = jnp.asarray(blur_levels, jnp.float32)
    N = L.shape[0]
    total = jnp.sum(L)
    w = (total - L) / jnp.maximum(total, 1e-12)
    if normalize:
        s = jnp.sum(w)
        # degenerate cases (single client, or all-zero blur) -> uniform
        w = jnp.where(s > 1e-12, w / jnp.maximum(s, 1e-12),
                      jnp.full_like(w, 1.0 / N))
    return w


def aggregate_flsimco(trees: Sequence, blur_levels, normalize: bool = True):
    """Blur-level-weighted aggregation (FLSimCo, Eq. 11)."""
    return _weighted_tree_sum(trees, flsimco_weights(blur_levels, normalize))


def aggregate_fedavg(trees: Sequence, data_sizes=None):
    """Baseline1: FedAvg; optionally weighted by local dataset size."""
    n = len(trees)
    if data_sizes is None:
        # analysis: allow=retrace-fresh-array -- legacy list-API entry
        # point; the stacked path uses SCHEME_WEIGHTS, not this
        w = jnp.full((n,), 1.0 / n)
    else:
        # analysis: allow=retrace-fresh-array -- legacy list-API entry
        s = jnp.asarray(data_sizes, jnp.float32)
        w = s / jnp.sum(s)
    return _weighted_tree_sum(trees, w)


def discard_weights(blur_levels, threshold: float):
    """Baseline2 weights: uniform over clients with blur L <= threshold.

    If every client exceeds the threshold, falls back to plain FedAvg
    weights (the RSU cannot emit an empty model).
    """
    L = jnp.asarray(blur_levels, jnp.float32)
    keep = (L <= threshold).astype(jnp.float32)
    n_keep = jnp.sum(keep)
    return jnp.where(n_keep > 0, keep / jnp.maximum(n_keep, 1.0),
                     jnp.full_like(keep, 1.0 / keep.shape[0]))


def aggregate_discard(trees: Sequence, blur_levels, threshold: float):
    """Baseline2: drop clients whose BLUR LEVEL (Eq. 2) exceeds
    `threshold`, FedAvg the rest.

    The threshold is in blur units, matching the registry contract and
    the mesh path (launch/steps.py); `FLConfig.blur_threshold` defaults
    to the blur level of the paper's 100 km/h cutoff
    (`mobility.BLUR_KMH_100`).
    """
    return _weighted_tree_sum(trees, discard_weights(blur_levels, threshold))


# --------------------------------------------------------------------------
# beyond-paper weighting variants (EXPERIMENTS.md §Paper-claims ablation)
# --------------------------------------------------------------------------

def softmax_weights(blur_levels, temperature: float = 5.0):
    """w ∝ softmax(−L/T): exponential rather than linear blur penalty.

    The paper's Eq. 11 is linear in L, so with many vehicles the weight
    spread collapses (w_n → 1/N as N grows at fixed L spread). A softmax
    keeps relative penalties scale-free in N — our proposed variant.
    """
    L = jnp.asarray(blur_levels, jnp.float32)
    return jax.nn.softmax(-L / temperature)


def aggregate_softmax(trees: Sequence, blur_levels, temperature: float = 5.0):
    return _weighted_tree_sum(trees, softmax_weights(blur_levels, temperature))


def inverse_weights(blur_levels, eps: float = 1.0):
    """w ∝ 1/(L+eps): treats blur as noise std — inverse-variance-flavored."""
    L = jnp.asarray(blur_levels, jnp.float32)
    w = 1.0 / (L + eps)
    return w / jnp.sum(w)


def aggregate_inverse(trees: Sequence, blur_levels, eps: float = 1.0):
    return _weighted_tree_sum(trees, inverse_weights(blur_levels, eps))


# Uniform dispatch signature: (cohort, cfg) where `cohort` is a
# `CohortBatch` (stacked trees + validity mask + device-resident
# blur/velocities) and `cfg` supplies the scheme's knobs
# (normalize_weights, blur_threshold). Each scheme is fully described
# by its WEIGHT function (``SCHEME_WEIGHTS``: (cohort, cfg) -> (n,)
# weights over the valid rows); the dispatch entry is always the same
# masked weighted sum over those weights. The split exists so the
# sharded aggregation path (core/hierarchical.py) can reuse the exact
# weight values — bit-for-bit the same scheme, only the reduction runs
# under shard_map. Weights are computed on the static valid slice
# (`cohort.valid_blur`) and zero-padded, so a bucketed (padded) cohort
# aggregates bit-exactly like an unpadded one (tests/test_cohort.py).
# FLConfig validates its `aggregator` field against these dicts, so
# adding a SCHEME_WEIGHTS entry is the whole story for a new scheme.

def _weights_flsimco(cohort, cfg):
    return flsimco_weights(cohort.valid_blur,
                           getattr(cfg, "normalize_weights", True))


def _weights_fedavg(cohort, cfg):
    return jnp.full((cohort.n,), 1.0 / cohort.n, jnp.float32)


def _weights_discard(cohort, cfg):
    # thresholds the Eq.-2 BLUR LEVEL (not raw velocity) against
    # cfg.blur_threshold, as the registry documents
    return discard_weights(cohort.valid_blur, cfg.blur_threshold)


def _weights_softmax(cohort, cfg):
    return softmax_weights(cohort.valid_blur)


def _weights_inverse(cohort, cfg):
    return inverse_weights(cohort.valid_blur)


SCHEME_WEIGHTS = {
    "flsimco": _weights_flsimco,
    "fedavg": _weights_fedavg,
    "discard": _weights_discard,
    "softmax": _weights_softmax,
    "inverse": _weights_inverse,
}


def _make_dispatch(weight_fn):
    def dispatch(cohort, cfg):
        with jax.named_scope("aggregate"):
            return cohort_weighted_sum(cohort, weight_fn(cohort, cfg))
    return dispatch


AGGREGATORS = {name: _make_dispatch(fn) for name, fn in SCHEME_WEIGHTS.items()}


# --------------------------------------------------------------------------
# mesh-level (collective) form
# --------------------------------------------------------------------------

def weighted_psum_tree(tree, weight, axis_names):
    """Per-cohort weighted all-reduce: params' <- Σ_cohorts w * params.

    Inside shard_map/pjit, `weight` is this cohort's *normalized* scalar
    weight (weights already sum to 1 across the axis). One psum over the
    federated axes replaces the RSU gather-scatter — Eq. 11 as a collective.
    """
    def red(x):
        y = x.astype(jnp.float32) * weight
        y = jax.lax.psum(y, axis_names)
        return y.astype(x.dtype)

    return jax.tree.map(red, tree)


def normalized_weight_on_axis(blur_level, axis_names, normalize: bool = True):
    """This cohort's Eq.-11 weight, computed collectively over the mesh axes.

    blur_level: scalar L for the local cohort. Uses two cheap psums
    (scalar) to form (ΣL − L)/ΣL / Σ_n weights without gathering models.
    """
    L = jnp.asarray(blur_level, jnp.float32)
    total = jax.lax.psum(L, axis_names)
    w = (total - L) / jnp.maximum(total, 1e-12)
    if normalize:
        wsum = jax.lax.psum(w, axis_names)
        n = jax.lax.psum(jnp.ones(()), axis_names)
        w = jnp.where(wsum > 1e-12, w / jnp.maximum(wsum, 1e-12), 1.0 / n)
    return w
