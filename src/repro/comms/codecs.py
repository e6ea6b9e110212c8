"""Model-exchange codecs — the ``CODECS`` registry (``FLConfig.codec``).

FLSimCo's binding constraint at fleet scale is the comms volume: every
round, every vehicle ships its full model tree to the RSU and downloads
the new global model (8 bytes/parameter/vehicle at f32, down + up). This
module makes the exchange a pluggable encode/decode stage, mirroring the
``AGGREGATORS``/``CLIENT_UPDATES`` registries:

  identity     today's exchange: the stacked trees pass through verbatim
               (the default — zero overhead, bit-identical behavior).
  delta        lossless delta upload: Δ_n = θ_n − θ encoded as the
               WRAPPING integer difference of the raw float bits
               (bitcast<int32>(θ_n) − bitcast<int32>(θ)). A plain float
               subtract does NOT round-trip (θ + (θ_n − θ) != θ_n in
               floating point); the bitcast-integer delta reconstructs
               θ_n bit for bit for ANY values, so decode-then-aggregate
               is bitwise-identical to today's aggregation for all five
               SCHEME_WEIGHTS schemes (tests/test_comms.py). Same bytes
               as f32 on the wire, but the downlink base θ is shared by
               the whole cohort (one broadcast per round instead of
               per-vehicle unicast) and near-converged deltas have tiny
               magnitudes — entropy-coder-friendly and the input the
               int8 tier quantizes.
  delta_int8   lossy delta upload: Δ_n raveled to one (m, P) f32 matrix
               and quantized blockwise to int8 (one f32 scale per
               `kernels.qdelta.BQ` = 256 parameters, round-half-even,
               zero-scale guard) with an ERROR-FEEDBACK residual: the
               previous round's quantization error is folded in before
               quantizing, so the error telescopes instead of
               accumulating. The residual lives in ``FLState.comms`` —
               one (vehicles_per_round, Ppad) f32 slot array, slot i =
               cohort position i (a documented approximation of
               per-client EF under cohort resampling). ~1.016
               bytes/parameter on the wire vs 4 for f32.

The aggregation itself NEVER runs in delta space: `roundtrip_cohort`
reconstructs θ̂_n = decode(encode(θ_n)) and hands the existing
aggregators the reconstructed cohort. θ + Σ w_n·Δ_n is only float-close
to Σ w_n·θ_n (the weights sum to 1, but float addition reassociates);
reconstruct-then-aggregate makes the lossless tier bit-exact on the host
path, the shard_mapped mesh path and inside the compiled engine bodies
with no per-scheme reasoning at all.

Every encode/decode is pure jnp/Pallas (jit- and shard_map-traceable,
row-wise over the cohort axis); the int8 quantize/dequantize dispatches
through kernels/ops.py — fused Pallas kernels on TPU, the jnp reference
path elsewhere, ``q8_backend("interpret")`` forcing the kernel anywhere
(the same backend contract as aggregation's `wagg_backend`).
"""
from __future__ import annotations

import contextlib
import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.kernels.qdelta import BQ

# Backend for the int8 quantize/dequantize kernels (mirrors
# aggregation._wagg_backend): auto = fused Pallas on TPU, jnp reference
# elsewhere; "interpret" forces the Pallas kernel in interpret mode.
_Q8_BACKENDS = ("auto", "fused", "interpret", "ref")
_q8_backend = "auto"


def set_q8_backend(mode: str) -> str:
    """Select the int8 codec backend; returns the previous mode."""
    # analysis: allow=purity-global-mutation -- the one deliberate
    # process-wide switch (scoped form: q8_backend() below)
    global _q8_backend
    if mode not in _Q8_BACKENDS:
        raise ValueError(f"q8 backend {mode!r} not in {_Q8_BACKENDS}")
    prev, _q8_backend = _q8_backend, mode
    return prev


@contextlib.contextmanager
def q8_backend(mode: str):
    """Scoped `set_q8_backend` (tests force 'interpret' through this)."""
    prev = set_q8_backend(mode)
    try:
        yield
    finally:
        set_q8_backend(prev)


# --------------------------------------------------------------------------
# byte accounting (static — works on ShapeDtypeStructs too)
# --------------------------------------------------------------------------

def tree_nbytes(tree) -> int:
    """Total bytes of a pytree of arrays (size x itemsize per leaf)."""
    return sum(int(l.size) * jnp.dtype(l.dtype).itemsize
               for l in jax.tree.leaves(tree))


def payload_nbytes(payload) -> int:
    """Wire bytes of an encoded payload (payloads are plain pytrees of
    arrays, so the accounting is `tree_nbytes`)."""
    return tree_nbytes(payload)


def flat_width(tree) -> int:
    """Raveled width P of ONE model tree, rounded up to the quantization
    block BQ — the per-row error-feedback slot width."""
    P = sum(int(l.size) for l in jax.tree.leaves(tree))
    return -(-P // BQ) * BQ


# --------------------------------------------------------------------------
# ravel helpers (row-major, the same leaf order as kernels/ops.py)
# --------------------------------------------------------------------------

def _ravel_rows(stacked) -> jnp.ndarray:
    """Stacked tree (every leaf (m, ...)) -> one (m, P) f32 matrix."""
    leaves = jax.tree.leaves(stacked)
    m = leaves[0].shape[0]
    return jnp.concatenate(
        [l.reshape(m, -1).astype(jnp.float32) for l in leaves], axis=1)


def _unravel_rows(flat, row_shapes, treedef):
    """(m, P) f32 -> a stacked tree with per-row leaf shapes
    `row_shapes` (f32 leaves; dtype casts happen at the base-add)."""
    m = flat.shape[0]
    out, off = [], 0
    for shape in row_shapes:
        n = 1
        for d in shape:
            n *= int(d)
        out.append(flat[:, off:off + n].reshape((m,) + tuple(shape)))
        off += n
    return jax.tree.unflatten(treedef, out)


def _int_twin(dtype) -> jnp.dtype:
    """The same-width signed integer dtype a float leaf bitcasts to."""
    return jnp.dtype(f"int{jnp.dtype(dtype).itemsize * 8}")


# --------------------------------------------------------------------------
# codec implementations
# --------------------------------------------------------------------------

def _identity_encode(stacked, base, ef=None, stacked_base=False):
    return {"trees": stacked}, None


def _identity_decode(payload, base, stacked_base=False):
    return payload["trees"]


def _delta_enc_leaf(x, b):
    b = jnp.broadcast_to(b, x.shape).astype(x.dtype)
    if jnp.issubdtype(x.dtype, jnp.floating):
        it = _int_twin(x.dtype)
        return (jax.lax.bitcast_convert_type(x, it)
                - jax.lax.bitcast_convert_type(b, it))
    return x - b


def _delta_dec_leaf(d, b):
    out_dtype = b.dtype
    b = jnp.broadcast_to(b, d.shape)
    if jnp.issubdtype(out_dtype, jnp.floating):
        it = _int_twin(out_dtype)
        raw = jax.lax.bitcast_convert_type(b, it) + d
        return jax.lax.bitcast_convert_type(raw, out_dtype)
    return (b + d).astype(out_dtype)


def _delta_encode(stacked, base, ef=None, stacked_base=False):
    """Wrapping bitcast-integer delta: integer subtraction wraps (two's
    complement), so decode's add undoes encode's subtract bit for bit,
    with no float rounding anywhere — exact for ANY values. Leafwise
    broadcasting handles single and stacked bases alike."""
    with jax.named_scope("codec"):
        return {"delta": jax.tree.map(_delta_enc_leaf, stacked, base)}, None


def _delta_decode(payload, base, stacked_base=False):
    with jax.named_scope("codec"):
        return jax.tree.map(lambda d, b: _delta_dec_leaf(d, b),
                            payload["delta"], base)


def _q8_delta_rows(stacked, base):
    """Per-row float delta, raveled to an (m, Ppad) f32 matrix with the
    tail zero-padded to the quantization block BQ."""
    delta = jax.tree.map(
        lambda x, b: x.astype(jnp.float32)
        - jnp.broadcast_to(b, x.shape).astype(jnp.float32),
        stacked, base)
    flat = _ravel_rows(delta)
    m, P = flat.shape
    pad = (-P) % BQ
    if pad:
        # analysis: allow=retrace-fresh-array -- device-side zero pad
        # to the quantization block; width follows P, nothing to hoist
        flat = jnp.concatenate([flat, jnp.zeros((m, pad), jnp.float32)],
                               axis=1)
    return flat


def _int8_encode(stacked, base, ef=None, stacked_base=False):
    from repro.kernels import ops as _kops   # deferred: keep comms light
    with jax.named_scope("codec"):
        flat = _q8_delta_rows(stacked, base)
        if ef is None:
            ef = jnp.zeros_like(flat)
        codes, scales, new_ef = _kops.q8_encode_flat(flat, ef,
                                                     backend=_q8_backend)
        return {"codes": codes, "scales": scales}, new_ef


def _int8_decode(payload, base, stacked_base=False):
    from repro.kernels import ops as _kops
    with jax.named_scope("codec"):
        flat = _kops.q8_decode_flat(payload["codes"], payload["scales"],
                                    backend=_q8_backend)
        leaves, treedef = jax.tree.flatten(base)
        # stacked_base says whether `base` carries the per-row leading
        # axis (the handover download: each row's base is its RSU's
        # model) — the caller knows, guessing from shapes is ambiguous
        # for small trees
        row_shapes = [tuple(l.shape[1:]) if stacked_base
                      else tuple(l.shape) for l in leaves]
        delta = _unravel_rows(flat, row_shapes, treedef)
        return jax.tree.map(
            lambda b, d: (jnp.broadcast_to(b, d.shape).astype(jnp.float32)
                          + d).astype(b.dtype),
            base, delta)


def _no_state(cfg, tree):
    return None


def _int8_init_state(cfg, tree):
    """Zero error-feedback residual: one slot per cohort position."""
    return {"ef": jnp.zeros((cfg.vehicles_per_round, flat_width(tree)),
                            jnp.float32)}


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class Codec:
    """One exchange codec.

    encode(stacked, base, ef, stacked_base) -> (payload, new_ef) — pure
        and ROW-WISE: row i of every output depends only on row i of
        the inputs, so group-wise application (MultiRSU / handover
        per-RSU groups) equals one full-cohort application. `base` is a
        single model tree (broadcast over rows), or — with
        stacked_base=True — a per-row stacked tree; `ef` is the
        (rows, Ppad) residual slice for stateful codecs, else None.
    decode(payload, base, stacked_base) -> stacked trees θ̂_n;
        bitwise-exact reconstruction for lossless codecs.
    init_state(cfg, tree) -> the round-0 ``FLState.comms`` payload
        (None when the codec carries no cross-round state).
    """

    name: str
    lossless: bool
    stateful: bool
    encode: Callable[..., Any]
    decode: Callable[..., Any]
    init_state: Callable[..., Optional[dict]]


CODECS = {
    "identity": Codec("identity", lossless=True, stateful=False,
                      encode=_identity_encode, decode=_identity_decode,
                      init_state=_no_state),
    "delta": Codec("delta", lossless=True, stateful=False,
                   encode=_delta_encode, decode=_delta_decode,
                   init_state=_no_state),
    "delta_int8": Codec("delta_int8", lossless=False, stateful=True,
                        encode=_int8_encode, decode=_int8_decode,
                        init_state=_int8_init_state),
}


def comms_init_state(cfg, tree) -> Optional[dict]:
    """The round-0 ``FLState.comms`` for cfg.codec."""
    return CODECS[cfg.codec].init_state(cfg, tree)


# --------------------------------------------------------------------------
# snapshot framing (the serving tier's single-tree payloads)
# --------------------------------------------------------------------------

def resolve_codec(codec) -> Codec:
    """A ``Codec`` from a registry name or a `Codec` instance (the
    injectable form the analysis contracts exercise)."""
    return CODECS[codec] if isinstance(codec, str) else codec


def encode_snapshot(codec, tree, base):
    """ONE model tree framed through a stacked-cohort codec: the tree
    gains a length-1 cohort axis and row 0 encodes against ``base`` (the
    model the fetching vehicle already holds; ignored by ``identity``).

    This is the serving tier's downlink payload format (serve/store.py):
    `ModelStore.publish` encodes round r ONCE as
    ``encode_snapshot(codec, model_r, served_{r-1})`` and every fetch
    for round r reuses the payload. Stateful codecs run with a zero
    residual — a snapshot is one payload per round, there is no
    cross-fetch error-feedback to telescope (lossy drift is handled by
    chaining each snapshot off the previous RECONSTRUCTION instead, so
    server and vehicles stay bitwise in step)."""
    codec = resolve_codec(codec)
    stacked = jax.tree.map(lambda l: l[None], tree)
    payload, _ = codec.encode(stacked, base)
    return payload


def decode_snapshot(codec, payload, base):
    """Invert `encode_snapshot`: decode the payload against ``base`` and
    strip the length-1 cohort axis — the vehicle-side reconstruction
    (bitwise equal to the published tree for lossless codecs)."""
    codec = resolve_codec(codec)
    stacked = codec.decode(payload, base)
    return jax.tree.map(lambda l: l[0], stacked)


# --------------------------------------------------------------------------
# the CohortBatch encode/decode stage
# --------------------------------------------------------------------------

def roundtrip_cohort(cfg, cohort, base, comms, rows=None,
                     stacked_base=False):
    """Encode->decode the cohort's VALID trees against `base` — the one
    insertion point every host exchange path shares (the compiled engine
    bodies call the same encode/decode pair on raw stacked trees).

    rows: static index array mapping cohort row -> error-feedback slot
    (slot = cohort position); None means slots [0, n) in order. Padding
    rows of a bucketed cohort are re-padded by replicating the last
    DECODED row — padding is masked out of every aggregation, and for
    lossless codecs the decoded rows equal the originals bitwise, so
    the padded cohort stays bit-identical too. Returns
    (cohort', comms').
    """
    if cfg.codec == "identity":
        return cohort, comms
    codec = CODECS[cfg.codec]
    ef = full_ef = None
    if codec.stateful:
        full_ef = comms["ef"]
        ef = full_ef[:cohort.n] if rows is None else full_ef[rows]
    payload, new_ef = codec.encode(cohort.valid_trees, base, ef,
                                   stacked_base=stacked_base)
    trees = codec.decode(payload, base, stacked_base=stacked_base)
    if cohort.size > cohort.n:
        pad = cohort.size - cohort.n

        def ext(x):
            reps = jnp.broadcast_to(x[-1:], (pad,) + x.shape[1:])
            return jnp.concatenate([x, reps])

        trees = jax.tree.map(ext, trees)
    new_cohort = dataclasses.replace(cohort, trees=trees)
    if codec.stateful:
        rows = slice(0, cohort.n) if rows is None else rows
        comms = {"ef": full_ef.at[rows].set(new_ef)}
    return new_cohort, comms
