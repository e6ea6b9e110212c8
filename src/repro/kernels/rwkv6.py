"""RWKV6 (Finch) chunked recurrence — Pallas TPU kernel.

The attention-free arch's hot loop: per (batch, head),
    S_t = diag(w_t) S_{t-1} + k_t v_t^T ;  o_t = r_t (S_{t-1} + diag(u) k_t v_t^T)
with data-dependent per-channel decay w_t. A token-sequential scan wastes
the MXU; the chunked form turns it into dense (C,D)x(D,D)/(C,C)x(C,D)
matmuls with the decay folded in AFTER the cum-difference (exponent <= 0,
so no rescaling pass — see models/layers.rwkv_tmix_chunked).

TPU mapping: grid is (BH, S/C) with the S/C dimension marked
sequential-innermost; the running state lives in a VMEM scratch buffer
(D, D) f32 that persists across chunk steps of the same (batch*head) row —
the standard linear-attention state-carrying pattern. Each chunk step is
a handful of MXU ops on (C, D) tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

CHUNK = 16


def _rwkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, state_out,
                  state_scr, *, n_chunks: int):
    step = pl.program_id(1)

    @pl.when(step == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    r = r_ref[0, 0].astype(jnp.float32)            # (C, D)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)            # (1, D) — per (batch*head) row
    S0 = state_scr[...]                         # (D, D)

    C, D = r.shape
    # inclusive prefix sum over the chunk as a lower-triangular matmul —
    # the TPU lowering has no cumsum primitive
    tri_incl = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0) >=
                jax.lax.broadcasted_iota(jnp.int32, (C, C), 1))
    cum = jax.lax.dot_general(tri_incl.astype(jnp.float32), lw,
                              (((1,), (0,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)  # (C, D)
    cum_prev = cum - lw
    # carry-in term
    a = r * jnp.exp(cum_prev)
    o = jax.lax.dot_general(a, S0, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)      # (C, D)
    # intra-chunk: scores_ij = sum_d r_id k_jd exp(cum_prev_i - cum_j)_d, j<i
    # built one row at a time (static unroll over the C rows): the TPU
    # lowering cannot form the (C, C, D) broadcast, and the decay stays
    # folded in after the cum-difference (exponent <= 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col_c = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    scores = jnp.zeros((C, C), jnp.float32)
    for i in range(1, C):
        dec = jnp.where(col_c < i,
                        jnp.exp(cum_prev[i:i + 1, :] - cum), 0.0)    # (C, D)
        s_i = jax.lax.dot_general(r[i:i + 1, :], k * dec,
                                  (((1,), (1,)), ((), ())),
                                  precision=jax.lax.Precision.HIGHEST,
                                  preferred_element_type=jnp.float32)  # (1, C)
        scores = scores + jnp.where(row == i, s_i, 0.0)
    o = o + jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    # bonus (current token)
    bonus = jnp.sum(r * u * k, axis=-1)                              # (C,)
    o = o + bonus[:, None] * v
    o_ref[0, 0] = o.astype(o_ref.dtype)

    # state update
    total = cum[C - 1:C, :]                                          # (1, D)
    kdec = k * jnp.exp(total - cum)                                  # (C, D)
    # exp(total) as a (D, 1) column: a masked row reduction stands in
    # for the (1, D) -> (D, 1) transpose
    eye = (jax.lax.broadcasted_iota(jnp.int32, (D, D), 0) ==
           jax.lax.broadcasted_iota(jnp.int32, (D, D), 1))
    decay_col = jnp.sum(jnp.where(eye, jnp.exp(total), 0.0), axis=1,
                        keepdims=True)                               # (D, 1)
    S_new = S0 * decay_col + jax.lax.dot_general(
        kdec, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    state_scr[...] = S_new

    @pl.when(step == n_chunks - 1)
    def _emit():
        state_out[0] = S_new


def rwkv6_pallas(r, k, v, logw, u, *, interpret: bool = True):
    """r,k,v,logw: (BH, S, D) with S % CHUNK == 0; u: (BH, D) or (D,)
    (per-head bonus; a (D,) u is broadcast to all rows).

    Returns (o (BH,S,D) f32, state (BH,D,D) f32). Matches kernels/ref.py
    rwkv6_ref with zero initial state.
    """
    BH, S, D = r.shape
    # (BH, 1, D): a (1, D) block of a 3-D array keeps the last two block
    # dims equal to the array's, which the TPU lowering requires
    u = jnp.broadcast_to(u, (BH, D)).reshape(BH, 1, D)
    assert S % CHUNK == 0, (S, CHUNK)
    n_chunks = S // CHUNK
    rc = r.reshape(BH, n_chunks, CHUNK, D)
    kc = k.reshape(BH, n_chunks, CHUNK, D)
    vc = v.reshape(BH, n_chunks, CHUNK, D)
    lwc = logw.reshape(BH, n_chunks, CHUNK, D)

    kernel = functools.partial(_rwkv6_kernel, n_chunks=n_chunks)
    o, state = pl.pallas_call(
        kernel,
        grid=(BH, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, CHUNK, D), lambda b, s: (b, s, 0, 0)),
            pl.BlockSpec((1, 1, CHUNK, D), lambda b, s: (b, s, 0, 0)),
            pl.BlockSpec((1, 1, CHUNK, D), lambda b, s: (b, s, 0, 0)),
            pl.BlockSpec((1, 1, CHUNK, D), lambda b, s: (b, s, 0, 0)),
            pl.BlockSpec((1, 1, D), lambda b, s: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, CHUNK, D), lambda b, s: (b, s, 0, 0)),
            pl.BlockSpec((1, D, D), lambda b, s: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, n_chunks, CHUNK, D), jnp.float32),
            jax.ShapeDtypeStruct((BH, D, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        interpret=interpret,
        name="rwkv6",
    )(rc, kc, vc, lwc, u)
    return o.reshape(BH, S, D), state
