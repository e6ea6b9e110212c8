"""Fused dual-temperature loss — Pallas TPU kernel.

The paper's inner-loop compute is an (M, M) similarity matrix followed by
TWO softmaxes (tau_alpha, tau_beta) and a weighted NLL. Unfused, XLA
materializes the logits twice ((M,M) f32 each) plus the probability
tensors — 3-4 HBM round trips of M^2 data. This kernel streams K-blocks
through VMEM once, maintaining online logsumexp accumulators for BOTH
temperatures simultaneously, and never writes an (M, M) intermediate.

Layout: grid over (M/BM) anchor-row blocks; inner fori_loop walks key
blocks of BN columns. Blocks are (BM, BN) = (128, 128) — MXU-aligned.
q/k rows are zero-padded to multiples of 128 by the ops.py wrapper
(padded rows produce sim 0 everywhere; the wrapper masks them out of the
mean).

TPU mapping notes (HARDWARE ADAPTATION): the (BM, D) @ (D, BN) tile hits
the MXU; the two exp/max/sum accumulator sets live in VREGs; f32
accumulation throughout (inputs may be bf16).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -1e30
BM = 128
BN = 128


def _dt_fwd_kernel(q_ref, k_ref, o_loss, o_lsea, o_lseb, o_pos, *,
                   tau_alpha: float, tau_beta: float, n_valid: int):
    """One grid step: BM anchors vs all keys (looped in BN blocks)."""
    row_block = pl.program_id(0)
    q = q_ref[...].astype(jnp.float32)                       # (BM, D)
    M = k_ref.shape[0]
    n_kb = M // BN

    row_ids = row_block * BM + jax.lax.broadcasted_iota(jnp.int32, (BM, 1), 0)

    def body(j, carry):
        m_a, l_a, m_b, l_b, pos = carry
        k = k_ref[pl.ds(j * BN, BN), :].astype(jnp.float32)
        sim = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # (BM, BN)
        col_ids = j * BN + jax.lax.broadcasted_iota(jnp.int32, (1, BN), 1)
        valid = col_ids < n_valid                            # mask padded keys
        sim = jnp.where(valid, sim, NEG)
        # capture diagonal positives
        is_diag = row_ids == col_ids
        pos = pos + jnp.sum(jnp.where(is_diag, sim, 0.0), axis=1,
                            keepdims=True)
        # online logsumexp at both temperatures
        sa = sim / tau_alpha
        sb = sim / tau_beta
        m_a2 = jnp.maximum(m_a, sa.max(axis=1, keepdims=True))
        l_a = l_a * jnp.exp(m_a - m_a2) + jnp.sum(
            jnp.where(sa <= NEG / 2, 0.0, jnp.exp(sa - m_a2)), axis=1,
            keepdims=True)
        m_b2 = jnp.maximum(m_b, sb.max(axis=1, keepdims=True))
        l_b = l_b * jnp.exp(m_b - m_b2) + jnp.sum(
            jnp.where(sb <= NEG / 2, 0.0, jnp.exp(sb - m_b2)), axis=1,
            keepdims=True)
        return m_a2, l_a, m_b2, l_b, pos

    # per-row accumulators are (BM, 1) columns, the layout the row
    # reductions produce on TPU
    col = (BM, 1)
    init = (jnp.full(col, NEG, jnp.float32), jnp.zeros(col, jnp.float32),
            jnp.full(col, NEG, jnp.float32), jnp.zeros(col, jnp.float32),
            jnp.zeros(col, jnp.float32))
    m_a, l_a, m_b, l_b, pos = jax.lax.fori_loop(0, n_kb, body, init)

    lse_a = m_a + jnp.log(jnp.maximum(l_a, 1e-30))
    lse_b = m_b + jnp.log(jnp.maximum(l_b, 1e-30))
    log_pa = pos / tau_alpha - lse_a
    w_a = 1.0 - jnp.exp(log_pa)
    w_b = 1.0 - jnp.exp(pos / tau_beta - lse_b)
    weight = w_b / jnp.maximum(w_a, 1e-8)
    o_loss[...] = -weight * log_pa
    o_lsea[...] = lse_a
    o_lseb[...] = lse_b
    o_pos[...] = pos


def dt_loss_fwd_pallas(q, k, tau_alpha: float, tau_beta: float,
                       n_valid: int, *, interpret: bool = True):
    """q, k: (Mp, D) with Mp % 128 == 0 (wrapper pads). Returns
    (loss_vec, lse_a, lse_b, pos) of shape (Mp,).

    The kernel writes (Mp, 1) columns: a 1-D (BM,) block does not match
    the TPU's layout of a 1-D array longer than BM."""
    Mp, D = q.shape
    assert Mp % BM == 0 and Mp % BN == 0, (Mp, BM)
    grid = (Mp // BM,)
    kernel = functools.partial(_dt_fwd_kernel, tau_alpha=tau_alpha,
                               tau_beta=tau_beta, n_valid=n_valid)
    out_shape = [jax.ShapeDtypeStruct((Mp, 1), jnp.float32)] * 4
    vec_spec = pl.BlockSpec((BM, 1), lambda i: (i, 0))
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BM, D), lambda i: (i, 0)),   # q rows for this block
            pl.BlockSpec((Mp, D), lambda i: (0, 0)),   # full k (streamed via dslice)
        ],
        out_specs=[vec_spec] * 4,
        out_shape=out_shape,
        interpret=interpret,
        name="dt_loss",
    )(q, k)
    return tuple(o[:, 0] for o in outs)
