"""Fused blur-weighted aggregation — Pallas TPU kernel.

Eq. (11) at the RSU is sum_n w_n * theta_n over N stacked client models.
Done naively (N scale-then-add tree ops) the parameter payload crosses HBM
N times plus N-1 more for the partial sums. This kernel tiles the flat
parameter axis into VMEM blocks and reduces all N clients inside one pass:
exactly P reads + P/N writes of traffic, the memory-bound optimum.

Grid: (P / block,). Block: (N, block) client-major so the N-reduction is a
VREG-resident dot with the (N,) weight vector, at full f32 precision
(`Precision.HIGHEST`: a model average must not round to bf16). On TPU
the block defaults to BP (VMEM-sized); in interpret mode callers may
pass a much larger block — there is no VMEM to respect and interpret
overhead is per grid *step*, so a multi-million-parameter model wants a
grid of ~1, not ~5000.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BP = 2048


def _wagg_kernel(x_ref, w_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)          # (N, BP)
    w = w_ref[...].astype(jnp.float32)          # (N,)
    o_ref[...] = jax.lax.dot_general(
        w[None, :], x, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)[0]


def _wagg_masked_kernel(x_ref, w_ref, m_ref, o_ref):
    """Masked variant: rows with mask 0 contribute exactly +0.0.

    The mask multiply happens inside the kernel (VREG-resident), so a
    padded `CohortBatch` feeds its stacked tensor straight in — no
    host-side compaction, and `w*1.0 == w` / `w*0.0 == 0.0` keep the
    result bit-identical to an unpadded call on the valid prefix.
    """
    x = x_ref[...].astype(jnp.float32)          # (N, BP)
    w = (w_ref[...] * m_ref[...]).astype(jnp.float32)   # (N,)
    o_ref[...] = jax.lax.dot_general(
        w[None, :], x, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)[0]


def wagg_pallas(stacked, w, mask=None, *, interpret: bool = True,
                block: int | None = None):
    """stacked: (N, P) with P % block == 0 (wrapper pads); w: (N,) -> (P,).

    `mask` (N,) optionally zeroes rows inside the kernel (padding rows of
    a bucketed cohort). block defaults to BP (the VMEM-sized tile).
    Interpret-mode callers should pass a large block (see module
    docstring); the wrapper in kernels/ops.py does this automatically.
    """
    N, P = stacked.shape
    block = BP if block is None else block
    assert P % block == 0
    in_specs = [
        pl.BlockSpec((N, block), lambda i: (0, i)),
        pl.BlockSpec((N,), lambda i: (0,)),
    ]
    operands = [stacked, w]
    kernel = _wagg_kernel
    if mask is not None:
        in_specs.append(pl.BlockSpec((N,), lambda i: (0,)))
        operands.append(mask)
        kernel = _wagg_masked_kernel
    return pl.pallas_call(
        kernel,
        grid=(P // block,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((P,), jnp.float32),
        interpret=interpret,
        name="wagg",
    )(*operands)
