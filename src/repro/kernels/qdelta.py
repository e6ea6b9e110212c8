"""Fused blockwise-int8 delta quantize/dequantize — Pallas TPU kernels.

The comms tier (src/repro/comms/) uploads per-client model deltas
Δ_n = θ_n − θ quantized to int8 with one f32 scale per 256-parameter
block (`BQ`) and an error-feedback residual folded in before
quantization. Done as separate jnp ops the (N, P) delta matrix crosses
HBM five times (add residual, absmax, scale, round, subtract); these
kernels fuse the whole codec step into one pass per direction:

  encode: (delta, ef) -> (codes int8, scales f32, new_ef f32)
  decode: (codes, scales) -> delta_hat f32

Grid: (P / block,) with `block` a multiple of BQ — each grid step holds
an (N, block) VMEM tile, reshapes it to (N, block/BQ, BQ) VREG-resident
sub-blocks and computes the per-block scales with a lane reduction. On
TPU the tile defaults to BT; in interpret mode callers pass one
whole-axis block (per-grid-step overhead dominates, same policy as
`wagg`). The op sequence matches kernels/ref.py `q8_encode_ref` /
`q8_decode_ref` exactly, so interpret-mode parity is bitwise
(tests/test_kernels.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BQ = 256       # quantization block: parameters sharing one f32 scale
BT = 2048      # TPU tile: BT // BQ scales per grid step


def _q8_encode_kernel(x_ref, e_ref, c_ref, s_ref, r_ref):
    N, T = x_ref.shape
    y = (x_ref[...] + e_ref[...]).reshape(N, T // BQ, BQ)
    absmax = jnp.max(jnp.abs(y), axis=-1)
    scales = absmax * jnp.float32(1.0 / 127.0)
    inv = jnp.where(scales > 0.0, 1.0 / scales, 0.0)
    codes = jnp.clip(jnp.round(y * inv[..., None]), -127.0, 127.0)
    codes = codes.astype(jnp.int8)
    deq = codes.astype(jnp.float32) * scales[..., None]
    c_ref[...] = codes.reshape(N, T)
    s_ref[0] = scales
    r_ref[...] = (y - deq).reshape(N, T)


def _q8_decode_kernel(c_ref, s_ref, o_ref):
    N, T = c_ref.shape
    deq = (c_ref[...].reshape(N, T // BQ, BQ).astype(jnp.float32)
           * s_ref[0][..., None])
    o_ref[...] = deq.reshape(N, T)


# The kernels see the scales tile-major, as (P/block, N, block/BQ): each
# grid step owns one (N, block/BQ) slab whose last two dims equal the
# array's, which the TPU lowering requires (an (N, block/BQ) block of an
# (N, P/BQ) array breaks the (8, 128) tiling rule for block/BQ = 8). The
# public layout stays (N, P/BQ); the transposes touch 1/BQ of the data.

def _scales_from_tiles(tiles):
    G, N, S = tiles.shape
    return tiles.transpose(1, 0, 2).reshape(N, G * S)


def _scales_to_tiles(scales, G):
    N = scales.shape[0]
    return scales.reshape(N, G, -1).transpose(1, 0, 2)


def q8_encode_pallas(flat, ef, *, interpret: bool = True,
                     block: int | None = None):
    """flat, ef: (N, P) f32 with P % block == 0 and block % BQ == 0.

    Returns (codes (N, P) int8, scales (N, P/BQ) f32, new_ef (N, P)
    f32). The wrapper in kernels/ops.py pads P and picks the block.
    """
    N, P = flat.shape
    block = BT if block is None else block
    assert P % block == 0 and block % BQ == 0, (P, block)
    G, S = P // block, block // BQ
    codes, scales, new_ef = pl.pallas_call(
        _q8_encode_kernel,
        grid=(G,),
        in_specs=[pl.BlockSpec((N, block), lambda i: (0, i)),
                  pl.BlockSpec((N, block), lambda i: (0, i))],
        out_specs=(pl.BlockSpec((N, block), lambda i: (0, i)),
                   pl.BlockSpec((1, N, S), lambda i: (i, 0, 0)),
                   pl.BlockSpec((N, block), lambda i: (0, i))),
        out_shape=(jax.ShapeDtypeStruct((N, P), jnp.int8),
                   jax.ShapeDtypeStruct((G, N, S), jnp.float32),
                   jax.ShapeDtypeStruct((N, P), jnp.float32)),
        interpret=interpret,
        name="q8_encode",
    )(flat, ef)
    return codes, _scales_from_tiles(scales), new_ef


def q8_decode_pallas(codes, scales, *, interpret: bool = True,
                     block: int | None = None):
    """codes: (N, P) int8, scales: (N, P/BQ) f32 -> (N, P) f32."""
    N, P = codes.shape
    block = BT if block is None else block
    assert P % block == 0 and block % BQ == 0, (P, block)
    G, S = P // block, block // BQ
    return pl.pallas_call(
        _q8_decode_kernel,
        grid=(G,),
        in_specs=[pl.BlockSpec((N, block), lambda i: (0, i)),
                  pl.BlockSpec((1, N, S), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((N, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((N, P), jnp.float32),
        interpret=interpret,
        name="q8_decode",
    )(codes, _scales_to_tiles(scales, G))
