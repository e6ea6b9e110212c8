"""ResNet-18 (CIFAR variant) + 128-D projection head — the FLSimCo backbone.

Paper Sec 5.1: "improved ResNet-18 with a fixed dimension of 128-D".
CIFAR stem (3x3 conv stride 1, no max-pool), stages [2,2,2,2] at widths
[64,128,256,512], BatchNorm with running stats, global average pool, and a
SimCLR-style 2-layer MLP projector to 128-D (L2-normalized output).

Functional: ``init_resnet`` -> (params, state) where ``state`` holds BN
running statistics. ``resnet_apply(params, state, x, train)`` returns
(features_128, new_state). BN stats are part of the federated aggregation
payload (DESIGN.md deviation #3).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.models.layers import normal_init

STAGES = (2, 2, 2, 2)
WIDTHS = (64, 128, 256, 512)


def _conv_init(key, kh, kw, cin, cout, dtype):
    fan_in = kh * kw * cin
    return normal_init(key, (kh, kw, cin, cout), math.sqrt(2.0 / fan_in), dtype)


def _init_bn(c):
    return ({"scale": jnp.ones((c,), jnp.float32),
             "bias": jnp.zeros((c,), jnp.float32)},
            {"mean": jnp.zeros((c,), jnp.float32),
             "var": jnp.ones((c,), jnp.float32)})


def init_resnet(cfg, key, dtype=jnp.float32):
    """Returns {"params": ..., "state": ...} pytree."""
    keys = iter(jax.random.split(key, 64))
    params: dict = {}
    state: dict = {}
    params["stem"] = _conv_init(next(keys), 3, 3, 3, WIDTHS[0], dtype)
    params["stem_bn"], state["stem_bn"] = _init_bn(WIDTHS[0])

    cin = WIDTHS[0]
    for si, (n_blocks, w) in enumerate(zip(STAGES, WIDTHS)):
        for bi in range(n_blocks):
            name = f"s{si}b{bi}"
            stride = 2 if (bi == 0 and si > 0) else 1
            blk: dict = {
                "conv1": _conv_init(next(keys), 3, 3, cin, w, dtype),
                "conv2": _conv_init(next(keys), 3, 3, w, w, dtype),
            }
            st: dict = {}
            blk["bn1"], st["bn1"] = _init_bn(w)
            blk["bn2"], st["bn2"] = _init_bn(w)
            if stride != 1 or cin != w:
                blk["proj"] = _conv_init(next(keys), 1, 1, cin, w, dtype)
                blk["proj_bn"], st["proj_bn"] = _init_bn(w)
            params[name] = blk
            state[name] = st
            cin = w

    # projector: 512 -> 512 -> 128 (SimCLR-style)
    params["proj1"] = normal_init(next(keys), (WIDTHS[-1], WIDTHS[-1]),
                                  1 / math.sqrt(WIDTHS[-1]), dtype)
    params["proj1_b"] = jnp.zeros((WIDTHS[-1],), dtype)
    params["proj2"] = normal_init(next(keys), (WIDTHS[-1], cfg.d_ff),
                                  1 / math.sqrt(WIDTHS[-1]), dtype)
    return {"params": params, "state": state}


def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(p, s, x, train: bool, momentum=0.9):
    """BatchNorm over NHW. Returns (y, new_state)."""
    with jax.named_scope("batch_norm"):
        if train:
            xf = x.astype(jnp.float32)
            mean = xf.mean(axis=(0, 1, 2))
            var = xf.var(axis=(0, 1, 2))
            new_s = {"mean": momentum * s["mean"] + (1 - momentum) * mean,
                     "var": momentum * s["var"] + (1 - momentum) * var}
        else:
            mean, var = s["mean"], s["var"]
            new_s = s
        y = (x.astype(jnp.float32) - mean) * jax.lax.rsqrt(var + 1e-5)
        y = y * p["scale"] + p["bias"]
        return y.astype(x.dtype), new_s


def resnet_apply(tree, x, train: bool = True):
    """x: (B, 32, 32, 3) -> (z128 L2-normalized, h512 pre-projector, new_state)."""
    p, s = tree["params"], tree["state"]
    ns: dict = {}
    h = _conv(x, p["stem"])
    h, ns["stem_bn"] = _bn(p["stem_bn"], s["stem_bn"], h, train)
    h = jax.nn.relu(h)

    cin = WIDTHS[0]
    for si, (n_blocks, w) in enumerate(zip(STAGES, WIDTHS)):
        for bi in range(n_blocks):
            name = f"s{si}b{bi}"
            blk, bst = p[name], s[name]
            stride = 2 if (bi == 0 and si > 0) else 1
            nbs: dict = {}
            y = _conv(h, blk["conv1"], stride)
            y, nbs["bn1"] = _bn(blk["bn1"], bst["bn1"], y, train)
            y = jax.nn.relu(y)
            y = _conv(y, blk["conv2"])
            y, nbs["bn2"] = _bn(blk["bn2"], bst["bn2"], y, train)
            if "proj" in blk:
                sc = _conv(h, blk["proj"], stride)
                sc, nbs["proj_bn"] = _bn(blk["proj_bn"], bst["proj_bn"], sc, train)
            else:
                sc = h
            h = jax.nn.relu(y + sc)
            ns[name] = nbs
            cin = w

    h = h.mean(axis=(1, 2))                                   # (B, 512)
    z = jax.nn.relu(h @ p["proj1"] + p["proj1_b"])
    z = z @ p["proj2"]                                        # (B, 128)
    z = z.astype(jnp.float32)
    z = z / jnp.maximum(jnp.linalg.norm(z, axis=-1, keepdims=True), 1e-8)
    return z, h, {"params": p, "state": ns}
