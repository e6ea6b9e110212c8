"""ResNet-18-CIFAR and its projector, the benchmark's plain float32 model.

CIFAR stem (one 3x3 convolution, no pooling), stages of `stages` basic
blocks at `widths` channels (stride 2 on the first block of every stage
but the first, with a 1x1 projection shortcut where the shape changes),
BatchNorm in training mode with running statistics (momentum 0.9), global
average pooling, and the `projector` MLP (hidden -> ReLU -> out, no bias
on the output). Imports nothing from the program; found by
`reference.backbone("resnet18-cifar")`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _conv_init(key, kh, kw, cin, cout):
    std = math.sqrt(2.0 / (kh * kw * cin))
    return jax.random.normal(key, (kh, kw, cin, cout), jnp.float32) * std


def _bn_init(c):
    return ({"scale": jnp.ones((c,), jnp.float32),
             "bias": jnp.zeros((c,), jnp.float32)},
            {"mean": jnp.zeros((c,), jnp.float32),
             "var": jnp.ones((c,), jnp.float32)})


def init(key, model: dict):
    """{"params", "state"} of ResNet-18-CIFAR plus projector, He-normal
    convolutions, identity BatchNorm. `model` is the configuration's
    "model" block (stages, widths, projector sizes)."""
    stages, widths = model["stages"], model["widths"]
    keys = iter(jax.random.split(key, 64))
    params, state = {}, {}
    params["stem"] = _conv_init(next(keys), 3, 3, model["in_channels"],
                                widths[0])
    params["stem_bn"], state["stem_bn"] = _bn_init(widths[0])
    cin = widths[0]
    for si, (n_blocks, w) in enumerate(zip(stages, widths)):
        for bi in range(n_blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            blk = {"conv1": _conv_init(next(keys), 3, 3, cin, w),
                   "conv2": _conv_init(next(keys), 3, 3, w, w)}
            st = {}
            blk["bn1"], st["bn1"] = _bn_init(w)
            blk["bn2"], st["bn2"] = _bn_init(w)
            if stride != 1 or cin != w:
                blk["proj"] = _conv_init(next(keys), 1, 1, cin, w)
                blk["proj_bn"], st["proj_bn"] = _bn_init(w)
            params[f"s{si}b{bi}"] = blk
            state[f"s{si}b{bi}"] = st
            cin = w
    hid, out = model["projector"]
    params["proj1"] = (jax.random.normal(next(keys), (cin, hid), jnp.float32)
                       / math.sqrt(cin))
    params["proj1_b"] = jnp.zeros((hid,), jnp.float32)
    params["proj2"] = (jax.random.normal(next(keys), (hid, out), jnp.float32)
                       / math.sqrt(hid))
    return {"params": params, "state": state}


def _conv(x, w, stride, precision):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


def _bn(p, s, x, momentum=0.9):
    mean = x.mean(axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    y = (x - mean) * jax.lax.rsqrt(var + 1e-5)
    y = y * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)
    dt = s["mean"].dtype
    new = {"mean": momentum * s["mean"] + (1 - momentum) * mean.astype(dt),
           "var": momentum * s["var"] + (1 - momentum) * var.astype(dt)}
    return y, new


def forward(params, state, x, model, precision):
    """Training-mode forward: (L2-normalised 128-D z in f32, new state)."""
    stages, widths = model["stages"], model["widths"]
    ns = {}
    h = _conv(x, params["stem"], 1, precision)
    h, ns["stem_bn"] = _bn(params["stem_bn"], state["stem_bn"], h)
    h = jax.nn.relu(h)
    cin = widths[0]
    for si, (n_blocks, w) in enumerate(zip(stages, widths)):
        for bi in range(n_blocks):
            name = f"s{si}b{bi}"
            blk, st = params[name], state[name]
            stride = 2 if (bi == 0 and si > 0) else 1
            nb = {}
            y = _conv(h, blk["conv1"], stride, precision)
            y, nb["bn1"] = _bn(blk["bn1"], st["bn1"], y)
            y = jax.nn.relu(y)
            y = _conv(y, blk["conv2"], 1, precision)
            y, nb["bn2"] = _bn(blk["bn2"], st["bn2"], y)
            if "proj" in blk:
                sc = _conv(h, blk["proj"], stride, precision)
                sc, nb["proj_bn"] = _bn(blk["proj_bn"], st["proj_bn"], sc)
            else:
                sc = h
            h = jax.nn.relu(y + sc)
            ns[name] = nb
            cin = w
    h = h.mean(axis=(1, 2))
    z = jax.nn.relu(jnp.dot(h, params["proj1"].astype(h.dtype),
                            precision=precision)
                    + params["proj1_b"].astype(h.dtype))
    z = jnp.dot(z, params["proj2"].astype(z.dtype), precision=precision)
    z = z.astype(jnp.float32)
    norm = jnp.sqrt(jnp.sum(z * z, axis=-1, keepdims=True))
    return z / jnp.maximum(norm, 1e-8), ns
