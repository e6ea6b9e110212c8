"""A toy stateless backbone for the tests of the backbone plug-in: a
patch embedding (`patch` x `patch` pixels to `width`), one dense layer
with ReLU, mean pooling over the patches, and the `projector` MLP.
`state` stays an empty dict."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _dense(key, cin, cout):
    return (jax.random.normal(key, (cin, cout), jnp.float32)
            / math.sqrt(cin))


def init(key, model: dict):
    p, c, w = model["patch"], model["in_channels"], model["width"]
    hid, out = model["projector"]
    k = jax.random.split(key, 4)
    params = {"embed": _dense(k[0], p * p * c, w),
              "embed_b": jnp.zeros((w,), jnp.float32),
              "dense": _dense(k[1], w, w),
              "dense_b": jnp.zeros((w,), jnp.float32),
              "proj1": _dense(k[2], w, hid),
              "proj1_b": jnp.zeros((hid,), jnp.float32),
              "proj2": _dense(k[3], hid, out)}
    return {"params": params, "state": {}}


def forward(params, state, x, model, precision):
    p = model["patch"]
    b, h, w, c = x.shape
    x = (x.reshape(b, h // p, p, w // p, p, c).transpose(0, 1, 3, 2, 4, 5)
         .reshape(b, (h // p) * (w // p), p * p * c))

    def dense(name, t):
        return (jnp.dot(t, params[name].astype(t.dtype), precision=precision)
                + params[name + "_b"].astype(t.dtype))

    t = dense("embed", x)
    t = jax.nn.relu(dense("dense", t)).mean(axis=1)
    z = jax.nn.relu(dense("proj1", t))
    z = jnp.dot(z, params["proj2"].astype(z.dtype), precision=precision)
    z = z.astype(jnp.float32)
    norm = jnp.sqrt(jnp.sum(z * z, axis=-1, keepdims=True))
    return z / jnp.maximum(norm, 1e-8), state
