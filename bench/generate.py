"""Inputs of a cell, made from `--seed` alone.

One seed gives three streams: the image pool and its IID split (numpy),
the model's weights (one jitted call on the device of the `init` of the
backbone that `model.arch` names, `reference.backbone`), and the FL seed
the program's `FLConfig.seed` and the reference's schedule replay both
use.
`--seed` may be any non-negative integer, also one wider than 32 bits:
`numpy.random.SeedSequence` folds it into the three streams.
"""
from __future__ import annotations

import functools

import jax
import numpy as np

from reference import backbone


def seeds(seed: int) -> dict:
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    data, weights, fl = np.random.SeedSequence(seed).generate_state(3)
    return {"data": int(data), "weights": int(weights),
            "fl": int(fl) & 0x7FFFFFFF}


def images(cfg: dict, seed: int) -> list:
    """The image pool, split IID over the fleet: a permutation of the
    pool cut into `n_vehicles` nearly equal parts (the split of
    `repro.data.synthetic.partition_iid`). The configuration's
    `image_dtype` gives the pool's type: "float32" (the default), uniform
    in [0, 1), or "uint8", 8-bit frames uniform in 0-255."""
    shape = (cfg["images"],) + tuple(cfg["image_shape"])
    rng = np.random.default_rng(seed)
    dtype = cfg.get("image_dtype", "float32")
    if dtype == "float32":
        pool = rng.random(shape, dtype=np.float32)
    elif dtype == "uint8":
        pool = rng.integers(0, 256, shape, dtype=np.uint8)
    else:
        raise ValueError(f"unknown image_dtype {dtype!r}; valid: "
                         f"['float32', 'uint8']")
    perm = np.random.RandomState(seed).permutation(cfg["images"])
    return [pool[p] for p in np.array_split(perm, cfg["n_vehicles"])]


@functools.lru_cache(maxsize=None)
def _init_fn(model_key):
    model = dict((k, list(v) if isinstance(v, tuple) else v)
                 for k, v in model_key)
    return jax.jit(functools.partial(backbone(model["arch"]).init,
                                     model=model))


def weights(cfg: dict, seed: int):
    model_key = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                             for k, v in cfg["model"].items()))
    return _init_fn(model_key)(jax.random.PRNGKey(seed))
