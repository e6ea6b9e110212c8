"""Operations and bytes of ResNet-18-CIFAR training, from the model's shapes.

The shapes come from `jax.eval_shape` of the benchmark's own initialiser
(`init` of `models/resnet18-cifar.py`), so nothing here reads the program. A
convolution's spatial size follows from the image size and the strides
(2 on the first block of stages 2-4 and on its 1x1 projection shortcut).

Counting rules:
- a multiply-add is 2 operations;
- training one image view costs the forward pass, the weight gradient of
  every layer, and the input gradient of every layer but the stem (the
  images need none): no recomputation is counted;
- the least bytes a convolution needs are its operands and its result,
  once each, in float32 (the precision the configuration states); the
  weights count once per call.
"""
from __future__ import annotations

import functools

import jax

F32 = 4


@functools.lru_cache(maxsize=None)
def _shapes(model_key):
    from reference import backbone
    model = {k: list(v) if isinstance(v, tuple) else v for k, v in model_key}
    init = backbone(model["arch"]).init
    tree = jax.eval_shape(lambda k: init(k, model),
                          jax.ShapeDtypeStruct((2,), jax.numpy.uint32))
    return jax.tree.map(lambda s: tuple(s.shape), tree["params"])


def _key(model: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()))


def convs(cfg: dict) -> list:
    """[(name, h_in, w_in, h_out, w_out, kh, kw, cin, cout), ...]"""
    p = _shapes(_key(cfg["model"]))
    h, w = cfg["image_shape"][:2]
    out = []
    kh, kw, cin, cout = p["stem"]
    out.append(("stem", h, w, h, w, kh, kw, cin, cout))
    for si, n_blocks in enumerate(cfg["model"]["stages"]):
        for bi in range(n_blocks):
            name = f"s{si}b{bi}"
            stride = 2 if (bi == 0 and si > 0) else 1
            ho, wo = -(-h // stride), -(-w // stride)
            kh, kw, cin, cout = p[name]["conv1"]
            out.append((f"{name}.conv1", h, w, ho, wo, kh, kw, cin, cout))
            kh, kw, cin, cout = p[name]["conv2"]
            out.append((f"{name}.conv2", ho, wo, ho, wo, kh, kw, cin, cout))
            if "proj" in p[name]:
                kh, kw, cin, cout = p[name]["proj"]
                out.append((f"{name}.proj", h, w, ho, wo, kh, kw, cin, cout))
            h, w = ho, wo
    return out


def dense(cfg: dict) -> list:
    """[(name, fan_in, fan_out), ...] of the projector."""
    p = _shapes(_key(cfg["model"]))
    return [("proj1", *p["proj1"]), ("proj2", *p["proj2"])]


def forward_macs_per_image(cfg: dict) -> int:
    macs = sum(ho * wo * kh * kw * cin * cout
               for _, _, _, ho, wo, kh, kw, cin, cout in convs(cfg))
    return macs + sum(i * o for _, i, o in dense(cfg))


def train_flops_per_view(cfg: dict) -> int:
    """Forward + weight gradients + input gradients (none for the stem)."""
    fwd = 2 * forward_macs_per_image(cfg)
    stem = convs(cfg)[0]
    stem_macs = stem[3] * stem[4] * stem[5] * stem[6] * stem[7] * stem[8]
    return 3 * fwd - 2 * stem_macs


def views_per_round(cfg: dict, traffic: dict) -> int:
    """Image views the client step trains on in one round."""
    return (traffic["vehicles_per_round"] * cfg["batch_size"] * 2
            * cfg["local_iters"])


def round_flops(cfg: dict, traffic: dict) -> int:
    return views_per_round(cfg, traffic) * train_flops_per_view(cfg)


def conv_roofline_s(cfg: dict, traffic: dict, peak_flops: float,
                    peak_bytes: float) -> tuple:
    """Least seconds a round's convolutions could take on the chip: each
    forward, input-gradient and weight-gradient convolution bounded by
    the larger of its operations over the FLOP peak and its bytes over
    the HBM peak. Returns (seconds, seconds bound by FLOPs, seconds
    bound by bytes)."""
    n = views_per_round(cfg, traffic)
    total = by_flops = by_bytes = 0.0
    for i, (_, hi, wi, ho, wo, kh, kw, cin, cout) in enumerate(convs(cfg)):
        flops = 2 * n * ho * wo * kh * kw * cin * cout
        x = n * hi * wi * cin * F32
        y = n * ho * wo * cout * F32
        wt = kh * kw * cin * cout * F32
        passes = [(flops, x + wt + y), (flops, x + y + wt)]   # fwd, wgrad
        if i:                                                  # dgrad
            passes.append((flops, y + wt + x))
        for f, b in passes:
            t_f, t_b = f / peak_flops, b / peak_bytes
            total += max(t_f, t_b)
            if t_f >= t_b:
                by_flops += t_f
            else:
                by_bytes += t_b
    return total, by_flops, by_bytes


def n_values(cfg: dict) -> int:
    """Values in one model tree: parameters plus BatchNorm statistics."""
    p = _shapes(_key(cfg["model"]))
    params = sum(int(_prod(s)) for s in jax.tree.leaves(
        p, is_leaf=lambda x: isinstance(x, tuple)))
    bn = 0
    for k, v in p.items():
        if isinstance(v, dict):
            if "scale" in v:
                bn += 2 * v["scale"][0]
            else:
                bn += sum(2 * b["scale"][0] for b in v.values()
                          if isinstance(b, dict) and "scale" in b)
    return params + bn


def _prod(shape):
    out = 1
    for d in shape:
        out *= d
    return out
