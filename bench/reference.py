"""Plain float32 reference of the FLSimCo round, written apart from the program.

It imports nothing from `repro` and takes nothing the program made: the
weights and images come from the benchmark's own generator
(`bench/generate.py`), and the round schedule is replayed here from the
cell's seed. The schedule replay copies the program's draw order (host
MT19937 for cohort ids and batch indices, the jax key chain for
velocities, client keys and augmentations, the cosine learning rate), so
that both sides train on the same images with the same keys; everything
after the draws is written out plainly from the paper and DESIGN.md:

- motion blur of each batch (Eq. 2, horizontal box PSF),
- the pi1 / pi2 augmentations,
- the backbone the configuration's `model.arch` names, a module of its
  own under `models/` (`backbone`), with its projector,
- the dual-temperature loss (Eq. 6-8) and `local_iters` SGD steps with
  momentum and weight decay,
- blockwise int8 delta uploads with error feedback (`delta_int8`),
- Eq. 11 blur weights and the aggregation of each topology: one RSU;
  two RSUs with a region merge (the mesh cell); two RSUs with handover,
  stale discount and a region sync every `sync_every` rounds.

Every matrix product and convolution runs at the precision the
configuration states (`matmul_precision`: "default" is one bfloat16
pass with float32 accumulation on the TPU, "highest" full float32);
everything else is float32. `dtype=jnp.bfloat16` gives the control: the
reference computed in bfloat16 throughout (parameters, the backbone's
state, activations, the SGD step, uploads and aggregation). `fault=`
plants one of the faults the check has to catch (see `FAULTS`). An 8-bit
image pool (the configuration's `image_dtype` "uint8") is cast to
float32 and divided by 255 before the blur.
"""
from __future__ import annotations

import functools
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

GRAY = (0.299, 0.587, 0.114)
FAULTS = ("half_batch", "no_exchange")
Q8_BLOCK = 256
MODELS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "models")


# --------------------------------------------------------------------------
# backbones
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _load(path: str):
    name = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(f"bench_model_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def backbone(arch: str):
    """The module `models/<arch>.py`, loaded by path. Each exposes
    `init(key, model) -> {"params", "state"}` and
    `forward(params, state, x, model, precision) -> (z, new_state)`:
    `model` is the configuration's "model" block, `z` the L2-normalised
    projector output in float32, and `state` (BatchNorm statistics, say)
    may be an empty dict."""
    path = os.path.join(MODELS, f"{arch}.py")
    if not os.path.isfile(path):
        valid = sorted(f[:-3] for f in os.listdir(MODELS) if f.endswith(".py"))
        raise SystemExit(f"unknown model arch {arch!r}; valid: {valid}")
    return _load(path)


# --------------------------------------------------------------------------
# augmentations and blur (FLSimCo Sec. 4 Step 2, Eq. 2)
# --------------------------------------------------------------------------

def _gray(x, precision):
    """Luma, the product of each pixel with the (0.299, 0.587, 0.114)
    weights."""
    g = jnp.dot(x, jnp.asarray(GRAY, x.dtype), precision=precision)
    return jnp.broadcast_to(g[..., None], x.shape)


def _maybe(key, p, fn, x):
    do = jax.random.bernoulli(key, p, (x.shape[0],))
    return jnp.where(do[:, None, None, None], fn(x), x)


def _jitter(key, x, precision, rng=0.4):
    b = x.shape[0]
    ks = jax.random.split(key, 4)
    br, ct, sat = [jax.random.uniform(k, (b, 1, 1, 1), minval=1 - rng,
                                      maxval=1 + rng) for k in ks[:3]]
    hue = jax.random.uniform(ks[3], (b, 1, 1), minval=-rng, maxval=rng)
    x = x * br
    mean = x.mean(axis=(1, 2, 3), keepdims=True)
    x = (x - mean) * ct + mean
    g = _gray(x, precision)
    x = g + (x - g) * sat
    theta = hue[..., None] * jnp.pi
    cos, sin = jnp.cos(theta), jnp.sin(theta)
    y = _gray(x, precision)[..., 0:1]
    r, gg, bb = x[..., 0:1], x[..., 1:2], x[..., 2:3]
    i = 0.596 * r - 0.274 * gg - 0.322 * bb
    q = 0.211 * r - 0.523 * gg + 0.312 * bb
    i2 = cos * i - sin * q
    q2 = sin * i + cos * q
    return jnp.concatenate([y + 0.956 * i2 + 0.621 * q2,
                            y - 0.272 * i2 - 0.647 * q2,
                            y - 1.106 * i2 + 1.703 * q2], axis=-1)


def pi1(key, x, precision):
    """Horizontal flip (p 0.5), then grayscale (p 0.2)."""
    k1, k2 = jax.random.split(key)
    x = _maybe(k1, 0.5, lambda im: im[:, :, ::-1, :], x)
    return _maybe(k2, 0.2, lambda im: _gray(im, precision), x)


def pi2(key, x, precision):
    """Colour jitter (range 0.4, p 0.8), then grayscale (p 0.4)."""
    k1, k2, k3 = jax.random.split(key, 3)
    jit = _jitter(k2, x, precision)
    do = jax.random.bernoulli(k1, 0.8, (x.shape[0],))
    x = jnp.where(do[:, None, None, None], jit, x)
    x = _maybe(k3, 0.4, lambda im: _gray(im, precision), x)
    return jnp.clip(x, 0.0, 1.0)


def motion_blur(images, v, camera_const, max_len=9):
    """Horizontal box blur whose length grows with velocity (Eq. 2)."""
    extent = jnp.clip(camera_const * v / 2.0, 1.0, float(max_len))
    idx = jnp.arange(max_len, dtype=jnp.float32)
    c = (max_len - 1) / 2.0
    w = jnp.where(jnp.abs(idx - c) <= (extent - 1.0) / 2.0 + 1e-6, 1.0, 0.0)
    w = jnp.maximum(w, jnp.where(idx == c, 1.0, 0.0))
    w = w / w.sum()
    pad = max_len // 2
    x = jnp.pad(images, ((0, 0), (0, 0), (pad, pad), (0, 0)), mode="edge")
    W = images.shape[2]
    out = x[:, :, 0:W, :] * w[0]
    for i in range(1, max_len):
        out = out + x[:, :, i:i + W, :] * w[i]
    return out


# --------------------------------------------------------------------------
# client step
# --------------------------------------------------------------------------

def dt_loss(q, k, tau_alpha, tau_beta, precision):
    """Eq. 6-8 over in-batch similarities; positives on the diagonal."""
    sim = jnp.dot(q, k.T, precision=precision)
    d = jnp.arange(q.shape[0])
    log_pa = jax.nn.log_softmax(sim / tau_alpha, axis=-1)[d, d]
    log_pb = jax.nn.log_softmax(sim / tau_beta, axis=-1)[d, d]
    w_a = 1.0 - jnp.exp(log_pa)
    w_b = 1.0 - jnp.exp(log_pb)
    weight = jax.lax.stop_gradient(w_b / jnp.maximum(w_a, 1e-8))
    return jnp.mean(-weight * log_pa)


@functools.lru_cache(maxsize=None)
def _client_step(model_key, opt_key, dtype_name, half_batch):
    """Jitted local training of one client: blur, `local_iters` SGD steps
    on the DT loss. Returns (new tree, mean loss, per-leaf grad norms of
    the first step)."""
    model = dict(model_key)
    lr_mom, wd, tau_a, tau_b, iters, cam, prec = opt_key
    dtype = jnp.dtype(dtype_name)
    precision = jax.lax.Precision[prec.upper()]
    forward = backbone(model["arch"]).forward

    def loss_fn(params, state, images, key):
        k1, k2 = jax.random.split(key)
        images = images.astype(dtype)
        v1 = pi1(k1, images, precision)
        v2 = pi2(k2, images, precision)
        q, st1 = forward(params, state, v1, model, precision)
        k, st2 = forward(params, st1, v2, model, precision)
        return dt_loss(q, k, tau_a, tau_b, precision), st2

    def step(tree, images, velocity, key, lr):
        if images.dtype == jnp.uint8:
            images = images.astype(jnp.float32) / 255.0
        images = motion_blur(images, velocity, cam)
        if half_batch:
            images = images[: images.shape[0] // 2]
        params, state = tree["params"], tree["state"]
        mom = jax.tree.map(jnp.zeros_like, params)
        losses, gnorms = [], None
        for k in jax.random.split(key, iters):
            (loss, new_state), g = jax.value_and_grad(
                loss_fn, has_aux=True)(params, state, images, k)
            if gnorms is None:
                gnorms = jax.tree.map(
                    lambda x: jnp.sqrt(jnp.sum(jnp.square(
                        x.astype(jnp.float32)))), g)
            g = jax.tree.map(lambda gi, p: gi + wd * p, g, params)
            mom = jax.tree.map(lambda m, gi: lr_mom * m + gi, mom, g)
            params = jax.tree.map(lambda p, m: (p - lr * m).astype(p.dtype),
                                  params, mom)
            state = new_state
            losses.append(loss)
        return ({"params": params, "state": state},
                jnp.mean(jnp.stack(losses)), gnorms)

    return jax.jit(step)


# --------------------------------------------------------------------------
# aggregation and codec
# --------------------------------------------------------------------------

def eq11_weights(blur):
    """Eq. 11, normalised to sum to one (DESIGN.md deviation 2)."""
    L = np.asarray(blur, np.float64)
    total = L.sum()
    w = (total - L) / max(total, 1e-12)
    s = w.sum()
    return w / s if s > 1e-12 else np.full_like(w, 1.0 / len(w))


@jax.jit
def _weighted_sum(trees, w):
    """sum_i w_i trees_i in the trees' own dtype."""
    out = jax.tree.map(lambda x: x * w[0].astype(x.dtype), trees[0])
    for i in range(1, len(trees)):
        out = jax.tree.map(lambda a, x: a + x * w[i].astype(x.dtype), out,
                           trees[i])
    return out


def weighted_sum(trees, w):
    return _weighted_sum(list(trees), jnp.asarray(w, jnp.float32))


@jax.jit
def _q8_roundtrip(tree, base, ef):
    """delta_int8 upload of one client: blockwise (256) symmetric int8 of
    (tree - base) + ef, scale = absmax / 127, round half to even; returns
    (reconstructed tree, new residual)."""
    leaves = jax.tree.leaves(tree)
    bases = jax.tree.leaves(base)
    flat = jnp.concatenate([(l - b).reshape(-1)
                            for l, b in zip(leaves, bases)])
    P = flat.shape[0]
    flat = jnp.pad(flat, (0, ef.shape[0] - P))
    y = (flat + ef).reshape(-1, Q8_BLOCK)
    scale = jnp.max(jnp.abs(y), axis=-1) * jnp.float32(1.0 / 127.0)
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    codes = jnp.clip(jnp.round(y * inv[:, None]), -127, 127)
    deq = codes * scale[:, None]
    new_ef = (y - deq).reshape(-1)
    deq = deq.reshape(-1)
    out, off = [], 0
    for l, b in zip(leaves, bases):
        out.append((b + deq[off:off + l.size].reshape(l.shape))
                   .astype(l.dtype))
        off += l.size
    return jax.tree.unflatten(jax.tree.structure(tree), out), new_ef


def q8_width(tree):
    P = sum(int(l.size) for l in jax.tree.leaves(tree))
    return -(-P // Q8_BLOCK) * Q8_BLOCK


# --------------------------------------------------------------------------
# schedule replay (the program's draw order)
# --------------------------------------------------------------------------

class Mobility:
    """Velocity law and ring road (FLSimCo Eq. 1-2, Table 1)."""

    def __init__(self, m: dict):
        self.v_min, self.v_max = m["v_min"], m["v_max"]
        self.mu, self.sigma = m["mu"], m["sigma"]
        self.camera_const = m["camera_const"]

    def _pdf(self, v):
        v = jnp.asarray(v, jnp.float32)
        z = (v - self.mu) / self.sigma
        base = jnp.exp(-0.5 * z * z) / (self.sigma * math.sqrt(2 * math.pi))
        lo = math.erf((self.v_min - self.mu) / (self.sigma * math.sqrt(2)))
        hi = math.erf((self.v_max - self.mu) / (self.sigma * math.sqrt(2)))
        inside = (v >= self.v_min) & (v <= self.v_max)
        return jnp.where(inside, base / (0.5 * (hi - lo)), 0.0)

    def sample(self, key, n):
        """Truncated Gaussian by inverse CDF on a 4097-point grid."""
        grid = jnp.linspace(self.v_min, self.v_max, 4097)
        cdf = jnp.cumsum(self._pdf(grid))
        cdf = cdf / cdf[-1]
        u = jax.random.uniform(key, (n,))
        idx = jnp.searchsorted(cdf, u)
        return grid[jnp.clip(idx, 0, grid.shape[0] - 1)]

    def blur(self, v):
        return self.camera_const * jnp.asarray(v, jnp.float32)


def cosine_lr(base, total, rnd):
    step = jnp.asarray(rnd, jnp.float32)
    t = jnp.clip(step / max(total, 1), 0.0, 1.0)
    return 0.5 * base * (1 + jnp.cos(jnp.pi * t))


def _batch_idx(rng, n, batch):
    return rng.choice(n, size=batch, replace=n < batch)


class Schedule:
    """Replays the per-round draws of one campaign from the FL seed."""

    def __init__(self, cfg: dict, vehicles_per_round: int, sizes, seed):
        self.cfg, self.n = cfg, vehicles_per_round
        self.sizes = sizes
        self.mob = Mobility(cfg["mobility"])
        self.rng = np.random.RandomState(seed)
        self.key = jax.random.PRNGKey(seed)
        topo = cfg["topology"]
        self.kind = topo["kind"]
        if self.kind == "handover":
            t = topo["kwargs"]
            self.R, self.range = t["n_rsus"], t["rsu_range"]
            self.road = self.R * self.range
            self.key, kp = jax.random.split(self.key)
            self.pos = np.asarray(jax.random.uniform(
                kp, (cfg["n_vehicles"],), minval=0.0, maxval=self.road))
            self.blur_sum = np.zeros(self.R)
            self.count = np.zeros(self.R)

    def _rsu(self, p):
        return (np.floor_divide(np.asarray(p), self.range)
                .astype(np.int64) % self.R)

    def next(self, rnd):
        cfg, n, batch = self.cfg, self.n, self.cfg["batch_size"]
        ids = self.rng.choice(cfg["n_vehicles"], size=n, replace=False)
        self.key, kv = jax.random.split(self.key)
        lr = cosine_lr(cfg["lr"], cfg["lr_horizon_rounds"], rnd)
        if self.kind != "handover":
            v = self.mob.sample(kv, n)
            self.key, *cks = jax.random.split(self.key, n + 1)
            idx = np.stack([_batch_idx(self.rng, self.sizes[c], batch)
                            for c in ids])
            return {"ids": ids, "idx": idx, "v": v, "lr": lr, "cks": cks}
        t = cfg["topology"]["kwargs"]
        fleet_v = self.mob.sample(kv, cfg["n_vehicles"])
        v = jnp.take(fleet_v, jnp.asarray(ids))
        self.key, *cks = jax.random.split(self.key, n + 1)
        down = self._rsu(self.pos[ids])
        idx = np.empty((n, batch), np.int64)
        for r in range(self.R):
            for i in np.where(down == r)[0]:
                idx[i] = _batch_idx(self.rng, self.sizes[ids[i]], batch)
        p = jnp.mod(jnp.asarray(self.pos, jnp.float32)
                    + jnp.asarray(fleet_v, jnp.float32)
                    * t["round_duration"], self.road)
        self.pos = np.asarray(p)
        up = self._rsu(self.pos[ids])
        stale = up != down
        blur = np.asarray(self.mob.blur(v))
        wmat = np.zeros((self.R, n))
        has_up = np.zeros(self.R, bool)
        for r in range(self.R):
            sel = np.where(up == r)[0]
            if not sel.size:
                continue
            w = eq11_weights(blur[sel]) * np.where(stale[sel],
                                                   t["stale_discount"], 1.0)
            if w.sum() <= 1e-12:
                continue
            wmat[r, sel] = w / w.sum()
            has_up[r] = True
            self.blur_sum[r] += float(blur[sel].sum())
            self.count[r] += sel.size
        sync_w = None
        if (rnd + 1) % t["sync_every"] == 0:
            mean_blur = np.where(
                self.count > 0, self.blur_sum / np.maximum(self.count, 1.0),
                float(self.mob.blur(self.mob.mu)))
            W = eq11_weights(mean_blur) * self.count
            sync_w = (W / W.sum() if W.sum() > 1e-12
                      else np.full(self.R, 1.0 / self.R))
            self.blur_sum = np.zeros(self.R)
            self.count = np.zeros(self.R)
        return {"ids": ids, "idx": idx, "v": v, "lr": lr, "cks": cks,
                "down": down, "wmat": wmat, "has_up": has_up,
                "sync_w": sync_w}


# --------------------------------------------------------------------------
# the campaign
# --------------------------------------------------------------------------

def initial_model(cfg: dict, tree0):
    """The model the check compares, at round 0: the RSU models under
    handover (each starts from `tree0`), else the global model."""
    if cfg["topology"]["kind"] == "handover":
        return [tree0] * cfg["topology"]["kwargs"]["n_rsus"]
    return tree0


def run(cfg: dict, traffic: dict, data, tree0, fl_seed: int, rounds: int,
        *, dtype=jnp.float32, fault=None, devices=None):
    """Follow the first `rounds` rounds of the cell from `tree0`.

    Returns {"losses": per-round mean loss, "models": the model after
    each round (the global model; for handover the list of RSU models),
    "velocities", "lrs": the schedule as drawn, "grad_norms": per-leaf
    norms of the cohort-mean gradient of round 1}. Client steps are
    spread over `devices` (one client at a time each).
    """
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; valid: {FAULTS}")
    devices = devices or [jax.devices()[0]]
    n = traffic["vehicles_per_round"]
    model_key = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                             for k, v in cfg["model"].items()))
    opt_key = (cfg["momentum"], cfg["weight_decay"], cfg["tau_alpha"],
               cfg["tau_beta"], cfg["local_iters"],
               cfg["mobility"]["camera_const"], cfg["matmul_precision"])
    step = _client_step(model_key, opt_key, jnp.dtype(dtype).name,
                        fault == "half_batch")
    sched = Schedule(cfg, n, [len(d) for d in data], fl_seed)
    topo = cfg["topology"]
    codec = traffic["codec"]
    home = devices[0]
    gtree = jax.device_put(jax.tree.map(lambda x: x.astype(dtype), tree0),
                           home)
    rsu = None
    if topo["kind"] == "handover":
        rsu = [gtree] * topo["kwargs"]["n_rsus"]
    ef = ([jnp.zeros((q8_width(tree0),), jnp.float32)] * n
          if codec == "delta_int8" else None)
    losses, vels, lrs, grad_norms, models = [], [], [], None, []
    for rnd in range(rounds):
        s = sched.next(rnd)
        vels.append(np.asarray(s["v"]).tolist())
        lrs.append(float(s["lr"]))
        bases = ([rsu[d] for d in s["down"]] if rsu is not None
                 else [gtree] * n)
        outs = []
        for i, c in enumerate(s["ids"]):
            dev = devices[i % len(devices)]
            outs.append(step(jax.device_put(bases[i], dev),
                             jax.device_put(data[c][s["idx"][i]], dev),
                             jax.device_put(s["v"][i], dev),
                             jax.device_put(s["cks"][i], dev),
                             jax.device_put(s["lr"], dev)))
        trees = [jax.device_put(o[0], home) for o in outs]
        losses.append(float(np.mean([float(o[1]) for o in outs])))
        if grad_norms is None:
            grad_norms = jax.tree.map(
                lambda *g: float(np.mean([float(x) for x in g])),
                *[o[2] for o in outs])
        if codec == "delta_int8":
            for i in range(n):
                trees[i], ef[i] = _q8_roundtrip(trees[i], bases[i], ef[i])
        elif codec != "identity":
            raise ValueError(f"reference has no codec {codec!r}")
        blur = np.asarray(sched.mob.blur(s["v"]))
        if topo["kind"] == "single":
            gtree = weighted_sum(trees, eq11_weights(blur))
        elif topo["kind"] == "multi":
            R = topo["kwargs"]["n_rsus"]
            sels = [np.where(np.arange(n) % R == r)[0] for r in range(R)]
            level1 = [weighted_sum([trees[i] for i in sel],
                                   eq11_weights(blur[sel])) for sel in sels]
            W = eq11_weights([blur[sel].mean() for sel in sels])
            W = W * np.array([len(sel) for sel in sels])
            gtree = (level1[0] if fault == "no_exchange"
                     else weighted_sum(level1, W / W.sum()))
        else:
            for r in range(len(rsu)):
                if s["has_up"][r]:
                    rsu[r] = weighted_sum(trees, s["wmat"][r])
            if s["sync_w"] is not None:
                gtree = weighted_sum(rsu, s["sync_w"])
                rsu = [gtree] * len(rsu)
        models.append(jax.device_get(rsu if rsu is not None else gtree))
    return {"losses": losses, "models": models,
            "velocities": vels, "lrs": lrs, "grad_norms": grad_norms}
