"""Bring-up smoke: the FLSimCo training path on one TPU, at paper widths.

    python3 chip_smoke.py             # one chip (the default)
    python3 chip_smoke.py --chips 4   # the sharded MultiRSU path, 4 chips

One process drives the system through its user entry points (`Scenario`
-> `run_round` / `run_campaign`) with the paper's ResNet-18 (widths
64-512, 128-D projector, random weights from the seed) on 50k synthetic
32x32 images split IID over 95 vehicles, 5 vehicles per round:

  (a) a TPU must be the default device; there is no CPU branch;
  (b) the campaign's scan program is compiled first and its
      `memory_analysis()` must fit the chip's HBM;
  (c) eager rounds and a compiled 3-round campaign: finite losses, the
      same schedule, the fused `wagg` kernel in the round program, and
      the fused aggregate of a real cohort within float tolerance of
      the jnp tree path;
  (d) one `delta_int8` round: the q8 kernels on the chip, decode bitwise
      against the jnp reference.

`--chips 4` runs only `MultiRSU(n_rsus=2, mesh_aggregate=True)` on the
(2, 2) ("pod", "data") mesh against the same scenario at
`mesh_aggregate=False`, in the same process.

Any failed check raises, so the exit code is non-zero and the result
line is not printed. The last line of a passing run is one JSON object:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.comms import codecs  # noqa: E402
from repro.core import aggregation as agg  # noqa: E402
from repro.core import engine, topology  # noqa: E402
from repro.core.clients import CLIENT_UPDATES  # noqa: E402
from repro.core.scenario import (Scenario, run, run_campaign,  # noqa: E402
                                 run_round)
from repro.core.topology import MultiRSU  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.qdelta import BQ  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.resnet import WIDTHS  # noqa: E402

SEED = 0
N_PER_CLASS = 5000          # 10 classes -> 50k images (CIFAR-10 scale)
N_VEHICLES = 95             # paper Table 1
COHORT = 5                  # vehicles per round (paper Fig. 5)
PAPER_BATCH = 512           # paper Table 1
BATCH = 256
ROUNDS = 3
# the (2, 2)-mesh comparison vmaps all 8 clients on one chip for its
# host side, so 8 x 128 images stays under the 5 x 256 that fits
COHORT_4 = 8
BATCH_4 = 128
ROUNDS_4 = 2
BATCH_REASON = (
    "the scan round program for 5 vehicles x batch 512 needs 26.33 GiB "
    "of HBM (XLA's out-of-memory report when compiling it for a v5e, "
    "15.75 GiB usable), so the cohort runs at batch 256")


def require_tpu():
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX's default device "
                         f"is {d.platform!r} ({d.device_kind})")
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    return devs


def assert_kernel(hlo_text: str, what: str):
    if "tpu_custom_call" not in hlo_text:
        raise AssertionError(f"{what}: no tpu_custom_call (Pallas kernel) "
                             f"in the compiled program")
    print(f"{what}: tpu_custom_call present", flush=True)


def assert_fused_backends():
    """On TPU the 'auto' kernel dispatch must pick the compiled kernels,
    never interpret mode or the jnp reference."""
    wagg = agg._resolve_wagg_backend()
    if wagg != "fused" or not ops.on_tpu():
        raise AssertionError(f"wagg backend resolves to {wagg!r} on TPU")
    x = jax.ShapeDtypeStruct((2, BQ), jnp.float32)
    c = jax.ShapeDtypeStruct((2, BQ), jnp.int8)
    s = jax.ShapeDtypeStruct((2, 1), jnp.float32)
    assert_kernel(jax.jit(ops.q8_encode_flat).lower(x, x).as_text(),
                  "q8 encode (auto)")
    assert_kernel(jax.jit(ops.q8_decode_flat).lower(c, s).as_text(),
                  "q8 decode (auto)")


def check_fits(compiled, device):
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    limit = device.memory_stats()["bytes_limit"]
    print(f"scan program memory: args {ma.argument_size_in_bytes / 2**30:.3f}"
          f" GiB + out {ma.output_size_in_bytes / 2**30:.3f} GiB + temp "
          f"{ma.temp_size_in_bytes / 2**30:.3f} GiB = {need / 2**30:.3f} GiB "
          f"of {limit / 2**30:.3f} GiB", flush=True)
    if need > limit:
        raise AssertionError("the batch-256 scan program does not fit HBM")


def _max_abs_diff(a, b):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _assert_finite(losses, what):
    losses = np.asarray(losses, np.float64)
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"{what}: non-finite loss {losses}")


def paper_scenario(**over) -> Scenario:
    kw = dict(topology="single", aggregator="flsimco", client="dtssl",
              n_vehicles=N_VEHICLES, vehicles_per_round=COHORT,
              batch_size=BATCH, lr=0.9, partitioner="iid",
              n_per_class=N_PER_CLASS, data_seed=SEED, seed=SEED)
    kw.update(over)
    return Scenario(**kw)


def describe(sc: Scenario):
    t = time.perf_counter()
    data = sc.data
    tree = sc.init_tree()
    p = tree["params"]
    widths = [int(p["stem"].shape[-1])] + [
        int(p[f"s{i}b0"]["conv1"].shape[-1]) for i in range(len(WIDTHS))]
    n_params = sum(int(l.size) for l in jax.tree.leaves(tree))
    sizes = [len(d) for d in data]
    print(f"model: ResNet-18 widths {widths}, projector "
          f"{p['proj1'].shape[0]}->{p['proj2'].shape[1]}, {n_params} "
          f"parameters (params + BN state)", flush=True)
    print(f"data: {sum(sizes)} images 32x32x3 over {len(data)} vehicles "
          f"(IID, {min(sizes)}-{max(sizes)} each), built in "
          f"{time.perf_counter() - t:.1f} s", flush=True)
    if widths != [64, 64, 128, 256, 512]:
        raise AssertionError(f"not the paper widths: {widths}")


def compile_scan(sc, state, rounds):
    """Compile the campaign's scan program for `rounds` rounds exactly as
    `run_campaign` will call it (same callable, same argument placement),
    for its memory analysis, HLO and output shardings."""
    fns = engine.campaign_callables(sc)
    xs_list = engine._plan_chunk(state, sc, rounds)[0]
    xs = jax.tree.map(lambda *ls: jnp.stack(ls), *xs_list)
    dstack = engine._data_stack(sc)
    carry = engine._carry_of(state, sc)
    rep = engine._replicated(sc)
    if rep is not None:
        dstack, carry = jax.device_put((dstack, carry), rep)
    t = time.perf_counter()
    compiled = fns["scan"].lower(dstack, carry, xs).compile()
    print(f"scan program ({rounds} rounds) compile: "
          f"{time.perf_counter() - t:.2f} s", flush=True)
    return compiled


def one_chip(devs):
    assert_fused_backends()
    print(f"batch: {BATCH} (paper {PAPER_BATCH}): {BATCH_REASON}",
          flush=True)
    sc = paper_scenario()
    describe(sc)
    state0 = sc.init_state()

    if engine.resolve_mode("auto") != "scan":
        raise AssertionError("run_campaign's auto mode is not scan on TPU")
    compiled = compile_scan(sc, state0, ROUNDS)
    check_fits(compiled, devs[0])
    assert_kernel(compiled.as_text(), "scan round program (fused wagg)")
    del compiled

    # eager rounds: the first one compiles the cohort step
    t = time.perf_counter()
    st_e, rec0 = run_round(state0, sc)
    jax.block_until_ready(st_e.global_tree)
    print(f"eager run_round 1 (compile included): "
          f"{time.perf_counter() - t:.2f} s, loss {rec0['loss']!r}",
          flush=True)
    st_e, hist_e = run(sc, st_e, rounds=ROUNDS - 1)
    hist_e = [rec0] + hist_e

    # compiled campaign from the same state
    st_c, hist_c = run_campaign(sc, state0, rounds=ROUNDS)

    for name, hist in (("eager", hist_e), ("campaign", hist_c)):
        _assert_finite([r["loss"] for r in hist], name)
        print(f"{name} losses: {[r['loss'] for r in hist]}", flush=True)
    for re_, rc in zip(hist_e, hist_c):
        if {k: v for k, v in re_.items() if k != "loss"} != \
                {k: v for k, v in rc.items() if k != "loss"}:
            raise AssertionError(f"schedule differs: {re_} vs {rc}")
    for a, b in zip(jax.tree.leaves((st_e.key, st_e.host_rng, st_e.round)),
                    jax.tree.leaves((st_c.key, st_c.host_rng, st_c.round))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("eager and compiled schedules equal (velocities, lr, cohort and "
          "batch draws, key chain)", flush=True)

    # fused vs tree aggregation on one round's real stacked cohort
    cfg = sc.cfg
    rng, ids, vel, lr, _, cks = topology._sample_cohort(state0, sc)
    batches = topology._draw_batches(rng, sc, ids, vel)
    cohort, _ = CLIENT_UPDATES["dtssl"].run_cohort(
        cfg, state0.global_tree, None, batches, cks, lr)
    cohort = cohort.with_stats(velocities=vel,
                               blur=sc.mobility.blur_level(vel))
    fused = agg.AGGREGATORS["flsimco"](cohort, cfg)
    with agg.wagg_backend("tree"):
        tree_ref = agg.AGGREGATORS["flsimco"](cohort, cfg)
    for a, b in zip(jax.tree.leaves(fused), jax.tree.leaves(tree_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    print(f"fused wagg vs tree on the real cohort: max |diff| "
          f"{_max_abs_diff(fused, tree_ref)!r} (rtol 1e-5, atol 1e-6)",
          flush=True)

    # delta_int8: one eager round, then the codec on this cohort
    sc8 = paper_scenario(codec="delta_int8", data=sc.data,
                         global_tree=sc.init_tree())
    t = time.perf_counter()
    st8, rec8 = run(sc8, rounds=1)
    print(f"delta_int8 run_round (compile included): "
          f"{time.perf_counter() - t:.2f} s, loss {rec8[0]['loss']!r}",
          flush=True)
    _assert_finite([rec8[0]["loss"]], "delta_int8")
    codec = codecs.CODECS["delta_int8"]
    base = state0.global_tree
    payload, _ = codec.encode(cohort.trees, base, None)
    flat = codecs._q8_delta_rows(cohort.trees, base)
    codes_r, scales_r, _ = ref.q8_encode_ref(flat, jnp.zeros_like(flat), BQ)
    dec = ops.q8_decode_flat(payload["codes"], payload["scales"])
    dec_r = ref.q8_decode_ref(payload["codes"], payload["scales"], BQ)
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(dec_r))
    np.testing.assert_array_equal(np.asarray(payload["codes"]),
                                  np.asarray(codes_r))
    np.testing.assert_array_equal(np.asarray(payload["scales"]),
                                  np.asarray(scales_r))
    print(f"delta_int8 q8 kernels bitwise equal to the reference over "
          f"{flat.size} values: encode codes, encode scales, decode",
          flush=True)


def four_chips(devs):
    if len(devs) != 4:
        raise SystemExit(f"--chips 4 needs 4 devices, have {len(devs)}")
    assert_fused_backends()
    sc_m = paper_scenario(topology=MultiRSU(n_rsus=2, mesh_aggregate=True),
                          vehicles_per_round=COHORT_4, batch_size=BATCH_4)
    describe(sc_m)
    sc_h = paper_scenario(topology=MultiRSU(n_rsus=2, mesh_aggregate=False),
                          vehicles_per_round=COHORT_4, batch_size=BATCH_4,
                          data=sc_m.data, global_tree=sc_m.init_tree())
    mesh = sc_m.topology.resolve_mesh(sc_m.cfg)
    if dict(mesh.shape) != {"pod": 2, "data": 2}:
        raise AssertionError(f"cohort mesh {dict(mesh.shape)}, want 2x2")
    print(f"cohort mesh: {dict(mesh.shape)} over "
          f"{sorted(d.id for d in mesh.devices.flat)}", flush=True)

    # the campaign's own program runs the client blocks on 4 devices,
    # 2 vehicles each: the per-round losses leave its shard_mapped client
    # step row-sharded over the mesh
    compiled = compile_scan(sc_m, sc_m.init_state(), ROUNDS_4)
    ys_sharding = compiled.output_shardings[1]
    shape = (ROUNDS_4, COHORT_4)
    devices = ys_sharding.device_set
    block = ys_sharding.shard_shape(shape)
    print(f"campaign program client blocks: {len(devices)} devices, "
          f"losses {shape} in blocks of {block}", flush=True)
    if len(devices) != 4 or block != (ROUNDS_4, COHORT_4 // 4):
        raise AssertionError("the campaign does not split the cohort over "
                             "4 devices")
    del compiled

    results = {}
    for name, sc in (("sharded", sc_m), ("host", sc_h)):
        st, hist = run_campaign(sc, rounds=ROUNDS_4)
        print(f"{name}: run_campaign {ROUNDS_4} rounds, losses "
              f"{[r['loss'] for r in hist]}", flush=True)
        _assert_finite([r["loss"] for r in hist], name)
        results[name] = (st, hist)
    (st_m, h_m), (st_h, h_h) = results["sharded"], results["host"]
    for a, b in zip(h_m, h_h):
        if {k: v for k, v in a.items() if k != "loss"} != \
                {k: v for k, v in b.items() if k != "loss"}:
            raise AssertionError(f"schedule differs: {a} vs {b}")
    print(f"sharded vs host: max |diff| "
          f"{_max_abs_diff(st_m.global_tree, st_h.global_tree)!r}",
          flush=True)
    for a, b in zip(jax.tree.leaves(st_m.global_tree),
                    jax.tree.leaves(st_h.global_tree)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=1e-4)
    print("sharded vs host models within atol 2e-5 / rtol 1e-4", flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devs = require_tpu()
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        four_chips(devs)
    else:
        one_chip(devs)
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
