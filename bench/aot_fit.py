"""Compile each cell's scan round program for a described TPU v5e, no chip.

    JAX_PLATFORMS=cpu python3 bench/aot_fit.py [--workload NAME] [--batch B]

For every cell of BENCHMARK.json (or the one named), builds the cell's
`Scenario` at its real sizes on the CPU, plans one chunk, and compiles
the campaign's scan program for one chip of a described `v5e:2x2`, or
for its (pod 2, data 2) mesh when the cell asks for four chips. Prints
`memory_analysis()` per device against the chip's 15.75 GiB, and
whether the Pallas kernels are in the program. `--batch` overrides the
configuration's batch size, to find the largest that fits.

The program decides its kernel backend and its mesh from the devices it
sees, which here are CPUs; this script points both at the described TPU
(`kernels.ops.on_tpu` and `MultiRSU.resolve_mesh`). It is a script, not a
test: one compile of the ResNet-18 round program takes minutes here.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]
os.environ["TPU_LOG_DIR"] = os.path.join(BENCH, "out", "tpu_logs")

HBM_LIMIT = 15.748 * 2**30     # bytes_limit a v5e reports (PR 11)


def fit(workload: str, batch: int | None, topo) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    import generate
    import harness
    from repro.core import engine, topology
    from repro.kernels import ops

    c = harness.cell(harness.benchmark(), workload)
    cfg, traffic = dict(c["cfg"]), c["traffic"]
    if batch:
        cfg["batch_size"] = batch
    chips = c["workload"]["chips"]
    if chips == 4:
        import numpy as np
        mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("pod", "data"))
        topology.MultiRSU.resolve_mesh = lambda self, cfg_: mesh
        sharding = NamedSharding(mesh, PartitionSpec())
    else:
        sharding = jax.sharding.SingleDeviceSharding(topo.devices[0])
    ops.on_tpu = lambda: True
    engine.reset_engine_caches()

    s = generate.seeds(0)
    sc = harness.scenario(cfg, traffic, generate.images(cfg, s["data"]),
                          generate.weights(cfg, s["weights"]), s["fl"])
    state = sc.init_state()
    xs_list = engine._plan_chunk(state, sc, traffic["chunk_rounds"])[0]
    xs = jax.tree.map(lambda *ls: jnp.stack(ls), *xs_list)
    n, maxlen = len(sc.data), max(len(d) for d in sc.data)
    dstack = jax.ShapeDtypeStruct((n, maxlen) + tuple(cfg["image_shape"]),
                                  jnp.float32, sharding=sharding)
    spec = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,  # noqa: E731
                                          sharding=sharding)
    carry = jax.tree.map(spec, engine._carry_of(state, sc))
    xs = jax.tree.map(spec, xs)
    t = time.perf_counter()
    compiled = engine.campaign_callables(sc)["scan"].lower(
        dstack, carry, xs).compile()
    secs = time.perf_counter() - t
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    return {"workload": workload, "batch": cfg["batch_size"],
            "compile_s": round(secs, 1),
            "args_gib": ma.argument_size_in_bytes / 2**30,
            "out_gib": ma.output_size_in_bytes / 2**30,
            "temp_gib": ma.temp_size_in_bytes / 2**30,
            "need_gib_per_chip": need / 2**30,
            "fits": need <= HBM_LIMIT,
            "pallas": "tpu_custom_call" in compiled.as_text()}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--batch", type=int)
    args = ap.parse_args(argv)
    from jax.experimental import topologies

    import harness
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = ([args.workload] if args.workload else
             [w["name"] for w in harness.benchmark()["workloads"]])
    for name in names:
        try:
            print(fit(name, args.batch, topo), flush=True)
        except Exception as e:      # report every cell, then go on
            print({"workload": name, "batch": args.batch,
                   "error": f"{type(e).__name__}: {str(e)[:400]}"},
                  flush=True)


if __name__ == "__main__":
    main()
