"""The control and the planted faults, at a cell's own size, on the chip.

    python3 bench/control.py --workload NAME --seeds 1,2,3 [--variants ...]

For each seed: the plain float32 reference follows the cell's first
chunk, then each variant of the reference is put in the program's place
and compared with it by the cell's own numbers (check.py):

  bf16            the control: the reference computed in bfloat16, the
                  nearest precision below the float32 the configuration
                  states
  half_batch      each vehicle trains on half its batch, the loss the
                  mean over the rest
  no_exchange     (mesh cell) the region merge left out: the global
                  model is the first RSU's

A state left unchanged reads 1 on `change_gap` by construction and
needs no run. The benchmark's own runs never run this script; its
readings set the upper end of each limit (PERF.md). One JSON line per
seed and variant; needs the same chips as the cell.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

VARIANTS = ("bf16", "half_batch", "no_exchange")


def readings(workload: str, seed: int, variants, devices):
    """{variant: check.numbers(...)} for one seed."""
    import jax.numpy as jnp

    import check
    import generate
    import harness
    import reference

    c = harness.cell(harness.benchmark(), workload)
    cfg, traffic = c["cfg"], c["traffic"]
    s = generate.seeds(seed)
    data = generate.images(cfg, s["data"])
    tree0 = generate.weights(cfg, s["weights"])
    rounds = traffic["chunk_rounds"]
    t = time.perf_counter()
    ref = reference.run(cfg, traffic, data, tree0, s["fl"], rounds,
                        devices=devices)
    out = {"reference_s": time.perf_counter() - t}
    for v in variants:
        t = time.perf_counter()
        got = reference.run(
            cfg, traffic, data, tree0, s["fl"], rounds, devices=devices,
            dtype=jnp.bfloat16 if v == "bf16" else jnp.float32,
            fault=None if v == "bf16" else v)
        nums = check.numbers(got, ref, reference.initial_model(cfg, tree0))
        nums["seconds"] = time.perf_counter() - t
        out[v] = nums
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="bf16,half_batch")
    args = ap.parse_args(argv)
    variants = args.variants.split(",")
    for v in variants:
        if v not in VARIANTS:
            raise SystemExit(f"unknown variant {v!r}; valid: {VARIANTS}")
    import harness
    c = harness.cell(harness.benchmark(), args.workload)
    devs = harness.require_chip(c["workload"]["chips"])
    for seed in (int(x) for x in args.seeds.split(",")):
        got = readings(args.workload, seed, variants, list(devs))
        print(json.dumps({"workload": args.workload, "seed": seed, **got}),
              flush=True)


if __name__ == "__main__":
    main()
