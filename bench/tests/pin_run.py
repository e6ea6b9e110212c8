"""The plain reference of one cell at the CPU size (tiny.py), two rounds
from one seed, on two CPU cores; prints SHA-256 digests of the image pool,
the weights, each round's model and round 1's gradient norms, and the
losses, as JSON.

    JAX_PLATFORMS=cpu python bench/tests/pin_run.py CELL

XLA's CPU code splits a convolution by the threads it has, and the split
changes the float32 rounding: two cores, whichever they are, give the
same bytes whatever the machine's core count. The affinity must be set
before JAX starts, so the test runs this file in a subprocess.
"""
import hashlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [HERE, BENCH, os.path.join(os.path.dirname(BENCH), "src")]

SEED = 2**33 + 5
ROUNDS = 2


def digest(tree) -> str:
    import jax
    import numpy as np
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return h.hexdigest()


def main(workload):
    os.sched_setaffinity(0, sorted(os.sched_getaffinity(0))[:2])
    import generate
    import harness
    import reference
    import tiny

    c = harness.cell(harness.benchmark(), workload)
    cfg = {**c["cfg"], **tiny.CFG}
    traffic = {**c["traffic"], **tiny.TRAFFIC}
    s = generate.seeds(SEED)
    data = generate.images(cfg, s["data"])
    tree0 = generate.weights(cfg, s["weights"])
    ref = reference.run(cfg, traffic, data, tree0, s["fl"], ROUNDS)
    print(json.dumps({"images": digest(data), "weights": digest(tree0),
                      "losses": [repr(x) for x in ref["losses"]],
                      "models": [digest(m) for m in ref["models"]],
                      "grad_norms": digest(ref["grad_norms"])}))


if __name__ == "__main__":
    main(sys.argv[1])
