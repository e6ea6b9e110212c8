"""Benchmark entry point: one run of one cell, as BENCHMARK.json names it.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints the result object as the last line of stdout (see harness.py).
Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
# the compile cache lives in the checkout, whatever the machine sets
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ["TPU_LOG_DIR"] = os.path.join(BENCH, "out", "tpu_logs")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), t0=T0)
    for k, v in result["checks"].items():
        print(f"{k}: {v['value']!r} (limit {v['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))


if __name__ == "__main__":
    main()
