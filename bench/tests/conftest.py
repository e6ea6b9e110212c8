"""Tests of the benchmark harness. CPU only: nothing here describes a TPU
or loads libtpu. The harness modules sit in bench/, the program in src/."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
