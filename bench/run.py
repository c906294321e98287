#!/usr/bin/env python3
"""Run one benchmark cell once; see ``bench/harness.py``.

    python3 bench/run.py --workload vgg16.offline --seed 7 --seconds 10 --trace 0

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""
import time

T0 = time.perf_counter()  # set-up is timed from here

import os  # noqa: E402
import sys  # noqa: E402

_BENCH = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_BENCH)
# the program's compile cache, if it sets one, is the benchmark's
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_BENCH, ".jax_cache")
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t0=T0))
