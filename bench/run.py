#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip it is started on.

    python3 bench/run.py --workload sf_q19.uniform_ugal_l --seed 7 \
        --seconds 25 --trace 0

Loads the cell's configuration and traffic mix, builds and warms up the
simulator (set-up), makes whole calls of its entry point for
`--seconds` (the window), checks one answer against the plain reference
in `bench/reference`, and prints one JSON line last.  `--trace 1` traces
one call with the profiler and reports the per-layer metrics instead.
It exits non-zero, printing no result, where JAX finds no TPU.
"""

import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT                  # the checkout, not bench/, comes first
sys.path.insert(1, os.path.join(ROOT, "src"))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
