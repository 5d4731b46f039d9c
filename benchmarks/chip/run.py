#!/usr/bin/env python3
"""Chip benchmark of this repository: one cell of ``BENCHMARK.json`` per run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the TPUs the cell asks
for; see ``chipbench/harness.py`` for what it prints.
"""
import time

T0 = time.monotonic()                    # set-up is timed from here

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t0=T0))
