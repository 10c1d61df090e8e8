#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once on the chip.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <run_seconds> --trace <0|1> [--control 1]

From the root of a checkout.  The last line of stdout is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, and with
``--trace 1`` ``breakdown``; the compared numbers and their limits come
last, under ``checks``).  Without a TPU, or with fewer chips than the
cell asks for, it exits 1 and prints no result.
"""
import os
import sys
import time

T_PROCESS = time.perf_counter()

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    from harness.runner import main

    sys.exit(main(sys.argv[1:], t_process=T_PROCESS))
