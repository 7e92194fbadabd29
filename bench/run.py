#!/usr/bin/env python3
"""DRONE's benchmark of record: one cell, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and metrics are listed in
``BENCHMARK.json`` at the root of the checkout; ``bench/harness.py`` says how
a run goes. The last line of standard output is the result's JSON object;
the numbers compared with the reference, each with its limit, are the last
lines of standard error. Exits 2, printing no result, where JAX finds no TPU
or fewer chips than the cell asks for.
"""
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

if __name__ == "__main__":
    from bench.harness import main
    sys.exit(main(t_start=T_START))
