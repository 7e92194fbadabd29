"""Benchmark harness entry (deliverable d) — one benchmark per paper
table/figure. ``python -m benchmarks.run [--scale small|large]``.

  Table 3  -> partitioner_metrics     Fig 4 -> cc_partitioner_exec
  Fig 5    -> strong_scaling          Table 4/Fig 6-7 -> sssp_variants
  Fig 8    -> breakdown               Fig 9 -> weak_scaling
  §8.5 trillion-edge claim -> trillion_dryrun (compile-only, if artifact
  present)

Results land in results/bench/*.json; tables print to stdout.
"""
from __future__ import annotations

import argparse
import time
import traceback

from benchmarks import (algo_suite, breakdown, cc_partitioner_exec,
                        kernel_roofline, partitioner_metrics, sssp_variants,
                        strong_scaling, trillion_dryrun, weak_scaling)
from repro.caches import enable_compile_cache

SUITES = [
    ("partitioner_metrics", partitioner_metrics.run),
    ("cc_partitioner_exec", cc_partitioner_exec.run),
    ("strong_scaling", strong_scaling.run),
    ("sssp_variants", sssp_variants.run),
    ("breakdown", breakdown.run),
    ("weak_scaling", weak_scaling.run),
    ("kernel_roofline", kernel_roofline.run),
    ("algo_suite", algo_suite.run),
    ("trillion_dryrun", trillion_dryrun.run),
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="small", choices=["small", "large"])
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    failures = []
    for name, fn in SUITES:
        if args.only and name != args.only:
            continue
        t0 = time.time()
        try:
            fn(args.scale) if name != "trillion_dryrun" else fn()
            print(f"[bench ok] {name} ({time.time()-t0:.1f}s)", flush=True)
        except Exception:
            failures.append(name)
            print(f"[bench FAIL] {name}\n{traceback.format_exc()[-1500:]}",
                  flush=True)
    if failures:
        raise SystemExit(f"failed: {failures}")
    print("all benchmarks passed")


if __name__ == "__main__":
    main()
