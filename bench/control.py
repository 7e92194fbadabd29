"""The control: a result that breaks a stated guarantee, put in the
program's place, must come out not correct.

    PYTHONPATH=src python -m bench.control --workload <cell> --seeds 1 2 3

For a traversal mix the control is the reference computed in bfloat16, the
precision below the float32 the configuration states (``bf16_distances``):
BFS levels survive it, SSSP distances must not (``sssp_rel_err``). For the
streaming mix it is a stale read: each answer is the reference before the
iteration's insert batch, against the configuration's "a query answers over
every edge flushed before it" (``cc_label_diff``). The graph, the keys and
the batches are the ones a run with the same seed draws, and the answers go
through ``harness.check`` and ``harness.verdict`` as a run's do, so the
control must read ``correct`` false. The benchmark's own runs never run
this. Prints one JSON line per seed with the readings, the failed answers
and ``correct``.
"""
from __future__ import annotations

import json
import sys

import jax
import numpy as np

from bench import graph500, harness, reference


def control_run(cell: harness.Cell, seed: int, answers: int) -> dict:
    """``answers`` control answers in the place of the program's, put
    through ``harness.check`` as a run's answers are: the readings, the
    failed answers and ``correct``."""
    gen = cell.config["generator"]
    graph = graph500.kronecker_edges(gen["scale"], gen["edge_factor"], seed)
    n, lo, hi, w, _ = graph
    drv = harness.Driver(cell.traffic, cell.config, graph, seed)
    queries = []

    def answer(program, key, batches, got):
        queries.append(harness.Query(program, key, batches, 0.0, None, None,
                                     0, 0.0, answer=got))

    steps = [s for s in cell.traffic["iteration"] if s["op"] == "query"]
    src, dst, wt = graph500.both_directions(lo, hi, w)
    if steps[0]["program"] == "ConnectedComponents":
        ref = reference.Reference(n, src, dst, wt)
        labels = ref.cc()
        for i in range(answers):
            u, v, _ = graph500.rmat_batch(drv.batch_rng, drv.scale, drv.perm,
                                          int(cell.traffic["iteration"][0]
                                              ["adds"]))
            drv.batches.append((u, v))
            answer("ConnectedComponents", None, i + 1, labels)   # stale
            labels = reference.merge_components(labels, u, v)
    else:
        s32, d32 = src.astype(np.int32), dst.astype(np.int32)
        for i in range(answers):
            prog = steps[i % len(steps)]["program"]
            key = drv.source("key")
            answer(prog, key, 0, reference.bf16_distances(
                n, s32, d32, wt, key, prog == "BFS"))
    del src, dst, wt
    run = harness.Run(cell, 0.0, 0.0, 0.0, 0.0, queries, 0, 0, drv.batches)
    readings, failed = harness.check(run, graph, seed)
    return {"readings": readings, "failed": failed,
            "correct": harness.verdict(readings, failed,
                                       cell.config["limits"])}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--answers", type=int, default=6)
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    harness.configure_caches()
    for seed in args.seeds:
        out = control_run(cell, seed, args.answers)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "device": jax.devices()[0].device_kind, **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
