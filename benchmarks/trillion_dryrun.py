"""Paper headline — trillion-edge capability. Compile-only proof on the
production mesh: the BSP CC superstep loop at 2^40 edges across 512 chips
(sharded SBS) must lower+compile and fit per-device HBM. Reads the JSON the
graph dry-run produced (or produces it)."""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import save, table


def run(scale: str = "small"):
    path = "results/dryrun/graph__trillion__cc__multipod.json"
    if not os.path.exists(path):
        subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun_graph", "--scale",
             "trillion", "--algo", "cc", "--mesh", "multipod"],
            check=False,
            # the parent has touched jax (and may hold the accelerator): the
            # compile-only child runs on the CPU's fake devices
            env=dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu"))
    if not os.path.exists(path):
        print("trillion dry-run artifact missing (run dryrun_graph)")
        return None
    rec = json.load(open(path))
    rows = [[rec["status"], rec.get("n_parts"),
             rec.get("meta", {}).get("e_max"),
             f"{rec.get('memory', {}).get('temp_size_in_bytes', 0)/2**30:.2f}GiB",
             f"{rec.get('walk', {}).get('collective_bytes_per_device', 0)/2**20:.1f}MiB"]]
    table("Trillion-edge capability (2^40 edges, 512 chips, compile-only)",
          ["status", "subgraphs", "edges/part", "temp/dev", "coll bytes/dev"],
          rows)
    return save("trillion_dryrun", rec)


if __name__ == "__main__":
    run()
