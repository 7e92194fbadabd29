"""A small-scale rehearsal of each cell on the CPU agrees with the scipy
reference, and the control (bfloat16 distances; a stale read) does not."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import control, graph500, harness
from bench.tests.rehearse import X4, cell, rehearse

SEED = 2**31 + 12345


@pytest.mark.parametrize("workload", ["g500-s16.traverse",
                                      "g500-s16.stream"])
def test_rehearsal_agrees_with_reference(workload, monkeypatch):
    out = rehearse(monkeypatch, workload, SEED)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["checks"]["answers_checked"]["value"] >= 1
    cell = harness.resolve(workload)
    assert set(out["metrics"]) == {m[0] for m in cell.end_to_end}
    assert list(out)[-1] == "checks"


def test_rehearsal_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-m", "bench.tests.rehearse",
         X4, str(SEED)],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4


@pytest.mark.parametrize("workload,number", [
    ("g500-s16.traverse", "sssp_rel_err"),
    ("g500-s16.stream", "cc_label_diff")])
def test_control_fails_a_limit(workload, number):
    c = cell(workload)
    out = control.control_run(c, SEED, 4)
    assert out["readings"][number] > c.config["limits"][number], out
    assert not out["correct"] and out["failed"] >= 1


def test_search_keys_are_drawn_as_graph500_draws_them():
    c = cell("g500-s16.traverse")
    gen = c.config["generator"]
    graph = graph500.kronecker_edges(gen["scale"], gen["edge_factor"], SEED)
    n, lo, hi, _, _ = graph
    deg = np.bincount(lo, minlength=n) + np.bincount(hi, minlength=n)
    keys = harness.Driver(c.traffic, c.config, graph, SEED).keys
    assert len(keys) == len(set(keys)) == c.traffic["keys"]["count"]
    assert (deg[keys] >= 1).all()
    assert keys == harness.Driver(c.traffic, c.config, graph, SEED).keys
    assert keys != harness.Driver(c.traffic, c.config, graph, SEED + 1).keys
