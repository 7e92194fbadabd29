"""Whole runs with the timed path broken underneath come out not correct:
for each fault a cell can have, ``correct`` reads false."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness
from bench.tests.rehearse import FAULTS, X4, rehearse

SEED = 2**31 + 777

CASES = [("g500-s16.traverse", f) for f in
         ("state_unchanged", "answer_altered", "exchange_left_out")] + \
        [("g500-s16.stream", f) for f in
         ("state_unchanged", "answer_altered", "exchange_left_out",
          "flush_unchanged", "half_batch")]


@pytest.mark.parametrize("workload,fault", CASES)
def test_fault_is_not_correct(workload, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = rehearse(monkeypatch, workload, SEED, seconds=1.0)
    assert not out["correct"], out["checks"]
    assert out["failed"] >= 1


def test_exchange_left_out_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-m", "bench.tests.rehearse",
         X4, str(SEED), "exchange_left_out"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert not out["correct"], out["checks"]
