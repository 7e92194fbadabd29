"""BENCHMARK.json against the harness: every cell finds its configuration,
its mix and its metric readers by name; the peaks table refuses an unknown
device; the measurement path refuses to run off a TPU, and without the
program beside it."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness, peaks

ROOT = harness.ROOT
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_resolves_by_name(workload):
    cell = harness.resolve(workload)
    w = {x["name"]: x for x in SPEC["workloads"]}[workload]
    assert cell.chips == w["chips"] == cell.config["chips"]
    assert os.path.isfile(os.path.join(ROOT, "bench", "traffic",
                                       f"{w['traffic']}.json"))
    e2e = [m[0] for m in cell.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for _, _, read in cell.end_to_end + cell.per_layer:
        assert callable(read)


def test_spec_shape():
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [c["name"] for c in SPEC["configs"]]
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/configs/")
        assert json.load(open(os.path.join(ROOT, c["file"])))["name"] == \
            c["name"]
        assert any(w["config"] == c["name"] for w in SPEC["workloads"])
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for w in SPEC["workloads"]:
        assert w["config"] in names and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    everything = names + CELLS + [m["name"] for m in
                                  SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(everything)) == len(everything)
    assert all(NAME.match(n) for n in everything)


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v99 imaginary")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_measurement_path_exits_nonzero_off_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "{" not in p.stdout


def test_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
