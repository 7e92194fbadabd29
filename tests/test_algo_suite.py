"""The algorithm-suite differential tests — every registered algorithm
(tests/harness.py ALGOS) through every harness check family:

  oracle parity on drawn power-law graphs and the pathological zoo,
  cross-edge-backend equivalence, fresh-vs-incremental parity over
  randomized delta schedules, sim-vs-shard_map parity (subprocess), and
  the loud-failure gate for custom sweeps that never declared their
  supported edge backends.
"""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import harness
from harness import (ALGOS, AlgoCase, case_by_name, canonicalize,
                     check_backend_equivalence, check_fresh_vs_incremental,
                     check_oracle, harness_powerlaw, pathological_graphs)
from repro.algos import SSSP, LabelPropagation, brandes_betweenness
from repro.core import EngineConfig, partition_and_build, resolve_edge_backend, run_sim
from repro.core.api import VertexProgram
from repro.graphgen import powerlaw_graph

CASE_NAMES = [c.name for c in ALGOS]
MONOTONE = ["bfs", "msbfs", "lp", "kcore2"]
ZOO = pathological_graphs()


# --------------------------------------------------------------------------- #
# oracle parity: power-law draws + the pathological zoo
# --------------------------------------------------------------------------- #
@settings(max_examples=harness.MAX_EXAMPLES, deadline=None)
@given(st.integers(0, 10_000))
def test_oracle_powerlaw(seed):
    g = harness_powerlaw(160, seed)
    for case in ALGOS:
        check_oracle(case, g)


@pytest.mark.parametrize("zoo", [z[0] for z in ZOO])
@pytest.mark.parametrize("name", CASE_NAMES)
def test_oracle_zoo(name, zoo):
    g = dict(ZOO)[zoo]
    check_oracle(case_by_name(name), g, n_parts=2)


@pytest.mark.parametrize("name", ["bfs", "lp", "kcore2", "triangles"])
def test_oracle_vc_mode(name):
    """Vertex-centric mode (no local fixpoint) reaches the same answers."""
    check_oracle(case_by_name(name), harness_powerlaw(160, 7), mode="vc")


@pytest.mark.parametrize("part", ["rh-vc", "rh-ec"])
@pytest.mark.parametrize("name", ["bfs", "kcore2", "triangles"])
def test_oracle_other_partitioners(name, part):
    check_oracle(case_by_name(name), harness_powerlaw(160, 11), part=part)


# --------------------------------------------------------------------------- #
# edge-backend equivalence
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("name", CASE_NAMES)
def test_backend_equivalence(name):
    check_backend_equivalence(case_by_name(name), harness_powerlaw(160, 3))


# --------------------------------------------------------------------------- #
# fresh vs incremental over randomized delta schedules
# --------------------------------------------------------------------------- #
@settings(max_examples=1 if harness.FAST else 2, deadline=None)
@given(st.integers(0, 10_000))
def test_fresh_vs_incremental(seed):
    g = harness_powerlaw(160, 2)
    for name in MONOTONE:
        check_fresh_vs_incremental(case_by_name(name), g, seed=seed,
                                   n_chunks=2 if harness.FAST else 3)


def test_kcore_incremental_is_delete_polarity():
    assert case_by_name("kcore2").make(harness_powerlaw(60, 0))[0] \
        .warm_under == "deletes"
    assert case_by_name("bfs").make(harness_powerlaw(60, 0))[0] \
        .warm_under == "inserts"


# --------------------------------------------------------------------------- #
# betweenness end-to-end: three staged programs -> centrality scores
# --------------------------------------------------------------------------- #
def test_betweenness_end_to_end():
    g = harness_powerlaw(120, 5)
    pg = partition_and_build(g, 4, "cdbh")
    cfg = EngineConfig(mode="sc")

    def query(prog, params):
        res, _ = run_sim(prog, pg, params, cfg)
        fill = np.inf if prog.combiner == "min" else 0.0
        return pg.collect(res, fill=fill)

    pv = harness._pivots(g)
    out = brandes_betweenness(query, pv)
    lev_e, sig_e, dl_e = harness.brandes_oracle(g, pv)
    np.testing.assert_array_equal(out["levels"], lev_e)
    np.testing.assert_allclose(out["sigma"], sig_e, rtol=1e-5)
    np.testing.assert_allclose(out["delta"], dl_e, rtol=1e-4, atol=1e-4)
    not_pivot = np.arange(g.n_vertices)[:, None] != np.asarray(pv)[None, :]
    np.testing.assert_allclose(out["bc"], (dl_e * not_pivot).sum(1) / 2.0,
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------- #
# custom sweeps must declare their edge backends — satellite gate
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class _UnregisteredSweep(VertexProgram):
    """Overrides sweep() but never declares supports_edge_backends."""

    def sweep(self, sg, params, state, ec):
        return state, np.int32(0)


@dataclasses.dataclass
class _BogusBackends(VertexProgram):
    supports_edge_backends = ("coo", "pallas_ultra")

    def sweep(self, sg, params, state, ec):
        return state, np.int32(0)


def test_unregistered_custom_sweep_fails_loudly():
    with pytest.raises(ValueError, match="supports_edge_backends"):
        resolve_edge_backend(_UnregisteredSweep(), EngineConfig())


def test_unknown_declared_backend_fails_loudly():
    with pytest.raises(ValueError, match="pallas_ultra"):
        resolve_edge_backend(_BogusBackends(), EngineConfig())


def test_declared_backend_fallback():
    # LP declares ('coo',): a pallas request resolves there, never crashes
    prog = LabelPropagation(hops=3)
    cfg = EngineConfig(edge_backend="pallas_windows")
    assert resolve_edge_backend(prog, cfg) == "coo"
    # declarative programs still honour the request
    assert resolve_edge_backend(SSSP(), cfg) == "pallas_windows"


# --------------------------------------------------------------------------- #
# sim vs shard_map parity (fake host devices need a fresh process)
# --------------------------------------------------------------------------- #
SHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import numpy as np
from jax.sharding import Mesh

import harness
from repro.core import EngineConfig, run_shard_map, run_sim

g = harness.harness_powerlaw(160, 3)
pg = harness.build(g, 4, "cdbh")
mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("sub",))
for name in ("bfs", "lp", "kcore2", "triangles"):
    case = harness.case_by_name(name)
    prog, params = case.make(g)
    sim, _ = run_sim(prog, pg, params, EngineConfig(mode="sc"))
    res, st = run_shard_map(prog, pg, mesh, params,
                            EngineConfig(backend="shard_map",
                                         subgraph_axes=("sub",), mode="sc"))
    a = pg.collect(sim, fill=case.fill)
    b = pg.collect(np.asarray(res), fill=case.fill)
    assert case.compare(a, b), f"{name}: shard_map != sim"
print("ALGO_SHARD_OK")
"""


def test_shard_map_parity():
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(os.path.dirname(here), "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [src, here] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    res = subprocess.run([sys.executable, "-c", SHARD_SCRIPT],
                         capture_output=True, text=True, timeout=1200,
                         env=env)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "ALGO_SHARD_OK" in res.stdout
