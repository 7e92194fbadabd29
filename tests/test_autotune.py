"""edge_backend='auto' policy tests: calibration-cache determinism, the
mixed-density fixture where every backend wins at least one partition,
auto-vs-COO result parity, and the zero-retrace pin that in-bucket
streaming growth never flips a partition's resolved backend mid-session
(both engine backends — the shard_map half runs in a subprocess like every
multi-device test)."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.algos import PageRank, SSSP
from repro.analysis.sanitizer import retrace_guard
from repro.core import (EngineConfig, build_partitioned_graph,
                        partition_and_build, run_sim)
from repro.core import autotune
from repro.core.engine import (normalize_edge_backend,
                               resolve_partition_backends)
from repro.core.graph import Graph
from repro.graphgen import powerlaw_graph
from repro.session import GraphSession

PR_TOL = dict(rtol=1e-5, atol=1e-8)


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("DRONE_AUTOTUNE_DIR", str(tmp_path))


def _mixed_density_graph():
    """Three 256-vertex blocks — dense (~50%), mid (~6%), ultra-sparse
    (~100 edges) — each mapped to its own partition, so the modeled costs
    put a different winner on each: tiles (dense amortizes the fixed MXU
    tile traffic), windows (~8 B/edge beats COO's ~24), COO (the kernel
    coverage floors dominate a hundred edges)."""
    rng = np.random.default_rng(42)
    B = 256
    src, dst, part = [], [], []

    def block(lo, n_edges, pid):
        s = rng.integers(lo, lo + B, n_edges)
        d = rng.integers(lo, lo + B, n_edges)
        keep = s != d
        src.append(s[keep]); dst.append(d[keep])
        part.append(np.full(int(keep.sum()), pid, np.int64))

    block(0, int(0.50 * B * B), 0)      # dense
    block(B, int(0.06 * B * B), 1)      # mid
    block(2 * B, 100, 2)                # ultra-sparse
    src = np.concatenate(src); dst = np.concatenate(dst)
    part = np.concatenate(part)
    w = rng.random(src.size).astype(np.float32) + 0.1
    g = Graph(3 * B, src, dst, w)
    return g, build_partitioned_graph(g, part, 3)


# --------------------------------------------------------------------------- #
# calibration cache: deterministic replay
# --------------------------------------------------------------------------- #
def test_calibration_deterministic(tmp_path):
    t1 = autotune.calibrate()
    t2 = autotune.calibrate()
    assert t1.to_json() == t2.to_json(), \
        "same platform must produce a byte-identical calibration table"
    _, pg = _mixed_density_graph()
    lay = pg.ensure_edge_layouts()
    p1 = autotune.pick_backends(t1, pg, lay)
    p2 = autotune.pick_backends(t2, pg, lay)
    assert p1 == p2


def test_table_disk_roundtrip():
    t1 = autotune.get_table(force=True)
    path = autotune.table_path(t1.platform)
    assert os.path.exists(path)
    t2 = autotune.load_table(t1.platform)
    assert t2 is not None and t2.to_json() == t1.to_json()
    # a second get_table serves the cached file, not a fresh sweep
    t3 = autotune.get_table()
    assert t3.to_json() == t1.to_json()


def test_schema_mismatch_invalidates():
    t1 = autotune.get_table(force=True)
    raw = t1.to_json().replace(f'"version": {autotune.SCHEMA_VERSION}',
                               '"version": 999')
    with pytest.raises(ValueError):
        autotune.CalibrationTable.from_json(raw)


# --------------------------------------------------------------------------- #
# the acceptance fixture: every backend wins somewhere
# --------------------------------------------------------------------------- #
def test_mixed_density_picks_all_three_backends():
    _, pg = _mixed_density_graph()
    lay = pg.ensure_edge_layouts()
    cfg = EngineConfig(edge_backend="auto")
    asg = resolve_partition_backends(SSSP(), cfg, pg, lay=lay)
    assert len(asg) == pg.n_parts
    assert set(asg) == {"coo", "pallas_tiles", "pallas_windows"}, \
        f"auto must pick each backend on the mixed fixture, got {asg}"
    assert asg[0] == "pallas_tiles" and asg[2] == "coo", asg


def test_auto_matches_coo_and_bills_per_partition():
    g, pg = _mixed_density_graph()
    want, _ = run_sim(SSSP(), pg, {"source": 0}, EngineConfig())
    got, st = run_sim(SSSP(), pg, {"source": 0},
                      EngineConfig(edge_backend="auto"))
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    assert st.edge_backend == "auto"
    assert len(st.partition_edge_backends) == pg.n_parts
    assert set(st.partition_edge_backends) == {"coo", "pallas_tiles",
                                               "pallas_windows"}
    assert len(st.partition_tile_density) == pg.n_parts
    assert st.partition_tile_density[0] > st.partition_tile_density[2]
    assert st.backend_flops > 0

    want_pr, _ = run_sim(PageRank(tol=1e-7), pg,
                         {"n_vertices": g.n_vertices}, EngineConfig())
    got_pr, _ = run_sim(PageRank(tol=1e-7), pg,
                        {"n_vertices": g.n_vertices},
                        EngineConfig(edge_backend="auto"))
    np.testing.assert_allclose(np.asarray(want_pr), np.asarray(got_pr),
                               **PR_TOL)


def test_non_sweep_program_normalizes_to_coo():
    from repro.algos.mssp import make_mssp
    prog, _ = make_mssp([0, 5])
    eb, cfg = normalize_edge_backend(prog, EngineConfig(edge_backend="auto"))
    assert eb == "coo" and cfg.edge_backend == "coo"


# --------------------------------------------------------------------------- #
# zero-retrace pin: in-bucket growth never flips the resolved backend
# --------------------------------------------------------------------------- #
def test_auto_inbucket_flush_never_flips_sim():
    g = powerlaw_graph(900, seed=5, weighted=True).as_undirected()
    sess = GraphSession.from_graph(g, 4, "ebv",
                                   cfg=EngineConfig(edge_backend="auto"))
    _, st0 = sess.query(SSSP(), {"source": 0})
    asg0 = tuple(st0.partition_edge_backends)
    lay = sess.pg.edge_layouts
    caps = (lay.t_max, lay.b_max)
    rng = np.random.default_rng(7)
    s = rng.integers(0, g.n_vertices, 30)
    d = rng.integers(0, g.n_vertices, 30)
    keep = s != d
    sess.update(adds=(s[keep], d[keep],
                      np.ones(int(keep.sum()), np.float32)))
    sess.flush()
    assert (lay.t_max, lay.b_max) == caps, "in-bucket by design"
    with retrace_guard(label="auto: in-bucket flush requery"):
        _, st1 = sess.query(SSSP(), {"source": 0})
    assert tuple(st1.partition_edge_backends) == asg0, \
        "in-bucket growth flipped a pinned backend"
    assert st1.compile_time == 0.0
    assert sess.stats.cache_misses == 1


AUTO_SHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["DRONE_AUTOTUNE_DIR"] = os.environ["AUTOTUNE_TMP"]
import jax
import numpy as np
from jax.sharding import Mesh

from repro.algos import SSSP
from repro.analysis.sanitizer import retrace_guard
from repro.core import EngineConfig
from repro.graphgen import powerlaw_graph
from repro.session import GraphSession

g = powerlaw_graph(900, seed=5, weighted=True).as_undirected()
mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("sub",))
sess = GraphSession.from_graph(g, 4, "ebv", mesh=mesh,
                               cfg=EngineConfig(edge_backend="auto"))
res, st0 = sess.query(SSSP(), {"source": 0})
asg0 = tuple(st0.partition_edge_backends)
assert len(asg0) == 4, asg0

# reference: a simulator session over the IDENTICAL partitioning (same
# router, seed, policy) on the pure-COO path
ref = GraphSession.from_graph(g, 4, "ebv")
want, _ = ref.query(SSSP(), {"source": 0}, cfg=EngineConfig(
    edge_backend="coo"))
np.testing.assert_array_equal(np.asarray(want), np.asarray(res))

lay = sess.pg.edge_layouts
caps = (lay.t_max, lay.b_max)
rng = np.random.default_rng(7)
s = rng.integers(0, g.n_vertices, 30)
d = rng.integers(0, g.n_vertices, 30)
keep = s != d
sess.update(adds=(s[keep], d[keep], np.ones(int(keep.sum()), np.float32)))
sess.flush()
assert (lay.t_max, lay.b_max) == caps, "in-bucket by design"
with retrace_guard(label="auto/shard_map: in-bucket flush requery"):
    _, st1 = sess.query(SSSP(), {"source": 0})
assert tuple(st1.partition_edge_backends) == asg0, (asg0,
    st1.partition_edge_backends)
assert st1.compile_time == 0.0, st1.compile_time
print("AUTO_SHARD_OK")
"""


def test_auto_inbucket_flush_never_flips_shard_map(tmp_path):
    env = dict(os.environ, AUTOTUNE_TMP=str(tmp_path))
    res = subprocess.run([sys.executable, "-c", AUTO_SHARD_SCRIPT],
                         capture_output=True, text=True, timeout=1200,
                         env=env)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "AUTO_SHARD_OK" in res.stdout


def test_measured_cost_sweep_runs():
    """The on-chip calibration path (timed kernels) is only recorded on a
    TPU; here it must at least run every backend at one grid point — it
    once fed the layouts 1-D values and failed on its first chip run. Its
    timed calls copy nothing from the host: every upload is explicit and
    made once, before the clock starts."""
    src, dst = autotune._synthetic_edges(256, 0.01, 1)
    with jax.transfer_guard_host_to_device("disallow"):
        costs = autotune._measured_costs(
            autotune._point_units(256, src, dst), src, dst)
    assert set(costs) == {"coo", "pallas_tiles", "pallas_windows"}
    assert all(np.isfinite(c) and c > 0 for c in costs.values())
