"""edge_backend='auto' policy tests: calibration-cache determinism per
sweep key, the mixed-density fixture where every backend wins at least one
partition, auto-vs-COO result parity, one table per sweep key (BFS, SSSP
and CC pick and pin separately), the measured path on the engine's own
products, and the zero-retrace pin that in-bucket streaming growth never
flips a partition's resolved backend mid-session (both engine backends —
the shard_map half runs in a subprocess like every multi-device test)."""
import glob
import json
import os
import subprocess
import sys
import types

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.algos import BFS, PageRank, SSSP, ConnectedComponents
from repro.analysis.sanitizer import retrace_guard
from repro.core import (EngineConfig, build_partitioned_graph,
                        partition_and_build, run_sim)
from repro.core import api, autotune, engine
from repro.core.engine import (_device_subgraph, make_sim_runner,
                               normalize_edge_backend,
                               resolve_partition_backends)
from repro.core.graph import Graph
from repro.graphgen import powerlaw_graph
from repro.session import GraphSession

PR_TOL = dict(rtol=1e-5, atol=1e-8)
HERE = os.path.dirname(os.path.abspath(__file__))


def _key(program=None, engine_backend="sim"):
    return autotune.sweep_key(program or SSSP(), engine_backend)


def _favouring(key, backend):
    """A hand-made table of ``key`` under which ``backend`` ('coo' or
    'pallas_windows') is the cheaper one for any partition."""
    cheap, dear = 1e-9, 1e-6
    coo = cheap if backend == "coo" else dear
    win = dear if backend == "coo" else cheap
    return autotune.CalibrationTable(
        key=key, source="modeled", points=[],
        unit_costs=dict(coo_edge=coo, coo_vertex=0.0, tile=1.0,
                        win_block=0.0, win_window=0.0, win_edge=win))


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
    t1 = autotune.calibrate(_key())
    t2 = autotune.calibrate(_key())
    assert t1.to_json() == t2.to_json(), \
        "same platform must produce a byte-identical calibration table"
    _, pg = _mixed_density_graph()
    lay = pg.ensure_edge_layouts()
    p1 = autotune.pick_backends(t1, pg, lay)
    p2 = autotune.pick_backends(t2, pg, lay)
    assert p1 == p2


def test_table_disk_roundtrip():
    t1 = autotune.get_table(_key(), force=True)
    path = autotune.table_path(t1.key)
    assert os.path.exists(path)
    t2 = autotune.load_table(t1.key)
    assert t2 is not None and t2.to_json() == t1.to_json()
    # a second get_table serves the cached file, not a fresh sweep
    t3 = autotune.get_table(_key())
    assert t3.to_json() == t1.to_json()


def test_schema_mismatch_invalidates():
    t1 = autotune.get_table(_key(), force=True)
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


@pytest.mark.parametrize("engine_backend", ["sim", "shard_map"])
def test_measured_cost_sweep_runs(monkeypatch, engine_backend):
    """The on-chip calibration path (timed kernels) is only recorded on a
    TPU; here, under a stubbed clock, it must run every backend at one
    small grid point through the engine's own product functions, in the
    form the key's runner executes them — it once fed the layouts 1-D
    values and failed on its first chip run. Its timed calls copy nothing
    from the host: every upload is explicit and made once, before the
    clock starts."""
    called = []

    def spy(mod, name):
        real = getattr(mod, name)

        def wrapper(*a, **k):
            called.append((name, np.ndim(a[1] if name != "coo_semiring_product"
                                         else a[2])))
            return real(*a, **k)
        monkeypatch.setattr(mod, name, wrapper)

    spy(engine, "_window_product")
    spy(engine, "_tile_product")
    spy(api, "coo_semiring_product")
    ticks = iter(range(10**6))
    monkeypatch.setattr(autotune, "time", types.SimpleNamespace(
        perf_counter=lambda: float(next(ticks))))
    edges = autotune._grid_edges(256, 2048, 1)
    key = _key(BFS(), engine_backend)
    with jax.transfer_guard_host_to_device("disallow"):
        costs = autotune._measured_costs(256, edges, key)
    assert set(costs) == {"coo", "pallas_tiles", "pallas_windows"}
    # one stubbed tick per timed call, spread over the stacked partitions
    parts = autotune.GRID_PARTS if engine_backend == "sim" else 1
    assert all(c == pytest.approx(1.0 / parts) for c in costs.values())
    names = {n for n, _ in called}
    assert names == {"_window_product", "_tile_product",
                     "coo_semiring_product"}
    # stacked [P, v, K] values on the simulator, [v, K] per partition on
    # shard_map (the vmapped COO product sees one partition either way)
    want = 3 if engine_backend == "sim" else 2
    assert {d for n, d in called if n != "coo_semiring_product"} >= {want}


# --------------------------------------------------------------------------- #
# one table per sweep key
# --------------------------------------------------------------------------- #
def test_sweep_keys_differ_per_program():
    bfs, sssp, cc = (_key(p) for p in (BFS(), SSSP(),
                                         ConnectedComponents()))
    assert len({bfs, sssp, cc, _key(BFS(), "shard_map")}) == 4
    assert (bfs.semiring, bfs.edge_values, bfs.dtype) == \
        ("min_plus", "one", "float32")
    assert (cc.edge_values, cc.dtype) == ("zero", "int32")
    assert len({autotune.table_path(k) for k in (bfs, sssp, cc)}) == 3


def test_opposite_tables_give_bfs_and_cc_different_picks():
    """The same graph, two sweep keys whose cached tables rank the backends
    the other way round: each program follows its own table."""
    _, pg = _mixed_density_graph()
    cfg = EngineConfig(edge_backend="auto")
    autotune.save_table(_favouring(_key(BFS()), "pallas_windows"))
    autotune.save_table(_favouring(_key(ConnectedComponents()), "coo"))
    assert resolve_partition_backends(BFS(), cfg, pg) == \
        ("pallas_windows",) * pg.n_parts
    assert resolve_partition_backends(ConnectedComponents(), cfg, pg) == \
        ("coo",) * pg.n_parts


def test_v2_cache_file_is_recalibrated():
    key = _key()
    kind = key.platform.lower()
    v2 = dict(version=2, platform=key.platform, source="measured",
              points=[], unit_costs=_favouring(key, "coo").unit_costs)
    os.makedirs(autotune.cache_dir(), exist_ok=True)
    old = os.path.join(autotune.cache_dir(), f"autotune_{kind}_v2.json")
    for path in (old, autotune.table_path(key)):
        with open(path, "w") as f:
            json.dump(v2, f)
    assert autotune.load_table(key) is None
    t = autotune.get_table(key)
    assert t.source == "modeled" and t.points
    with open(autotune.table_path(key)) as f:
        assert json.load(f)["version"] == autotune.SCHEMA_VERSION == 3


def test_calibration_grid_at_engine_sizes():
    """Vertex slots 4,096-65,536, edges ~32 k-2 M, skewed destination
    degrees; the dense tiles of the large points do not fit and cost
    inf, and never feed the tile fit."""
    nvs = [nv for nv, _ in autotune.GRID]
    nes = [ne for _, ne in autotune.GRID]
    assert min(nvs) == 4096 and max(nvs) == 65536
    assert min(nes) == 32768 and max(nes) == 2097152
    src, dst, _ = autotune._grid_edges(4096, 32768, 3)
    deg = np.bincount(dst, minlength=4096)
    assert deg.max() > 20 * max(deg.mean(), 1)         # a power-law head
    assert np.all(np.diff(dst) >= 0)                   # dst-sorted
    t = autotune.calibrate(_key())
    costs = [p["cost_tiles"] for p in t.points]
    assert np.isinf(costs[-1]) and np.isfinite(costs[0])
    assert np.isfinite(t.unit_costs["tile"])


def test_tiles_priced_out_past_the_budget(monkeypatch):
    _, pg = _mixed_density_graph()
    lay = pg.ensure_edge_layouts()
    cfg = EngineConfig(edge_backend="auto")
    assert "pallas_tiles" in resolve_partition_backends(SSSP(), cfg, pg)
    monkeypatch.setattr(autotune, "TILE_BUDGET_BYTES", 1)
    asg = resolve_partition_backends(SSSP(), cfg, pg, lay=lay)
    assert "pallas_tiles" not in asg


# --------------------------------------------------------------------------- #
# auto on a table that favours windows answers as coo does
# --------------------------------------------------------------------------- #
def _share_of_spans(tdir):
    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    return [dict(e.stats).get("pallas_edge_share")
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name == "drone/session/query"]


def test_auto_favouring_windows_answers_as_coo(tmp_path):
    g = powerlaw_graph(600, seed=11, weighted=True).as_undirected()
    programs = [(BFS(), {"source": 3}), (SSSP(), {"source": 3}),
                (ConnectedComponents(), None)]
    for prog, _ in programs:
        autotune.save_table(_favouring(_key(prog), "pallas_windows"))
    auto = GraphSession.from_graph(g, 4, "cdbh",
                                   cfg=EngineConfig(edge_backend="auto"))
    coo = GraphSession.from_graph(g, 4, "cdbh")
    for prog, params in programs:
        want, st_coo = coo.query(prog, params, warm=False)
        got, st = auto.query(prog, params, warm=False)
        assert st.partition_edge_backends == ["pallas_windows"] * 4
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got),
                                      err_msg=type(prog).__name__)
        # the same work: every superstep and local sweep, edge for edge
        assert (st.supersteps, st.processed_edges) == \
            (st_coo.supersteps, st_coo.processed_edges)
    with jax.profiler.trace(str(tmp_path)):
        auto.query(BFS(), {"source": 5}, warm=False)
        coo.query(BFS(), {"source": 5}, warm=False)
    assert _share_of_spans(str(tmp_path)) == [1.0, 0.0]


def test_uniform_auto_lowers_to_the_uniform_product():
    """Every partition on one backend: the 'auto' runner is that backend's
    own program — no group slicing, no scatter back into the stack."""
    g = powerlaw_graph(300, seed=2, weighted=True).as_undirected()
    pg = partition_and_build(g, 4, "cdbh")
    lay = pg.ensure_edge_layouts()
    sgs = _device_subgraph(pg)
    prog = SSSP()
    for backend in ("pallas_windows", "coo"):
        asg = (backend,) * pg.n_parts
        blk = engine._auto_layout_blocks(lay, pg, prog, asg)
        auto = make_sim_runner(prog, EngineConfig(edge_backend="auto"),
                               pg.n_slots, partition_backends=asg)
        uni = make_sim_runner(prog, EngineConfig(edge_backend=backend),
                              pg.n_slots)
        params = {"source": np.int32(0)}
        uni_args = (sgs, params) if backend == "coo" else (sgs, blk, params)
        assert blk is None if backend == "coo" else \
            blk is lay.device_windows()
        a = jax.jit(auto).lower(sgs, blk, params).as_text()
        b = jax.jit(uni).lower(*uni_args).as_text()
        assert a == b, backend


# --------------------------------------------------------------------------- #
# BFS, SSSP and CC pin separately in one session
# --------------------------------------------------------------------------- #
def pin_three_programs(sess, autotune_dir):
    """Query BFS, SSSP and CC on an 'auto' session whose BFS table favours
    windows and CC's coo, flush in-bucket, query again: each program keeps
    its own pin and its runner, with no retrace."""
    os.environ["DRONE_AUTOTUNE_DIR"] = autotune_dir
    eng = sess.cfg.backend
    autotune.save_table(_favouring(_key(BFS(), eng), "pallas_windows"))
    autotune.save_table(_favouring(_key(ConnectedComponents(), eng),
                                   "coo"))
    programs = [(BFS(), {"source": 0}), (SSSP(), {"source": 0}),
                (ConnectedComponents(), None)]
    pins = {}
    for prog, params in programs:
        _, st = sess.query(prog, params, warm=False)
        pins[type(prog).__name__] = tuple(st.partition_edge_backends)
    n = sess.pg.n_parts
    assert pins["BFS"] == ("pallas_windows",) * n, pins
    assert pins["ConnectedComponents"] == ("coo",) * n, pins
    assert len(sess._auto_pin) == 3
    lay = sess.pg.edge_layouts
    caps = (lay.t_max, lay.b_max)
    shape = sess.shape_key
    rng = np.random.default_rng(7)
    s = rng.integers(0, sess.pg.n_vertices, 30)
    d = rng.integers(0, sess.pg.n_vertices, 30)
    keep = s != d
    sess.update(adds=(s[keep], d[keep],
                      np.ones(int(keep.sum()), np.float32)))
    sess.flush()
    assert (lay.t_max, lay.b_max) == caps and sess.shape_key == shape, \
        "in-bucket by design"
    for prog, params in programs:
        with retrace_guard(label=f"auto pin {type(prog).__name__}"):
            _, st = sess.query(prog, params, warm=False)
        assert tuple(st.partition_edge_backends) == \
            pins[type(prog).__name__], type(prog).__name__
        assert st.compile_time == 0.0
    return pins


def test_bfs_sssp_cc_pin_separately_sim(tmp_path):
    g = powerlaw_graph(900, seed=5, weighted=True).as_undirected()
    sess = GraphSession.from_graph(g, 4, "ebv",
                                   cfg=EngineConfig(edge_backend="auto"))
    pin_three_programs(sess, str(tmp_path))
    assert sess.stats.cache_misses == 3


PIN_SHARD_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.environ["TESTS_DIR"])
import jax
import numpy as np
from jax.sharding import Mesh

from repro.core import EngineConfig
from repro.graphgen import powerlaw_graph
from repro.session import GraphSession
from test_autotune import pin_three_programs

g = powerlaw_graph(900, seed=5, weighted=True).as_undirected()
mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("sub",))
sess = GraphSession.from_graph(g, 4, "ebv", mesh=mesh,
                               cfg=EngineConfig(edge_backend="auto"))
pin_three_programs(sess, os.environ["AUTOTUNE_TMP"])
assert sess.stats.cache_misses == 3, sess.stats.cache_misses
print("PIN_SHARD_OK")
"""


def test_bfs_sssp_cc_pin_separately_shard_map(tmp_path):
    env = dict(os.environ, AUTOTUNE_TMP=str(tmp_path), TESTS_DIR=HERE)
    res = subprocess.run([sys.executable, "-c", PIN_SHARD_SCRIPT],
                         capture_output=True, text=True, timeout=1200,
                         env=env)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "PIN_SHARD_OK" in res.stdout


MIXED_SHARD_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["DRONE_AUTOTUNE_DIR"] = os.environ["AUTOTUNE_TMP"]
import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import shortest_path

import repro.algos as algos
import repro.session as session
from repro.compat import make_mesh
from repro.core import EngineConfig
from repro.core.layouts import EdgeLayouts
from repro.graphgen import kronecker_graph

MIXED = ("pallas_windows", "coo", "pallas_windows", "coo")
session.resolve_partition_backends = lambda *a, **k: MIXED


def no_tiles(*a, **k):
    raise AssertionError("a dense tile block was built")


for name in ("_realize_tiles", "device_tiles", "device_tiles_sharded"):
    setattr(EdgeLayouts, name, no_tiles)

g = kronecker_graph(10, seed=3, weighted=True)
sess = session.GraphSession.from_graph(g, 4, "cdbh",
                                       mesh=make_mesh((4,), ("sub",)))
prog = getattr(algos, sys.argv[1])()
key = int(np.argmax(np.bincount(g.src, minlength=g.n_vertices)))
cfg = EngineConfig(edge_backend="auto", subgraph_axes=("sub",))
res, st = sess.query(prog, {"source": key}, warm=False, cfg=cfg)
assert tuple(st.partition_edge_backends) == MIXED, st.partition_edge_backends
got = sess.pg.collect(res, fill=np.float32(np.inf)).astype(np.float64)

A = csr_matrix((g.weight.astype(np.float64), (g.src, g.dst)),
               shape=(g.n_vertices, g.n_vertices))
want = shortest_path(A, directed=True, unweighted=sys.argv[1] == "BFS",
                     indices=key)
assert np.array_equal(np.isfinite(got), np.isfinite(want))
fin = np.isfinite(want)
np.testing.assert_allclose(got[fin], want[fin], rtol=1e-5)

ref, st_coo = sess.query(prog, {"source": key}, warm=False,
                         cfg=EngineConfig(edge_backend="coo",
                                          subgraph_axes=("sub",)))
np.testing.assert_array_equal(np.asarray(ref), np.asarray(res))
assert (st.supersteps, st.processed_edges) == \
    (st_coo.supersteps, st_coo.processed_edges)

# the tiles input is the stub: one identity tile a partition
tiles, windows, ids = sess._layout_arg(prog, "auto", sess._normalize_cfg(cfg))
assert tiles.tiles.shape[:2] == (4, 1), tiles.tiles.shape
assert windows.eslot.shape == (4, sess.pg.e_max)
assert np.asarray(ids).tolist() == [2, 0, 2, 0]
print("MIXED_SHARD_OK")
"""


@pytest.mark.parametrize("program", ["BFS", "SSSP"])
def test_mixed_shard_map_builds_no_block_for_an_unused_backend(tmp_path,
                                                               program):
    """A mixed shard_map ``'auto'`` assignment (windows on two partitions,
    coo on two) answers as the oracle and as coo, with the same supersteps,
    and never realizes the dense tiles that no partition runs."""
    env = dict(os.environ, AUTOTUNE_TMP=str(tmp_path))
    res = subprocess.run([sys.executable, "-c", MIXED_SHARD_SCRIPT, program],
                         capture_output=True, text=True, timeout=1200,
                         env=env)
    assert res.returncode == 0, res.stdout + res.stderr[-4000:]
    assert "MIXED_SHARD_OK" in res.stdout
