"""GraphSession serving API: compiled-runner caching (zero retraces on
repeat + shape-preserving updates, exactly one rebuild on capacity growth),
auto warm starts, the folded streaming lifecycle, legacy-wrapper parity, and
the EngineConfig / combiner_identity construction-time validation."""
import subprocess
import sys

import numpy as np
import pytest

from repro.algos import ConnectedComponents, PageRank, SSSP
from repro.analysis.sanitizer import retrace_guard
from repro.core import EngineConfig, ShapePolicy, partition_and_build, run_sim
from repro.core.api import combiner_identity
from repro.graphgen import powerlaw_graph
from repro.session import GraphSession
from repro.stream import write_edge_log


@pytest.fixture(scope="module")
def graph():
    return powerlaw_graph(2000, seed=2, weighted=True).as_undirected()


@pytest.fixture()
def session(graph):
    return GraphSession.from_graph(graph, 5, "cdbh")


def _grow_insert(g, pg, n=40, seed=8):
    """Insert-only batch attaching brand-new vertices: guarantees capacity
    growth (new membership rows + new edges) while staying warm-safe."""
    new = np.arange(pg.n_vertices, pg.n_vertices + n, dtype=np.int64)
    zeros = np.zeros(n, np.int64)
    return (np.concatenate([zeros, new]), np.concatenate([new, zeros]),
            np.full(2 * n, 9.0, np.float32))


# --------------------------------------------------------------------------- #
# compilation caching (satellite: trace-counter regression tests)
# --------------------------------------------------------------------------- #
def test_second_identical_query_zero_traces(session):
    r1, s1 = session.query(SSSP(), {"source": 0})
    assert s1.compile_time > 0.0              # cold query paid the compile
    with retrace_guard(label="second identical query"):
        r2, s2 = session.query(SSSP(), {"source": 0})
    assert s2.compile_time == 0.0             # billed zero on a cache hit
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    assert session.stats.cache_misses == 1 and session.stats.cache_hits == 1


def test_shape_preserving_update_zero_traces(session):
    """A flush that grows no padded dim (parallel copy of a resident edge in
    an under-capacity partition) must reuse the compiled runner."""
    session.query(SSSP(), {"source": 0})
    pg = session.pg
    p = int(np.argmin(pg.edges_per_part))
    assert pg.edges_per_part[p] < pg.e_max, "need slack for this test"
    m = pg.emask[p]
    gs = int(pg.gvid[p][pg.esrc[p][m]][0])
    gd = int(pg.gvid[p][pg.edst[p][m]][0])
    shape_before = session.shape_key
    session.update(adds=([gs], [gd], [50.0]))
    st = session.flush()
    assert not st.repadded and session.shape_key == shape_before
    with retrace_guard(label="shape-preserving update"):
        r, s = session.query(SSSP(), {"source": 0})
    assert s.compile_time == 0.0
    # ...and the device pytree was re-uploaded (the graph did change)
    assert session.stats.uploads == 2
    cold, _ = session.query(SSSP(), {"source": 0}, warm=False)
    np.testing.assert_array_equal(np.asarray(r), np.asarray(cold))


def test_capacity_growing_update_compiles_exactly_once(graph):
    # exact policy: this test probes the growth/evict/rebuild mechanics, so
    # capacity must overflow on a small insert (buckets would absorb it)
    session = GraphSession.from_graph(graph, 5, "cdbh",
                                      shape_policy=ShapePolicy.exact())
    session.query(SSSP(), {"source": 0})
    session.update(adds=_grow_insert(graph, session.pg))
    st = session.flush()
    assert st.repadded, "delta was supposed to grow the padded shapes"
    assert not session._runners, "stale-shape runners must be evicted"
    misses = session.stats.cache_misses
    _, s = session.query(SSSP(), {"source": 0})
    assert session.stats.cache_misses == misses + 1, \
        "capacity growth must rebuild the runner exactly once"
    assert s.compile_time > 0.0
    with retrace_guard(label="second post-growth query"):
        session.query(SSSP(), {"source": 0})


def test_param_values_share_one_runner(session):
    """Params are traced inputs: SSSP from any source reuses the compiled
    executable (the serving pattern the cache exists for)."""
    session.query(SSSP(), {"source": 0})
    with retrace_guard(label="per-source queries"):
        for src in (3, 11, 42):
            session.query(SSSP(), {"source": src})
    assert session.stats.cache_misses == 1 and session.stats.cache_hits == 3


def test_multi_algorithm_cache_entries(graph, session):
    session.query(SSSP(), {"source": 0})
    session.query(ConnectedComponents())
    session.query(PageRank(tol=1e-9), {"n_vertices": graph.n_vertices})
    assert session.stats.cache_misses == 3
    with retrace_guard(label="repeat algorithm queries"):
        session.query(SSSP(), {"source": 1})
        session.query(ConnectedComponents())
        session.query(PageRank(tol=1e-9), {"n_vertices": graph.n_vertices})
    assert session.stats.cache_misses == 3
    # a different EngineConfig is a different runner
    session.query(ConnectedComponents(), cfg=EngineConfig(mode="vc"))
    assert session.stats.cache_misses == 4


# --------------------------------------------------------------------------- #
# query semantics: parity with the low-level layer, warm starts
# --------------------------------------------------------------------------- #
BILL = ("supersteps", "total_messages", "total_bytes", "processed_edges",
        "backend_flops", "tile_density", "edge_backend",
        "partition_edge_backends", "partition_tile_density",
        "partition_flops", "partition_edge_counts")


@pytest.mark.parametrize("edge_backend", ["coo", "pallas_windows", "auto"])
def test_query_matches_run_sim(graph, edge_backend):
    # exact policy: bit-identical [P, v_max, K] layout + bill parity with
    # the low-level one-shot layer (buckets pad differently); the one-shot
    # graph takes the session's layout policy, so 'auto' picks alike
    cfg = EngineConfig(edge_backend=edge_backend)
    session = GraphSession.from_graph(graph, 5, "cdbh", cfg=cfg,
                                      shape_policy=ShapePolicy.exact())
    pg = partition_and_build(graph, 5, "cdbh")
    pg.ensure_edge_layouts(shape_policy=ShapePolicy.exact())
    for prog, params in ((SSSP(), {"source": 7}), (ConnectedComponents(),
                                                   None)):
        r_sess, s_sess = session.query(prog, params, warm=False)
        r_ref, s_ref = run_sim(prog, pg, params, cfg)
        np.testing.assert_array_equal(np.asarray(r_sess), np.asarray(r_ref))
        for field in BILL:
            assert getattr(s_sess, field) == getattr(s_ref, field), field
        assert s_sess.processed_edges > 0
    r_pr, _ = session.query(PageRank(tol=1e-9),
                            {"n_vertices": graph.n_vertices})
    r_ref, _ = run_sim(PageRank(tol=1e-9), pg,
                       {"n_vertices": graph.n_vertices}, cfg)
    np.testing.assert_array_equal(np.asarray(r_pr), np.asarray(r_ref))


def test_warm_auto_after_insert_matches_cold(graph, session):
    session.query(SSSP(), {"source": 0})
    rng = np.random.default_rng(3)
    n = graph.n_edges // 200
    s = rng.integers(0, graph.n_vertices, n)
    d = rng.integers(0, graph.n_vertices, n)
    keep = s != d
    s, d = s[keep], d[keep]
    w = rng.uniform(5, 10, s.size).astype(np.float32)
    session.update(adds=(np.concatenate([s, d]), np.concatenate([d, s]),
                         np.concatenate([w, w])))
    session.flush()
    warm, st_w = session.query(SSSP(), {"source": 0})          # warm="auto"
    cold, st_c = session.query(SSSP(), {"source": 0}, warm=False)
    np.testing.assert_array_equal(np.asarray(warm), np.asarray(cold))
    assert st_w.supersteps < st_c.supersteps, \
        (st_w.supersteps, st_c.supersteps)
    assert session.stats.warm_queries >= 1


def test_warm_is_per_params(session):
    """Source-0 distances must never seed a source-7 query."""
    session.query(SSSP(), {"source": 0})
    r7, s7 = session.query(SSSP(), {"source": 7})    # no warm entry for 7
    ref, _ = session.query(SSSP(), {"source": 7}, warm=False)
    np.testing.assert_array_equal(np.asarray(r7), np.asarray(ref))


def test_warm_true_raises_without_entry(session):
    with pytest.raises(ValueError, match="not monotone"):
        session.query(PageRank(), {"n_vertices": 10}, warm=True)
    with pytest.raises(ValueError, match="no previous converged result"):
        session.query(SSSP(), {"source": 0}, warm=True)
    session.query(SSSP(), {"source": 0})
    session.query(SSSP(), {"source": 0}, warm=True)  # now fine


def test_deletes_invalidate_warm(graph, session):
    session.query(SSSP(), {"source": 0})
    session.update(deletes=(graph.src[:50], graph.dst[:50]))
    session.flush()
    with pytest.raises(ValueError, match="no previous converged result"):
        session.query(SSSP(), {"source": 0}, warm=True)
    # auto falls back cold and matches a from-scratch reference
    r, _ = session.query(SSSP(), {"source": 0})
    ref_sess = GraphSession(session.pg)
    ref, _ = ref_sess.query(SSSP(), {"source": 0})
    np.testing.assert_array_equal(np.asarray(r), np.asarray(ref))


def test_query_flushes_pending_updates(graph, session):
    """A query must see every mutation accepted by update()."""
    r0, _ = session.query(ConnectedComponents())
    new = session.pg.n_vertices
    session.update(adds=([0, new], [new, 0]))
    assert len(session.buffer) == 2
    r1, _ = session.query(ConnectedComponents())
    assert len(session.buffer) == 0 and session.stats.flushes == 1
    lab = session.pg.collect(r1, fill=-1)
    assert lab[new] == lab[0], "buffered edge must be visible to the query"


def test_flush_after_auto_flush_returns_stats(graph):
    """A threshold auto-flush inside update() must not make the explicit
    flush() return None (regression: benchmarks dereferenced .n_added)."""
    sess = GraphSession.from_graph(graph, 5, "cdbh", max_buffer_edges=8)
    rng = np.random.default_rng(0)
    s = rng.integers(0, graph.n_vertices, 32).astype(np.int64)
    d = (s + 1) % graph.n_vertices
    sess.update(adds=(s, d))                 # trips the threshold in-flight
    assert sess.stats.flushes >= 1 and len(sess.buffer) == 0
    st = sess.flush()
    assert st is not None and st.n_added > 0
    assert sess.flush() is st                # idempotent: last applied patch


def test_compact_carries_warm_results(graph):
    # exact policy so the deletes are guaranteed to shrink the capacities
    # (a bucketed session may legitimately stay on the same bucket floor)
    sess = GraphSession.from_graph(graph, 5, "cdbh",
                                   shape_policy=ShapePolicy.exact())
    rng = np.random.default_rng(7)
    sel = rng.choice(graph.n_edges, size=graph.n_edges // 3, replace=False)
    sess.update(deletes=(np.concatenate([graph.src[sel], graph.dst[sel]]),
                         np.concatenate([graph.dst[sel], graph.src[sel]])))
    sess.flush()
    cold, _ = sess.query(SSSP(), {"source": 0})
    prev = sess.pg.collect(cold, fill=np.float32(np.inf))
    cs = sess.compact()
    assert cs.shrunk
    warm, st_w = sess.query(SSSP(), {"source": 0})
    np.testing.assert_array_equal(
        sess.pg.collect(warm, fill=np.float32(np.inf)), prev)
    assert st_w.supersteps <= 2, \
        "compaction changes layout, not the graph: warm is already converged"


def test_from_edge_log(graph, tmp_path):
    d = str(tmp_path / "log")
    write_edge_log(graph, d, chunk_size=8192)
    sess = GraphSession.from_edge_log(d, 5, "cdbh")
    assert sess.ingest_stats.n_edges == graph.n_edges
    mem = GraphSession.from_graph(graph, 5, "cdbh")
    r1, _ = sess.query(ConnectedComponents())
    r2, _ = mem.query(ConnectedComponents())
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))


def test_readonly_session_rejects_updates(graph):
    pg = partition_and_build(graph, 5, "cdbh")
    sess = GraphSession(pg)                      # no StreamContext
    sess.query(ConnectedComponents())            # queries are fine
    with pytest.raises(ValueError, match="StreamContext"):
        sess.update(adds=([0], [1]))
    with pytest.raises(ValueError, match="StreamContext"):
        sess.compact()


def test_trace_cfg_delegates_to_run_sim(graph, session):
    r, st = session.query(ConnectedComponents(),
                          cfg=EngineConfig(mode="vc", trace=True))
    assert st.messages_per_step, "trace mode keeps per-superstep stats"
    ref_pg = partition_and_build(graph, 5, "cdbh")
    ref, _ = run_sim(ConnectedComponents(), ref_pg, None,
                     EngineConfig(mode="vc"))
    # padded layouts differ (bucketed session vs exact one-shot build):
    # compare the collected global labels
    np.testing.assert_array_equal(session.pg.collect(np.asarray(r), fill=-1),
                                  ref_pg.collect(np.asarray(ref), fill=-1))


# --------------------------------------------------------------------------- #
# construction-time validation satellites
# --------------------------------------------------------------------------- #
def test_engineconfig_validates_at_construction():
    with pytest.raises(ValueError, match=r"mode.*'sc', 'vc'"):
        EngineConfig(mode="subgraph")
    with pytest.raises(ValueError, match=r"backend.*'sim', 'shard_map'"):
        EngineConfig(backend="gpu")
    with pytest.raises(ValueError, match="axis names"):
        EngineConfig(subgraph_axes="sub")        # bare string, not a tuple
    with pytest.raises(ValueError, match="max_supersteps"):
        EngineConfig(max_supersteps=0)
    with pytest.raises(ValueError, match="sparse_sync_capacity"):
        EngineConfig(sparse_sync_capacity=-1)
    # lists normalize to tuples so the config stays hashable (cache key)
    cfg = EngineConfig(subgraph_axes=["pod", "data"], edge_axes=[])
    assert cfg.subgraph_axes == ("pod", "data") and hash(cfg) is not None


def test_combiner_identity_error_names_pairs():
    with pytest.raises(ValueError, match=r"\('min', float32\)"):
        combiner_identity("min", np.float64)
    with pytest.raises(ValueError, match="supported"):
        combiner_identity("prod", np.float32)
    assert combiner_identity("min", np.float32) == np.float32(np.inf)


# --------------------------------------------------------------------------- #
# shard_map backend (subprocess: needs fake devices before jax init)
# --------------------------------------------------------------------------- #
SHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
from repro.analysis.sanitizer import retrace_guard
from repro.compat import make_mesh
from repro.session import GraphSession
from repro.core import EngineConfig
from repro.graphgen import powerlaw_graph
from repro.algos import SSSP, ConnectedComponents, PageRank

g = powerlaw_graph(400, seed=7, weighted=True).as_undirected()
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = EngineConfig(subgraph_axes=("pod", "data"), edge_axes=("model",))
sess = GraphSession.from_graph(g, 4, "cdbh", mesh=mesh, cfg=cfg)
sim = GraphSession.from_graph(g, 4, "cdbh")

# cross-backend parity + zero retraces on the second identical query
r1, s1 = sess.query(SSSP(), {"source": 0})
rs, ss = sim.query(SSSP(), {"source": 0})
assert (np.asarray(r1) == np.asarray(rs)).all(), "shard != sim"
assert s1.supersteps == ss.supersteps
with retrace_guard(label="second shard-backend query"):
    r2, s2 = sess.query(SSSP(), {"source": 0})
assert s2.compile_time == 0.0
assert (np.asarray(r1) == np.asarray(r2)).all(), "repeat not bit-identical"

# params are traced inputs on the shard backend too
with retrace_guard(label="new-source shard-backend query"):
    r3, _ = sess.query(SSSP(), {"source": 5})
r3s, _ = sim.query(SSSP(), {"source": 5}, warm=False)
assert (np.asarray(r3) == np.asarray(r3s)).all()

# non-monotone program parity
rp, _ = sess.query(PageRank(tol=1e-9), {"n_vertices": g.n_vertices})
rp2, _ = sim.query(PageRank(tol=1e-9), {"n_vertices": g.n_vertices})
assert np.allclose(np.asarray(rp), np.asarray(rp2), atol=1e-6)

# insert-only update: warm-auto == cold bit-for-bit, strictly fewer steps,
# superstep parity with the sim session
rng = np.random.default_rng(8)
n = 32
s = rng.integers(0, g.n_vertices, n); d = rng.integers(0, g.n_vertices, n)
keep = s != d; s, d = s[keep], d[keep]
w = rng.uniform(5, 10, s.size).astype(np.float32)
adds = (np.concatenate([s, d]), np.concatenate([d, s]),
        np.concatenate([w, w]))
for ss_ in (sess, sim):
    ss_.update(adds=adds)
    ss_.flush()
warm, st_w = sess.query(SSSP(), {"source": 0})
cold, st_c = sess.query(SSSP(), {"source": 0}, warm=False)
assert (np.asarray(warm) == np.asarray(cold)).all(), "warm != cold"
assert st_w.supersteps < st_c.supersteps, (st_w.supersteps, st_c.supersteps)
wsim, st_wsim = sim.query(SSSP(), {"source": 0})
assert (np.asarray(warm) == np.asarray(wsim)).all(), "shard warm != sim warm"
assert st_w.supersteps == st_wsim.supersteps
print("SESSION_SHARD_OK")
"""


def test_session_shard_map_backend():
    res = subprocess.run([sys.executable, "-c", SHARD_SCRIPT],
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "SESSION_SHARD_OK" in res.stdout


PLACEMENT_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
from repro.algos import SSSP
from repro.compat import make_mesh
from repro.core import EngineConfig
from repro.core.engine import shard_placement
from repro.graphgen import powerlaw_graph
from repro.session import GraphSession

g = powerlaw_graph(400, seed=7, weighted=True).as_undirected()
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
edge = EngineConfig(subgraph_axes=("pod", "data"), edge_axes=("model",),
                    backend="shard_map", edge_backend="pallas_windows")
flat = EngineConfig(subgraph_axes=("pod", "data"), backend="shard_map",
                    edge_backend="pallas_windows")
sess = GraphSession.from_graph(g, 4, "cdbh", mesh=mesh, cfg=edge)
want, _ = GraphSession.from_graph(g, 4, "cdbh").query(SSSP(), {"source": 0})

# every resident input of a query sits where that query's runner reads it,
# whichever axes its cfg names; the graph moves only when the axes change
uploads = []
for cfg in (flat, flat, edge, flat):
    res, st = sess.query(SSSP(), {"source": 0}, cfg=cfg, warm=False)
    assert (np.asarray(res) == np.asarray(want)).all(), cfg
    uploads.append(sess.stats.uploads)
    pl = shard_placement(mesh, cfg)
    dev = sess.device_graph(cfg)
    assert dev.esrc.sharding == pl.graph.esrc, (dev.esrc.sharding, cfg)
    lay = sess._layout_arg(SSSP(), "pallas_windows", cfg)
    assert lay.eslot.sharding == pl.windows.eslot, cfg
    warm = sess._warm_arg(SSSP(), None, False, cfg)
    assert warm.sharding == pl.warm, cfg
assert uploads == [1, 1, 2, 3], uploads
print("PLACEMENT_OK")
"""


def test_session_mesh_placement_follows_query_cfg():
    res = subprocess.run([sys.executable, "-c", PLACEMENT_SCRIPT],
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "PLACEMENT_OK" in res.stdout
