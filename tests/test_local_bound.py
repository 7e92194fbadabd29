"""The local phase's bound (``engine.local_bound``) and the structural flag
it reads, ``PartitionedGraph.local_paths``: true where most of a
partition's edges join interior vertices (a ring cut into ranges), false on
vertex-cut partitions (Kronecker or a ring under CDBH), and equal to a
from-scratch derivation after a frontier re-election and after a stream
flush. ``min`` programs answer bit-equal to the unbounded local fixed
point on the simulator (``coo`` and ``pallas_windows``) and on
``shard_map`` while sweeping once a superstep; a ``sum`` program keeps its
supersteps; the query spans carry ``one_sweep_share``."""
import dataclasses
import glob
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.algos import BFS, SSSP, ConnectedComponents, PageRank
from repro.core import EngineConfig, partition_and_build, run_sim
from repro.core.engine import local_bound
from repro.core.subgraph import recompute_frontier
from repro.graphgen import kronecker_graph, ring_graph
from repro.obs import SPAN_PREFIX
from repro.session import GraphSession


def _exact_local_paths(pg):
    """The rule, derived edge by edge: at least half of a partition's real
    edges join two interior vertices."""
    out = []
    for p in range(pg.n_parts):
        m = pg.emask[p]
        interior = pg.vmask[p] & ~pg.is_frontier[p]
        both = interior[pg.esrc[p][m]] & interior[pg.edst[p][m]]
        out.append(m.sum() > 0 and 2 * both.sum() >= m.sum())
    return np.array(out)


def _unbounded(pg):
    """``pg`` as a graph whose every partition holds local paths: the local
    phase then runs to its fixed point everywhere, as before the bound."""
    return dataclasses.replace(pg, local_paths=np.ones(pg.n_parts, bool))


@pytest.fixture(scope="module")
def kron():
    return kronecker_graph(10, seed=3, weighted=True)


def _sources(g, n=2):
    deg = np.bincount(g.src, minlength=g.n_vertices)
    return [int(v) for v in np.nonzero(deg)[0][:n]]


# --------------------------------------------------------------------------- #
# the structural flag
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("graph,partitioner,n_parts,want", [
    ("ring", "range", 4, True),
    ("ring", "cdbh", 4, False),
    ("kron", "cdbh", 4, False),
    ("kron", "cdbh", 16, False),
])
def test_local_paths_rule(kron, graph, partitioner, n_parts, want):
    g = ring_graph(512) if graph == "ring" else kron
    pg = partition_and_build(g, n_parts, partitioner)
    assert pg.local_paths.tolist() == [want] * n_parts
    np.testing.assert_array_equal(pg.local_paths, _exact_local_paths(pg))


def test_local_paths_after_recompute_frontier():
    pg = partition_and_build(ring_graph(512), 4, "range")
    pg.local_paths = np.zeros(4, bool)             # stale
    recompute_frontier(pg)
    np.testing.assert_array_equal(pg.local_paths, _exact_local_paths(pg))
    assert pg.local_paths.all()


def test_local_paths_follow_a_flush():
    """Random chords across a ranged ring replicate most vertices: the
    flush turns the flag off, the next query sweeps once a superstep, and
    its answer is the unbounded local fixed point's."""
    g = ring_graph(512)
    sess = GraphSession.from_graph(g, 4, "range")
    cc = ConnectedComponents()
    sess.query(cc, warm=False)
    assert sess.pg.local_paths.all()
    rng = np.random.default_rng(0)
    s = rng.integers(0, g.n_vertices, 600)
    d = rng.integers(0, g.n_vertices, 600)
    keep = s != d
    s, d = s[keep], d[keep]
    w = np.ones(s.shape[0], np.float32)
    sess.update(adds=(np.r_[s, d], np.r_[d, s], np.r_[w, w]))
    sess.flush()
    pg = sess.pg
    np.testing.assert_array_equal(pg.local_paths, _exact_local_paths(pg))
    assert not pg.local_paths.any()
    got, st = sess.query(cc, warm=False)
    want, _ = run_sim(cc, _unbounded(pg), None, EngineConfig())
    np.testing.assert_array_equal(got, np.asarray(want))
    assert st.processed_edges == \
        st.supersteps * int(pg.edges_per_part.sum())


# --------------------------------------------------------------------------- #
# the bound
# --------------------------------------------------------------------------- #
def test_local_bound_rule():
    lp = np.array([True, False])
    sc, vc = EngineConfig(max_local_iters=40), EngineConfig(mode="vc")
    assert local_bound(BFS(), sc, lp).tolist() == [40, 1]
    assert local_bound(dataclasses.replace(BFS(), combiner="max"), sc,
                       lp).tolist() == [40, 1]
    assert local_bound(PageRank(), sc, lp) == 40
    assert local_bound(BFS(), vc, lp) == 1
    assert local_bound(PageRank(), vc, lp) == 1


@pytest.mark.parametrize("edge_backend", ["coo", "pallas_windows"])
@pytest.mark.parametrize("name", ["bfs", "sssp", "cc"])
def test_one_sweep_answers_equal_unbounded(kron, name, edge_backend):
    """Bit-equal answers; each partition sweeps exactly once a superstep,
    so the counted passes are the supersteps; fewer than unbounded."""
    pg = partition_and_build(kron, 8, "cdbh")
    assert not pg.local_paths.any()
    cfg = EngineConfig(edge_backend=edge_backend)
    prog = {"bfs": BFS(), "sssp": SSSP(), "cc": ConnectedComponents()}[name]
    params = [None] if name == "cc" else \
        [{"source": s} for s in _sources(kron)]
    edges = int(pg.edges_per_part.sum())
    for p in params:
        got, st = run_sim(prog, pg, p, cfg)
        want, st_u = run_sim(prog, _unbounded(pg), p, cfg)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert st.processed_edges == st.supersteps * edges
        assert st.processed_edges < st_u.processed_edges


def test_one_sweep_answers_equal_unbounded_scale_12():
    g = kronecker_graph(12, seed=5, weighted=True)
    pg = partition_and_build(g, 16, "cdbh")
    for prog in (BFS(), SSSP()):
        for src in _sources(g, 3):
            got, st = run_sim(prog, pg, {"source": src}, EngineConfig())
            want, _ = run_sim(prog, _unbounded(pg), {"source": src},
                              EngineConfig())
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want))
            assert st.processed_edges == \
                st.supersteps * int(pg.edges_per_part.sum())


def test_sum_program_keeps_its_supersteps(kron):
    pg = partition_and_build(kron, 8, "cdbh")
    params = {"n_vertices": kron.n_vertices}
    got, st = run_sim(PageRank(), pg, params, EngineConfig())
    want, st_u = run_sim(PageRank(), _unbounded(pg), params, EngineConfig())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert st.supersteps == st_u.supersteps
    assert st.processed_edges == st_u.processed_edges


SHARD_SCRIPT = r"""
import dataclasses
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import numpy as np
from jax.sharding import Mesh

from repro.algos import BFS, SSSP, ConnectedComponents
from repro.core import EngineConfig, partition_and_build, run
from repro.graphgen import kronecker_graph

g = kronecker_graph(10, seed=3, weighted=True)
pg = partition_and_build(g, 4, "cdbh")
assert not pg.local_paths.any()
pg_u = dataclasses.replace(pg, local_paths=np.ones(4, bool))
mesh = Mesh(np.array(jax.devices()[:4]), ("sub",))
edges = int(pg.edges_per_part.sum())
deg = np.bincount(g.src, minlength=g.n_vertices)
src = int(np.nonzero(deg)[0][0])
for prog, params, eb in [(BFS(), {"source": src}, "coo"),
                         (SSSP(), {"source": src}, "coo"),
                         (SSSP(), {"source": src}, "pallas_windows"),
                         (ConnectedComponents(), None, "coo")]:
    cfg = EngineConfig(backend="shard_map", edge_backend=eb)
    got, st = run(prog, pg, params, cfg, mesh=mesh)
    want, st_u = run(prog, pg_u, params, cfg, mesh=mesh)
    np.testing.assert_array_equal(got, want)
    assert st.processed_edges == st.supersteps * edges, (st, edges)
    assert st.processed_edges < st_u.processed_edges
print("SHARD_ONE_SWEEP_OK")
"""


def test_one_sweep_answers_equal_unbounded_shard_map():
    res = subprocess.run([sys.executable, "-c", SHARD_SCRIPT],
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "SHARD_ONE_SWEEP_OK" in res.stdout


# --------------------------------------------------------------------------- #
# one_sweep_share on the query spans
# --------------------------------------------------------------------------- #
def _span_stat(tdir, name, stat):
    """``stat`` of every ``drone/<name>`` event of the profile in ``tdir``."""
    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    return [dict(e.stats).get(stat)
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name == SPAN_PREFIX + name]


def test_query_spans_carry_one_sweep_share(kron, tmp_path):
    sess = GraphSession.from_graph(kron, 4, "cdbh")
    ring = GraphSession.from_graph(ring_graph(512), 4, "range")
    pr = {"n_vertices": kron.n_vertices}
    with jax.profiler.trace(str(tmp_path)):
        sess.query(BFS(), {"source": 0}, warm=False)
        sess.query(PageRank(), pr)
        sess.query(PageRank(), pr, cfg=EngineConfig(mode="vc"))
        ring.query(ConnectedComponents(), warm=False)
        sess.query_batch(BFS(), [{"source": 0}, {"source": 2}])
    assert _span_stat(str(tmp_path), "session/query", "one_sweep_share") \
        == [1.0, 0.0, 1.0, 0.0]
    assert _span_stat(str(tmp_path), "session/query_batch",
                      "one_sweep_share") == [1.0]
