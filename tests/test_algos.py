"""Algorithm correctness vs networkx / numpy oracles, across partitioners
(vertex-cut CDBH/RH, edge-cut RH = DRONE-EC) and execution modes (SC / VC)."""
import numpy as np
import networkx as nx
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import EngineConfig, partition_and_build, run_sim
from repro.algos import ConnectedComponents, PageRank, SSSP
from repro.algos.gsim import make_gsim
from repro.graphgen import grid_graph, powerlaw_graph, random_graph, ring_graph

PARTS = ["cdbh", "rh-vc", "rh-ec"]
MODES = ["sc", "vc"]


def _cc_oracle(g):
    G = nx.Graph()
    G.add_nodes_from(range(g.n_vertices))
    G.add_edges_from(zip(g.src.tolist(), g.dst.tolist()))
    lab = np.arange(g.n_vertices)
    for comp in nx.connected_components(G):
        lab[list(comp)] = min(comp)
    return lab


def _sssp_oracle(g, source):
    G = nx.DiGraph()
    G.add_nodes_from(range(g.n_vertices))
    for s, d, w in zip(g.src.tolist(), g.dst.tolist(), g.weights.tolist()):
        if not G.has_edge(s, d) or G[s][d]["weight"] > w:
            G.add_edge(s, d, weight=w)
    dist = np.full(g.n_vertices, np.inf)
    for v, d in nx.single_source_dijkstra_path_length(G, source).items():
        dist[v] = d
    return dist


def _pr_oracle(g, alpha=0.85, iters=300):
    n = g.n_vertices
    outd = np.bincount(g.src, minlength=n).astype(float)
    pr, cur = np.zeros(n), np.full(n, (1 - alpha) / n)
    for _ in range(iters):
        pr += cur
        nxt = np.zeros(n)
        push = alpha * np.where(outd > 0, cur / np.maximum(outd, 1), 0.0)
        np.add.at(nxt, g.dst, push[g.src])
        cur = nxt
        if cur.max() < 1e-16:
            break
    return pr


@pytest.mark.parametrize("part", PARTS)
@pytest.mark.parametrize("mode", MODES)
def test_cc(part, mode):
    g = powerlaw_graph(600, seed=1).as_undirected()
    pg = partition_and_build(g, 6, part)
    res, stats = run_sim(ConnectedComponents(), pg, None, EngineConfig(mode=mode))
    np.testing.assert_array_equal(pg.collect(res, fill=-1), _cc_oracle(g))
    assert stats.supersteps >= 1 and stats.total_messages > 0


@pytest.mark.parametrize("part", PARTS)
@pytest.mark.parametrize("mode", MODES)
def test_sssp(part, mode):
    g = grid_graph(16, weighted=True, seed=2)
    pg = partition_and_build(g, 5, part)
    res, _ = run_sim(SSSP(), pg, {"source": 7}, EngineConfig(mode=mode))
    dist = pg.collect(res, fill=np.float32(np.inf))
    ref = _sssp_oracle(g, 7)
    finite = np.isfinite(ref)
    np.testing.assert_allclose(dist[finite], ref[finite], rtol=1e-5, atol=1e-4)
    assert np.isinf(dist[~finite]).all()


def test_sssp_unreachable():
    # two disjoint cliques; distances to the far one must stay inf
    e = np.array([[0, 1], [1, 2], [3, 4], [4, 5]], np.int64)
    from repro.core.graph import Graph
    g = Graph(6, e[:, 0], e[:, 1]).as_undirected()
    pg = partition_and_build(g, 2, "cdbh")
    res, _ = run_sim(SSSP(), pg, {"source": 0}, EngineConfig())
    dist = pg.collect(res, fill=np.float32(np.inf))
    assert np.isfinite(dist[:3]).all() and np.isinf(dist[3:]).all()


@pytest.mark.parametrize("part", PARTS)
@pytest.mark.parametrize("mode", MODES)
def test_pagerank(part, mode):
    g = powerlaw_graph(500, seed=3)
    pg = partition_and_build(g, 4, part)
    cfg = EngineConfig(mode=mode, max_local_iters=300, max_supersteps=3000)
    res, _ = run_sim(PageRank(tol=1e-9), pg, {"n_vertices": g.n_vertices}, cfg)
    mine = pg.collect(res, fill=0.0)
    ref = _pr_oracle(g)
    np.testing.assert_allclose(mine, ref, atol=5e-5)
    # ranks are a probability-mass-like vector (no dangling redistribution)
    assert 0 < mine.sum() <= 1.0 + 1e-3


@pytest.mark.parametrize("part", PARTS)
def test_gsim(part):
    rng = np.random.default_rng(4)
    g = powerlaw_graph(400, seed=4)
    labels = rng.integers(0, 4, size=g.n_vertices).astype(np.int32)
    qadj = np.array([[0, 1, 1], [0, 0, 1], [0, 0, 0]], np.int32)
    qlabel = np.array([0, 1, 2], np.int32)
    pg = partition_and_build(g, 5, part)
    pg.set_vertex_labels(labels)
    prog, params = make_gsim(qadj, qlabel)
    res, _ = run_sim(prog, pg, params, EngineConfig())
    sim = pg.collect(res, fill=0).astype(bool)

    # oracle: naive pruning fixpoint
    VQ = 3
    ref = np.zeros((g.n_vertices, VQ), bool)
    for u in range(VQ):
        ref[:, u] = labels == qlabel[u]
    adj = [[] for _ in range(g.n_vertices)]
    for s, d in zip(g.src.tolist(), g.dst.tolist()):
        adj[s].append(d)
    changed = True
    while changed:
        changed = False
        for u in range(VQ):
            succ = np.nonzero(qadj[u])[0]
            for v in range(g.n_vertices):
                if ref[v, u] and any(not ref[adj[v], up].any() if adj[v] else True
                                     for up in succ):
                    ref[v, u] = False
                    changed = True
    np.testing.assert_array_equal(sim, ref)


def test_large_diameter_superstep_gap():
    """Paper §3/§8: SC needs far fewer supersteps than VC on large-diameter
    graphs (ring = extreme case), given a locality-preserving partition.
    (With a hash partition the subgraphs are scattered fragments and SC loses
    its advantage — the paper's own observation about hash partitioning
    destroying local structure, §3.)"""
    g = ring_graph(512)
    pg = partition_and_build(g, 4, "range")
    _, sc = run_sim(ConnectedComponents(), pg, None, EngineConfig(mode="sc"))
    _, vc = run_sim(ConnectedComponents(), pg, None, EngineConfig(mode="vc"))
    assert sc.supersteps * 10 < vc.supersteps
    # and on the same partition, SC also sends far fewer messages
    assert sc.total_messages * 5 < vc.total_messages


def test_single_partition_no_frontier():
    g = powerlaw_graph(300, seed=6).as_undirected()
    pg = partition_and_build(g, 1, "cdbh")
    assert pg.n_slots == 0
    res, stats = run_sim(ConnectedComponents(), pg, None, EngineConfig())
    np.testing.assert_array_equal(pg.collect(res, fill=-1), _cc_oracle(g))
    assert stats.total_messages == 0


@settings(max_examples=15, deadline=None)
@given(st.integers(8, 80), st.integers(1, 6), st.integers(0, 4),
       st.sampled_from(PARTS))
def test_cc_random_property(n_v, n_parts, seed, part):
    g = random_graph(n_v, n_v * 2, seed=seed, undirected=True)
    pg = partition_and_build(g, n_parts, part, seed=seed)
    res, _ = run_sim(ConnectedComponents(), pg, None, EngineConfig())
    np.testing.assert_array_equal(pg.collect(res, fill=-1), _cc_oracle(g))


@settings(max_examples=10, deadline=None)
@given(st.integers(8, 60), st.integers(1, 5), st.integers(0, 4))
def test_sssp_random_property(n_v, n_parts, seed):
    g = random_graph(n_v, n_v * 3, seed=seed, weighted=True)
    pg = partition_and_build(g, n_parts, "cdbh", seed=seed)
    src = seed % n_v
    res, _ = run_sim(SSSP(), pg, {"source": src}, EngineConfig())
    dist = pg.collect(res, fill=np.float32(np.inf))
    ref = _sssp_oracle(g, src)
    finite = np.isfinite(ref)
    np.testing.assert_allclose(dist[finite], ref[finite], rtol=1e-5, atol=1e-4)
    assert np.isinf(dist[~finite]).all()
