"""Partitioner + subgraph-builder invariants (paper §4.1 Eq. 2-3, §6)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (PARTITIONERS, Graph, build_partitioned_graph,
                        partition_metrics)
from repro.core.partition import cdbh_vertex_cut, random_hash_vertex_cut
from repro.graphgen import powerlaw_graph, random_graph


def _graph(n_v=200, n_e=800, seed=0, undirected=False):
    return random_graph(n_v, n_e, seed=seed, weighted=True,
                        undirected=undirected)


@pytest.mark.parametrize("name", list(PARTITIONERS))
def test_edge_partition_complete_and_disjoint(name):
    """Eq. 2: E = union E_i, disjoint — every edge exactly once, intact."""
    g = _graph()
    part = PARTITIONERS[name](g, 5)
    pg = build_partitioned_graph(g, part, 5)
    seen = []
    for p in range(5):
        m = pg.emask[p]
        gs = pg.gvid[p][pg.esrc[p][m]]
        gd = pg.gvid[p][pg.edst[p][m]]
        seen.append(np.stack([gs, gd], 1))
    seen = np.concatenate(seen, 0)
    assert seen.shape[0] == g.n_edges
    want = np.sort(g.src * np.int64(g.n_vertices) + g.dst)
    got = np.sort(seen[:, 0] * np.int64(g.n_vertices) + seen[:, 1])
    np.testing.assert_array_equal(want, got)


@pytest.mark.parametrize("name", list(PARTITIONERS))
def test_vertex_sets_are_edge_endpoints(name):
    """Eq. 3: V_i = endpoints of E_i (+ round-robin isolated vertices)."""
    g = _graph()
    part = PARTITIONERS[name](g, 4)
    pg = build_partitioned_graph(g, part, 4)
    iso = set(g.isolated_vertices().tolist())
    for p in range(4):
        m = pg.emask[p]
        endpoints = set(pg.gvid[p][pg.esrc[p][m]]) | set(pg.gvid[p][pg.edst[p][m]])
        vids = set(pg.gvid[p][pg.vmask[p]].tolist())
        assert endpoints <= vids
        assert vids - endpoints <= iso


def test_frontier_slots_and_masters():
    g = _graph()
    part = cdbh_vertex_cut(g, 6)
    pg = build_partitioned_graph(g, part, 6)
    # every frontier vertex has exactly one master across partitions
    master_count = np.zeros(g.n_vertices, np.int64)
    sel = pg.vmask & pg.is_master
    np.add.at(master_count, pg.gvid[sel], 1)
    present = np.zeros(g.n_vertices, np.int64)
    np.add.at(present, pg.gvid[pg.vmask], 1)
    assert (master_count[present > 0] == 1).all()
    # frontier <=> replicated
    frontier = set(pg.frontier_gvid.tolist())
    assert frontier == set(np.nonzero(present >= 2)[0].tolist())
    # slot ids consistent across replicas
    slot_of = {}
    for p in range(pg.n_parts):
        for lv in np.nonzero(pg.vmask[p] & pg.is_frontier[p])[0]:
            gv = pg.gvid[p][lv]
            s = pg.slot[p][lv]
            assert slot_of.setdefault(gv, s) == s


def test_cdbh_canonical_codirection():
    """(u,v) and (v,u) must land in the same partition (§6.3)."""
    g = _graph(undirected=True)
    part = cdbh_vertex_cut(g, 7)
    lut = {}
    for s, d, p in zip(g.src, g.dst, part):
        key = (min(s, d), max(s, d))
        assert lut.setdefault(key, p) == p


def test_partitioners_deterministic():
    g = _graph()
    for name, fn in PARTITIONERS.items():
        a = fn(g, 5, seed=3)
        b = fn(g, 5, seed=3)
        np.testing.assert_array_equal(a, b)


def test_cdbh_beats_rh_on_powerlaw_replication():
    """Paper Table 3: CDBH replication factor <= RH on power-law graphs."""
    g = powerlaw_graph(3000, alpha=2.1, avg_degree=16, seed=0).as_undirected()
    mc = partition_metrics(build_partitioned_graph(g, cdbh_vertex_cut(g, 16), 16))
    mr = partition_metrics(build_partitioned_graph(g, random_hash_vertex_cut(g, 16), 16))
    assert mc.replication_factor < mr.replication_factor
    assert mc.imbalance < 1.2 and mr.imbalance < 1.2


def test_metrics_bounds():
    g = _graph()
    for name in PARTITIONERS:
        pg = build_partitioned_graph(g, PARTITIONERS[name](g, 4), 4)
        m = partition_metrics(pg)
        assert m.imbalance >= 1.0 - 1e-9
        assert m.replication_factor >= 1.0 - 1e-9


def test_grid_nonsquare_uses_all_partitions():
    """Regression: the non-square grid fold used to be the identity on
    cell ids — partitions [q*q, P) never received an edge. The exact r x c
    factorization must feed every partition with bounded imbalance."""
    g = powerlaw_graph(5000, alpha=2.2, avg_degree=10, seed=2)
    for P in (6, 10, 12):
        part = PARTITIONERS["grid"](g, P, seed=0)
        counts = np.bincount(part, minlength=P)
        assert (counts > 0).all(), (P, counts)
        assert counts.max() / counts.mean() < 1.8, (P, counts)
    # square P keeps the historical sqrt x sqrt cell mapping
    part9 = PARTITIONERS["grid"](g, 9, seed=0)
    assert part9.min() >= 0 and part9.max() < 9


def test_grid_replication_bound():
    """Each vertex's edges stay inside one grid row + column: it can meet
    at most r + c - 1 partitions."""
    from repro.core.partition import route_edges_grid
    g = _graph(n_v=150, n_e=3000, seed=4)
    for P, bound in ((12, 3 + 4 - 1), (16, 4 + 4 - 1)):
        part = route_edges_grid(g.src, g.dst, P, seed=1)
        touched = {}
        for s, d, p in zip(g.src.tolist(), g.dst.tolist(), part.tolist()):
            touched.setdefault(s, set()).add(p)
            touched.setdefault(d, set()).add(p)
        assert max(len(v) for v in touched.values()) <= bound


@settings(max_examples=25, deadline=None)
@given(st.integers(20, 150), st.integers(10, 500), st.integers(2, 9),
       st.integers(0, 5), st.integers(1, 97))
def test_stream_routers_chunk_invariant(n_v, n_e, n_parts, seed, chunk):
    """Stateless STREAM_ROUTERS are pure per-edge: routing a stream in any
    chunking must equal routing it whole (the delta path depends on this).
    Stateful specs (ebv) are exempt — their placements depend on history
    and are pinned by checkpoint/replay tests instead."""
    from repro.core.partition import STREAM_ROUTERS, is_stateful_router
    g = random_graph(n_v, n_e, seed=seed)
    degrees = g.total_degrees()
    for name, entry in STREAM_ROUTERS.items():
        if is_stateful_router(entry):
            continue
        whole = entry(g.src, g.dst, degrees, n_v, n_parts, seed)
        parts = [entry(g.src[i:i + chunk], g.dst[i:i + chunk], degrees,
                       n_v, n_parts, seed)
                 for i in range(0, g.src.size, chunk)]
        np.testing.assert_array_equal(whole, np.concatenate(parts),
                                      err_msg=name)


@settings(max_examples=25, deadline=None)
@given(st.integers(10, 120), st.integers(0, 400), st.integers(1, 9),
       st.integers(0, 5))
def test_builder_properties_random(n_v, n_e, n_parts, seed):
    g = random_graph(n_v, max(n_e, 1), seed=seed)
    for name in ("cdbh", "rh-vc", "rh-ec"):
        part = PARTITIONERS[name](g, n_parts, seed=seed)
        pg = build_partitioned_graph(g, part, n_parts)
        assert pg.emask.sum() == g.n_edges
        # all vertices present somewhere
        present = np.zeros(g.n_vertices, bool)
        present[pg.gvid[pg.vmask]] = True
        assert present.all()
        # collect() roundtrip: identity values
        vals = np.where(pg.vmask, pg.gvid, 0).astype(np.int64)
        out = pg.collect(vals, fill=-1)
        np.testing.assert_array_equal(out, np.arange(g.n_vertices))
