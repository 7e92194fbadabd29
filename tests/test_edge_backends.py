"""Edge-compute backend parity: every SemiringSweep program must produce
the same answer on 'coo', 'pallas_tiles' and 'pallas_windows' — bit-identical
for the min_plus algorithms (SSSP float32, CC int32), allclose for the
plus_times accumulation (PageRank sums in a different order on the MXU
path) — on BOTH engine backends, cold and warm, through a live
stream-flush-then-query cycle, with trace-counter pins proving in-bucket
flushes retrace nothing per backend."""
import subprocess
import sys

import numpy as np
import pytest


from repro.algos import ConnectedComponents, PageRank, SSSP
from repro.algos.mssp import make_mssp
from repro.analysis.sanitizer import retrace_guard
from repro.core import (EngineConfig, partition_and_build,
                        resolve_edge_backend, run_sim)
from repro.core.layouts import build_edge_layouts
from repro.core.subgraph import ShapePolicy
from repro.graphgen import powerlaw_graph
from repro.session import GraphSession

PALLAS = ("pallas_tiles", "pallas_windows")
PR_TOL = dict(rtol=1e-5, atol=1e-8)     # plus_times reassociation tolerance


def _algos(nv):
    return [("sssp", SSSP(), {"source": 0}, True),
            ("cc", ConnectedComponents(), None, True),
            ("pagerank", PageRank(tol=1e-7), {"n_vertices": nv}, False)]


@pytest.fixture(scope="module")
def graph():
    return powerlaw_graph(900, seed=5, weighted=True).as_undirected()


@pytest.fixture(scope="module")
def pg(graph):
    return partition_and_build(graph, 4, "cdbh")


@pytest.fixture(scope="module")
def coo_sim(pg, graph):
    return {name: run_sim(prog, pg, params, EngineConfig())[0]
            for name, prog, params, _ in _algos(graph.n_vertices)}


def _check(name, exact, want, got):
    if exact:
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got),
                                      err_msg=name)
    else:
        np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                                   err_msg=name, **PR_TOL)


# --------------------------------------------------------------------------- #
# one-shot parity, simulator backend
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("eb", PALLAS)
def test_sim_parity_all_algos(pg, graph, coo_sim, eb):
    for name, prog, params, exact in _algos(graph.n_vertices):
        res, st = run_sim(prog, pg, params, EngineConfig(edge_backend=eb))
        _check(f"{name}/{eb}", exact, coo_sim[name], res)
        assert st.edge_backend == eb
        assert st.backend_flops > 0
        if eb == "pallas_tiles":
            assert 0.0 < st.tile_density <= 1.0


@pytest.mark.parametrize("semiring,kind,dtype", [
    ("min_plus", "weight", np.float32), ("min_plus", "zero", np.int32),
    ("plus_times", "one", np.float32)])
def test_tile_density_counted_without_dense_tiles(pg, semiring, kind, dtype):
    """The density stats count filled tile entries from the edges; they
    must agree with the dense realization they no longer build."""
    from repro.core.layouts import TM, TN
    from repro.kernels.ref import tile_pad_identity
    lay = build_edge_layouts(pg, ShapePolicy.exact())
    dens = lay.partition_density(pg, semiring, kind, dtype)
    assert not lay._tiles, "density must not realize dense tiles"
    tiles = lay.tile_values(pg, semiring, kind, dtype)
    filled = (tiles != tile_pad_identity(semiring, np.dtype(dtype))).sum(
        axis=(1, 2, 3))
    want = filled / np.maximum(lay.n_tiles * TM * TN, 1)
    np.testing.assert_allclose(dens, want, rtol=0, atol=0)
    assert lay.density(pg, semiring, kind, dtype) == pytest.approx(
        filled.sum() / (lay.n_tiles.sum() * TM * TN))


def test_cc_stays_int32_on_tiles(pg, coo_sim):
    """The dtype satellite: int32 min_plus rides the tile kernel without a
    float round-trip (labels above 2**24 would corrupt in float32)."""
    res, _ = run_sim(ConnectedComponents(), pg, None,
                     EngineConfig(edge_backend="pallas_tiles"))
    assert np.asarray(res).dtype == np.int32
    np.testing.assert_array_equal(coo_sim["cc"], res)


# --------------------------------------------------------------------------- #
# one-shot parity, shard_map backend — in a subprocess, like every other
# multi-device test in this suite: fake host devices must be requested
# before jax initializes, and the main pytest process has long since done
# that with a single CPU device
# --------------------------------------------------------------------------- #
SHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import numpy as np
from jax.sharding import Mesh

from repro.algos import ConnectedComponents, PageRank, SSSP
from repro.core import (EngineConfig, partition_and_build,
                        run_shard_map, run_sim)
from repro.graphgen import powerlaw_graph

g = powerlaw_graph(900, seed=5, weighted=True).as_undirected()
pg = partition_and_build(g, 4, "cdbh")
algos = [("sssp", SSSP(), {"source": 0}, True),
         ("cc", ConnectedComponents(), None, True),
         ("pagerank", PageRank(tol=1e-7), {"n_vertices": g.n_vertices},
          False)]
coo = {name: run_sim(prog, pg, params, EngineConfig())[0]
       for name, prog, params, _ in algos}

mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("sub",))
for eb in ("pallas_tiles", "pallas_windows"):
    cfg = EngineConfig(backend="shard_map", subgraph_axes=("sub",),
                       edge_backend=eb)
    for name, prog, params, exact in algos:
        res, st = run_shard_map(prog, pg, mesh, params, cfg)
        assert st.edge_backend == eb, (name, eb, st.edge_backend)
        if exact:
            np.testing.assert_array_equal(coo[name], np.asarray(res),
                                          err_msg=f"{name}/{eb}")
        else:
            np.testing.assert_allclose(coo[name], np.asarray(res),
                                       rtol=1e-5, atol=1e-8,
                                       err_msg=f"{name}/{eb}")

# edge-axis sharding: each partition's tile/window lists shard over the
# 'edge' mesh axis and the generated sweep's EdgeCombine epilogue reduces
# the per-shard partial segment results — results must stay bit-identical
# (min_plus) / allclose (PageRank) to the unsharded runs above
mesh2 = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("sub", "edge"))
for eb in ("pallas_tiles", "pallas_windows", "auto"):
    cfg2 = EngineConfig(backend="shard_map", subgraph_axes=("sub",),
                        edge_axes=("edge",), edge_backend=eb)
    for name, prog, params, exact in algos:
        res, st = run_shard_map(prog, pg, mesh2, params, cfg2)
        assert st.edge_backend == eb, (name, eb, st.edge_backend)
        if exact:
            np.testing.assert_array_equal(coo[name], np.asarray(res),
                                          err_msg=f"{name}/{eb}/sharded")
        else:
            np.testing.assert_allclose(coo[name], np.asarray(res),
                                       rtol=1e-5, atol=1e-8,
                                       err_msg=f"{name}/{eb}/sharded")
    if eb == "auto":
        assert len(st.partition_edge_backends) == pg.n_parts
print("SHARD_EB_OK")
"""


def test_shard_map_parity_and_edge_sharding():
    res = subprocess.run([sys.executable, "-c", SHARD_SCRIPT],
                         capture_output=True, text=True, timeout=1200)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "SHARD_EB_OK" in res.stdout


# --------------------------------------------------------------------------- #
# fallback: programs without a SemiringSweep always run COO
# --------------------------------------------------------------------------- #
def test_mssp_custom_sweep_falls_back_to_coo(pg):
    prog, params = make_mssp([0, 5, 9])
    cfg = EngineConfig(edge_backend="pallas_tiles")
    assert resolve_edge_backend(prog, cfg) == "coo"
    want, _ = run_sim(prog, pg, params, EngineConfig())
    got, st = run_sim(prog, pg, params, cfg)
    assert st.edge_backend == "coo"
    np.testing.assert_array_equal(want, got)


def test_engine_config_validates_edge_backend():
    with pytest.raises(ValueError, match="edge_backend"):
        EngineConfig(edge_backend="cusparse")


# --------------------------------------------------------------------------- #
# serving lifecycle: warm starts + stream-flush-then-query per backend
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("eb", ("coo",) + PALLAS)
def test_session_flush_then_query_cycle(graph, eb):
    """update -> flush -> warm query on every backend: the warm result is
    bit-identical to cold and converges in no more supersteps."""
    sess = GraphSession.from_graph(graph, 4, "cdbh",
                                   cfg=EngineConfig(edge_backend=eb))
    r0, _ = sess.query(SSSP(), {"source": 0})

    # attach a brand-new vertex through a long detour + one shortcut edge
    nv = sess.pg.n_vertices
    sess.update(adds=([0, nv], [nv, 1], [2.5, 2.5]))
    st = sess.flush()
    assert st.warm_start_safe

    warm, st_w = sess.query(SSSP(), {"source": 0})           # warm="auto"
    assert sess.stats.warm_queries == 1
    cold, st_c = sess.query(SSSP(), {"source": 0}, warm=False)
    np.testing.assert_array_equal(np.asarray(warm), np.asarray(cold))
    assert st_w.supersteps <= st_c.supersteps
    assert st_w.edge_backend == eb


@pytest.mark.parametrize("eb", PALLAS)
def test_inbucket_flush_zero_retraces(graph, eb):
    """The acceptance pin: a flush that stays inside every bucket (padded
    shapes AND layout capacities) must re-hit the compiled Pallas runner
    with zero retraces."""
    sess = GraphSession.from_graph(graph, 4, "cdbh",
                                   cfg=EngineConfig(edge_backend=eb))
    sess.query(SSSP(), {"source": 0})
    pg = sess.pg
    p = int(np.argmin(pg.edges_per_part))
    m = pg.emask[p]
    gs = int(pg.gvid[p][pg.esrc[p][m]][0])
    gd = int(pg.gvid[p][pg.edst[p][m]][0])
    lay = pg.edge_layouts
    caps_before = (lay.t_max, lay.b_max)
    sess.update(adds=([gs], [gd], [40.0]))
    sess.flush()
    assert (lay.t_max, lay.b_max) == caps_before, "in-bucket by design"
    with retrace_guard(label=f"{eb}: in-bucket flush requery"):
        _, st = sess.query(SSSP(), {"source": 0})
    assert st.compile_time == 0.0
    assert sess.stats.cache_misses == 1


def test_cross_backend_runners_coexist(graph):
    """One session serving mixed-backend traffic keeps one runner per
    backend (cfg is part of the cache key), all returning the same answer."""
    sess = GraphSession.from_graph(graph, 4, "cdbh")
    res = {}
    for eb in ("coo",) + PALLAS:
        res[eb], _ = sess.query(SSSP(), {"source": 2},
                                cfg=EngineConfig(edge_backend=eb),
                                warm=False)
    assert sess.stats.cache_misses == 3
    np.testing.assert_array_equal(res["coo"], res["pallas_tiles"])
    np.testing.assert_array_equal(res["coo"], res["pallas_windows"])
    # ...and repeat traffic hits all three
    for eb in ("coo",) + PALLAS:
        sess.query(SSSP(), {"source": 2},
                   cfg=EngineConfig(edge_backend=eb), warm=False)
    assert sess.stats.cache_misses == 3


# --------------------------------------------------------------------------- #
# incremental layout maintenance (stream/delta.py)
# --------------------------------------------------------------------------- #
def test_delta_rebuilds_only_touched_partitions(graph):
    """apply_delta refreshes layout geometry in place for the patched
    partitions and leaves the object (and untouched partitions' realized
    tiles) alone; the result matches a from-scratch build."""
    sess = GraphSession.from_graph(graph, 4, "cdbh",
                                   cfg=EngineConfig(edge_backend="pallas_tiles"))
    sess.query(SSSP(), {"source": 0})
    pg = sess.pg
    lay = pg.edge_layouts
    tiles_before = lay.tile_values(pg, "min_plus", "weight",
                                   np.float32).copy()
    p = int(np.argmin(pg.edges_per_part))
    m = pg.emask[p]
    gs = int(pg.gvid[p][pg.esrc[p][m]][0])
    gd = int(pg.gvid[p][pg.edst[p][m]][0])
    sess.update(adds=([gs], [gd], [0.125]))
    sess.flush()
    assert pg.edge_layouts is lay, "in-bucket delta must patch in place"

    fresh = build_edge_layouts(pg, lay.policy, lay.block_edges)
    tiles_inc = lay.tile_values(pg, "min_plus", "weight", np.float32)
    tiles_new = fresh.tile_values(pg, "min_plus", "weight", np.float32)
    np.testing.assert_array_equal(lay.n_tiles, fresh.n_tiles)
    np.testing.assert_array_equal(lay.n_blocks, fresh.n_blocks)
    for q in range(pg.n_parts):
        T = int(fresh.n_tiles[q])
        np.testing.assert_array_equal(lay.tile_dst[q, :T],
                                      fresh.tile_dst[q, :T])
        np.testing.assert_array_equal(tiles_inc[q, :T], tiles_new[q, :T])
        if q != p:
            np.testing.assert_array_equal(tiles_inc[q], tiles_before[q])


def test_compact_rebuilds_layouts(graph):
    """Compaction repacks the grid: layouts are rebuilt at assembly time and
    post-compact Pallas queries still match COO."""
    sess = GraphSession.from_graph(graph, 4, "cdbh",
                                   cfg=EngineConfig(edge_backend="pallas_windows"))
    sess.query(SSSP(), {"source": 0})
    lay0 = sess.pg.edge_layouts
    # delete a vertex's edges then compact
    m = sess.pg.emask[0]
    gs = sess.pg.gvid[0][sess.pg.esrc[0][m]]
    gd = sess.pg.gvid[0][sess.pg.edst[0][m]]
    sess.update(deletes=(gs[:3], gd[:3]))
    sess.flush()
    sess.compact()
    assert sess.pg.edge_layouts is not lay0, "compact rebuilds the layouts"
    got, st = sess.query(SSSP(), {"source": 0})
    assert st.edge_backend == "pallas_windows"
    want, _ = sess.query(SSSP(), {"source": 0},
                         cfg=EngineConfig(edge_backend="coo"), warm=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _assert_slots_sorted_unique(eslot):
    """The windowed product's scatter promises XLA sorted, unique slots:
    each row is a strictly ascending run of real slots, then padding."""
    for row in np.asarray(eslot):
        ne = int((row >= 0).sum())
        assert (row[ne:] == -1).all(), "padding edge before a real edge"
        assert (np.diff(row[:ne]) > 0).all(), "slots not strictly ascending"


@pytest.mark.parametrize("when", ["built", "flushed", "edge_sharded"])
def test_window_slots_sorted_and_unique(graph, when):
    sess = GraphSession.from_graph(
        graph, 4, "cdbh", cfg=EngineConfig(edge_backend="pallas_windows"))
    pg = sess.pg
    if when == "flushed":
        m = pg.emask[1]
        gs = pg.gvid[1][pg.esrc[1][m]]
        gd = pg.gvid[1][pg.edst[1][m]]
        sess.update(adds=([0, 7, 11], [900 - 1, 3, 5], [1.5, 2.5, 3.5]),
                    deletes=(gs[:4], gd[:4]))
        sess.flush()
    got, st = sess.query(SSSP(), {"source": 0}, warm=False)
    assert st.edge_backend == "pallas_windows"
    want, _ = sess.query(SSSP(), {"source": 0},
                         cfg=EngineConfig(edge_backend="coo"), warm=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    lay = pg.edge_layouts
    if when == "edge_sharded":
        eslot = lay._sharded_geometry(pg, 2)["eslot"]
        Se = lay.e_max // 2
        for s in range(2):
            _assert_slots_sorted_unique(eslot[:, s * Se:(s + 1) * Se])
    else:
        _assert_slots_sorted_unique(lay.eslot)


# --------------------------------------------------------------------------- #
# byte-accounted LRU (satellite: max_runner_bytes / max_warm_bytes)
# --------------------------------------------------------------------------- #
def test_runner_cache_byte_bound(graph):
    sess = GraphSession.from_graph(graph, 4, "cdbh")
    sess.query(SSSP(), {"source": 0})
    info = sess.cache_info()
    assert info and info[0]["nbytes"] > 0
    assert sess.stats.runner_cache_bytes == sum(e["nbytes"] for e in info)
    # a bound below one entry keeps exactly the newest runner
    sess.max_runner_bytes = info[0]["nbytes"] // 2
    sess.query(ConnectedComponents())
    assert len(sess.cache_info()) == 1
    assert sess.stats.cache_evictions_lru == 1
    assert sess.cache_info()[0]["program"] == "ConnectedComponents"


def test_warm_memory_byte_bound(graph):
    sess = GraphSession.from_graph(graph, 4, "cdbh")
    sess.query(SSSP(), {"source": 0})
    one = sess.stats.warm_cache_bytes
    assert one > 0
    sess.max_warm_bytes = int(one * 1.5)        # room for one entry only
    sess.query(SSSP(), {"source": 1})
    assert len(sess._warm) == 1
    assert sess.stats.warm_evictions == 1
    assert sess.stats.warm_cache_bytes <= sess.max_warm_bytes


# --------------------------------------------------------------------------- #
# lazy warm-block remap (satellite: pending-remap chain)
# --------------------------------------------------------------------------- #
def test_lazy_warm_remap_defers_until_use(graph):
    """N insert-only flushes cost zero remaps; the next warm query replays
    the pending chain once per logged flush, bit-identically to cold."""
    sess = GraphSession.from_graph(graph, 4, "cdbh")
    sess.query(SSSP(), {"source": 0})
    nv = sess.pg.n_vertices
    for i in range(3):
        sess.update(adds=([0], [nv + i], [3.0 + i]))
        sess.flush()
    assert sess.stats.warm_remaps_applied == 0, "flushes must not remap"
    assert len(sess._remap_log) == 3
    warm, _ = sess.query(SSSP(), {"source": 0})
    assert sess.stats.warm_remaps_applied == 3, "chain replayed on use"
    cold, _ = sess.query(SSSP(), {"source": 0}, warm=False)
    np.testing.assert_array_equal(np.asarray(warm), np.asarray(cold))
    # the entry written by the warm query is current: the log is prunable
    assert not sess._remap_log


def test_remap_log_cleared_by_deleting_flush(graph):
    sess = GraphSession.from_graph(graph, 4, "cdbh")
    sess.query(SSSP(), {"source": 0})
    nv = sess.pg.n_vertices
    sess.update(adds=([0], [nv], [3.0]))
    sess.flush()
    assert len(sess._remap_log) == 1
    sess.update(deletes=([0], [nv]))
    sess.flush()
    assert not sess._remap_log and not sess._warm
