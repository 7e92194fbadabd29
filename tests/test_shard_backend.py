"""shard_map backend == simulator backend, run in a subprocess with 8 fake
host devices (2 pods x 2 data x 2 model: 4 subgraphs, edge lists sharded
2-way over the model axis — the hierarchical SVHM mapping of DESIGN.md §2)."""
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
from repro.core import partition_and_build, run_sim, run_shard_map, EngineConfig
from repro.graphgen import powerlaw_graph, grid_graph
from repro.algos import ConnectedComponents, SSSP, PageRank
from repro.algos.gsim import make_gsim

from repro.compat import make_mesh

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg_sim = EngineConfig(mode="sc")
cfg_shard = EngineConfig(mode="sc", backend="shard_map",
                         subgraph_axes=("pod", "data"), edge_axes=("model",))

g = powerlaw_graph(300, seed=5).as_undirected()
pg = partition_and_build(g, 4, "cdbh")
cc = ConnectedComponents()
r1, s1 = run_sim(cc, pg, None, cfg_sim)
r2, s2 = run_shard_map(cc, pg, mesh, None, cfg_shard)
assert (r1 == r2).all(), "CC mismatch"
assert s1.supersteps == s2.supersteps and s1.total_messages == s2.total_messages

g2 = grid_graph(12, weighted=True, seed=3)
pg2 = partition_and_build(g2, 4, "cdbh")
r1, _ = run_sim(SSSP(), pg2, {"source": 0}, cfg_sim)
r2, _ = run_shard_map(SSSP(), pg2, mesh, {"source": 0}, cfg_shard)
assert np.allclose(r1, r2), "SSSP mismatch"

gd = powerlaw_graph(300, seed=6)
pg3 = partition_and_build(gd, 4, "cdbh")
pr = PageRank(tol=1e-9)
r1, _ = run_sim(pr, pg3, {"n_vertices": gd.n_vertices}, cfg_sim)
r2, _ = run_shard_map(pr, pg3, mesh, {"n_vertices": gd.n_vertices}, cfg_shard)
assert np.allclose(r1, r2, atol=1e-6), "PR mismatch"

labels = np.random.default_rng(0).integers(0, 3, size=gd.n_vertices).astype(np.int32)
pg4 = partition_and_build(gd, 4, "cdbh")
pg4.set_vertex_labels(labels)
prog, params = make_gsim(np.array([[0,1,0],[0,0,1],[0,0,0]], np.int32),
                         np.array([0,1,2], np.int32))
r1, _ = run_sim(prog, pg4, params, cfg_sim)
r2, _ = run_shard_map(prog, pg4, mesh, params, cfg_shard)
assert (r1 == r2).all(), "GSim mismatch"

# compacted sparse SBS == dense SBS
cfg_sparse = EngineConfig(mode="sc", backend="shard_map",
                          subgraph_axes=("pod", "data"), edge_axes=("model",),
                          sparse_sync_capacity=pg.n_slots + 1)
r3, _ = run_shard_map(cc, pg, mesh, None, cfg_sparse)
r4, _ = run_sim(cc, pg, None, cfg_sim)
assert (r3 == r4).all(), "sparse-sync mismatch"

# sharded-SBS (slot shards over the model axis) == dense SBS
cfg_ss = EngineConfig(mode="sc", backend="shard_map",
                      subgraph_axes=("pod", "data"), edge_axes=("model",),
                      shard_slots=True)
r7, s7 = run_shard_map(cc, pg, mesh, None, cfg_ss)
assert (r7 == run_sim(cc, pg, None, cfg_sim)[0]).all(), "shard_slots CC"
r8, _ = run_shard_map(pr, pg3, mesh, {"n_vertices": gd.n_vertices}, cfg_ss)
r9, _ = run_sim(pr, pg3, {"n_vertices": gd.n_vertices}, cfg_sim)
assert np.allclose(r8, r9, atol=1e-6), "shard_slots PR"

# 2D mesh without edge sharding (subgraph axes only)
mesh2 = make_mesh((8,), ("sub",))
pg8 = partition_and_build(g, 8, "cdbh")
cfg8 = EngineConfig(mode="sc", backend="shard_map", subgraph_axes=("sub",))
r5, _ = run_shard_map(cc, pg8, mesh2, None, cfg8)
r6, _ = run_sim(cc, pg8, None, cfg_sim)
assert (r5 == r6).all(), "8-way mismatch"

# ---- device-side warm start after an insert-only delta -------------------- #
import tempfile
from repro.core import run
from repro.stream import EdgeDelta, apply_delta, compact, streaming_ingest, \
    write_edge_log
gw = powerlaw_graph(400, seed=7, weighted=True).as_undirected()
logd = tempfile.mkdtemp(prefix="drone_shard_log_")
write_edge_log(gw, logd, chunk_size=4096)
pgw, ctx, _ = streaming_ingest(logd, 4, "cdbh")
sssp = SSSP()
r0, _ = run_sim(sssp, pgw, {"source": 0}, cfg_sim)
prev = pgw.collect(r0, fill=np.float32(np.inf))
rng = np.random.default_rng(8)
n_add = max(gw.n_edges // 100, 16)
s = rng.integers(0, pgw.n_vertices, n_add)
d = rng.integers(0, pgw.n_vertices, n_add)
keep = s != d
s, d = s[keep], d[keep]
w = rng.uniform(5, 10, s.size).astype(np.float32)
st = apply_delta(pgw, ctx, EdgeDelta(add_src=np.concatenate([s, d]),
                                     add_dst=np.concatenate([d, s]),
                                     add_w=np.concatenate([w, w])))
assert st.warm_start_safe
cold, st_c = run_shard_map(sssp, pgw, mesh, {"source": 0}, cfg_shard)
warm, st_w = run_shard_map(sssp, pgw, mesh, {"source": 0}, cfg_shard,
                           init_state=prev)
assert (np.asarray(cold) == np.asarray(warm)).all(), "warm != cold bit-for-bit"
assert st_w.supersteps < st_c.supersteps, (st_w.supersteps, st_c.supersteps)
sim_warm, sim_sw = run_sim(sssp, pgw, {"source": 0}, cfg_sim, init_state=prev)
assert (np.asarray(warm) == np.asarray(sim_warm)).all(), "shard warm != sim warm"
assert st_w.supersteps == sim_sw.supersteps, "warm superstep parity"
# run() routes init_state to the shard_map backend and rejects resume_from
r_run, st_run = run(sssp, pgw, {"source": 0}, cfg_shard, mesh=mesh,
                    init_state=prev)
assert (np.asarray(r_run) == np.asarray(warm)).all()
assert st_run.supersteps == st_w.supersteps
try:
    run(sssp, pgw, {"source": 0}, cfg_shard, mesh=mesh, resume_from="x")
    raise SystemExit("resume_from on shard_map must raise")
except NotImplementedError:
    pass

# shard_map on a compacted graph == sim (n_slots shrank under the runner)
dels = EdgeDelta(del_src=np.concatenate([gw.src[::2], gw.dst[::2]]),
                 del_dst=np.concatenate([gw.dst[::2], gw.src[::2]]))
apply_delta(pgw, ctx, dels)
cs = compact(pgw, ctx)
assert cs.shrunk and pgw.n_slots < cs.n_slots_before
rs, _ = run_shard_map(sssp, pgw, mesh, {"source": 0}, cfg_shard)
rss, _ = run_sim(sssp, pgw, {"source": 0}, cfg_sim)
assert (np.asarray(rs) == np.asarray(rss)).all(), "compacted shard != sim"

# ---- total_bytes matches the exchange actually used ----------------------- #
ns = pg.n_slots
itm = np.dtype(np.float32).itemsize
r_d, s_d = run_shard_map(cc, pg, mesh, None, cfg_shard)
assert s_d.total_bytes == s_d.supersteps * (ns + 1) * itm * 4, "dense bytes"
cap = max(ns // 4, 1)
cfg_sp = EngineConfig(mode="sc", backend="shard_map",
                      subgraph_axes=("pod", "data"), edge_axes=("model",),
                      sparse_sync_capacity=cap)
r_sp, s_sp = run_shard_map(cc, pg, mesh, None, cfg_sp)
assert (np.asarray(r_sp) == np.asarray(r_d)).all()
assert s_sp.total_bytes == s_sp.supersteps * cap * (4 + itm) * 4, "sparse bytes"
assert s_sp.total_bytes < s_d.total_bytes, "sparse SBS must bill fewer bytes"
n_loc = -(-(ns + 1) // 2)
r_ss, s_ss = run_shard_map(cc, pg, mesh, None, cfg_ss)
assert s_ss.total_bytes == s_ss.supersteps * (n_loc + 1) * itm * 4 * 2, \
    "sharded bytes"

# ---- a shard_map GraphSession query bills what run_shard_map bills -------- #
import dataclasses
from repro.core import ShapePolicy
from repro.session import GraphSession
BILL = ("supersteps", "total_messages", "total_bytes", "processed_edges",
        "backend_flops", "tile_density", "edge_backend",
        "partition_edge_backends", "partition_tile_density",
        "partition_flops", "partition_edge_counts")
for prog, params, c in ((cc, None, cfg_shard), (cc, None, cfg_ss),
                        (SSSP(), {"source": 0},
                         dataclasses.replace(cfg_shard,
                                             edge_backend="pallas_windows"))):
    pgs = partition_and_build(g, 4, "cdbh")
    sess = GraphSession(pgs, mesh=mesh, cfg=c,
                        shape_policy=ShapePolicy.exact())
    r_q, s_q = sess.query(prog, params, warm=False)
    r_r, s_r = run_shard_map(prog, sess.pg, mesh, params, c)
    assert (np.asarray(r_q) == np.asarray(r_r)).all(), "session != one-shot"
    for field in BILL:
        assert getattr(s_q, field) == getattr(s_r, field), \
            (field, getattr(s_q, field), getattr(s_r, field))
print("SHARD_BACKEND_OK")
"""


def test_shard_map_backend_matches_sim():
    res = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "SHARD_BACKEND_OK" in res.stdout
