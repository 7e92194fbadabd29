"""Kernel-layer roofline: the SVHM local sweep as (a) XLA gather/scatter,
(b) windowed one-hot segment-combine (Pallas, MXU for sums), (c) dense-tile
block-sparse SpMV (Pallas) — modeled v5e time per sweep from the layouts'
actual byte/FLOP footprints on a real Kronecker partition. Correctness of
both kernels vs the jnp oracle is asserted (interpret mode) on a subsample.

This is the dry-run-style profile for the kernel layer: CPU wall-times of
interpret mode are meaningless, the *layout-derived* roofline terms are the
deliverable (DESIGN.md §5).
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.core import autotune, partition_and_build
from repro.graphgen import kronecker_graph
from repro.kernels import ops
from repro.kernels.bsp_spmv import TM, TN

from benchmarks.common import save, table

HBM_BW = 819e9
PEAK = 197e12


def run(scale: str = "small"):
    g = kronecker_graph(13 if scale == "small" else 16, seed=3, weighted=True)
    pg = partition_and_build(g, 16, "cdbh")
    p = int(np.argmax(pg.edges_per_part))          # busiest partition
    m = pg.emask[p]
    src = pg.esrc[p][m].astype(np.int64)
    dst = pg.edst[p][m].astype(np.int64)
    w = pg.ew[p][m]
    nv = int(pg.vertices_per_part[p])
    ne = src.shape[0]

    # (a) XLA scatter path: read vals[src] (gather 4B) + edge ids (8B) +
    #     weights (4B) + scatter-combine writes (read+write 8B per edge)
    bytes_scatter = ne * (4 + 8 + 4 + 8) + nv * 8

    # (b) windowed one-hot kernel: edge messages (padded) + local_dst +
    #     out windows; FLOPs = onehot matmul 2*Be*W per block
    wl = ops.window_align_edges(dst, nv, block_edges=512)
    padded = wl.n_blocks * wl.block_edges
    bytes_window = padded * (4 + 4) + wl.n_windows * 128 * 4 * 2 + ne * 4
    flops_window = 2.0 * padded * 128

    # (c) dense-tile SpMV: tile bytes dominate; MXU flops 2*TM*TN per tile
    tl = ops.build_tiles(src, dst, w, nv, nv, "plus_times")
    ntiles = tl.tiles.shape[0]
    bytes_tiles = ntiles * TM * TN * 4 + ntiles * (TN + TM) * 4
    flops_tiles = 2.0 * ntiles * TM * TN

    rows = [
        ["xla-scatter", ne, "-", f"{bytes_scatter/2**20:.1f}",
         f"{bytes_scatter/HBM_BW*1e6:.1f}", "-", "serializing scatter"],
        ["windowed-onehot", padded, wl.n_blocks,
         f"{bytes_window/2**20:.1f}", f"{bytes_window/HBM_BW*1e6:.1f}",
         f"{flops_window/PEAK*1e6:.2f}", "MXU segment-sum"],
        ["dense-tiles", ntiles, f"density={tl.density:.4f}",
         f"{bytes_tiles/2**20:.1f}", f"{bytes_tiles/HBM_BW*1e6:.1f}",
         f"{flops_tiles/PEAK*1e6:.2f}",
         ("HBM-competitive (density>~1/3)" if tl.density > 1 / 3 else
          "too sparse for dense tiles -> use windowed-onehot")],
    ]
    table("Kernel roofline — one SVHM sweep on the busiest CDBH partition "
          f"({ne} edges, {nv} vertices)",
          ["impl", "units", "blocks", "MiB moved", "HBM µs", "MXU µs",
           "note"], rows)

    # correctness spot-check, interpret mode, subsample
    k = min(ne, 20_000)
    vals = np.random.default_rng(0).uniform(0, 2, (nv, 1)).astype(np.float32)
    got = np.asarray(ops.spmv(src[:k], dst[:k], w[:k], vals, nv,
                              semiring="plus_times", kernel="windowed"))
    dense = np.zeros((nv,), np.float32)
    np.add.at(dense, dst[:k], w[:k] * vals[src[:k], 0])
    np.testing.assert_allclose(got[:, 0], dense, rtol=2e-4, atol=2e-4)

    return save("kernel_roofline", {
        "edges": ne, "vertices": nv,
        "scatter_bytes": bytes_scatter,
        "window": dict(blocks=int(wl.n_blocks), padded_edges=int(padded),
                       bytes=bytes_window, flops=flops_window),
        "tiles": dict(n=int(ntiles), density=float(tl.density),
                      bytes=bytes_tiles, flops=flops_tiles),
    })


def crossover(smoke: bool = False):
    """Backend-crossover sweep: for the simulator's sweep key of each
    shipped semiring program (BFS, SSSP, CC, PageRank), report each grid
    point's fitted sweep latency per backend next to the
    ``edge_backend='auto'`` pick, and what the key's calibration took. With
    ``smoke`` the pick is asserted never slower than the worst manual
    backend at any point — the guardrail CI runs against the shipped
    policy."""
    from repro.algos import BFS, SSSP, ConnectedComponents, PageRank
    backends = autotune.BACKEND_ORDER
    out = []
    for prog in (BFS(), SSSP(), ConnectedComponents(), PageRank()):
        tbl = autotune.get_table(autotune.sweep_key(prog, "sim"))
        name, rows, points = type(prog).__name__, [], []
        for p in tbl.points:
            kw = dict(n_edges=[p["n_edges"]], n_vertices=p["n_vertices"],
                      n_tiles=[p["n_tiles"]], n_blocks=[p["n_blocks"]],
                      n_windows=p["n_windows"])
            fitted = {b: float(c[0])
                      for b, c in tbl.partition_costs(**kw).items()}
            (pick,) = tbl.pick(**kw)
            sampled = {"coo": p["cost_coo"], "pallas_tiles": p["cost_tiles"],
                       "pallas_windows": p["cost_windows"]}
            rows.append([p["n_vertices"], p["n_edges"]]
                        + [f"{fitted[b]*1e6:.2f}" for b in backends]
                        + [f"{sampled[b]*1e6:.2f}" for b in backends]
                        + [pick])
            points.append(dict(n_vertices=p["n_vertices"],
                               n_edges=p["n_edges"], pick=pick,
                               fitted_us={b: fitted[b] * 1e6
                                          for b in backends},
                               sampled_us={b: sampled[b] * 1e6
                                           for b in backends}))
            if smoke:
                worst = max(sampled.values())
                assert sampled[pick] <= worst * (1.0 + 1e-9), (
                    f"auto picked {pick} ({sampled[pick]:.3e}s) but the "
                    f"worst manual backend costs {worst:.3e}s at "
                    f"{p['n_vertices']} vertices, {p['n_edges']} edges")
        k = tbl.key
        table(f"Edge-backend crossover — {name} "
              f"({k.semiring}/{k.edge_values}/{k.dtype}, {k.engine}), "
              f"{tbl.source} calibration ({k.platform}, "
              f"{tbl.seconds:.1f}s), µs per partition sweep",
              ["nv", "edges"] + [f"{b} fit" for b in backends]
              + [f"{b} point" for b in backends] + ["auto pick"], rows)
        picks = {b: sum(1 for o in points if o["pick"] == b)
                 for b in backends}
        print(f"picks: {picks}" + ("  [smoke: pick never worst — OK]"
                                   if smoke else ""))
        out.append(dict(key=k._asdict(), program=name,
                        source=tbl.source, seconds=tbl.seconds,
                        unit_costs=tbl.unit_costs, points=points,
                        picks=picks))
    return save("kernel_crossover", {"tables": out})


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", default="small", choices=("small", "large"))
    ap.add_argument("--crossover", action="store_true",
                    help="sweep each sweep key's calibration grid and "
                         "report per-backend latency plus the auto "
                         "policy's pick")
    ap.add_argument("--smoke", action="store_true",
                    help="with --crossover: assert the auto pick is never "
                         "slower than the worst manual backend")
    a = ap.parse_args()
    if a.crossover:
        crossover(smoke=a.smoke)
    else:
        run(scale=a.scale)
