import os
os.environ["XLA_FLAGS"] = os.environ.get("DRYRUN_XLA_FLAGS",
                                         "--xla_force_host_platform_device_count=512")
os.environ["JAX_PLATFORMS"] = "cpu"   # compile-only: never take an accelerator
# ^ MUST precede any jax import (see dryrun.py).

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import time          # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np   # noqa: E402

from repro.algos import ConnectedComponents, PageRank, SSSP  # noqa: E402
from repro.core.api import DeviceSubgraph                    # noqa: E402
from repro.core.engine import EngineConfig, make_bsp_runner  # noqa: E402
from repro.launch import hlo_stats, hlo_walk                 # noqa: E402
from repro.launch.mesh import make_mesh, make_production_mesh  # noqa: E402

"""Graph-engine multi-pod dry-run — the paper's own workload on the
production mesh, including the TRILLION-EDGE capability point (the paper's
headline: 'orders of magnitude larger than previously reported by SC
frameworks').

Subgraph arrays are ShapeDtypeStruct stand-ins sized from (n_edges, n_parts,
replication-factor estimate); the BSP superstep loop (engine.make_bsp_runner:
local fixed-point sweeps + SBS combiner all-reduce) is lowered + compiled for
(pod, data) x model = 512 chips. memory_analysis proves the per-device
footprint fits; the roofline terms come from the compiled HLO.
"""


@dataclasses.dataclass
class GraphScale:
    name: str
    n_edges: int
    n_vertices: int
    rf: float = 4.0          # replication factor estimate (CDBH, power-law)
    frontier_frac: float = 0.5

    def meta(self, n_parts, edge_shards, pad=1.05):
        e_max = int(self.n_edges / n_parts * pad)
        e_max = -(-e_max // (128 * edge_shards)) * (128 * edge_shards)
        v_max = int(self.n_vertices * self.rf / n_parts * pad)
        v_max = -(-v_max // 128) * 128
        n_slots = min(int(self.n_vertices * self.frontier_frac),
                      v_max * n_parts)
        return dict(e_max=e_max, v_max=v_max, n_slots=n_slots)


SCALES = {
    "kron26": GraphScale("kron26", 2 ** 26 * 16 * 2, 2 ** 26),       # 2.1B
    "kron30": GraphScale("kron30", 2 ** 30 * 16 * 2, 2 ** 30),       # 34B
    "kron33-100B": GraphScale("kron33-100B", 2 ** 33 * 16, 2 ** 33),  # 137B
    # 1.1T edges (Kronecker scale-34, edge-factor 64): raw capacity needs
    # >= 4 v5e pods (13TB of edges), so this runs on an 8-pod
    # (8,16,16)=2048-chip mesh — the 1000+-node design point. Requires
    # DRYRUN_XLA_FLAGS=--xla_force_host_platform_device_count=2048
    "trillion": GraphScale("trillion", 2 ** 40, 2 ** 34, rf=2.5,
                           frontier_frac=0.25),                      # 1.1T
}
TRILLION_MESH = (8, 16, 16)
INT32_LIMIT = 2 ** 31

ALGOS = {
    "cc": (ConnectedComponents, None),
    "sssp": (SSSP, {"source": jnp.int32(0)}),
    "pagerank": (PageRank, {"n_vertices": 2.0 ** 30}),
}


def _sds_subgraph(meta, n_parts, mesh, sub_axes, edge_axes):
    from jax.sharding import NamedSharding, PartitionSpec as P
    e, v = meta["e_max"], meta["v_max"]
    espec = NamedSharding(mesh, P(sub_axes, edge_axes or None))
    vspec = NamedSharding(mesh, P(sub_axes, None))

    def E(dt):
        return jax.ShapeDtypeStruct((n_parts, e), dt, sharding=espec)

    def V(dt):
        return jax.ShapeDtypeStruct((n_parts, v), dt, sharding=vspec)

    return DeviceSubgraph(
        esrc=E(jnp.int32), edst=E(jnp.int32), ew=E(jnp.float32),
        emask=E(jnp.bool_), slot=V(jnp.int32), vmask=V(jnp.bool_),
        vid32=V(jnp.int32), is_frontier=V(jnp.bool_), out_deg=V(jnp.float32),
        in_deg=V(jnp.float32), is_master=V(jnp.bool_),
        local_paths=jax.ShapeDtypeStruct(
            (n_parts,), jnp.bool_,
            sharding=NamedSharding(mesh, P(sub_axes))),
        vlabel=None)


def lower_graph_cell(scale_name: str, algo: str, multi_pod: bool,
                     *, max_local_iters=64, dense_slots=False,
                     lean=True):
    if scale_name == "trillion":
        if len(jax.devices()) < int(np.prod(TRILLION_MESH)):
            raise RuntimeError(
                "trillion point needs a 2048-chip mesh: rerun with "
                "DRYRUN_XLA_FLAGS=--xla_force_host_platform_device_count=2048")
        mesh = make_mesh(TRILLION_MESH, ("pod", "data", "model"))
        sub_axes = ("pod", "data")
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
        sub_axes = ("pod", "data") if multi_pod else ("data",)
    edge_axes = ("model",)
    n_parts = int(np.prod([mesh.shape[a] for a in sub_axes]))
    sc = SCALES[scale_name]
    meta = sc.meta(n_parts, mesh.shape["model"])
    if meta["v_max"] >= INT32_LIMIT:
        raise ValueError(
            f"per-partition vertex table v_max={meta['v_max']:.3e} exceeds "
            "int32 local indexing — scale out to more subgraphs "
            "(design constraint, DESIGN.md §7)")

    prog_cls, params = ALGOS[algo]
    prog = prog_cls()
    cfg = EngineConfig(mode="sc", backend="shard_map",
                       subgraph_axes=sub_axes, edge_axes=edge_axes,
                       max_local_iters=max_local_iters,
                       shard_slots=not dense_slots, lean_frontier=lean)
    go = make_bsp_runner(prog, mesh, cfg, meta["n_slots"], has_vlabel=False)
    sgs = _sds_subgraph(meta, n_parts, mesh, sub_axes, edge_axes)
    with mesh:
        lowered = jax.jit(go).lower(sgs, params)
        compiled = lowered.compile()
    return meta, n_parts, compiled


def run_cell(scale_name, algo, mesh_kind, out_dir, force=False,
             variant="opt"):
    suffix = "" if variant == "opt" else f"__{variant}"
    path = os.path.join(out_dir,
                        f"graph__{scale_name}__{algo}__{mesh_kind}{suffix}.json")
    if os.path.exists(path) and not force:
        return json.load(open(path))
    rec = {"scale": scale_name, "algo": algo, "mesh": mesh_kind,
           "kind": "graph_engine", "variant": variant}
    t0 = time.time()
    try:
        meta, n_parts, compiled = lower_graph_cell(
            scale_name, algo, mesh_kind == "multipod",
            dense_slots=(variant == "dense"), lean=(variant != "dense"))
        txt = compiled.as_text()
        rec.update(status="ok", compile_s=round(time.time() - t0, 1),
                   meta=meta, n_parts=n_parts,
                   cost=hlo_stats.cost_stats(compiled),
                   memory=hlo_stats.memory_stats(compiled),
                   collectives=hlo_stats.collective_stats(txt),
                   walk=hlo_walk.analyze(txt))
    except (RuntimeError, ValueError) as e:
        # capacity/topology constraints -> documented skip, not a bug
        rec.update(status="skipped", reason=str(e))
    except Exception as e:
        import traceback
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    os.makedirs(out_dir, exist_ok=True)
    json.dump(rec, open(path, "w"), indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="all")
    ap.add_argument("--algo", default="cc")
    ap.add_argument("--mesh", default="both")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="opt", choices=["opt", "dense"])
    args = ap.parse_args()
    scales = list(SCALES) if args.scale == "all" else [args.scale]
    meshes = ["single", "multipod"] if args.mesh == "both" else [args.mesh]
    algos = list(ALGOS) if args.algo == "all" else [args.algo]
    bad = 0
    for s in scales:
        for a in algos:
            for mk in meshes:
                rec = run_cell(s, a, mk, args.out, args.force,
                               variant=args.variant)
                ok = rec["status"] == "ok"
                bad += not ok
                if ok:
                    mem = rec["memory"].get("temp_size_in_bytes", 0)
                    arg = rec["memory"].get("argument_size_in_bytes", 0)
                    print(f"[ok   ] graph {s:12s} {a:8s} {mk:8s} "
                          f"temp={mem/2**30:.2f}GiB args={arg/2**30:.1f}GiB "
                          f"coll/step~{rec['walk']['collective_bytes_per_device']/2**20:.1f}MiB",
                          flush=True)
                else:
                    print(f"[error] graph {s} {a} {mk}: {rec['error'][:200]}",
                          flush=True)
    if bad:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
