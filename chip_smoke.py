#!/usr/bin/env python3
"""Chip smoke test: serve graph queries from a resident Graph500 Kronecker
graph through ``GraphSession`` on a TPU, and check every answer against a
plain scipy / numpy reference that does not use the engine.

    python chip_smoke.py               # one chip: scale-20 graph, 16 parts
    python chip_smoke.py --chips 4     # only the shard_map path, 4 chips
    JAX_PLATFORMS=cpu python chip_smoke.py --scale 10   # CPU rehearsal

One chip: connected components, SSSP from two sources and BFS on the
``coo`` and ``pallas_windows`` edge backends over the full graph, and on
``auto`` (BFS, and each query whose sweep key's assignment is not all on
one of those two); one repeated query that must hit the compiled-runner cache; one small
``update`` + ``flush`` followed by a warm query. Then, on the largest
graph of scale <= 16 whose dense tiles fit: PageRank on those three
backends and every query on ``pallas_tiles``. ``--chips 4``: a session over
a 4-chip mesh (axis ``sub``, one partition per chip — the shard_map backend
maps partitions to devices one to one), CC and SSSP on ``coo`` and
``pallas_windows``, and each device's share of the graph.

The default scale keeps a run inside 20 minutes on a v5e. At scale 22 the
run passed every query it reached but was stopped at 1300 s, two phases
short: host set-up took 295 s and each query 42-105 s. Full-scale
PageRank does not fit at scale 20 either (478 s on ``coo``). PERF.md has
the per-phase times.

Times printed are information, not metrics. The last line of stdout is
``{"ok": true, "device": {...}}`` only when every check passed on a TPU;
on any other platform the phases run (with ``--scale``) but the script
exits non-zero, and without ``--scale`` it stops before building anything.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import scipy.sparse as sp  # noqa: E402
from scipy.sparse import csgraph  # noqa: E402

from repro.algos import BFS, SSSP, ConnectedComponents, PageRank  # noqa: E402
from repro.caches import enable_compile_cache  # noqa: E402
from repro.compat import make_mesh  # noqa: E402
from repro.core import EngineConfig  # noqa: E402
from repro.core import autotune  # noqa: E402
from repro.graphgen import kronecker_graph  # noqa: E402
from repro.kernels.bsp_spmv import TM, TN, default_interpret  # noqa: E402
from repro.session import GraphSession  # noqa: E402

DEFAULT_SCALE = 20
SMALL_SCALE = 16
N_PARTS = 16
SEED = 0
ALPHA = 0.85
#: PageRank limit, as a share of the largest rank: good f32 runs on a v5e
#: miss by 7.5e-5 of it, a tile product at the MXU's single bf16 pass by
#: 1.1e-3 (PERF.md)
PR_TOL = 2e-4
TILE_BYTES = TM * TN * 4
T0 = time.perf_counter()


def pr_seed(n: int) -> float:
    """Every vertex's PageRank seed mass ``(1 - alpha) / N``."""
    return (1.0 - ALPHA) / n


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T0:8.1f}s] {msg}", flush=True)


class Checks:
    """Pass/fail per named check; a failure is recorded, not raised, so
    one run reports every phase."""

    def __init__(self):
        self.failed: list = []
        self.n = 0

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        self.n += 1
        log(f"{'PASS' if ok else 'FAIL'} {name}{' — ' + detail if detail else ''}")
        if not ok:
            self.failed.append(name)
        return ok


# --------------------------------------------------------------------------- #
# references: scipy / numpy over the plain edge list
# --------------------------------------------------------------------------- #
class Reference:
    """Answers of a plain implementation over the graph's edge list."""

    def __init__(self, g):
        n = g.n_vertices
        self.n = n
        self.A = sp.csr_matrix((g.weights.astype(np.float64), (g.src, g.dst)),
                               shape=(n, n))
        self.outd = np.bincount(g.src, minlength=n).astype(np.float64)
        self.degree = self.outd + np.bincount(g.dst, minlength=n)

    def cc(self) -> np.ndarray:
        """Min vertex id of each weakly connected component."""
        _, comp = csgraph.connected_components(self.A, directed=True,
                                               connection="weak")
        _, first = np.unique(comp, return_index=True)
        return first[comp].astype(np.int64)   # vertices are visited in order

    def sssp(self, source: int) -> np.ndarray:
        return csgraph.dijkstra(self.A, directed=True, indices=source)

    def bfs(self, source: int) -> np.ndarray:
        return csgraph.shortest_path(self.A, directed=True, unweighted=True,
                                     indices=source)

    def pagerank(self, tol: float) -> np.ndarray:
        """``sum_k alpha^k M^k r`` with ``r = (1-alpha)/N`` and dangling mass
        not redistributed — the fixed point ``PageRank`` converges to."""
        At = self.A.T.tocsr()
        At.data[:] = 1.0
        rate = np.where(self.outd > 0, ALPHA / np.maximum(self.outd, 1), 0.0)
        cur = np.full(self.n, pr_seed(self.n))
        pr = np.zeros(self.n)
        while cur.max() > tol * 1e-2:
            pr += cur
            cur = At @ (cur * rate)
        return pr


def held_mask(pg) -> np.ndarray:
    """Vertices the partitioned graph holds (a master replica somewhere)."""
    m = np.zeros(pg.n_vertices, bool)
    m[pg.gvid[pg.vmask & pg.is_master]] = True
    return m


def compare(kind: str, got, want, held, degree) -> tuple:
    """(ok, detail). Vertices without edges are not held by any partition;
    their reference answer must be the trivial one."""
    lone = ~held
    if kind == "cc":
        ok = np.array_equal(got[held], want[held]) and \
            np.array_equal(want[lone], np.nonzero(lone)[0])
        bad = int((got[held] != want[held]).sum())
        return ok and not (degree[lone] > 0).any(), f"{bad} labels differ"
    if kind == "pagerank":
        g, w = got[held].astype(np.float64), want[held]
        err = float(np.abs(g - w).max()) if held.any() else 0.0
        scale = float(w.max()) if held.any() else 1.0
        return err <= PR_TOL * scale, \
            f"max abs err {err:.3e} (max rank {scale:.3e})"
    g, w = got[held].astype(np.float64), want[held]
    fin = np.isfinite(w)
    ok = np.array_equal(np.isfinite(g), fin)
    if kind == "bfs":
        ok = ok and np.array_equal(g[fin], w[fin])
    else:
        ok = ok and np.allclose(g[fin], w[fin], rtol=1e-5, atol=1e-4)
    err = float(np.abs(g[fin] - w[fin]).max()) if fin.any() else 0.0
    return ok, f"{int(fin.sum())} reachable, max abs err {err:.3e}"


def pick_sources(g, k: int = 2) -> list:
    """The ``k`` highest-degree vertices: sources in the giant component."""
    deg = np.bincount(g.src, minlength=g.n_vertices)
    return [int(v) for v in np.argsort(-deg, kind="stable")[:k]]


def query_suite(g, sources, ref: Reference, kinds):
    """(name, program, params, kind, reference answer) per query."""
    qs = []
    if "cc" in kinds:
        qs.append(("cc", ConnectedComponents(), None, "cc", ref.cc()))
    if "sssp" in kinds:
        qs += [(f"sssp[{s}]", SSSP(), {"source": s}, "sssp", ref.sssp(s))
               for s in sources]
    if "bfs" in kinds:
        qs.append((f"bfs[{sources[0]}]", BFS(), {"source": sources[0]}, "bfs",
                   ref.bfs(sources[0])))
    if "pagerank" in kinds:
        tol = 1e-5 * pr_seed(g.n_vertices)
        qs.append(("pagerank", PageRank(tol=tol),
                   {"n_vertices": g.n_vertices}, "pagerank",
                   ref.pagerank(tol)))
    return qs


def last_runner(sess):
    return next(reversed(sess._runner_cache.entries.values())).compiled


def run_queries(sess, queries, backend: str, check: Checks, on_tpu: bool,
                ref: Reference) -> None:
    held = held_mask(sess.pg)
    cfg = EngineConfig(edge_backend=backend)
    for name, prog, params, kind, want in queries:
        fill = -1 if kind == "cc" else (0.0 if kind == "pagerank"
                                        else np.float32(np.inf))
        try:
            res, st = sess.query(prog, params, cfg=cfg, warm=False)
        except Exception:
            log(traceback.format_exc())
            check(f"{backend}/{name}", False, "raised")
            continue
        got = sess.pg.collect(res, fill=fill)
        ok, detail = compare(kind, got, want, held, ref.degree)
        check(f"{backend}/{name}", ok,
              f"{detail}; supersteps={st.supersteps} "
              f"compile={st.compile_time:.2f}s wall={st.wall_time:.3f}s")
        # 'auto' may rightly assign every partition to coo
        kernels = set(st.partition_edge_backends or [backend]) - {"coo"}
        if kernels and on_tpu and st.compile_time > 0:
            check(f"{backend}/{name} runner holds a Pallas kernel",
                  "tpu_custom_call" in last_runner(sess).as_text())


# --------------------------------------------------------------------------- #
# phases
# --------------------------------------------------------------------------- #
def build(scale: int, n_parts: int, **kw):
    t = time.perf_counter()
    g = kronecker_graph(scale, seed=SEED, weighted=True)
    log(f"kronecker scale {scale}: {g.n_vertices} vertices, {g.n_edges} "
        f"directed edges ({time.perf_counter() - t:.1f}s)")
    t = time.perf_counter()
    sess = GraphSession.from_graph(g, n_parts=n_parts, partitioner="cdbh",
                                   **kw)
    pg = sess.pg
    log(f"session: {n_parts} parts, v_max={pg.v_max} e_max={pg.e_max} "
        f"slots={sess.slot_capacity} ({time.perf_counter() - t:.1f}s)")
    return g, sess


def references(g, sources, kinds):
    t = time.perf_counter()
    ref = Reference(g)
    qs = query_suite(g, sources, ref, kinds)
    log(f"references ({time.perf_counter() - t:.1f}s)")
    return ref, qs


def tile_bytes(sess) -> tuple:
    lay = sess.pg.ensure_edge_layouts(shape_policy=sess.shape_policy)
    log(f"layouts: t_max={lay.t_max} b_max={lay.b_max} "
        f"(block of {lay.block_edges} edges)")
    return int(lay.n_tiles.sum()), lay.n_parts * lay.t_max * TILE_BYTES


def one_chip(args, check: Checks, on_tpu: bool) -> None:
    g, sess = build(args.scale, N_PARTS)
    ref, queries = references(g, pick_sources(g), ("cc", "sssp", "bfs"))
    checked = ("coo", "pallas_windows")
    for backend in checked:
        run_queries(sess, queries, backend, check, on_tpu, ref)
    run_auto(sess, queries, check, on_tpu, ref, checked)
    n_tiles, nbytes = tile_bytes(sess)
    budget = device_limit() // 8
    log(f"pallas_tiles at scale {args.scale}: {n_tiles} tiles, "
        f"{nbytes / 2**30:.2f} GiB padded; budget {budget / 2**30:.2f} GiB "
        f"(1/8 of device memory)")
    served(sess, g, queries[-1], check)
    sess.close()
    del sess, ref, queries

    # PageRank needs hundreds of local sweeps (478 s on COO at scale 20 on
    # a v5e), and pallas_tiles one dense 64 KiB tile per occupied 128x128
    # block: both run on the largest smaller graph whose tiles fit
    scale = min(args.scale, SMALL_SCALE)
    while True:
        g, sess = build(scale, N_PARTS)
        n_tiles, nbytes = tile_bytes(sess)
        log(f"pallas_tiles at scale {scale}: {n_tiles} tiles, "
            f"{nbytes / 2**20:.1f} MiB padded")
        if nbytes <= budget or scale <= 4:
            break
        sess.close()
        scale -= 1
    if check("a tile layout fits", nbytes <= budget):
        ref, queries = references(g, pick_sources(g),
                                  ("cc", "sssp", "bfs", "pagerank"))
        pagerank = [q for q in queries if q[3] == "pagerank"]
        for backend in ("coo", "pallas_windows", "auto"):
            run_queries(sess, pagerank, backend, check, on_tpu, ref)
        run_queries(sess, queries, "pallas_tiles", check, on_tpu, ref)
    sess.close()


def run_auto(sess, queries, check: Checks, on_tpu: bool, ref: Reference,
             checked) -> None:
    """``'auto'`` under the calibration of each query's sweep key (measured
    on a TPU). A query whose assignment puts every partition on one
    backend already ``checked`` computes what that backend computed, and
    is skipped; BFS, the cheapest, runs in any case to show ``auto``
    runs."""
    cfg = EngineConfig(edge_backend="auto")
    run = []
    for q in queries:
        prog = q[1]
        t = time.perf_counter()
        table = autotune.get_table(autotune.sweep_key(prog, cfg.backend))
        k = table.key
        log(f"autotune table {k.semiring}/{k.edge_values}/{k.dtype} on "
            f"{k.platform!r}: {table.source} "
            f"({time.perf_counter() - t:.1f}s here, calibration "
            f"{table.seconds:.1f}s) unit costs "
            f"{json.dumps(table.unit_costs)}")
        check(f"auto/{q[0]} calibration measured on a TPU, modeled "
              f"elsewhere",
              table.source == ("measured" if on_tpu else "modeled"))
        asg = sess._resolve_assignment(prog, cfg)
        log(f"auto assignment for {q[0]}: {dict(collections.Counter(asg))}")
        if len(set(asg)) > 1 or asg[0] not in checked or q is queries[-1]:
            run.append(q)
    run_queries(sess, run, "auto", check, on_tpu, ref)


def served(sess, g, repeat, check: Checks) -> None:
    """Query ``repeat`` (run on ``coo`` before) again: a runner-cache hit.
    Then a small insert-only update is answered warm, and right."""
    name, prog, params, _, _ = repeat
    _, st = sess.query(prog, params, cfg=EngineConfig(), warm=False)
    check(f"repeat coo/{name} is a runner-cache hit", st.compile_time == 0.0,
          f"compile_time={st.compile_time} wall={st.wall_time:.3f}s")
    rng = np.random.default_rng(SEED + 7)
    u = rng.integers(0, g.n_vertices, 4)
    v = rng.integers(0, g.n_vertices, 4)
    u, v = u[u != v], v[u != v]
    w = np.ones(u.size, np.float32)
    warm_before = sess.stats.warm_queries
    t = time.perf_counter()
    sess.update(adds=(np.r_[u, v], np.r_[v, u], np.r_[w, w]))
    sess.flush()
    log(f"update + flush of {2 * u.size} edges "
        f"({time.perf_counter() - t:.1f}s)")
    res, st = sess.query(ConnectedComponents(), cfg=EngineConfig())
    ref = Reference(type(g)(g.n_vertices, np.r_[g.src, u, v],
                            np.r_[g.dst, v, u], np.r_[g.weights, w, w]))
    ok, detail = compare("cc", sess.pg.collect(res, fill=-1), ref.cc(),
                         held_mask(sess.pg), ref.degree)
    check("warm CC after update+flush",
          ok and sess.stats.warm_queries == warm_before + 1,
          f"{detail}; warm_queries {warm_before}->{sess.stats.warm_queries} "
          f"compile={st.compile_time:.2f}s wall={st.wall_time:.3f}s")


def four_chips(args, check: Checks, on_tpu: bool) -> None:
    n = len(jax.devices())
    if n != 4:
        check("four devices", False, f"found {n}")
        return
    mesh = make_mesh((4,), ("sub",))
    g, sess = build(args.scale, 4, mesh=mesh)
    sources = pick_sources(g, 1)
    ref, queries = references(g, sources, ("cc", "sssp"))
    for backend in ("coo", "pallas_windows"):
        run_queries(sess, queries, backend, check, on_tpu, ref)
    dev = sess.device_graph()
    share = {}
    for leaf in jax.tree.leaves(dev):
        for s in leaf.addressable_shards:
            share[s.device.id] = share.get(s.device.id, 0) + s.data.nbytes
    for d in jax.devices():
        stats = d.memory_stats() or {}
        log(f"device {d.id}: resident graph share {share.get(d.id, 0)} B, "
            f"bytes_in_use {stats.get('bytes_in_use', 'n/a')}")
    check("every device holds its share of the graph",
          len(share) == 4 and min(share.values()) > 0 and
          max(share.values()) <= 2 * min(share.values()))


def device_limit() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit", 16 * 2**30))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--scale", type=int, default=None,
                    help=f"Kronecker scale (default {DEFAULT_SCALE}); off "
                         "the TPU the phases run only when it is given")
    args = ap.parse_args()

    d0 = jax.devices()[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices())}
    on_tpu = d0.platform == "tpu"
    log(f"device: {json.dumps(device)}")
    if not on_tpu and args.scale is None:
        print("no TPU found: nothing to measure (give --scale to rehearse "
              "the phases)", file=sys.stderr)
        return 2
    args.scale = DEFAULT_SCALE if args.scale is None else args.scale
    log(f"compile cache: {enable_compile_cache()}")

    check = Checks()
    check("kernels run compiled (not interpreted)",
          default_interpret() is (not on_tpu))
    try:
        (four_chips if args.chips == 4 else one_chip)(args, check, on_tpu)
    except Exception:
        log(traceback.format_exc())
        check("phases ran to the end", False)
    stats = d0.memory_stats() or {}
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'n/a')} "
        f"(device 0)")
    log(f"{check.n - len(check.failed)}/{check.n} checks passed"
        + (f"; failed: {check.failed}" if check.failed else ""))
    if check.failed or not on_tpu:
        if not on_tpu:
            print("not a TPU: rehearsal only", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
