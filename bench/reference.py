"""Plain answers over the edge list, and the numbers compared against them.

``Reference`` is the scipy / numpy reference of ``chip_smoke.py`` (kept here
so that no change to the program can move it): weakly connected components
as the least vertex id of each component, Dijkstra distances and unweighted
BFS levels over the directed edge list, all in float64. It imports nothing of
the program.

What is compared, and why the limits are what they are:

- BFS levels are small integers, exact in float32: every level must equal
  the reference's (limit 0 differing vertices).
- Reachability is exact: a vertex is reached (finite answer) exactly when the
  reference reaches it (limit 0). A vertex that no partition holds is
  answered with the fill value (unreached) and must be unreached in the
  reference too, so edges lost by the partitioner show up here.
- SSSP distances are float32 sums of float32 weights along a path: they
  differ from the float64 reference by rounding only. ``sssp_rel_err`` is the
  largest ``|got - want| / max(want, 1)``; its limit comes from the
  configuration (PERF.md gives the readings it was set from).
- Connected-component labels are exact (limit 0 differing vertices); a
  vertex with no edge at the time of the query may instead carry the fill
  value (no partition holds it).

``bf16_distances`` is the control: the same relaxation computed in
bfloat16, the precision below the float32 the configuration states. It must
fail ``sssp_rel_err``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp
from scipy.sparse import csgraph


class Reference:
    """Answers of a plain implementation over the graph's edge list."""

    def __init__(self, n: int, src: np.ndarray, dst: np.ndarray,
                 weights: np.ndarray):
        self.n = n
        self.A = sp.csr_matrix((weights.astype(np.float64), (src, dst)),
                               shape=(n, n))
        self.degree = np.bincount(src, minlength=n) + \
            np.bincount(dst, minlength=n)

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


def component_edges(labels: np.ndarray, lo: np.ndarray) -> np.ndarray:
    """Undirected edges per component (indexed by the component's label),
    from each edge's lower endpoint: both endpoints share a label."""
    return np.bincount(labels[lo], minlength=labels.size)


def merge_components(labels: np.ndarray, u: np.ndarray,
                     v: np.ndarray) -> np.ndarray:
    """The least-id component labels after the edges ``(u, v)`` join the
    graph whose labels are ``labels``: components linked by a new edge
    merge, and the merged label is the least of theirs."""
    lu, lv = labels[u], labels[v]
    nodes = np.unique(np.concatenate([lu, lv]))
    iu, iv = np.searchsorted(nodes, lu), np.searchsorted(nodes, lv)
    q = sp.coo_matrix((np.ones(iu.size), (iu, iv)),
                      shape=(nodes.size, nodes.size))
    _, comp = csgraph.connected_components(q, directed=False)
    least = np.full(comp.max() + 1, np.iinfo(np.int64).max)
    np.minimum.at(least, comp, nodes)
    pos = np.clip(np.searchsorted(nodes, labels), 0, nodes.size - 1)
    hit = nodes[pos] == labels
    out = labels.copy()
    out[hit] = least[comp[pos[hit]]]
    return out


def traversal_readings(kind: str, got: np.ndarray,
                       want: np.ndarray) -> dict:
    """Numbers compared for one BFS or SSSP answer over every vertex
    (unreached: ``inf`` in both)."""
    g = got.astype(np.float64)
    reached = np.isfinite(want)
    out = {"reach_diff": int((np.isfinite(g) != reached).sum())}
    both = reached & np.isfinite(g)
    if kind == "bfs":
        out["bfs_level_diff"] = int((g[both] != want[both]).sum())
    else:
        err = np.abs(g[both] - want[both]) / np.maximum(want[both], 1.0)
        out["sssp_rel_err"] = float(err.max()) if err.size else 0.0
    return out


def cc_readings(got: np.ndarray, want: np.ndarray,
                has_edge: np.ndarray, fill: int = -1) -> dict:
    """Labels must equal the reference's; a vertex with no edge may also be
    held by no partition, and then carries ``fill``."""
    got = got.astype(np.int64)
    bad = (got != want) & (has_edge | (got != fill))
    return {"cc_label_diff": int(bad.sum())}


@functools.partial(jax.jit, static_argnames=("n", "unit"))
def _bf16_relax(src, dst, w, source, n: int, unit: bool):
    wt = jnp.ones(w.shape, jnp.bfloat16) if unit else w.astype(jnp.bfloat16)
    d0 = jnp.full((n,), jnp.inf, jnp.bfloat16).at[source].set(0)

    def body(c):
        d, _ = c
        nd = d.at[dst].min(d[src] + wt)
        return nd, jnp.any(nd < d)

    d, _ = jax.lax.while_loop(lambda c: c[1], body, (d0, jnp.bool_(True)))
    return d.astype(jnp.float32)


def bf16_distances(n: int, src, dst, w, source: int, unit: bool):
    """Bellman-Ford to its fixed point with bfloat16 values and weights
    (unit weights for BFS): the control, in the precision below float32."""
    return np.asarray(_bf16_relax(src, dst, w, jnp.int32(source), n, unit))
