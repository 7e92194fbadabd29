"""Subgraph construction (paper §4.1, §6.3 "construct subgraphs by received
data").

Given an edge -> partition assignment (any vertex-cut or edge-cut partitioner),
build the device-ready ``PartitionedGraph``: dense padded per-partition arrays
with *local* int32 vertex indexing, plus the frontier-slot structure that SBS
(subgraph boundary synchronization) reduces over.

Frontier vertices (replicated in >= 2 partitions) each get a global *slot* in
``[0, n_slots)``. SBS scatters local contributions into a ``[n_slots(+1)]``
buffer, all-reduces it with the algorithm's combiner across the subgraph mesh
axes, and gathers merged values back — the TPU-native realization of the
paper's master/mirror Aggregate+Disseminate (DESIGN.md §2). The paper's
master designation survives as ``is_master`` (random replica election via
hash, §4.3) and is used for result collection and the aggregation-balance
statistic.

The builder is split into composable layers so the streaming subsystem
(repro.stream) can assemble partitions from per-partition spill shards
without ever materializing the global edge list:

  - ``frontier_election``        — slots + master election from per-partition
                                   vertex membership alone (no edges);
  - ``assemble_partitioned_graph`` — fill the padded arrays, pulling each
                                   partition's edges through a loader
                                   callback (one partition resident at a
                                   time);
  - ``build_partitioned_graph``  — the classic one-shot in-memory wrapper;
  - ``recompute_frontier``       — re-derive slots/masters in place after a
                                   membership patch (stream/delta.py).

Wherever ``is_frontier`` is derived, so is ``local_paths``: per partition,
whether at least half of its real edges join two interior (unreplicated)
vertices, i.e. whether it holds paths that a local fixed point walks without
an exchange. The engine bounds the local phase by it (engine.local_bound).

Padded capacities (``v_max``/``e_max``) are chosen by a ``ShapePolicy``.
The default everywhere in this low-level layer is the exact policy (round
the content maximum up to ``pad_multiple`` — the historical behavior, and
what the bit-identical streaming-parity tests pin). Serving sessions pass a
*bucketed* policy instead, so capacities land on a geometric bucket grid and
a growing graph changes its padded shapes O(log growth) times instead of
once per flush (docs/ARCHITECTURE.md, "shape-bucket lifecycle").
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional, Sequence

import numpy as np

from repro.core.graph import Graph, splitmix64
from repro.core.partition import route_vertices_rh

__all__ = ["PartitionedGraph", "ShapePolicy", "build_partitioned_graph",
           "frontier_election", "assemble_partitioned_graph",
           "partition_vertex_sets", "recompute_frontier", "local_paths_of",
           "repack_partitions", "localize_edges"]


# --------------------------------------------------------------------------- #
# Padded-shape policy
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class ShapePolicy:
    """How content sizes become padded device capacities.

    Every compiled runner is specialized to the padded shapes
    ``(P, v_max, e_max, n_slots)``, so each distinct capacity costs one
    trace+compile. ``bucket(n)`` rounds a content maximum up to the next
    value of the geometric series ``pad_multiple * growth^k`` (each bucket
    itself rounded to ``pad_multiple``): under streaming growth the
    capacities change O(log growth) times instead of once per flush — the
    jax_pallas analogue of amortizing per-partition setup cost across
    queries in subgraph-centric engines (GoFFish, arXiv:1311.5949).

    growth       geometric ratio between buckets; ``1.0`` disables
                 bucketing (``bucket`` = exact round-up to ``pad_multiple``,
                 the historical behavior — see ``ShapePolicy.exact``).
    headroom     multiplier (>= 1) applied to the content size *before*
                 bucketing, so capacity is re-chosen while there is still
                 slack rather than exactly at overflow.
    pad_multiple every capacity is a multiple of this (device tiling).
    bucket_slots also bucket the SBS slot count fed to the runners.
                 ``n_slots`` is not a host-array dimension, only the
                 compiled exchange-buffer height, and it moves on *every*
                 frontier re-election — without bucketing it is the main
                 source of shape churn. Padded slot rows only ever hold the
                 combiner identity, so over-provisioning is sound.
    """

    growth: float = 2.0
    headroom: float = 1.0
    pad_multiple: int = 8
    bucket_slots: bool = True

    def __post_init__(self):
        if self.growth < 1.0:
            raise ValueError(f"ShapePolicy.growth must be >= 1.0, got "
                             f"{self.growth}")
        if self.headroom < 1.0:
            raise ValueError(f"ShapePolicy.headroom must be >= 1.0, got "
                             f"{self.headroom}")
        if self.pad_multiple < 1:
            raise ValueError(f"ShapePolicy.pad_multiple must be >= 1, got "
                             f"{self.pad_multiple}")

    @classmethod
    def exact(cls, pad_multiple: int = 8) -> "ShapePolicy":
        """The no-bucketing legacy policy: capacities are the content
        maximum rounded up to ``pad_multiple``, slot counts are exact."""
        return cls(growth=1.0, headroom=1.0, pad_multiple=pad_multiple,
                   bucket_slots=False)

    # ------------------------------------------------------------------ #
    def _round(self, n: int) -> int:
        return int(-(-max(n, 1) // self.pad_multiple) * self.pad_multiple)

    def bucket(self, n: int) -> int:
        """Smallest admissible capacity >= ``n * headroom`` (the bucket
        floor). Compaction uses the same function, so shrink-then-regrow
        traffic inside one bucket keeps the padded shapes — and therefore
        the compiled runners — stable."""
        need = max(1, int(math.ceil(max(n, 1) * self.headroom)))
        if self.growth <= 1.0:
            return self._round(need)
        b = self.pad_multiple
        while b < need:
            b = self._round(int(math.ceil(b * self.growth)))
        return b

    def slot_capacity(self, n_slots: int) -> int:
        """Exchange-buffer slot count a runner is built with. Slots in
        ``[n_slots, capacity)`` receive only identity contributions and are
        never gathered by a live vertex, so the padding is invisible to
        results (it is all-reduced, though — bytes are billed on the padded
        height)."""
        if not self.bucket_slots or self.growth <= 1.0:
            return int(n_slots)
        return self.bucket(n_slots)


def resolve_shape_policy(shape_policy: Optional[ShapePolicy],
                         pad_multiple: int) -> ShapePolicy:
    """Default every low-level builder to the exact legacy policy; callers
    that want buckets (GraphSession) pass one explicitly. An explicit
    policy always wins: it carries its own ``pad_multiple``, and the bare
    ``pad_multiple`` parameter the builders keep for backward compatibility
    is consulted only when no policy is given."""
    if shape_policy is None:
        return ShapePolicy.exact(pad_multiple)
    return shape_policy


def _pad_to(arr: np.ndarray, n: int, fill) -> np.ndarray:
    out = np.full((n,) + arr.shape[1:], fill, dtype=arr.dtype)
    out[: arr.shape[0]] = arr
    return out


def local_paths_of(pg: "PartitionedGraph") -> np.ndarray:
    """[P] bool: whether at least half of each partition's real edges join
    two interior (not replicated) vertices. Such a partition holds paths a
    local fixed point walks in one superstep; elsewhere (a random
    vertex-cut leaves almost no interior edge) paths cross partitions and
    the exchange, not the local phase, carries them. An edge-less
    partition has none.

    Every edge of an interior vertex lies in its one partition, so the
    full degrees of the interior vertices bound the interior edges from
    above without touching an edge: only a partition that bound leaves
    open pays the per-edge count (a flush runs this on every partition)."""
    interior = pg.vmask & ~pg.is_frontier
    real = pg.emask.sum(axis=1)
    into = np.where(interior, pg.in_deg, 0).sum(axis=1, dtype=np.float64)
    outof = np.where(interior, pg.out_deg, 0).sum(axis=1, dtype=np.float64)
    out = np.zeros(pg.n_parts, bool)
    for p in np.nonzero((real > 0) & (2 * np.minimum(into, outof) >= real))[0]:
        m = pg.emask[p]
        both = interior[p][pg.esrc[p][m]] & interior[p][pg.edst[p][m]]
        out[p] = 2 * int(both.sum()) >= real[p]
    return out


def localize_edges(lv: np.ndarray, gs: np.ndarray, gd: np.ndarray, w):
    """Global-id edges -> local int32 indices against the sorted membership
    ``lv``, stably sorted by destination (segment ops expect ascending dst).
    Every writer of the dense edge arrays (assembly, delta patching,
    compaction) must go through this so the layout invariant lives in one
    place."""
    ls = np.searchsorted(lv, gs).astype(np.int32)
    ld = np.searchsorted(lv, gd).astype(np.int32)
    eo = np.argsort(ld, kind="stable")
    return ls[eo], ld[eo], np.asarray(w, dtype=np.float32)[eo]


@dataclasses.dataclass
class PartitionedGraph:
    """Dense, padded, device-ready partitioned graph.

    All ``[P, ...]`` arrays are numpy on the host; the engine moves them to
    device (full for the simulator backend, per-shard under shard_map).
    """

    n_parts: int
    n_vertices: int      # global vertex count
    n_edges: int         # global edge count (unpadded)
    n_slots: int         # number of frontier (replicated) vertices
    v_max: int           # padded per-partition vertex capacity
    e_max: int           # padded per-partition edge capacity

    gvid: np.ndarray     # [P, v_max] int64 global id per local slot (-1 pad)
    vmask: np.ndarray    # [P, v_max] bool
    esrc: np.ndarray     # [P, e_max] int32 local src index (0 where padded)
    edst: np.ndarray     # [P, e_max] int32 local dst index, sorted ascending
    ew: np.ndarray       # [P, e_max] float32 edge weight (0 where padded)
    emask: np.ndarray    # [P, e_max] bool
    slot: np.ndarray     # [P, v_max] int32 frontier slot id; n_slots if none
    is_frontier: np.ndarray  # [P, v_max] bool — vertex replicated elsewhere
    out_deg: np.ndarray  # [P, v_max] float32 FULL (global) out-degree
    in_deg: np.ndarray   # [P, v_max] float32 FULL (global) in-degree
    is_master: np.ndarray  # [P, v_max] bool

    frontier_gvid: np.ndarray  # [n_slots] int64
    # [P] bool — most real edges join interior vertices; derived here
    # (local_paths_of) unless given, and again by recompute_frontier
    local_paths: Optional[np.ndarray] = None
    edge_part: Optional[np.ndarray] = None  # [E] int32 host-side assignment
    vlabel: Optional[np.ndarray] = None     # [P, v_max] int32 (gsim labels)
    # Stacked tile/window decompositions for the Pallas edge-compute
    # backends (core/layouts.py EdgeLayouts) — built on demand via
    # ensure_edge_layouts (or eagerly at assembly), kept incrementally
    # fresh by stream/delta.py, rebuilt by repack_partitions.
    edge_layouts: Optional[object] = None

    def __post_init__(self):
        if self.local_paths is None:
            self.local_paths = local_paths_of(self)

    # ------------------------------------------------------------------ #
    @property
    def edges_per_part(self) -> np.ndarray:
        return self.emask.sum(axis=1)

    @property
    def vertices_per_part(self) -> np.ndarray:
        return self.vmask.sum(axis=1)

    def device_arrays(self) -> dict:
        """The pytree the engine ships to device."""
        d = dict(esrc=self.esrc, edst=self.edst, ew=self.ew, emask=self.emask,
                 slot=self.slot, vmask=self.vmask, is_frontier=self.is_frontier,
                 out_deg=self.out_deg, in_deg=self.in_deg,
                 is_master=self.is_master, local_paths=self.local_paths)
        if self.vlabel is not None:
            d["vlabel"] = self.vlabel
        return d

    # ------------------------------------------------------------------ #
    def collect(self, values: np.ndarray, fill=0) -> np.ndarray:
        """Gather per-vertex results from master replicas into a global
        [n_vertices, ...] array (paper: masters hold the primary value)."""
        values = np.asarray(values)
        out = np.full((self.n_vertices,) + values.shape[2:], fill,
                      dtype=values.dtype)
        sel = self.vmask & self.is_master
        out[self.gvid[sel]] = values[sel]
        return out

    def ensure_edge_layouts(self, shape_policy: Optional["ShapePolicy"] = None,
                            block_edges: int = 512):
        """The ``EdgeLayouts`` for this graph, built on first use (and
        rebuilt whenever the padded shapes moved since — delta patching
        keeps an existing one fresh incrementally instead). The policy of
        the first build sticks; callers with a bucketed serving policy
        (GraphSession) pass it here before the first Pallas query."""
        from repro.core.layouts import build_edge_layouts
        lay = self.edge_layouts
        if lay is not None and lay.matches(self):
            return lay
        policy = resolve_shape_policy(
            shape_policy if lay is None or shape_policy is not None
            else lay.policy, 8)
        if lay is not None and shape_policy is None:
            block_edges = lay.block_edges
        self.edge_layouts = build_edge_layouts(self, policy, block_edges)
        return self.edge_layouts

    def set_vertex_labels(self, labels: np.ndarray) -> None:
        """Attach global per-vertex int labels (graph simulation §7.3)."""
        lab = np.zeros((self.n_parts, self.v_max), dtype=np.int32)
        lab[self.vmask] = labels[self.gvid[self.vmask]]
        self.vlabel = lab


# --------------------------------------------------------------------------- #
# Layer 1 — vertex membership (in-memory path; streaming derives its own
# membership incrementally from spill shards)
# --------------------------------------------------------------------------- #
def partition_vertex_sets(src: np.ndarray, dst: np.ndarray,
                          edge_part: np.ndarray, n_parts: int,
                          n_vertices: int, *,
                          isolated: Optional[np.ndarray] = None
                          ) -> list[np.ndarray]:
    """Per-partition sorted unique vertex ids: the endpoints of each
    partition's edges (Eq. 3), plus hash-round-robin isolated vertices."""
    pair_part = np.concatenate([edge_part, edge_part]).astype(np.int64)
    pair_vid = np.concatenate([src, dst])
    key = pair_part * np.int64(n_vertices) + pair_vid
    ukey = np.unique(key)
    up = (ukey // n_vertices).astype(np.int32)
    uv = (ukey % n_vertices).astype(np.int64)
    if isolated is not None and isolated.size:
        iso_p = route_vertices_rh(isolated, n_parts)
        up = np.concatenate([up, iso_p])
        uv = np.concatenate([uv, isolated])
        re = np.lexsort((uv, up))
        up, uv = up[re], uv[re]
    starts = np.searchsorted(up, np.arange(n_parts + 1))
    return [uv[starts[p]:starts[p + 1]] for p in range(n_parts)]


# --------------------------------------------------------------------------- #
# Layer 2 — frontier slots + master election from membership alone
# --------------------------------------------------------------------------- #
def frontier_election(part_vertices: Sequence[np.ndarray], n_vertices: int):
    """Slots and masters from per-partition vertex membership.

    Returns ``(frontier_gvid, slot_of_gvid, masters)`` where ``masters[p]``
    is a bool array aligned with ``part_vertices[p]``. The elected master of
    v is its ``hash(v) % replica_count(v)``-th replica in partition-id order
    (paper §4.3 random replica election) — a pure function of membership, so
    streaming ingest, one-shot build and delta patching all agree."""
    replica_count = np.zeros(n_vertices, dtype=np.int64)
    for lv in part_vertices:
        replica_count[lv] += 1
    frontier_gvid = np.nonzero(replica_count >= 2)[0].astype(np.int64)
    n_slots = int(frontier_gvid.shape[0])
    slot_of_gvid = np.full(n_vertices, n_slots, dtype=np.int64)
    slot_of_gvid[frontier_gvid] = np.arange(n_slots)

    pick = (splitmix64(np.arange(n_vertices, dtype=np.uint64))
            % np.maximum(replica_count, 1).astype(np.uint64)).astype(np.int64)
    seen = np.zeros(n_vertices, dtype=np.int64)   # replicas in partitions < p
    masters = []
    for lv in part_vertices:
        masters.append(seen[lv] == pick[lv])
        seen[lv] += 1
    return frontier_gvid, slot_of_gvid, masters


# --------------------------------------------------------------------------- #
# Layer 3 — padded assembly, one partition resident at a time
# --------------------------------------------------------------------------- #
def assemble_partitioned_graph(
        n_parts: int, n_vertices: int, n_edges: int,
        part_vertices: Sequence[np.ndarray],
        edge_counts: np.ndarray,
        load_edges: Callable[[int], tuple],
        out_degrees: np.ndarray, in_degrees: np.ndarray,
        *, pad_multiple: int = 8,
        shape_policy: Optional[ShapePolicy] = None,
        edge_part: Optional[np.ndarray] = None,
        build_edge_layouts: bool = False) -> PartitionedGraph:
    """Fill the dense padded arrays.

    ``load_edges(p) -> (src, dst, w)`` supplies partition p's edges in global
    ids, in their original stream order; only one partition's edge list is
    resident at a time, so callers can stream from spill shards
    (``edge_counts`` pre-sizes ``e_max`` without loading anything).

    ``shape_policy`` picks ``v_max``/``e_max`` from the content maxima;
    omitted, it is ``ShapePolicy.exact(pad_multiple)``.

    ``build_edge_layouts=True`` also assembles the Pallas edge-compute
    layouts (core/layouts.py) under the same policy — what a serving
    session that knows it will run ``edge_backend='pallas_*'`` wants;
    otherwise they are built lazily by ``ensure_edge_layouts`` on first
    use, and maintained incrementally either way.
    """
    P = n_parts
    policy = resolve_shape_policy(shape_policy, pad_multiple)
    frontier_gvid, slot_of_gvid, masters = frontier_election(
        part_vertices, n_vertices)
    n_slots = int(frontier_gvid.shape[0])

    vcounts = np.array([lv.shape[0] for lv in part_vertices], dtype=np.int64)
    v_max = policy.bucket(int(vcounts.max()) if P else 1)
    e_max = policy.bucket(int(np.max(edge_counts)) if P else 1)

    gvid = np.full((P, v_max), -1, dtype=np.int64)
    vmask = np.zeros((P, v_max), dtype=bool)
    slot = np.full((P, v_max), n_slots, dtype=np.int32)
    is_master = np.zeros((P, v_max), dtype=bool)
    out_deg = np.zeros((P, v_max), dtype=np.float32)
    in_deg = np.zeros((P, v_max), dtype=np.float32)
    esrc = np.zeros((P, e_max), dtype=np.int32)
    edst = np.zeros((P, e_max), dtype=np.int32)
    ew = np.zeros((P, e_max), dtype=np.float32)
    emask = np.zeros((P, e_max), dtype=bool)

    g_out = out_degrees.astype(np.float32)
    g_in = in_degrees.astype(np.float32)

    for p in range(P):
        lv = part_vertices[p]                        # sorted ascending
        nv = lv.shape[0]
        gvid[p, :nv] = lv
        vmask[p, :nv] = True
        slot[p, :nv] = slot_of_gvid[lv]
        is_master[p, :nv] = masters[p]
        out_deg[p, :nv] = g_out[lv]
        in_deg[p, :nv] = g_in[lv]

        es, ed, w = load_edges(p)
        ls, ld, ww = localize_edges(lv, es, ed, w)
        ne = es.shape[0]
        esrc[p, :ne] = ls
        edst[p, :ne] = ld
        ew[p, :ne] = ww
        emask[p, :ne] = True

    pg = PartitionedGraph(
        n_parts=P, n_vertices=n_vertices, n_edges=n_edges,
        n_slots=n_slots, v_max=v_max, e_max=e_max,
        gvid=gvid, vmask=vmask, esrc=esrc, edst=edst, ew=ew, emask=emask,
        slot=slot, is_frontier=(slot < n_slots) & vmask,
        out_deg=out_deg, in_deg=in_deg, is_master=is_master,
        frontier_gvid=frontier_gvid, edge_part=edge_part,
    )
    if build_edge_layouts:
        pg.ensure_edge_layouts(shape_policy=policy)
    return pg


# --------------------------------------------------------------------------- #
# One-shot in-memory wrapper (the classic path)
# --------------------------------------------------------------------------- #
def build_partitioned_graph(g: Graph, edge_part: np.ndarray, n_parts: int,
                            *, pad_multiple: int = 8,
                            shape_policy: Optional[ShapePolicy] = None,
                            include_isolated: bool = True,
                            build_edge_layouts: bool = False
                            ) -> PartitionedGraph:
    edge_part = np.asarray(edge_part, dtype=np.int32)
    assert edge_part.shape == g.src.shape

    # ---- group edges by partition -------------------------------------- #
    order = np.argsort(edge_part, kind="stable")
    ps, pd = g.src[order], g.dst[order]
    pw = g.weights[order]
    counts = np.bincount(edge_part, minlength=n_parts).astype(np.int64)
    starts = np.concatenate([[0], np.cumsum(counts)])

    iso = g.isolated_vertices() if include_isolated else None
    part_vertices = partition_vertex_sets(g.src, g.dst, edge_part, n_parts,
                                          g.n_vertices, isolated=iso)

    def load_edges(p):
        return (ps[starts[p]:starts[p + 1]], pd[starts[p]:starts[p + 1]],
                pw[starts[p]:starts[p + 1]])

    return assemble_partitioned_graph(
        n_parts, g.n_vertices, g.n_edges, part_vertices, counts, load_edges,
        g.out_degrees(), g.in_degrees(), pad_multiple=pad_multiple,
        shape_policy=shape_policy, edge_part=edge_part,
        build_edge_layouts=build_edge_layouts)


# --------------------------------------------------------------------------- #
# In-place repack at fresh capacities (stream/delta.py compaction)
# --------------------------------------------------------------------------- #
def repack_partitions(pg: PartitionedGraph,
                      part_vertices: Sequence[np.ndarray],
                      part_edges: Sequence[tuple],
                      *, pad_multiple: int = 8,
                      shape_policy: Optional[ShapePolicy] = None
                      ) -> np.ndarray:
    """Rebuild ``pg``'s dense padded arrays in place from explicit
    per-partition membership (sorted unique global ids) and edge lists
    ``(src, dst, w)`` in global ids, re-deriving ``v_max``/``e_max`` from the
    new content — capacities *shrink* when the content does, unlike the
    grow-only delta path. Under a bucketed ``shape_policy`` they shrink to
    the **bucket floor** (the smallest admissible bucket), not the exact
    minimum, so compact-then-regrow traffic inside one bucket keeps the
    padded shapes stable. Frontier slots and masters are re-elected from the
    new membership; per-vertex tables (degrees, labels) are carried through
    their global ids.

    Returns ``remap``: ``[P, old_v_max]`` int32 mapping each old local row to
    its new local row (-1 for evicted members and padding), so live
    per-partition state survives the repack.
    """
    P = pg.n_parts
    old_v_max = pg.v_max
    policy = resolve_shape_policy(shape_policy, pad_multiple)

    new_v_max = policy.bucket(
        max((lv.shape[0] for lv in part_vertices), default=1))
    new_e_max = policy.bucket(
        max((e[0].shape[0] for e in part_edges), default=1))

    # global per-vertex tables, read from the old replicas (all agree)
    sel = pg.vmask
    g_out = np.zeros(pg.n_vertices, np.float32)
    g_in = np.zeros(pg.n_vertices, np.float32)
    g_out[pg.gvid[sel]] = pg.out_deg[sel]
    g_in[pg.gvid[sel]] = pg.in_deg[sel]
    g_lab = None
    if pg.vlabel is not None:
        g_lab = np.zeros(pg.n_vertices, np.int32)
        g_lab[pg.gvid[sel]] = pg.vlabel[sel]

    remap = np.full((P, old_v_max), -1, np.int32)
    gvid = np.full((P, new_v_max), -1, np.int64)
    vmask = np.zeros((P, new_v_max), bool)
    out_deg = np.zeros((P, new_v_max), np.float32)
    in_deg = np.zeros((P, new_v_max), np.float32)
    vlabel = np.zeros((P, new_v_max), np.int32) if g_lab is not None else None
    esrc = np.zeros((P, new_e_max), np.int32)
    edst = np.zeros((P, new_e_max), np.int32)
    ew = np.zeros((P, new_e_max), np.float32)
    emask = np.zeros((P, new_e_max), bool)

    for p in range(P):
        lv = np.asarray(part_vertices[p], np.int64)
        nv = lv.shape[0]
        gvid[p, :nv] = lv
        vmask[p, :nv] = True
        out_deg[p, :nv] = g_out[lv]
        in_deg[p, :nv] = g_in[lv]
        if vlabel is not None:
            vlabel[p, :nv] = g_lab[lv]

        old_lv = pg.gvid[p][pg.vmask[p]]
        pos = np.searchsorted(lv, old_lv)
        kept = np.zeros(old_lv.shape[0], bool)
        in_range = pos < nv
        kept[in_range] = lv[pos[in_range]] == old_lv[in_range]
        remap[p, :old_lv.shape[0]] = np.where(kept, pos, -1).astype(np.int32)

        gs, gd, w = part_edges[p]
        ne = gs.shape[0]
        ls, ld, ww = localize_edges(lv, gs, gd, w)
        esrc[p, :ne] = ls
        edst[p, :ne] = ld
        ew[p, :ne] = ww
        emask[p, :ne] = True

    pg.gvid, pg.vmask = gvid, vmask
    pg.out_deg, pg.in_deg, pg.vlabel = out_deg, in_deg, vlabel
    pg.esrc, pg.edst, pg.ew, pg.emask = esrc, edst, ew, emask
    pg.v_max, pg.e_max = new_v_max, new_e_max
    pg.n_edges = int(emask.sum())
    pg.edge_part = None
    recompute_frontier(pg)
    if pg.edge_layouts is not None:
        # a repack moves the tile/window grid (v_max changed, rows moved):
        # rebuild the layouts under their own policy at assembly time, so
        # Pallas queries after a compaction see fresh geometry immediately
        old = pg.edge_layouts
        pg.edge_layouts = None
        pg.ensure_edge_layouts(shape_policy=old.policy,
                               block_edges=old.block_edges)
    return remap


# --------------------------------------------------------------------------- #
# Frontier maintenance after a membership patch (stream/delta.py)
# --------------------------------------------------------------------------- #
def recompute_frontier(pg: PartitionedGraph) -> None:
    """Re-derive ``slot``/``is_frontier``/``is_master``/``frontier_gvid``
    in place from the current ``gvid``/``vmask`` membership, and
    ``local_paths`` from them and the current edges. Uses the same
    hash election as the builders, so an unchanged membership round-trips
    bit-identically; a patched membership gets consistent fresh slots."""
    part_vertices = [pg.gvid[p][pg.vmask[p]] for p in range(pg.n_parts)]
    frontier_gvid, slot_of_gvid, masters = frontier_election(
        part_vertices, pg.n_vertices)
    n_slots = int(frontier_gvid.shape[0])
    pg.slot = np.full((pg.n_parts, pg.v_max), n_slots, dtype=np.int32)
    pg.is_master = np.zeros((pg.n_parts, pg.v_max), dtype=bool)
    for p in range(pg.n_parts):
        nv = part_vertices[p].shape[0]
        pg.slot[p, :nv] = slot_of_gvid[part_vertices[p]]
        pg.is_master[p, :nv] = masters[p]
    pg.n_slots = n_slots
    pg.frontier_gvid = frontier_gvid
    pg.is_frontier = (pg.slot < n_slots) & pg.vmask
    pg.local_paths = local_paths_of(pg)
