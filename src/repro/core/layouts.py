"""Engine-facing edge-compute layouts: stacked [P, ...] tile/window
decompositions of a ``PartitionedGraph``'s per-partition adjacencies, feeding
the Pallas semiring kernels (``repro.kernels``) from inside the BSP sweep.

``repro.kernels.ops`` holds the single-partition reference builders; this
module is their serving-grade counterpart, with three extra obligations:

  - **stacked + padded** — every per-partition quantity is padded to a
    shared capacity (``t_max`` tiles, ``b_max`` edge blocks) so the whole
    graph is one dense pytree: the simulator backend flattens all P
    partitions into a *single* kernel launch (tile/window ids offset by
    ``p * n_dst_tiles``), and the shard_map backend shards the leading axis.
    Padding tiles hold the semiring identity and point at the last dst tile
    (keeping the dst-major sort); padding blocks point at the last window.
  - **program-independent geometry, per-program realization** — the
    expensive part (edge -> tile/slot assignment) depends only on the graph
    and is built once; the dense tile *values* depend on the program's
    ``SemiringSweep`` (semiring x edge-value map x dtype) and are realized
    lazily per key and cached. Window layouts never bake values at all
    (messages are computed in-sweep), so one geometry serves every program.
  - **ShapePolicy-bucketed capacities** — ``t_max``/``b_max`` come from the
    same geometric bucketing as ``v_max``/``e_max`` (docs/ARCHITECTURE.md,
    "shape-bucket lifecycle") and are *grow-only* under delta patching, so a
    serving session's compiled Pallas runners survive in-bucket streaming
    growth with zero retraces. ``rebuild_partitions`` refreshes only the
    partitions a delta touched.

Layout invariants the kernels rely on (see kernels/bsp_spmv.py):
tile lists are (dst, src)-sorted per partition with every dst tile row
covered at least once; ``bwin`` is ascending covering every window; padded
edge slots are ``-1`` (dropped by the scatter); all values at padded
positions are the semiring/combiner identity.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, NamedTuple, Optional, Tuple

import numpy as np

from repro.kernels.bsp_spmv import TM, TN
from repro.kernels.segment_combine import W
from repro.kernels.ref import tile_pad_identity

__all__ = ["EdgeLayouts", "TileBlock", "WindowBlock", "build_edge_layouts",
           "EDGE_VALUE_KINDS"]

EDGE_VALUE_KINDS = ("weight", "zero", "one")
DEFAULT_BLOCK_EDGES = 512


class TileBlock(NamedTuple):
    """Device pytree for the ``pallas_tiles`` backend (stacked [P, ...])."""
    tiles: object      # [P, t_max, TM, TN] program dtype
    tile_dst: object   # [P, t_max] int32, partition-local dst tile ids
    tile_src: object   # [P, t_max] int32


class WindowBlock(NamedTuple):
    """Device pytree for the ``pallas_windows`` backend (stacked [P, ...])."""
    eslot: object      # [P, e_max] int32 buffer slot per edge (-1 = padding)
    ldst: object       # [P, b_max*Be] int32 dst row within the 128-window
    bwin: object       # [P, b_max] int32 window id per block (ascending)


def _to_device(host: NamedTuple, shardings=None):
    """A host layout tuple as device arrays: on the default device, or
    each leaf ``device_put`` with its entry of ``shardings`` (a tuple of
    the same type holding ``NamedSharding``s)."""
    import jax
    import jax.numpy as jnp
    if shardings is None:
        return type(host)(*[jnp.asarray(x) for x in host])
    return type(host)(*jax.device_put(tuple(host), tuple(shardings)))


def _edge_values(kind: str, ew: np.ndarray, dtype) -> np.ndarray:
    """The declarative edge-value map of a ``SemiringSweep``: what each edge
    contributes to the semiring product (SSSP relaxes by the weight, CC
    propagates labels over 0-weight edges, PageRank pushes unweighted)."""
    if kind == "weight":
        return ew.astype(dtype)
    if kind == "zero":
        return np.zeros(ew.shape[0], dtype)
    if kind == "one":
        return np.ones(ew.shape[0], dtype)
    raise ValueError(f"unknown edge-value kind {kind!r}; "
                     f"expected one of {EDGE_VALUE_KINDS}")


def _tile_geometry(ls, ld, ndt: int, nst: int):
    """(local src, local dst) -> (tile_dst, tile_src, edge_tile, r, c).

    Tile list sorted (dst, src)-major with identity fillers covering every
    dst tile row; ``edge_tile[e]`` indexes the *final* sorted list.
    """
    key = (ld.astype(np.int64) // TM) * nst + (ls.astype(np.int64) // TN)
    uniq = np.unique(key)
    covered = np.zeros(ndt, bool)
    covered[(uniq // nst).astype(np.int64)] = True
    missing = np.nonzero(~covered)[0]
    T = uniq.shape[0] + missing.shape[0]

    tile_dst = np.zeros(T, np.int32)
    tile_src = np.zeros(T, np.int32)
    tile_dst[:uniq.shape[0]] = (uniq // nst).astype(np.int32)
    tile_src[:uniq.shape[0]] = (uniq % nst).astype(np.int32)
    tile_dst[uniq.shape[0]:] = missing.astype(np.int32)

    final = np.lexsort((tile_src, tile_dst))
    inv = np.empty(T, np.int64)
    inv[final] = np.arange(T)
    edge_tile = inv[np.searchsorted(uniq, key)].astype(np.int32)
    return (tile_dst[final], tile_src[final], edge_tile,
            (ld % TM).astype(np.int32), (ls % TN).astype(np.int32))


def _window_geometry(ld, nw: int, Be: int):
    """Ascending-dst local edges -> (eslot, ldst, bwin, n_blocks)."""
    win = ld.astype(np.int64) // W
    counts = np.bincount(win, minlength=nw)
    blocks = np.maximum(-(-counts // Be), 1)          # >= 1 block per window
    n_blocks = int(blocks.sum())
    bwin = np.repeat(np.arange(nw, dtype=np.int32), blocks)
    woff = np.concatenate([[0], np.cumsum(blocks)])[:-1] * Be
    estart = np.concatenate([[0], np.cumsum(counts)])[:-1]
    eslot = (woff[win] + (np.arange(ld.shape[0]) - estart[win])).astype(
        np.int32)
    ldst = np.zeros(n_blocks * Be, np.int32)
    ldst[eslot] = (ld % W).astype(np.int32)
    return eslot, ldst, bwin, n_blocks


@dataclasses.dataclass
class EdgeLayouts:
    """Host-side stacked layout state attached to a ``PartitionedGraph``
    (``PartitionedGraph.ensure_edge_layouts``). All arrays are numpy; the
    ``device_tiles``/``device_windows`` accessors return cached jnp pytrees
    that a runner takes as explicit inputs (never closed over — the
    session's zero-retrace contract needs them to be arguments)."""

    n_parts: int
    v_max: int
    e_max: int
    t_max: int                    # padded tiles per partition (bucketed)
    b_max: int                    # padded edge blocks per partition
    block_edges: int
    policy: object                # ShapePolicy governing t_max/b_max growth

    tile_dst: np.ndarray          # [P, t_max] int32
    tile_src: np.ndarray          # [P, t_max] int32
    n_tiles: np.ndarray           # [P] int64 real (content) tiles
    edge_tile: np.ndarray         # [P, e_max] int32 (-1 = padding edge)
    edge_r: np.ndarray            # [P, e_max] int32 row within tile
    edge_c: np.ndarray            # [P, e_max] int32 col within tile
    eslot: np.ndarray             # [P, e_max] int32 (-1 = padding edge)
    ldst: np.ndarray              # [P, b_max*Be] int32
    bwin: np.ndarray              # [P, b_max] int32
    n_blocks: np.ndarray          # [P] int64 real blocks

    _tiles: Dict[Tuple, np.ndarray] = dataclasses.field(default_factory=dict)
    _filled: Dict[Tuple, np.ndarray] = dataclasses.field(
        default_factory=dict)             # [P] non-identity entries per part
    _density: Dict[Tuple, float] = dataclasses.field(default_factory=dict)
    _device: Dict[Tuple, object] = dataclasses.field(default_factory=dict)
    uploaded_bytes: int = 0       # bytes of every device pytree built here
    # edge-axis-sharded geometry (shard_map cfg.edge_axes on the Pallas
    # backends): host geometry per shard count, rebuilt wholesale on any
    # graph change; the per-shard caps are grow-only across rebuilds so a
    # compiled sharded runner survives in-bucket streaming growth.
    _shard_geom: Dict[int, Dict] = dataclasses.field(default_factory=dict)
    _shard_caps: Dict[int, Tuple[int, int]] = dataclasses.field(
        default_factory=dict)             # S -> (t_loc, b_loc), grow-only

    # ------------------------------------------------------------------ #
    @property
    def n_dst_tiles(self) -> int:
        return max(-(-self.v_max // TM), 1)

    @property
    def n_src_tiles(self) -> int:
        return max(-(-self.v_max // TN), 1)

    @property
    def n_windows(self) -> int:
        return max(-(-self.v_max // W), 1)

    def shape_key(self, backend: str, n_shards: int = 1, pg=None) -> tuple:
        """What a compiled Pallas runner is additionally specialized to —
        joins the session's padded-shape key for cache lookup/eviction.
        ``n_shards > 1`` keys the edge-axis-sharded variant (``pg``
        required: the per-shard caps come from the sharded geometry)."""
        if n_shards > 1:
            assert pg is not None, "sharded shape_key needs the graph"
            self._sharded_geometry(pg, n_shards)
            t_loc, b_loc = self._shard_caps[int(n_shards)]
            if backend == "pallas_tiles":
                return ("tiles", int(n_shards), t_loc, self.n_dst_tiles,
                        self.n_src_tiles)
            return ("windows", int(n_shards), b_loc, self.block_edges,
                    self.n_windows)
        if backend == "pallas_tiles":
            return ("tiles", self.t_max, self.n_dst_tiles, self.n_src_tiles)
        return ("windows", self.b_max, self.block_edges, self.n_windows)

    # ------------------------------------------------------------------ #
    # realization: dense tile values per (semiring, edge-value map, dtype)
    # ------------------------------------------------------------------ #
    def _realize_tiles(self, pg, key, parts: Optional[Iterable[int]] = None):
        semiring, kind, dtype_str = key
        dtype = np.dtype(dtype_str)
        # tile contents are ADDED to values under min_plus: integer dtypes
        # pad with the wrap-safe halved identity (kernels/ref.py)
        ident = tile_pad_identity(semiring, dtype)
        tiles = self._tiles.get(key)
        if tiles is None or parts is None:
            tiles = np.full((self.n_parts, self.t_max, TM, TN), ident, dtype)
            parts = range(self.n_parts)
            self._tiles[key] = tiles
        for p in parts:
            tiles[p] = ident
            valid = self.edge_tile[p] >= 0
            vals = _edge_values(kind, pg.ew[p][valid], dtype)
            idx = (self.edge_tile[p][valid], self.edge_r[p][valid],
                   self.edge_c[p][valid])
            if semiring == "plus_times":
                np.add.at(tiles[p], idx, vals)
            else:
                np.minimum.at(tiles[p], idx, vals)
        return tiles

    def _count_filled(self, pg, key, parts: Optional[Iterable[int]] = None):
        """Per-partition non-identity tile entries of the ``key``
        realization, counted from the edges themselves: a sparse graph's
        dense tiles (64 KiB each, about one per edge) need not fit in host
        memory for its density to be known. Per partition, so an incremental
        rebuild recounts only the partitions it touched."""
        semiring, kind, dtype_str = key
        dtype = np.dtype(dtype_str)
        ident = tile_pad_identity(semiring, dtype)
        reduce = np.add.reduceat if semiring == "plus_times" \
            else np.minimum.reduceat
        filled = self._filled.get(key)
        if filled is None or parts is None:
            filled = np.zeros(self.n_parts, np.int64)
            parts = range(self.n_parts)
            self._filled[key] = filled
        for p in parts:
            valid = self.edge_tile[p] >= 0
            cell = ((self.edge_tile[p][valid].astype(np.int64) * TM
                     + self.edge_r[p][valid]) * TN + self.edge_c[p][valid])
            order = np.argsort(cell, kind="stable")
            cell = cell[order]
            vals = _edge_values(kind, pg.ew[p][valid], dtype)[order]
            first = np.flatnonzero(np.r_[True, cell[1:] != cell[:-1]]) \
                if cell.size else np.zeros(0, np.int64)
            # one value per tile entry, combined as the dense realization
            # combines it (the identity pad never wins a min, adds 0 to a sum)
            entry = reduce(vals, first) if first.size else vals
            filled[p] = int((entry != ident).sum())
        self._density[key] = int(filled.sum()) / max(
            int(self.n_tiles.sum()) * TM * TN, 1)

    def tile_values(self, pg, semiring: str, kind: str, dtype) -> np.ndarray:
        key = (semiring, kind, np.dtype(dtype).str)
        if key not in self._tiles:
            self._realize_tiles(pg, key)
        return self._tiles[key]

    def density(self, pg, semiring: str, kind: str, dtype) -> float:
        """Fraction of non-identity entries across the real (content) tiles
        — the utilization the dense-tile MXU path achieves; low density
        means ``pallas_windows`` (or COO) is the better backend."""
        key = (semiring, kind, np.dtype(dtype).str)
        if key not in self._density:
            self._count_filled(pg, key)
        return self._density[key]

    def partition_density(self, pg, semiring: str, kind: str,
                          dtype) -> np.ndarray:
        """[P] per-partition tile density (non-identity fraction of each
        partition's real tiles) — the actual input of the ``'auto'`` backend
        policy, surfaced per partition in ``ExecutionStats``."""
        key = (semiring, kind, np.dtype(dtype).str)
        if key not in self._filled:
            self._count_filled(pg, key)
        denom = np.maximum(self.n_tiles * (TM * TN), 1).astype(np.float64)
        return self._filled[key].astype(np.float64) / denom

    # ------------------------------------------------------------------ #
    # device pytrees (cached per placement; invalidated by any rebuild).
    # ``shardings`` (a TileBlock / WindowBlock of NamedShardings) places
    # each array on a mesh; None leaves it on the default device.
    # ------------------------------------------------------------------ #
    def _upload(self, host: NamedTuple, shardings=None):
        """``_to_device``, counted in ``uploaded_bytes``."""
        blk = _to_device(host, shardings)
        self.uploaded_bytes += sum(int(x.nbytes) for x in blk)
        return blk

    def device_tiles(self, pg, semiring: str, kind: str, dtype, *,
                     shardings: Optional[TileBlock] = None) -> TileBlock:
        key = ("tiles", semiring, kind, np.dtype(dtype).str, shardings)
        blk = self._device.get(key)
        if blk is None:
            vals = self.tile_values(pg, semiring, kind, dtype)
            blk = self._upload(TileBlock(vals, self.tile_dst, self.tile_src),
                               shardings)
            self._device[key] = blk
        return blk

    def device_windows(self, *, shardings: Optional[WindowBlock] = None
                       ) -> WindowBlock:
        key = ("windows", shardings)
        blk = self._device.get(key)
        if blk is None:
            blk = self._upload(WindowBlock(self.eslot, self.ldst, self.bwin),
                               shardings)
            self._device[key] = blk
        return blk

    def device_stub(self, backend: str, semiring: str, dtype,
                    n_shards: int = 1, *, shardings=None):
        """A block of ``backend``'s pytree structure that holds one tile or
        one window a partition (edge shard), every edge padding: the input
        a mixed shard_map ``'auto'`` runner takes for a backend that no
        partition runs, whose branch is traced but never taken."""
        S, P = int(n_shards), self.n_parts
        key = ("stub", backend, semiring, np.dtype(dtype).str, S, shardings)
        blk = self._device.get(key)
        if blk is None:
            if backend == "pallas_tiles":
                ident = tile_pad_identity(semiring, np.dtype(dtype))
                host = TileBlock(np.full((P, S, TM, TN), ident, dtype),
                                 np.zeros((P, S), np.int32),
                                 np.zeros((P, S), np.int32))
            else:
                host = WindowBlock(np.full((P, self.e_max), -1, np.int32),
                                   np.zeros((P, S * self.block_edges),
                                            np.int32),
                                   np.zeros((P, S), np.int32))
            blk = self._upload(host, shardings)
            self._device[key] = blk
        return blk

    # ------------------------------------------------------------------ #
    # edge-axis-sharded geometry (shard_map edge_axes on Pallas backends)
    # ------------------------------------------------------------------ #
    def _sharded_geometry(self, pg, n_shards: int) -> Dict:
        """Per-(partition, shard) tile/window geometry over the ``n_shards``
        contiguous ``e_max / n_shards`` column chunks of the edge arrays —
        the chunks a ``P(sub_axes, edge_axes)`` sharding hands each device.

        Each partition's valid edges are dst-sorted ascending along the
        columns (``localize_edges``), so any chunk's valid subset is itself
        dst-ascending and the per-shard builders apply unchanged.
        Each shard gets its own coverage fillers (every dst tile / window
        covered at least once), per-shard-local slot ids, and a shared
        bucketed per-shard capacity (``t_loc`` tiles / ``b_loc`` blocks,
        grow-only across rebuilds) so the stacked arrays split evenly:
        tiles [P, S*t_loc, TM, TN], bwin [P, S*b_loc], ldst
        [P, S*b_loc*Be], eslot [P, e_max] holding *shard-local* slots."""
        S = int(n_shards)
        geom = self._shard_geom.get(S)
        if geom is not None:
            return geom
        assert self.e_max % S == 0, \
            (f"e_max={self.e_max} must divide by n_shards={S}; pad edges "
             f"to a multiple of the edge axes")
        Se = self.e_max // S
        ndt, nst, nw = self.n_dst_tiles, self.n_src_tiles, self.n_windows
        Be = self.block_edges
        P = self.n_parts

        per = []                       # (p, s) -> geometry pieces
        need_t = need_b = 1
        for p in range(P):
            m = pg.emask[p]
            for s in range(S):
                cols = slice(s * Se, (s + 1) * Se)
                ms = m[cols]
                ls, ld = pg.esrc[p][cols][ms], pg.edst[p][cols][ms]
                td, ts, et, er, ec = _tile_geometry(ls, ld, ndt, nst)
                es, ldst, bw, nb = _window_geometry(ld, nw, Be)
                per.append((np.nonzero(ms)[0] + s * Se, td, ts, et, er, ec,
                            es, ldst, bw, nb))
                need_t = max(need_t, td.shape[0])
                need_b = max(need_b, nb)
        prev_t, prev_b = self._shard_caps.get(S, (0, 0))
        t_loc = max(prev_t, self.policy.bucket(need_t))
        b_loc = max(prev_b, self.policy.bucket(need_b))
        self._shard_caps[S] = (t_loc, b_loc)

        geom = dict(
            n_shards=S, t_loc=t_loc, b_loc=b_loc,
            tile_dst=np.full((P, S * t_loc), ndt - 1, np.int32),
            tile_src=np.full((P, S * t_loc), nst - 1, np.int32),
            edge_tile=np.full((P, self.e_max), -1, np.int32),
            edge_r=np.zeros((P, self.e_max), np.int32),
            edge_c=np.zeros((P, self.e_max), np.int32),
            eslot=np.full((P, self.e_max), -1, np.int32),
            ldst=np.zeros((P, S * b_loc * Be), np.int32),
            bwin=np.full((P, S * b_loc), nw - 1, np.int32),
            n_tiles=np.zeros((P, S), np.int64),
            n_blocks=np.zeros((P, S), np.int64),
        )
        it = iter(per)
        for p in range(P):
            for s in range(S):
                cols, td, ts, et, er, ec, es, ldst, bw, nb = next(it)
                T = td.shape[0]
                t0, b0 = s * t_loc, s * b_loc
                geom["tile_dst"][p, t0:t0 + T] = td
                geom["tile_src"][p, t0:t0 + T] = ts
                geom["n_tiles"][p, s] = T
                # edge_tile indexes the concatenated [S*t_loc] list: the
                # host-side value realization scatters through it; on
                # device each shard sees only its own [t_loc] slice
                geom["edge_tile"][p, cols] = et + t0
                geom["edge_r"][p, cols] = er
                geom["edge_c"][p, cols] = ec
                geom["eslot"][p, cols] = es        # shard-local slot ids
                geom["ldst"][p, b0 * Be:b0 * Be + ldst.shape[0]] = ldst
                geom["bwin"][p, b0:b0 + nb] = bw
                geom["n_blocks"][p, s] = nb
        self._shard_geom[S] = geom
        return geom

    def device_tiles_sharded(self, pg, semiring: str, kind: str, dtype,
                             n_shards: int, *,
                             shardings: Optional[TileBlock] = None
                             ) -> TileBlock:
        S = int(n_shards)
        key = ("tiles_sharded", S, semiring, kind, np.dtype(dtype).str,
               shardings)
        blk = self._device.get(key)
        if blk is None:
            g = self._sharded_geometry(pg, S)
            dt = np.dtype(dtype)
            ident = tile_pad_identity(semiring, dt)
            tiles = np.full((self.n_parts, S * g["t_loc"], TM, TN), ident,
                            dt)
            for p in range(self.n_parts):
                valid = g["edge_tile"][p] >= 0
                vals = _edge_values(kind, pg.ew[p][valid], dt)
                idx = (g["edge_tile"][p][valid], g["edge_r"][p][valid],
                       g["edge_c"][p][valid])
                if semiring == "plus_times":
                    np.add.at(tiles[p], idx, vals)
                else:
                    np.minimum.at(tiles[p], idx, vals)
            blk = self._upload(TileBlock(tiles, g["tile_dst"],
                                         g["tile_src"]), shardings)
            self._device[key] = blk
        return blk

    def device_windows_sharded(self, pg, n_shards: int, *,
                               shardings: Optional[WindowBlock] = None
                               ) -> WindowBlock:
        S = int(n_shards)
        key = ("windows_sharded", S, shardings)
        blk = self._device.get(key)
        if blk is None:
            g = self._sharded_geometry(pg, S)
            blk = self._upload(WindowBlock(g["eslot"], g["ldst"], g["bwin"]),
                               shardings)
            self._device[key] = blk
        return blk

    # ------------------------------------------------------------------ #
    # accounting
    # ------------------------------------------------------------------ #
    def flops_per_sweep(self, backend: str, K: int, n_shards: int = 1,
                        pg=None) -> np.ndarray:
        """[P] semiring ops one local sweep costs per partition: the dense
        work the kernels actually issue (multiply+accumulate per tile entry;
        compare+combine per block slot), *including* identity padding inside
        real tiles/blocks — that is the density tax the stats surface.
        ``n_shards > 1`` bills the per-shard coverage fillers of the
        edge-axis-sharded launch."""
        if n_shards > 1:
            g = self._sharded_geometry(pg, n_shards)
            if backend == "pallas_tiles":
                return (g["n_tiles"].sum(axis=1)
                        * (2 * TM * TN * K)).astype(np.int64)
            return (g["n_blocks"].sum(axis=1)
                    * (2 * W * self.block_edges * K)).astype(np.int64)
        if backend == "pallas_tiles":
            return (self.n_tiles * (2 * TM * TN * K)).astype(np.int64)
        return (self.n_blocks * (2 * W * self.block_edges * K)).astype(
            np.int64)

    # ------------------------------------------------------------------ #
    # (re)build
    # ------------------------------------------------------------------ #
    def _build_partition(self, pg, p: int):
        """Recompute partition ``p``'s geometry rows in place (caps must
        already fit; callers grow them first)."""
        m = pg.emask[p]
        ls, ld = pg.esrc[p][m], pg.edst[p][m]
        ne = ls.shape[0]
        ndt, nst, nw = self.n_dst_tiles, self.n_src_tiles, self.n_windows
        td, ts, et, er, ec = _tile_geometry(ls, ld, ndt, nst)
        T = td.shape[0]
        self.tile_dst[p] = ndt - 1       # padding tiles: last dst row
        self.tile_src[p] = nst - 1
        self.tile_dst[p, :T] = td
        self.tile_src[p, :T] = ts
        self.n_tiles[p] = T
        self.edge_tile[p] = -1
        self.edge_r[p] = 0
        self.edge_c[p] = 0
        self.edge_tile[p, :ne] = et
        self.edge_r[p, :ne] = er
        self.edge_c[p, :ne] = ec

        es, ldst, bw, nb = _window_geometry(ld, nw, self.block_edges)
        self.eslot[p] = -1
        self.eslot[p, :ne] = es
        self.ldst[p] = 0
        self.ldst[p, :ldst.shape[0]] = ldst
        self.bwin[p] = nw - 1            # padding blocks: last window
        self.bwin[p, :nb] = bw
        self.n_blocks[p] = nb

    def _partition_caps(self, pg, p: int) -> Tuple[int, int]:
        """(tiles, blocks) partition ``p`` needs at the current shapes."""
        m = pg.emask[p]
        ls, ld = pg.esrc[p][m], pg.edst[p][m]
        nst, nw = self.n_src_tiles, self.n_windows
        key = (ld.astype(np.int64) // TM) * nst + (ls.astype(np.int64) // TN)
        uniq = np.unique(key)
        covered = np.zeros(self.n_dst_tiles, bool)
        covered[(uniq // nst).astype(np.int64)] = True
        T = uniq.shape[0] + int((~covered).sum())
        counts = np.bincount(ld.astype(np.int64) // W, minlength=nw)
        B = int(np.maximum(-(-counts // self.block_edges), 1).sum())
        return T, B

    def _grow_caps(self, need_t: int, need_b: int) -> bool:
        """Grow ``t_max``/``b_max`` to the policy bucket (grow-only, like
        ``e_max`` under a delta). Returns True if anything grew."""
        grew = False
        if need_t > self.t_max:
            new_t = max(self.t_max, self.policy.bucket(need_t))
            pad = new_t - self.t_max
            self.tile_dst = np.concatenate(
                [self.tile_dst, np.full((self.n_parts, pad),
                                        self.n_dst_tiles - 1, np.int32)], 1)
            self.tile_src = np.concatenate(
                [self.tile_src, np.full((self.n_parts, pad),
                                        self.n_src_tiles - 1, np.int32)], 1)
            for key, tiles in list(self._tiles.items()):
                ident = tile_pad_identity(key[0], np.dtype(key[2]))
                self._tiles[key] = np.concatenate(
                    [tiles, np.full((self.n_parts, pad, TM, TN), ident,
                                    tiles.dtype)], 1)
            self.t_max = new_t
            grew = True
        if need_b > self.b_max:
            new_b = max(self.b_max, self.policy.bucket(need_b))
            pad = new_b - self.b_max
            self.bwin = np.concatenate(
                [self.bwin, np.full((self.n_parts, pad),
                                    self.n_windows - 1, np.int32)], 1)
            self.ldst = np.concatenate(
                [self.ldst, np.zeros((self.n_parts, pad * self.block_edges),
                                     np.int32)], 1)
            self.b_max = new_b
            grew = True
        return grew

    def rebuild_partitions(self, pg, parts: Iterable[int]) -> None:
        """Incrementally refresh the layout after a delta patched ``parts``
        (stream/delta.py): grow the bucketed caps if any patched partition
        overflows them, rebuild only the touched partitions' geometry, and
        re-realize only their rows of every cached tile realization. The
        capacities are grow-only, so untouched partitions' rows are valid
        as-is."""
        parts = sorted(set(int(p) for p in parts))
        need_t = need_b = 0
        for p in parts:
            t, b = self._partition_caps(pg, p)
            need_t, need_b = max(need_t, t), max(need_b, b)
        self._grow_caps(need_t, need_b)
        for p in parts:
            self._build_partition(pg, p)
        for key in self._tiles:
            self._realize_tiles(pg, key, parts)
        for key in self._filled:
            self._count_filled(pg, key, parts)
        self._device.clear()
        self._shard_geom.clear()    # caps persist (grow-only) in _shard_caps

    def sync_capacity(self, pg) -> bool:
        """Column-grow the per-edge arrays after ``e_max`` growth (``v_max``
        growth moves the tile/window grid and needs a full rebuild — then
        this returns False). Geometry content is untouched: new columns are
        padding until ``rebuild_partitions`` fills them."""
        if self.n_parts != pg.n_parts or self.v_max != pg.v_max:
            return False
        if pg.e_max > self.e_max:
            pad = pg.e_max - self.e_max

            def grow(a, fill):
                return np.concatenate(
                    [a, np.full((self.n_parts, pad), fill, a.dtype)], 1)

            self.edge_tile = grow(self.edge_tile, -1)
            self.edge_r = grow(self.edge_r, 0)
            self.edge_c = grow(self.edge_c, 0)
            self.eslot = grow(self.eslot, -1)
            self.e_max = pg.e_max
            self._device.clear()
            self._shard_geom.clear()
        return self.e_max == pg.e_max

    def matches(self, pg) -> bool:
        """False when the graph's padded shapes moved under us (bucket
        growth, compaction): the tile/window grid is derived from ``v_max``,
        so the whole geometry must be rebuilt."""
        return (self.n_parts == pg.n_parts and self.v_max == pg.v_max
                and self.e_max == pg.e_max)


def build_edge_layouts(pg, policy,
                       block_edges: int = DEFAULT_BLOCK_EDGES) -> EdgeLayouts:
    """Full build for all partitions of ``pg`` (assembly time / first use);
    capacities land on ``policy`` buckets so in-bucket streaming growth
    never changes a compiled runner's input shapes."""
    P, v_max, e_max = pg.n_parts, pg.v_max, pg.e_max
    lay = EdgeLayouts(
        n_parts=P, v_max=v_max, e_max=e_max, t_max=0, b_max=0,
        block_edges=int(block_edges), policy=policy,
        tile_dst=np.zeros((P, 0), np.int32),
        tile_src=np.zeros((P, 0), np.int32),
        n_tiles=np.zeros(P, np.int64),
        edge_tile=np.full((P, e_max), -1, np.int32),
        edge_r=np.zeros((P, e_max), np.int32),
        edge_c=np.zeros((P, e_max), np.int32),
        eslot=np.full((P, e_max), -1, np.int32),
        ldst=np.zeros((P, 0), np.int32),
        bwin=np.zeros((P, 0), np.int32),
        n_blocks=np.zeros(P, np.int64),
    )
    need_t = need_b = 1
    for p in range(P):
        t, b = lay._partition_caps(pg, p)
        need_t, need_b = max(need_t, t), max(need_b, b)
    lay._grow_caps(need_t, need_b)
    for p in range(P):
        lay._build_partition(pg, p)
    return lay
