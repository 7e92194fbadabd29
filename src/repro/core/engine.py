"""SVHM BSP engine (paper §4).

Executes a ``VertexProgram`` over a ``PartitionedGraph`` in bulk-synchronous
supersteps:

  superstep =  apply merged frontier data (paper: incoming messages M_i)
             → iterate local sweeps to a fixed point    ["think like a graph"]
             → emit frontier contributions ΔD_i
             → SBS combiner all-reduce (Aggregate + Disseminate, §4.3)
             → vote-to-halt when no partition changed anything and no
               messages are pending.

``mode='vc'`` bounds local iteration at one hop — the vertex-centric
(Pregel/Giraph) baseline the paper compares against. ``mode='sc'`` iterates to
the local fixed point — the subgraph-centric model. The partitioner choice
(vertex-cut vs edge-cut) is orthogonal and lives in the PartitionedGraph,
exactly the DRONE-VC / DRONE-EC split of §8.

Backends:
  - ``sim``       — single-process: [P, ...] stacked arrays, vmapped local
    phase, SBS = axis-0 reductions. Used by tests/benchmarks on CPU.
  - ``shard_map`` — production: partitions on the (pod, data) mesh axes, the
    model axis shards each partition's *edges* (hierarchical SVHM,
    DESIGN.md §2); SBS = lax.pmin/psum over (pod, data), intra-partition
    edge-combine = collectives over (model,).

This module is the **low-level one-shot layer**: ``run``/``run_sim``/
``run_shard_map`` build a fresh runner, upload the graph and execute a single
job. For serving — repeated queries, streaming updates, amortized
compilation — use ``repro.session.GraphSession``, which keeps the device
pytree resident and caches the compiled runners built by
``make_sim_runner``/``make_bsp_runner`` below.

Invariants the runner builders guarantee (sessions and tests rely on them):

  - **warm blocks are dtype-cast on entry** — ``_warm_block`` casts a
    previous global result to ``program.dtype`` and fills padded rows with
    the combiner identity *before* the array reaches either backend, so a
    caller's float64 numpy result can never leak its dtype into the
    compiled superstep loop (and force a retrace or an upcast sweep).
  - **``n_slots`` may be over-provisioned** — a runner built with
    ``n_slots >= `` the graph's actual frontier count is correct: slot rows
    in ``[actual, n_slots)`` only ever receive identity contributions
    (``scatter_combine`` routes unchanged/non-frontier vertices to identity)
    and are never gathered by a live vertex, whose sentinel row is identity
    too. ``GraphSession`` exploits this to build runners on *bucketed* slot
    capacities that survive frontier re-elections.
  - **the warm input is structural** — a runner either takes the
    ``[P, v_max, K]`` warm block (``warm_start=True``; cold starts feed the
    combiner identity) or does not take it at all; there is no silent
    dropped-argument path, so a non-monotone program's cold start is
    visible in the lowered HLO.
"""
from __future__ import annotations

import dataclasses
import os
import time
from functools import partial
from typing import Any, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import shard_map
from repro.core import sbs
from repro.core.api import DeviceSubgraph, SemiringSweep, VertexProgram
from repro.core.layouts import EdgeLayouts, TileBlock, WindowBlock
from repro.core.metrics import ExecutionStats
from repro.core.subgraph import PartitionedGraph
from repro.kernels.bsp_spmv import TM, TN, bsp_spmv
from repro.kernels.ref import combine_identity, tile_pad_identity
from repro.kernels.segment_combine import W, segment_combine_windowed

__all__ = ["EngineConfig", "EdgeCombine", "run", "run_sim", "run_shard_map",
           "make_sim_runner", "make_bsp_runner", "resolve_edge_backend",
           "normalize_edge_backend", "resolve_partition_backends",
           "ShardSpecs", "shard_specs", "shard_placement"]


# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class EdgeCombine:
    """Merges edge-parallel partial aggregates inside a partition.

    Programs call ``ec.sum/min/max`` on any value derived from a reduction
    over the partition's edges. In the simulator this is the identity; under
    shard_map it reduces over the model axis, which shards the edge list.
    """

    axis_names: tuple = ()

    def sum(self, x):
        return jax.lax.psum(x, self.axis_names) if self.axis_names else x

    def min(self, x):
        return jax.lax.pmin(x, self.axis_names) if self.axis_names else x

    def max(self, x):
        return jax.lax.pmax(x, self.axis_names) if self.axis_names else x


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static engine configuration. Frozen so the module-level default
    instances in ``run``/``run_sim`` signatures stay shared-state-free
    (params travel as explicit arguments, never stashed on the config)."""

    mode: str = "sc"                  # 'sc' | 'vc'
    max_local_iters: int = 10_000     # straggler bound (DESIGN.md §7)
    max_supersteps: int = 100_000
    backend: str = "sim"              # 'sim' | 'shard_map'
    edge_backend: str = "coo"         # 'coo' | 'pallas_tiles' |
                                      # 'pallas_windows' | 'auto' — how the
                                      # local sweep's semiring product is
                                      # computed for SemiringSweep programs
                                      # (programs without a spec always run
                                      # COO). 'auto' picks per partition
                                      # from the calibrated density policy
                                      # (core/autotune.py)
    trace: bool = False               # python superstep loop w/ per-step stats
    sparse_sync_capacity: int = 0     # >0: compacted all-gather SBS (shard)
    shard_slots: bool = False         # shard the SBS buffer over edge_axes
    lean_frontier: bool = False       # detect changes vs last *merged* value
                                      # (no last_out buffer; suppresses
                                      # globally-dominated updates — §Perf)
    subgraph_axes: tuple = ("sub",)   # mesh axes carrying partitions
    edge_axes: tuple = ()             # mesh axes sharding edges in-partition
    checkpoint_every: int = 0         # supersteps; 0 = off (trace mode only)
    checkpoint_dir: Optional[str] = None

    _MODES = ("sc", "vc")
    _BACKENDS = ("sim", "shard_map")
    # backends a partition's sweep can actually execute on; 'auto' resolves
    # to one of these per partition (resolve_partition_backends)
    _CONCRETE_EDGE_BACKENDS = ("coo", "pallas_tiles", "pallas_windows")
    _EDGE_BACKENDS = _CONCRETE_EDGE_BACKENDS + ("auto",)

    def __post_init__(self):
        """Fail at construction, not deep inside a run (a typo'd mode would
        otherwise silently degrade: anything != 'vc' iterates to the local
        fixed point)."""
        if self.mode not in self._MODES:
            raise ValueError(
                f"EngineConfig.mode={self.mode!r}: allowed values are "
                f"{self._MODES}")
        if self.backend not in self._BACKENDS:
            raise ValueError(
                f"EngineConfig.backend={self.backend!r}: allowed values are "
                f"{self._BACKENDS}")
        if self.edge_backend not in self._EDGE_BACKENDS:
            raise ValueError(
                f"EngineConfig.edge_backend={self.edge_backend!r}: allowed "
                f"values are {self._EDGE_BACKENDS}")
        for name in ("subgraph_axes", "edge_axes"):
            axes = getattr(self, name)
            if isinstance(axes, str) or not all(
                    isinstance(a, str) for a in tuple(axes)):
                raise ValueError(
                    f"EngineConfig.{name}={axes!r} must be a tuple of mesh "
                    f"axis names, e.g. ('pod', 'data')")
            object.__setattr__(self, name, tuple(axes))   # lists hash too
        for name in ("max_local_iters", "max_supersteps"):
            if getattr(self, name) < 1:
                raise ValueError(f"EngineConfig.{name} must be >= 1, got "
                                 f"{getattr(self, name)}")
        for name in ("sparse_sync_capacity", "checkpoint_every"):
            if getattr(self, name) < 0:
                raise ValueError(f"EngineConfig.{name} must be >= 0, got "
                                 f"{getattr(self, name)}")

    @property
    def local_bound(self) -> int:
        return 1 if self.mode == "vc" else self.max_local_iters


# --------------------------------------------------------------------------- #
class ShardSpecs(NamedTuple):
    """Where each shard_map runner input lives on the mesh — the runner's
    ``in_specs`` (``make_bsp_runner``) and the placement of the resident
    inputs (``shard_placement``) come from this one definition, so a placed
    input never has to move at launch."""
    graph: DeviceSubgraph     # stacked [P, ...] graph arrays
    tiles: TileBlock          # pallas_tiles layout
    windows: WindowBlock      # pallas_windows layout
    part: Any                 # [P] per-partition vectors ('auto' backend ids)
    warm: Any                 # [P, v_max, K] warm-state blocks


def shard_specs(cfg: EngineConfig, has_vlabel: bool = False) -> ShardSpecs:
    """PartitionSpecs of the shard_map runner's inputs: partitions over
    ``cfg.subgraph_axes``; edge arrays and tile/window lists additionally
    over ``cfg.edge_axes`` (each edge shard's slice is a standalone
    per-shard list, ``EdgeLayouts._sharded_geometry``)."""
    sub_axes = tuple(cfg.subgraph_axes)
    e_ax = tuple(cfg.edge_axes) or None
    edge_spec = P(sub_axes, e_ax)
    vert_spec = P(sub_axes, None)
    return ShardSpecs(
        graph=DeviceSubgraph(
            esrc=edge_spec, edst=edge_spec, ew=edge_spec, emask=edge_spec,
            slot=vert_spec, vmask=vert_spec, vid32=vert_spec,
            is_frontier=vert_spec, out_deg=vert_spec, in_deg=vert_spec,
            is_master=vert_spec, vlabel=vert_spec if has_vlabel else None),
        tiles=TileBlock(tiles=P(sub_axes, e_ax, None, None),
                        tile_dst=P(sub_axes, e_ax),
                        tile_src=P(sub_axes, e_ax)),
        windows=WindowBlock(eslot=edge_spec, ldst=P(sub_axes, e_ax),
                            bwin=P(sub_axes, e_ax)),
        part=P(sub_axes),
        warm=P(sub_axes, None, None))


def shard_placement(mesh: Mesh, cfg: EngineConfig,
                    has_vlabel: bool = False) -> ShardSpecs:
    """``shard_specs`` as ``NamedSharding``s on ``mesh`` — what the
    resident graph, layouts and warm blocks are ``device_put`` with."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        shard_specs(cfg, has_vlabel),
                        is_leaf=lambda x: isinstance(x, P))


def _device_subgraph(pg: PartitionedGraph,
                     placement: Optional[ShardSpecs] = None) -> DeviceSubgraph:
    """Stacked [P, ...] DeviceSubgraph pytree: on the default device, or —
    given a ``shard_placement`` — straight from host memory into each
    device's share of the mesh."""
    assert pg.n_vertices < 2**31
    vid32 = pg.gvid.astype(np.int64).copy()
    vid32[~pg.vmask] = np.iinfo(np.int32).max
    host = DeviceSubgraph(
        esrc=pg.esrc, edst=pg.edst, ew=pg.ew, emask=pg.emask, slot=pg.slot,
        vmask=pg.vmask, vid32=vid32.astype(np.int32),
        is_frontier=pg.is_frontier, out_deg=pg.out_deg, in_deg=pg.in_deg,
        is_master=pg.is_master, vlabel=pg.vlabel)
    if placement is None:
        return jax.tree.map(jnp.asarray, host)
    return jax.device_put(host, placement.graph)


# --------------------------------------------------------------------------- #
# Edge-compute backends: how a SemiringSweep program's local relaxation
# product is evaluated. 'coo' is the reference dense gather/scatter
# (api.coo_semiring_product, inside program.sweep); the Pallas backends
# route the product through the kernels in repro.kernels against the
# device layouts built by core.layouts (interpret mode off-TPU).
# --------------------------------------------------------------------------- #
def resolve_edge_backend(program: VertexProgram, cfg: EngineConfig) -> str:
    """The backend this (program, config) pair actually runs.

    Declarative ``sweep_spec`` programs run on whatever
    ``cfg.edge_backend`` asks for — the engine generates their product;
    ``'auto'`` passes through here and resolves per *partition* in
    ``resolve_partition_backends``. Programs that override ``sweep``
    declare the backends their hand-rolled code implements via the
    ``supports_edge_backends`` class attribute (today ``("coo",)`` for
    every shipped custom sweep); when the requested backend — including
    ``'auto'``, which no custom sweep can implement — is unsupported they
    fall back to the first declared one so a session can serve a mixed
    program suite under one config. A custom sweep that declares nothing
    is refused outright: silently running it on an arbitrary backend it
    ignores is exactly the bug class this resolution step exists to
    prevent."""
    declared = program.supports_edge_backends
    if declared is not None:
        allowed = EngineConfig._CONCRETE_EDGE_BACKENDS
        unknown = tuple(b for b in declared if b not in allowed)
        if unknown or not declared:
            raise ValueError(
                f"{type(program).__name__}.supports_edge_backends={declared!r}"
                f" contains unknown backends {unknown!r}; allowed values are "
                f"{allowed}")
        return cfg.edge_backend if cfg.edge_backend in declared else declared[0]
    if program.sweep_spec is not None:
        return cfg.edge_backend           # generated product: any backend
    raise ValueError(
        f"{type(program).__name__} overrides sweep but does not declare "
        "supports_edge_backends: a hand-rolled sweep must name the edge "
        "backends it implements (e.g. supports_edge_backends = ('coo',)) "
        "so the engine cannot silently route it onto a backend it ignores")


def normalize_edge_backend(program: VertexProgram,
                           cfg: EngineConfig) -> tuple:
    """``(resolved backend, config rewritten to it)`` — the ONLY sanctioned
    way to consume ``cfg.edge_backend`` outside this resolution layer
    (drone-lint DL007). Raw reads are a correctness trap: a session serving
    a custom-sweep program under a Pallas or ``'auto'`` config would key
    its runner cache and pick its argument protocol off the *requested*
    backend while the engine silently runs the *resolved* one."""
    eb = resolve_edge_backend(program, cfg)
    if eb != cfg.edge_backend:
        cfg = dataclasses.replace(cfg, edge_backend=eb)
    return eb, cfg


#: lax.switch branch ids of the shard_map mixed-backend sweep
_BACKEND_IDS = {"coo": 0, "pallas_tiles": 1, "pallas_windows": 2}


def resolve_partition_backends(program: VertexProgram, cfg: EngineConfig,
                               pg: PartitionedGraph, *, lay=None,
                               table=None) -> tuple:
    """Per-partition concrete backend assignment. Uniform (non-``'auto'``)
    configs broadcast the resolved backend; ``'auto'`` consults the
    platform's calibration table (core/autotune.py) over the partition's
    layout-geometry unit counts. Deterministic for a given (table,
    geometry) — sessions additionally pin the assignment per shape bucket
    so in-bucket growth cannot flip it."""
    eb = resolve_edge_backend(program, cfg)
    if eb != "auto":
        return (eb,) * pg.n_parts
    from repro.core import autotune
    if lay is None:
        lay = pg.ensure_edge_layouts()
    if table is None:
        table = autotune.get_table()
    return autotune.pick_backends(table, pg, lay)


def _tile_product(blk: TileBlock, vals, spec: SemiringSweep, v_max: int):
    """Semiring product via bsp_spmv for one partition ([v_max, K] vals) or
    the whole stacked graph ([P, v_max, K]): the stacked case flattens every
    partition's tile list into ONE kernel launch by offsetting the tile ids
    with ``p * n_tiles_per_partition`` — per-partition lists are dst-major
    sorted, so the concatenation is too, and each partition covers its own
    dst rows (no cross-partition accumulation is possible)."""
    ident = tile_pad_identity(spec.semiring, vals.dtype)
    if not jnp.issubdtype(vals.dtype, jnp.floating):
        # integer min_plus: pads are ADDED to values — clamp so that
        # ident + ident cannot wrap (sound below 2**30, see kernels/ref.py)
        vals = jnp.minimum(vals, ident)
    ndt = max(-(-v_max // TM), 1)
    nst = max(-(-v_max // TN), 1)
    if vals.ndim == 2:                                     # one partition
        K = vals.shape[-1]
        v = jnp.pad(vals, ((0, nst * TN - v_max), (0, 0)),
                    constant_values=ident)
        out = bsp_spmv(blk.tiles, blk.tile_dst, blk.tile_src,
                       v.reshape(nst, TN, K), n_dst_tiles=ndt,
                       semiring=spec.semiring)
        return out.reshape(ndt * TM, K)[:v_max]
    P, _, K = vals.shape                                   # stacked [P, ...]
    t_max = blk.tiles.shape[1]
    v = jnp.pad(vals, ((0, 0), (0, nst * TN - v_max), (0, 0)),
                constant_values=ident)
    offs = jnp.arange(P, dtype=jnp.int32)[:, None]
    out = bsp_spmv(blk.tiles.reshape(P * t_max, TM, TN),
                   (blk.tile_dst + offs * ndt).reshape(-1),
                   (blk.tile_src + offs * nst).reshape(-1),
                   v.reshape(P * nst, TN, K), n_dst_tiles=P * ndt,
                   semiring=spec.semiring)
    return out.reshape(P, ndt * TM, K)[:, :v_max]


def _edge_messages(spec: SemiringSweep, vals, esrc, ew):
    """Per-edge semiring messages ``vals[src] (+|*) ev`` (padding edges are
    computed too — their buffer slot is out of range and dropped)."""
    sv = jnp.take_along_axis(vals, esrc[..., None], axis=-2) \
        if vals.ndim == 3 else vals[esrc]
    if spec.edge_values == "weight":
        ev = ew.astype(vals.dtype)[..., None]
        return sv + ev if spec.semiring == "min_plus" else sv * ev
    if spec.edge_values == "zero":
        return sv if spec.semiring == "min_plus" else jnp.zeros_like(sv)
    # 'one': * 1 is the identity, but + 1 is NOT — min_plus over unit edge
    # values is hop counting (BFS levels). The COO reference and the baked
    # tile layouts (layouts._edge_values) both add the 1; returning ``sv``
    # here would make the windowed backend count every hop as free.
    return sv + jnp.asarray(1, vals.dtype) if spec.semiring == "min_plus" \
        else sv


def _window_product(blk: WindowBlock, vals, spec: SemiringSweep, v_max: int,
                    esrc, ew):
    """Semiring product via segment_combine_windowed for one partition
    ([v_max, K] vals) or the stacked graph ([P, v_max, K]), which runs one
    launch per partition under ``lax.map``: one compiled kernel, and each
    launch's prefetched block -> window map stays one partition long."""
    if vals.ndim == 3:
        return jax.lax.map(
            lambda a: _window_product(a[0], a[1], spec, v_max, a[2], a[3]),
            (blk, vals, esrc, ew))
    ident = combine_identity(spec.combiner, vals.dtype)
    nw = max(-(-v_max // W), 1)
    msgs = _edge_messages(spec, vals, esrc, ew)
    K = vals.shape[-1]
    n_buf = blk.ldst.shape[-1]
    slot = jnp.where(blk.eslot >= 0, blk.eslot, n_buf)       # pad -> dropped
    buf = jnp.full((n_buf, K), ident, vals.dtype)
    buf = buf.at[slot].set(msgs, mode="drop")
    out = segment_combine_windowed(buf, blk.ldst, blk.bwin, n_windows=nw,
                                   combiner=spec.combiner)
    return out.reshape(nw * W, K)[:v_max]


def _make_pallas_sweep(program: VertexProgram, edge_backend: str):
    """Per-partition sweep closure for the shard_map body (and the
    superstep of ``_batched_local_phase``): pre-transform -> kernel product
    -> edge-combine -> fold, exactly the shape of the base-class COO sweep
    in api.py."""
    spec = program.sweep_spec

    def sweep(sg: DeviceSubgraph, lay_blk, params, state, ec: EdgeCombine):
        vals = program.sweep_values(sg, params, state)
        squeeze = vals.ndim == sg.vmask.ndim           # [.., v_max] -> K=1
        v = vals[..., None] if squeeze else vals
        v_max = sg.vmask.shape[-1]
        if edge_backend == "pallas_tiles":
            agg = _tile_product(lay_blk, v, spec, v_max)
        else:
            agg = _window_product(lay_blk, v, spec, v_max, sg.esrc, sg.ew)
        agg = ec.min(agg) if spec.semiring == "min_plus" else ec.sum(agg)
        if squeeze:
            agg = agg[..., 0]
        return program.sweep_fold(sg, params, state, agg)

    return sweep


def _layout_block_from(lay: EdgeLayouts, pg: PartitionedGraph,
                       program: VertexProgram, edge_backend: str,
                       n_shards: int = 1,
                       placement: Optional[ShardSpecs] = None):
    """Device layout pytree a Pallas runner takes as an explicit input
    (never closed over: the arrays change under streaming, the compiled
    runner must not bake them in). ``n_shards > 1`` returns the
    edge-axis-sharded variant (per-shard tile/window lists); a
    ``placement`` puts it on the mesh."""
    spec = program.sweep_spec
    t_pl = None if placement is None else placement.tiles
    w_pl = None if placement is None else placement.windows
    if edge_backend == "pallas_tiles":
        if not jnp.issubdtype(jnp.dtype(program.dtype), jnp.floating):
            assert pg.n_vertices < 2**30, \
                ("integer min_plus through the tile kernel clamps values to "
                 "iinfo.max >> 1 (kernels/ref.py tile_pad_identity); ids "
                 "must stay below 2**30")
        if n_shards > 1:
            return lay.device_tiles_sharded(pg, spec.semiring,
                                            spec.edge_values, program.dtype,
                                            n_shards, shardings=t_pl)
        return lay.device_tiles(pg, spec.semiring, spec.edge_values,
                                program.dtype, shardings=t_pl)
    if n_shards > 1:
        return lay.device_windows_sharded(pg, n_shards, shardings=w_pl)
    return lay.device_windows(shardings=w_pl)


def _assignment_groups(assignment) -> tuple:
    """Static per-backend partition groups of an ``'auto'`` assignment:
    ``((backend, [P_g] int64 indices), ...)`` in a fixed order."""
    groups = []
    for b in EngineConfig._CONCRETE_EDGE_BACKENDS:
        idx = np.asarray([p for p, a in enumerate(assignment) if a == b],
                         np.int64)
        if idx.size:
            groups.append((b, idx))
    return tuple(groups)


def _auto_layout_blocks(lay: EdgeLayouts, pg: PartitionedGraph,
                        program: VertexProgram, assignment,
                        mixed_shard: bool = False, n_shards: int = 1,
                        placement: Optional[ShardSpecs] = None):
    """Layout input of an ``'auto'`` runner.

    Simulator (``mixed_shard=False``): ``(tiles, windows)`` with each block
    group-sliced to just the partitions its backend owns (``None`` when the
    backend owns nothing) — the mixed superstep launches one kernel per
    group over its sub-stack. Cached on the layouts' device cache so
    repeated queries reuse the slices until a rebuild invalidates them.

    shard_map (``mixed_shard=True``): ``(tiles, windows, backend_ids)``
    with *full* (possibly edge-axis-sharded) blocks — every device gets
    same-shaped slices and a ``lax.switch`` on its partition's backend id
    picks the path, so one executable serves any assignment shape;
    ``placement`` puts all three on the mesh."""
    spec = program.sweep_spec
    if mixed_shard:
        ids = np.asarray([_BACKEND_IDS[b] for b in assignment], np.int32)
        ids = jnp.asarray(ids) if placement is None \
            else jax.device_put(ids, placement.part)
        return (_layout_block_from(lay, pg, program, "pallas_tiles",
                                   n_shards, placement),
                _layout_block_from(lay, pg, program, "pallas_windows",
                                   n_shards, placement), ids)
    t_idx = tuple(p for p, b in enumerate(assignment)
                  if b == "pallas_tiles")
    w_idx = tuple(p for p, b in enumerate(assignment)
                  if b == "pallas_windows")
    key = ("auto_groups", t_idx, w_idx, spec.semiring, spec.edge_values,
           np.dtype(program.dtype).str)
    blk = lay._device.get(key)
    if blk is None:
        t_blk = w_blk = None
        if t_idx:
            full = _layout_block_from(lay, pg, program, "pallas_tiles")
            t_blk = TileBlock(*[x[np.asarray(t_idx)] for x in full])
        if w_idx:
            full = lay.device_windows()
            w_blk = WindowBlock(*[x[np.asarray(w_idx)] for x in full])
        blk = (t_blk, w_blk)
        lay._device[key] = blk
    return blk


def _mixed_product(program: VertexProgram, groups, sgs, lay_blks, v):
    """Stacked [P, v_max, K] semiring product under a mixed per-partition
    backend assignment: one launch per backend group over its (static)
    partition sub-stack, scattered back into the full aggregate. Matches
    the uniform paths bit-for-bit per partition — the COO group is the
    vmapped reference product, the Pallas groups are the flattened kernel
    launches over group-sliced layout blocks."""
    from repro.core.api import coo_semiring_product
    spec = program.sweep_spec
    t_blk, w_blk = lay_blks
    v_max = sgs.vmask.shape[-1]
    agg = jnp.zeros(v.shape, v.dtype)       # every row overwritten below
    for backend, gidx in groups:
        if backend == "coo":
            sub = jax.tree.map(lambda a: a[gidx], sgs)
            part = jax.vmap(
                lambda sg, vv: coo_semiring_product(sg, spec, vv)
            )(sub, v[gidx])
        elif backend == "pallas_tiles":
            part = _tile_product(t_blk, v[gidx], spec, v_max)
        else:
            part = _window_product(w_blk, v[gidx], spec, v_max,
                                   sgs.esrc[gidx], sgs.ew[gidx])
        agg = agg.at[jnp.asarray(gidx)].set(part)
    return agg


def _local_phase(program: VertexProgram, sg: DeviceSubgraph, params, state,
                 merged_v, ec: EdgeCombine, bound: int, first,
                 sweep_fn=None):
    """apply incoming -> sweep to local fixed point (or one hop).

    ``first`` is True at superstep 0, where there are no incoming messages
    (paper Algorithm 1's ``if superstep = 0`` branch) and apply is skipped.
    ``sweep_fn`` overrides ``program.sweep`` (Pallas edge backends).
    """
    sweep = sweep_fn if sweep_fn is not None else program.sweep
    state = jax.lax.cond(
        first, lambda st: st,
        lambda st: program.apply_frontier(sg, params, st, merged_v, ec)[0],
        state)
    state, ch = sweep(sg, params, state, ec)

    def cond(c):
        i, _, chg = c
        return (chg > 0) & (i < bound)

    def body(c):
        i, st, _ = c
        st, chg = sweep(sg, params, st, ec)
        return (i + 1, st, chg)

    i, state, last_ch = jax.lax.while_loop(cond, body, (jnp.int32(1), state, ch))
    out = program.frontier_out(sg, params, state)
    return state, out, i, last_ch


def _batched_local_phase(program: VertexProgram, sgs, lay_blk, params, state,
                         merged_v, ec: EdgeCombine, bound: int, first,
                         edge_backend: str, groups=None):
    """Stacked-graph local phase for the simulator's Pallas (and mixed
    ``'auto'``) path.

    The vmapped ``_local_phase`` cannot host a Pallas call (the batching
    rule would have to lift the kernel); instead the whole [P, ...] stack
    goes through ONE flattened kernel launch per sweep — per backend group
    under a mixed assignment — and the while loop emulates vmap-of-while
    semantics by hand: a partition whose local fixed point is reached stops
    updating (its rows are select-frozen) while the others continue —
    identical results, per-partition sweep counts, and straggler bound as
    the vmapped COO path."""
    state = jax.lax.cond(
        first, lambda st: st,
        lambda st: jax.vmap(
            lambda sg, s, m: program.apply_frontier(sg, params, s, m, ec)[0]
        )(sgs, st, merged_v), state)

    def sweep_all(st):
        vals = jax.vmap(
            lambda sg, s: program.sweep_values(sg, params, s))(sgs, st)
        squeeze = vals.ndim == 2
        v = vals[..., None] if squeeze else vals
        v_max = sgs.vmask.shape[-1]
        if edge_backend == "auto":
            agg = _mixed_product(program, groups, sgs, lay_blk, v)
        elif edge_backend == "pallas_tiles":
            agg = _tile_product(lay_blk, v, program.sweep_spec, v_max)
        else:
            agg = _window_product(lay_blk, v, program.sweep_spec, v_max,
                                  sgs.esrc, sgs.ew)
        if squeeze:
            agg = agg[..., 0]
        return jax.vmap(
            lambda sg, s, a: program.sweep_fold(sg, params, s, a)
        )(sgs, st, agg)

    state, ch = sweep_all(state)
    n_parts = sgs.vmask.shape[0]
    i0 = jnp.ones((n_parts,), jnp.int32)

    def cond(c):
        i, _, chg = c
        return jnp.any((chg > 0) & (i < bound))

    def body(c):
        i, st, chg = c
        live = (chg > 0) & (i < bound)
        st2, ch2 = sweep_all(st)
        st = jax.tree.map(
            lambda a, b: jnp.where(live.reshape((-1,) + (1,) * (b.ndim - 1)),
                                   b, a), st, st2)
        return (jnp.where(live, i + 1, i), st, jnp.where(live, ch2, chg))

    i, state, last_ch = jax.lax.while_loop(cond, body, (i0, state, ch))
    out = jax.vmap(
        lambda sg, s: program.frontier_out(sg, params, s))(sgs, state)
    return state, out, i, last_ch


def _pack(program: VertexProgram, sg: DeviceSubgraph, out, last_out,
          n_slots: int):
    changed = program.changed_mask(out, last_out) & sg.frontier
    buf = sbs.scatter_combine(out, sg.slot, changed, n_slots,
                              program.combiner, program.identity)
    return buf, changed


def _warm_block(program: VertexProgram, pg: PartitionedGraph,
                init_state) -> np.ndarray:
    """Map a previous *global* converged result [n_vertices(, K)] into the
    [P, v_max, K] per-partition local layout the backends feed to
    ``program.warm_init`` — combiner identity at padded rows, cast to the
    program dtype on entry (a float64 result array must not leak its dtype
    into the superstep loop). Shorter arrays (the graph grew since the run)
    are padded with the identity: new vertices start cold."""
    K = program.payload
    ident = program.identity
    dt = np.dtype(program.dtype)
    warm = np.asarray(init_state)
    if warm.ndim == 1:
        warm = warm[:, None]
    warm = warm.astype(dt, copy=False)
    if warm.shape[0] < pg.n_vertices:      # graph grew since the run
        warm = np.concatenate(
            [warm, np.full((pg.n_vertices - warm.shape[0], warm.shape[1]),
                           ident, dtype=dt)])
    wv = np.full((pg.n_parts, pg.v_max, K), ident, dtype=dt)
    wv[pg.vmask] = warm[pg.gvid[pg.vmask]]
    return wv


def _exchange_bytes_per_step(cfg: EngineConfig, n_slots: int, K: int,
                             dtype, n_parts: int, n_edge_shards: int) -> int:
    """Collective bytes one superstep's SBS exchange moves — matching the
    exchange variant the runner actually lowered, so sparse-vs-dense
    benchmark comparisons measure real volume. Counts the inter-partition
    (subgraph-axes) collective only: intra-partition edge-axis combines
    (sweep reductions, the sharded merged-view rebuild) are excluded
    everywhere, like the paper's network-message metric."""
    itemsize = np.dtype(dtype).itemsize
    if cfg.shard_slots and n_edge_shards > 1:
        # each of the n_edge_shards slot slices is all-reduced over the
        # subgraph axes: n_loc + 1 rows (incl. the dump row) per device,
        # n_parts * n_edge_shards devices
        n_loc = -(-(n_slots + 1) // n_edge_shards)
        return (n_loc + 1) * K * itemsize * n_parts * n_edge_shards
    if cfg.sparse_sync_capacity > 0:
        # compacted all-gather: capacity (int32 idx, K-vector val) pairs
        cap = min(cfg.sparse_sync_capacity, n_slots + 1)
        return cap * (4 + K * itemsize) * n_parts
    return (n_slots + 1) * K * itemsize * n_parts


def _flops_per_sweep(program: VertexProgram, edge_backend: str,
                     pg: PartitionedGraph,
                     lay: Optional[EdgeLayouts], assignment=None,
                     n_edge_shards: int = 1) -> np.ndarray:
    """[P] semiring ops one local sweep issues per partition, for
    ``ExecutionStats.backend_flops``: the COO path pays one combine + one
    reduce per resident edge per payload lane; the Pallas backends pay for
    the dense tiles/blocks they actually launch (identity padding included —
    that is the density tax the stats make visible). Under ``'auto'`` each
    partition is billed at its *assigned* backend's rate."""
    K = program.payload
    coo = 2 * K * pg.edges_per_part.astype(np.int64)
    if edge_backend == "coo" or lay is None:
        return coo
    if edge_backend == "auto":
        out = coo.copy()
        asg = np.asarray(assignment)
        for b in ("pallas_tiles", "pallas_windows"):
            m = asg == b
            if m.any():
                out[m] = lay.flops_per_sweep(
                    b, K, n_shards=n_edge_shards, pg=pg)[m]
        return out
    return lay.flops_per_sweep(edge_backend, K, n_shards=n_edge_shards,
                               pg=pg)


# --------------------------------------------------------------------------- #
# Simulator backend
# --------------------------------------------------------------------------- #
def _make_sim_superstep(program: VertexProgram, cfg: EngineConfig,
                        n_slots: int, edge_backend: str = "coo",
                        assignment=None):
    """One BSP superstep over the stacked [P, ...] pytree: vmapped local
    phase on the COO backend, one flattened Pallas launch per sweep on the
    kernel backends (per backend group under a mixed ``'auto'``
    ``assignment``). ``lay`` is the device layout pytree (None for COO)."""
    ident = program.identity
    ec = EdgeCombine(())
    ex = sbs.SimExchange()
    groups = _assignment_groups(assignment) if edge_backend == "auto" \
        else None

    def superstep(sgs, lay, params, state, last_out, merged_buf, first):
        merged_v = jax.vmap(lambda sg: sbs.gather_merged(merged_buf, sg.slot))(sgs)
        if edge_backend == "coo":
            state, out, sweeps, last_ch = jax.vmap(
                lambda sg, st, m: _local_phase(program, sg, params, st, m, ec,
                                               cfg.local_bound, first)
            )(sgs, state, merged_v)
        else:
            state, out, sweeps, last_ch = _batched_local_phase(
                program, sgs, lay, params, state, merged_v, ec,
                cfg.local_bound, first, edge_backend, groups)
        bufs, changed = jax.vmap(
            lambda sg, o, lo: _pack(program, sg, o, lo, n_slots)
        )(sgs, out, last_out)
        merged_buf = ex.all_combine(bufs, program.combiner)
        merged_buf = merged_buf.at[n_slots].set(ident)
        msgs = jnp.sum(changed, dtype=jnp.int32)
        active = jnp.sum(last_ch > 0, dtype=jnp.int32)
        return state, out, merged_buf, msgs, active, sweeps

    return superstep


def make_sim_runner(program: VertexProgram, cfg: EngineConfig, n_slots: int,
                    *, warm_start=False, batch=False,
                    partition_backends=None):
    """Build the simulator BSP loop as a pure function

        runner(sgs[, lay], params[, warm_block]) ->
            (results, supersteps, total_messages, sweeps_per_part)

    ``sgs`` is the stacked [P, ...] DeviceSubgraph pytree, ``params`` the
    program's parameter pytree (traced — repeated calls with different
    params reuse one compilation), ``warm_block`` (``warm_start=True``) a
    [P, v_max, K] previous-result block threaded into ``program.warm_init``.

    ``batch=True`` builds the cross-request micro-batching variant
    (serving/batcher.py): every params leaf — and the warm block — carries
    a leading batch axis B, the graph (and layout) inputs stay shared, and
    ONE launch returns per-lane ``(results[B], steps[B], msgs[B],
    sweeps[B, P])``. The COO path vmaps the whole BSP loop over the lanes
    (vmap-of-while: a converged lane's carry is select-frozen while the
    rest run on, so per-lane math is identical to a singleton run); the
    Pallas backends cannot ride vmap's lifting of ``pallas_call``, so they
    scan the lanes sequentially inside the same single launch instead —
    same executable-count and dispatch amortization, no lane parallelism.

    When ``resolve_edge_backend(program, cfg)`` picks a Pallas backend the
    runner takes the device layout pytree (``TileBlock``/``WindowBlock``,
    built by ``_layout_block_from``) as its second argument — an explicit
    input,
    not a closure, so a serving session's compiled executable keeps working
    as the layouts evolve under streaming. Under ``'auto'`` the caller must
    pass the per-partition ``partition_backends`` assignment
    (``resolve_partition_backends``) and the layout argument becomes the
    group-sliced ``(tiles, windows)`` pair of ``_auto_layout_blocks``.

    ``run_sim`` calls the runner eagerly once per job; ``GraphSession``
    wraps it in ``jax.jit``, AOT-compiles it once per
    (program, config, padded shapes) key and reuses the executable across
    queries with zero retraces."""
    K = program.payload
    ident = program.identity
    ec = EdgeCombine(())
    edge_backend = resolve_edge_backend(program, cfg)
    if edge_backend == "auto" and partition_backends is None:
        raise ValueError("edge_backend='auto' runners need the resolved "
                         "partition_backends assignment "
                         "(resolve_partition_backends)")
    superstep = _make_sim_superstep(program, cfg, n_slots, edge_backend,
                                    partition_backends)

    def _run(sgs, lay, params, warm):
        n_parts, v_max = sgs.vmask.shape
        v_init = jax.vmap(lambda sg: program.init(sg, params, ec))(sgs)
        if warm_start:
            v_init = jax.vmap(
                lambda sg, st, w: program.warm_init(sg, params, st, w)
            )(sgs, v_init, warm[0])
        last0 = jnp.full((n_parts, v_max, K), ident, dtype=program.dtype)
        merged0 = jnp.full((n_slots + 1, K), ident, dtype=program.dtype)

        def cond(c):
            step, msgs, active = c[0], c[-2], c[-1]
            return (step == 0) | (((msgs > 0) | (active > 0))
                                  & (step < cfg.max_supersteps))

        def body(c):
            step, state, last_out, merged_buf, tot_msgs, tot_sweeps, _, _ = c
            state, out, merged_buf, msgs, active, sweeps = superstep(
                sgs, lay, params, state, last_out, merged_buf, step == 0)
            return (step + 1, state, out, merged_buf, tot_msgs + msgs,
                    tot_sweeps + sweeps, msgs, active)

        carry = (jnp.int32(0), v_init, last0, merged0, jnp.int32(0),
                 jnp.zeros((n_parts,), jnp.int32), jnp.int32(1),
                 jnp.int32(1))
        carry = jax.lax.while_loop(cond, body, carry)
        (steps, state, last_out, merged_buf, tot_msgs, tot_sweeps, *_) = carry
        results = jax.vmap(
            lambda sg, st: program.result(sg, params, st))(sgs, state)
        return results, steps, tot_msgs, tot_sweeps

    if not batch:
        if edge_backend == "coo":
            def runner(sgs, params, *warm):
                return _run(sgs, None, params, warm)
        else:
            def runner(sgs, lay, params, *warm):
                return _run(sgs, lay, params, warm)
        return runner

    if edge_backend == "coo":
        def runner(sgs, params, *warm):
            return jax.vmap(lambda p, w: _run(sgs, None, p, w),
                            in_axes=(0, 0))(params, warm)
    else:
        def runner(sgs, lay, params, *warm):
            def step(c, x):
                p, w = x
                return c, _run(sgs, lay, p, w)
            _, out = jax.lax.scan(step, jnp.int32(0), (params, warm))
            return out

    return runner


def run_sim(program: VertexProgram, pg: PartitionedGraph, params=None,
            cfg: EngineConfig = EngineConfig(), *, resume_from=None,
            init_state=None):
    """One-shot simulator job: upload ``pg``, build the runner, execute.
    (Low-level layer — ``repro.session.GraphSession`` amortizes the upload
    and the compilation across queries.)

    ``resume_from``: path to a BSP checkpoint written by a previous trace
    run (cfg.checkpoint_every) — restart mid-job (DESIGN.md §7).

    ``init_state``: global per-vertex values [n_vertices(, K)] from a
    previous *converged* run (e.g. before a stream delta was applied) — a
    warm start. Only sound for monotone programs (values tighten under the
    combiner; SSSP/MSSP/CC after edge/vertex growth): non-monotone programs
    (PageRank) silently fall back to a cold start. Shorter arrays (the graph
    grew) are padded with the combiner identity."""
    sgs = _device_subgraph(pg)
    n_slots, K = pg.n_slots, program.payload
    warm = init_state is not None and program.monotone
    edge_backend = resolve_edge_backend(program, cfg)
    lay = lay_blk = assignment = None
    if edge_backend == "auto":
        lay = pg.ensure_edge_layouts()
        assignment = resolve_partition_backends(program, cfg, pg, lay=lay)
        lay_blk = _auto_layout_blocks(lay, pg, program, assignment)
    elif edge_backend != "coo":
        lay = pg.ensure_edge_layouts()
        lay_blk = _layout_block_from(lay, pg, program, edge_backend)

    stats = ExecutionStats(edge_backend=edge_backend)
    epp_host = pg.edges_per_part.astype(np.int64)
    flops_pp = _flops_per_sweep(program, edge_backend, pg, lay, assignment)
    if assignment is not None:
        stats.partition_edge_backends = list(assignment)
    if edge_backend in ("pallas_tiles", "auto"):
        spec = program.sweep_spec
        stats.tile_density = lay.density(pg, spec.semiring, spec.edge_values,
                                         program.dtype)
        stats.partition_tile_density = list(lay.partition_density(
            pg, spec.semiring, spec.edge_values, program.dtype))
    t0 = time.perf_counter()

    if cfg.trace:
        ident = program.identity
        ec = EdgeCombine(())
        v_init = jax.vmap(lambda sg: program.init(sg, params, ec))(sgs)
        if warm:
            wv = _warm_block(program, pg, init_state)
            v_init = jax.vmap(
                lambda sg, st, w: program.warm_init(sg, params, st, w)
            )(sgs, v_init, jnp.asarray(wv))
        last0 = jnp.full((pg.n_parts, pg.v_max, K), ident,
                         dtype=program.dtype)
        merged0 = jnp.full((n_slots + 1, K), ident, dtype=program.dtype)
        start_step = 0
        if resume_from is not None:
            from repro.training.checkpoint import load_pytree
            ckpt, meta = load_pytree(
                resume_from, like=dict(state=v_init, last_out=last0,
                                       merged=merged0, step=jnp.int32(0)))
            v_init, last0, merged0 = (ckpt["state"], ckpt["last_out"],
                                      ckpt["merged"])
            start_step = int(ckpt["step"])

        superstep = _make_sim_superstep(program, cfg, n_slots, edge_backend,
                                        assignment)
        step_fn = jax.jit(lambda st, lo, mb, first: superstep(
            sgs, lay_blk, params, st, lo, mb, first))
        state, last_out, merged_buf = v_init, last0, merged0
        for step in range(start_step, cfg.max_supersteps):
            state, last_out, merged_buf, msgs, active, sweeps = step_fn(
                state, last_out, merged_buf, jnp.bool_(step == 0))
            msgs, active = int(msgs), int(active)
            stats.messages_per_step.append(msgs)
            stats.active_parts_per_step.append(active)
            stats.total_messages += msgs
            sweeps_h = np.asarray(sweeps, dtype=np.int64)
            stats.processed_edges += int((sweeps_h * epp_host).sum())
            stats.backend_flops += int((sweeps_h * flops_pp).sum())
            stats.total_bytes += (n_slots + 1) * K * np.dtype(program.dtype).itemsize * pg.n_parts
            stats.supersteps = step + 1
            if cfg.checkpoint_every and (step + 1) % cfg.checkpoint_every == 0 \
                    and cfg.checkpoint_dir:
                from repro.training.checkpoint import save_pytree
                os.makedirs(cfg.checkpoint_dir, exist_ok=True)
                save_pytree(f"{cfg.checkpoint_dir}/bsp_{step + 1:06d}.npz",
                            dict(state=state, last_out=last_out,
                                 merged=merged_buf, step=step + 1))
            if msgs == 0 and active == 0:
                break
        results = jax.vmap(
            lambda sg, st: program.result(sg, params, st))(sgs, state)
    else:
        assert resume_from is None, "resume requires trace mode"
        runner = make_sim_runner(program, cfg, n_slots, warm_start=warm,
                                 partition_backends=assignment)
        args = (sgs,) if edge_backend == "coo" else (sgs, lay_blk)
        args += (params,)
        if warm:
            args += (jnp.asarray(_warm_block(program, pg, init_state)),)
        results, steps, tot_msgs, tot_sweeps = runner(*args)
        stats.supersteps = int(steps)
        stats.total_messages = int(tot_msgs)
        sweeps_h = np.asarray(tot_sweeps, dtype=np.int64)
        stats.processed_edges = int((sweeps_h * epp_host).sum())
        stats.backend_flops = int((sweeps_h * flops_pp).sum())
        stats.total_bytes = stats.supersteps * (n_slots + 1) * K * \
            np.dtype(program.dtype).itemsize * pg.n_parts

    stats.wall_time = time.perf_counter() - t0
    return np.asarray(results), stats


# --------------------------------------------------------------------------- #
# shard_map backend
# --------------------------------------------------------------------------- #
def make_bsp_runner(program: VertexProgram, mesh: Mesh,
                    cfg: EngineConfig, n_slots: int, *, params=None,
                    has_vlabel=False, warm_start=False,
                    params_as_input=False, batch=False,
                    partition_backends=None):
    """Build the shard_map'd BSP loop (shared by run_shard_map, the
    graph-engine dry-run — which lowers it against ShapeDtypeStructs — and
    ``GraphSession``'s compiled-runner cache).

    ``params`` is the program's parameter pytree. By default it is closed
    over at trace time (EngineConfig is frozen and never carries it). With
    ``params_as_input=True`` it is instead a *template*: the returned runner
    takes a pytree of the same structure as its last argument, replicated
    (``P()``) across the mesh — so one compiled runner serves every
    parameter value (e.g. SSSP from any source) with zero retraces.

    ``warm_start=True`` builds the runner with an extra input: a
    [P, v_max, K] warm-state block sharded like the vertex tables, threaded
    into ``program.warm_init`` right after on-device init — the incremental
    recompute path (docs/STREAMING.md). The caller owns the soundness check
    (monotone program, insert-only delta).

    When ``resolve_edge_backend(program, cfg)`` picks a Pallas backend the
    runner takes the device layout pytree as an additional input directly
    after ``sgs`` (positional protocol: ``sgs[, layout][, warm][, params]``),
    sharded over the subgraph axes like the vertex tables. With
    ``cfg.edge_axes`` set, the tile/window lists are additionally sharded
    over the edge axes (``EdgeLayouts._sharded_geometry``): each edge shard
    runs the kernel product over its own per-shard tile/window lists and
    the ``EdgeCombine`` epilogue of the generated sweep (pmin for
    ``min_plus``, psum for ``plus_times``) reduces the partial per-vertex
    aggregates across the shards before the fold — bit-identical to the
    unsharded launch for min-combines, float-associativity-tolerant for
    sums, exactly like the COO path's sharded product. Under ``'auto'``
    (``partition_backends`` required) the layout input is
    ``(tiles, windows, backend_ids)`` with full blocks and a per-partition
    ``lax.switch`` picks the sweep — one executable serves any assignment.

    ``batch=True`` (requires ``params_as_input=True``) builds the
    micro-batching variant: the warm block (when present) and every params
    leaf carry a leading batch axis B, and the returned runner scans the
    lanes through the shard_map'd superstep loop inside one launch —
    ``lax.scan`` rather than vmap, because a vmap would have to batch
    through the shard_map collectives. Outputs gain the same leading B."""
    sub_axes = tuple(cfg.subgraph_axes)
    edge_axes = tuple(cfg.edge_axes)
    K = program.payload
    ident = program.identity
    ec = EdgeCombine(edge_axes)
    ex = sbs.ShardExchange(sub_axes)
    edge_backend = resolve_edge_backend(program, cfg)

    specs = shard_specs(cfg, has_vlabel)
    vert_spec = P(sub_axes, None)

    def _squeeze(x):
        return None if x is None else x.reshape(x.shape[1:])

    n_edge_shards = int(np.prod([mesh.shape[a] for a in edge_axes])) \
        if edge_axes else 1
    shard_slots = cfg.shard_slots and n_edge_shards > 1
    n_loc = -(-(n_slots + 1) // n_edge_shards) if shard_slots else n_slots + 1

    # Pallas layout specs (specs.tiles / specs.windows): tile/window lists
    # shard over the edge axes like the edge arrays themselves, and the
    # EdgeCombine epilogue inside the generated sweep merges the partial
    # aggregates across shards. With no edge axes these reduce to the
    # replicated-within-partition specs of the unsharded launch.
    lay_specs = None
    if edge_backend == "auto":
        if partition_backends is None:
            raise ValueError("edge_backend='auto' runners need the resolved "
                             "partition_backends assignment "
                             "(resolve_partition_backends)")
        lay_specs = (specs.tiles, specs.windows, specs.part)
        tiles_sweep = _make_pallas_sweep(program, "pallas_tiles")
        windows_sweep = _make_pallas_sweep(program, "pallas_windows")
    elif edge_backend != "coo":
        lay_specs = specs.tiles if edge_backend == "pallas_tiles" \
            else specs.windows
        pallas_sweep = _make_pallas_sweep(program, edge_backend)

    def _body(sg_block, lay_block, warm_block, params):
        sg = DeviceSubgraph(*[_squeeze(x) for x in sg_block])
        sweep_fn = None
        if lay_block is not None and edge_backend == "auto":
            t_raw, w_raw, bid = lay_block
            t_lay = TileBlock(*[_squeeze(x) for x in t_raw])
            w_lay = WindowBlock(*[_squeeze(x) for x in w_raw])
            bid = _squeeze(bid)                      # () int32 backend id

            def sweep_fn(sg_, p_, st_, ec_):
                return jax.lax.switch(
                    bid,
                    [lambda s: program.sweep(sg_, p_, s, ec_),
                     lambda s: tiles_sweep(sg_, t_lay, p_, s, ec_),
                     lambda s: windows_sweep(sg_, w_lay, p_, s, ec_)],
                    st_)
        elif lay_block is not None:
            lay = type(lay_block)(*[_squeeze(x) for x in lay_block])
            sweep_fn = (lambda sg_, p_, st_, ec_:
                        pallas_sweep(sg_, lay, p_, st_, ec_))
        state = program.init(sg, params, ec)
        if warm_block is not None:
            state = program.warm_init(sg, params, state,
                                      _squeeze(warm_block))
        last0 = jnp.full((sg.v_max, K), ident, dtype=program.dtype)
        merged_v0 = jnp.full((sg.v_max, K), ident, dtype=program.dtype)

        def _exchange_dense(out, changed):
            buf = sbs.scatter_combine(out, sg.slot, changed, n_slots,
                                      program.combiner, ident)
            if cfg.sparse_sync_capacity > 0:
                merged = sbs.compact_allgather_exchange(
                    buf, ident, program.combiner, n_slots,
                    cfg.sparse_sync_capacity, sub_axes)
            else:
                merged = ex.all_combine(buf, program.combiner)
            merged = merged.at[n_slots].set(ident)
            return sbs.gather_merged(merged, sg.slot)

        def _exchange_sharded(out, changed):
            # Sharded SBS (DESIGN.md §7): frontier slots are owned by the
            # edge-axis shard slot % n_edge_shards; the (pod,data) combiner
            # all-reduce runs on the 1/n_edge_shards slot slice, and the
            # per-vertex merged view is rebuilt with an edge-axis combine —
            # O(n_slots / n_edge_shards) state per device, which is what
            # keeps the trillion-edge configuration within HBM.
            rank = jax.lax.axis_index(edge_axes)
            owned = changed & (sg.slot % n_edge_shards == rank)
            slot_loc = jnp.where(owned, sg.slot // n_edge_shards, n_loc)
            buf = sbs.scatter_combine(out, slot_loc, owned, n_loc,
                                      program.combiner, ident)
            merged = ex.all_combine(buf, program.combiner)
            gather_own = sg.frontier & (sg.slot % n_edge_shards == rank)
            mv = jnp.where(
                gather_own[:, None],
                merged[jnp.clip(sg.slot // n_edge_shards, 0, n_loc)], ident)
            if program.combiner == "min":
                return ec.min(mv)
            if program.combiner == "max":
                return ec.max(mv)
            return ec.sum(jnp.where(gather_own[:, None], mv, 0).astype(mv.dtype))

        def superstep(state, last_out, merged_v, first):
            state, out, sweeps, last_ch = _local_phase(
                program, sg, params, state, merged_v, ec, cfg.local_bound,
                first, sweep_fn=sweep_fn)
            ref = merged_v if cfg.lean_frontier else last_out
            changed = program.changed_mask(out, ref) & sg.frontier
            if shard_slots:
                merged_v = _exchange_sharded(out, changed)
            else:
                merged_v = _exchange_dense(out, changed)
            msgs = ex.all_sum_scalar(jnp.sum(changed, dtype=jnp.int32))
            active = ex.all_sum_scalar((last_ch > 0).astype(jnp.int32))
            return state, out, merged_v, msgs, active, sweeps

        def cond(c):
            step, msgs, active = c[0], c[-2], c[-1]
            return (step == 0) | (((msgs > 0) | (active > 0))
                                  & (step < cfg.max_supersteps))

        if cfg.lean_frontier:
            # no last_out buffer: 2 fewer [v_max, K] live values in the loop
            def body(c):
                step, state, merged_v, tm, tsw, _, _ = c
                state, _, merged_v, msgs, active, sweeps = superstep(
                    state, None, merged_v, step == 0)
                return (step + 1, state, merged_v, tm + msgs, tsw + sweeps,
                        msgs, active)

            carry = (jnp.int32(0), state, merged_v0, jnp.int32(0),
                     jnp.int32(0), jnp.int32(1), jnp.int32(1))
        else:
            def body(c):
                step, state, last_out, merged_v, tm, tsw, _, _ = c
                state, out, merged_v, msgs, active, sweeps = superstep(
                    state, last_out, merged_v, step == 0)
                return (step + 1, state, out, merged_v, tm + msgs,
                        tsw + sweeps, msgs, active)

            carry = (jnp.int32(0), state, last0, merged_v0, jnp.int32(0),
                     jnp.int32(0), jnp.int32(1), jnp.int32(1))
        steps, state, *_, tm, tsw, _, _ = jax.lax.while_loop(cond, body, carry)
        res = program.result(sg, params, state)
        return res[None], steps, tm, tsw[None]

    out_specs = (vert_spec, P(), P(), specs.part)
    # positional protocol (in this order): sgs [, layout][, warm][, params]
    in_specs = [specs.graph]
    if lay_specs is not None:
        in_specs.append(lay_specs)
    if warm_start:
        in_specs.append(specs.warm)
    if params_as_input:
        in_specs.append(jax.tree.map(lambda _: P(), params))

    @partial(shard_map, mesh=mesh, in_specs=tuple(in_specs),
             out_specs=out_specs)
    def go(*args):
        it = iter(args)
        sg_block = next(it)
        lay_block = next(it) if lay_specs is not None else None
        warm_block = next(it) if warm_start else None
        p = next(it) if params_as_input else params
        return _body(sg_block, lay_block, warm_block, p)

    if not batch:
        return go

    assert params_as_input, "batch=True batches the params input"
    # positional protocol unchanged (sgs[, layout][, warm][, params]); the
    # warm block and params are the scanned ("moving") inputs, graph and
    # layout stay shared across the lanes
    n_static = 2 if lay_specs is not None else 1

    def go_batched(*args):
        static, moving = args[:n_static], tuple(args[n_static:])

        def step(c, x):
            return c, go(*static, *x)

        _, out = jax.lax.scan(step, jnp.int32(0), moving)
        return out

    return go_batched


def run_shard_map(program: VertexProgram, pg: PartitionedGraph, mesh: Mesh,
                  params=None, cfg: EngineConfig = EngineConfig(), *,
                  init_state=None):
    """``init_state``: global per-vertex values from a previous converged
    run, injected on-device through ``program.warm_init`` (same semantics as
    ``run_sim``: monotone programs only; non-monotone programs get an
    explicit cold start — the runner is built without the warm input, so the
    fallback is visible in the lowered program, never a silent drop)."""
    sub_axes = tuple(cfg.subgraph_axes)
    edge_axes = tuple(cfg.edge_axes)
    n_sub = int(np.prod([mesh.shape[a] for a in sub_axes]))
    n_edge = int(np.prod([mesh.shape[a] for a in edge_axes])) if edge_axes else 1
    assert pg.n_parts == n_sub, (pg.n_parts, n_sub)
    assert pg.e_max % n_edge == 0, "pad edges to a multiple of the edge axes"

    n_slots, K = pg.n_slots, program.payload
    warm = init_state is not None and program.monotone
    placement = shard_placement(mesh, cfg, pg.vlabel is not None)
    sgs = _device_subgraph(pg, placement)
    edge_backend = resolve_edge_backend(program, cfg)
    lay = assignment = None
    args = (sgs,)
    if edge_backend == "auto":
        lay = pg.ensure_edge_layouts()
        assignment = resolve_partition_backends(program, cfg, pg, lay=lay)
        args += (_auto_layout_blocks(lay, pg, program, assignment,
                                     mixed_shard=True, n_shards=n_edge,
                                     placement=placement),)
    elif edge_backend != "coo":
        lay = pg.ensure_edge_layouts()
        args += (_layout_block_from(lay, pg, program, edge_backend,
                                    n_shards=n_edge, placement=placement),)
    go = make_bsp_runner(program, mesh, cfg, n_slots, params=params,
                         has_vlabel=pg.vlabel is not None, warm_start=warm,
                         partition_backends=assignment)

    t0 = time.perf_counter()
    with mesh:
        if warm:
            args += (jax.device_put(_warm_block(program, pg, init_state),
                                    placement.warm),)
        res, steps, tot_msgs, sweeps_per_part = go(*args)
    res = np.asarray(res)
    sweeps_per_part = np.asarray(sweeps_per_part, dtype=np.int64)
    stats = ExecutionStats(
        supersteps=int(steps), total_messages=int(tot_msgs),
        processed_edges=int(
            (sweeps_per_part * pg.edges_per_part.astype(np.int64)).sum()),
        total_bytes=int(steps) * _exchange_bytes_per_step(
            cfg, n_slots, K, program.dtype, pg.n_parts, n_edge),
        wall_time=time.perf_counter() - t0,
        edge_backend=edge_backend,
        backend_flops=int((sweeps_per_part * _flops_per_sweep(
            program, edge_backend, pg, lay, assignment,
            n_edge_shards=n_edge)).sum()),
    )
    if assignment is not None:
        stats.partition_edge_backends = list(assignment)
    if edge_backend in ("pallas_tiles", "auto"):
        spec = program.sweep_spec
        stats.tile_density = lay.density(pg, spec.semiring, spec.edge_values,
                                         program.dtype)
        stats.partition_tile_density = list(lay.partition_density(
            pg, spec.semiring, spec.edge_values, program.dtype))
    return res, stats


def run(program: VertexProgram, pg: PartitionedGraph, params=None,
        cfg: EngineConfig = EngineConfig(), mesh: Optional[Mesh] = None,
        *, init_state=None, resume_from=None):
    if cfg.backend == "sim":
        return run_sim(program, pg, params, cfg, resume_from=resume_from,
                       init_state=init_state)
    if cfg.backend != "shard_map":
        raise ValueError(f"unknown backend {cfg.backend!r}")
    if mesh is None:
        raise ValueError("shard_map backend needs a mesh")
    if resume_from is not None:
        raise NotImplementedError(
            "checkpoint resume is a trace-mode feature of the simulator "
            "backend; rerun with cfg.backend='sim' (and cfg.trace=True)")
    return run_shard_map(program, pg, mesh, params, cfg,
                         init_state=init_state)
