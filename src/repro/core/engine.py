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
the local fixed point — the subgraph-centric model — on a partition that
holds interior paths (``PartitionedGraph.local_paths``: at least half of its
real edges join two unreplicated vertices). For a ``min``/``max`` program it
sweeps once a superstep on every other partition: there paths cross
partitions, and after t supersteps a run that exchanges after every sweep
holds values no worse than t lock-step local sweeps would, so the local fixed
point's extra sweeps buy no superstep the exchange does not. ``local_bound``
derives the bound, per partition, on device; ``sum`` programs (PageRank)
keep the local fixed point everywhere. The partitioner choice
(vertex-cut vs edge-cut) is orthogonal and lives in the PartitionedGraph,
exactly the DRONE-VC / DRONE-EC split of §8.

Backends — one superstep and one loop (``_make_superstep``, ``_make_loop``)
over a stacked local block [p, ...], run by both:
  - ``sim``       — single-process: the block holds all P partitions, the
    local phase is vmapped (or one kernel launch over the stack), SBS =
    axis-0 reductions (``sbs.SimExchange``). Used by tests/benchmarks on CPU.
  - ``shard_map`` — production: the block holds one partition a device,
    partitions on the (pod, data) mesh axes, the model axis shards each
    partition's *edges* (hierarchical SVHM, DESIGN.md §2); SBS =
    lax.pmin/psum over (pod, data) (``sbs.ShardExchange``), intra-partition
    edge-combine = collectives over (model,).
Both runners take ``(sgs[, lay], params[, warm])``; ``execution_stats`` bills
every run.

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
from typing import Any, NamedTuple, Optional

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
from repro.obs import scope

__all__ = ["EngineConfig", "EdgeCombine", "run", "run_sim", "run_shard_map",
           "make_sim_runner", "make_bsp_runner", "resolve_edge_backend",
           "local_bound", "execution_stats",
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


def local_bound(program: VertexProgram, cfg: EngineConfig, local_paths):
    """Sweeps the local phase may run in a superstep, per partition: one
    under ``mode='vc'``; for a ``min``/``max`` program under ``mode='sc'``,
    ``cfg.max_local_iters`` where the partition holds interior paths
    (``local_paths``) and one elsewhere; ``cfg.max_local_iters`` for any
    other combiner, whose sweeps the monotone argument does not cover.

    ``local_paths`` is the graph's per-partition flag: traced inside a
    runner (a [P] vector, or a scalar per partition), so the bound is a
    traced value and a flush that flips it recompiles nothing; or the
    host's [P] array, for the session's ``one_sweep_share``."""
    if cfg.mode == "vc":
        return 1
    if program.combiner not in ("min", "max"):
        return cfg.max_local_iters
    xp = np if isinstance(local_paths, np.ndarray) else jnp
    return xp.where(local_paths, cfg.max_local_iters, 1)


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
            is_master=vert_spec, local_paths=P(sub_axes),
            vlabel=vert_spec if has_vlabel else None),
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
        is_master=pg.is_master, local_paths=pg.local_paths,
        vlabel=pg.vlabel)
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


#: lax.switch branch ids of the shard_map mixed-backend product
_BACKEND_IDS = {"coo": 0, "pallas_tiles": 1, "pallas_windows": 2}


def resolve_partition_backends(program: VertexProgram, cfg: EngineConfig,
                               pg: PartitionedGraph, *, lay=None,
                               table=None) -> tuple:
    """Per-partition concrete backend assignment. Uniform (non-``'auto'``)
    configs broadcast the resolved backend; ``'auto'`` consults the
    calibration table of the program's sweep key (device kind, engine
    backend, semiring, edge values, dtype — core/autotune.py) over the
    partition's layout-geometry unit counts. Deterministic for a given
    (table, geometry) — sessions additionally pin the assignment per sweep
    key and shape bucket so in-bucket growth cannot flip it."""
    eb = resolve_edge_backend(program, cfg)
    if eb != "auto":
        return (eb,) * pg.n_parts
    from repro.core import autotune
    if lay is None:
        lay = pg.ensure_edge_layouts()
    if table is None:
        table = autotune.get_table(autotune.sweep_key(program, cfg.backend))
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
    # a partition's real edges lead its edge slots in ascending buffer
    # slot (localize_edges sorts them by dst, padding trails), so with each
    # padding edge sent to a slot of its own past the buffer the scatter is
    # sorted and unique: XLA then needs no sort of the slots before it
    pad = n_buf + jnp.arange(blk.eslot.shape[-1], dtype=jnp.int32)
    slot = jnp.where(blk.eslot >= 0, blk.eslot, pad)         # pad -> dropped
    buf = jnp.full((n_buf, K), ident, vals.dtype)
    buf = buf.at[slot].set(msgs, mode="drop", indices_are_sorted=True,
                           unique_indices=True)
    out = segment_combine_windowed(buf, blk.ldst, blk.bwin, n_windows=nw,
                                   combiner=spec.combiner)
    return out.reshape(nw * W, K)[:v_max]


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


def _effective_backend(edge_backend: str, assignment) -> str:
    """The backend whose path a runner takes: an ``'auto'`` assignment that
    puts every partition on one backend runs that backend's own uniform
    path (no group slicing, no per-partition switch) — the form the
    calibration timed."""
    if edge_backend == "auto" and len(set(assignment)) == 1:
        return assignment[0]
    return edge_backend


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

    A uniform assignment takes the uniform backend's own input (None for
    ``coo``), as ``_effective_backend`` runs its path.

    Simulator (``mixed_shard=False``): ``(tiles, windows)`` with each block
    group-sliced to just the partitions its backend owns (``None`` when the
    backend owns nothing) — the mixed superstep launches one kernel per
    group over its sub-stack. Cached on the layouts' device cache so
    repeated queries reuse the slices until a rebuild invalidates them.

    shard_map (``mixed_shard=True``): ``(tiles, windows, backend_ids)``
    — every device gets same-shaped slices and a ``lax.switch`` on its
    partition's backend id picks the path. A backend that some partition
    runs takes its full (possibly edge-axis-sharded) block; one that no
    partition runs takes ``EdgeLayouts.device_stub``, one tile or window a
    partition, for a branch that is traced but never taken (a graph's
    dense tiles can outgrow any memory). ``placement`` puts all three on
    the mesh."""
    spec = program.sweep_spec
    uniform = _effective_backend("auto", assignment)
    if uniform == "coo":
        return None
    if uniform != "auto":
        return _layout_block_from(lay, pg, program, uniform, n_shards,
                                  placement)
    if mixed_shard:
        ids = np.asarray([_BACKEND_IDS[b] for b in assignment], np.int32)
        ids = jnp.asarray(ids) if placement is None \
            else jax.device_put(ids, placement.part)
        lay.uploaded_bytes += ids.nbytes
        t_pl = None if placement is None else placement.tiles
        w_pl = None if placement is None else placement.windows
        return tuple(
            _layout_block_from(lay, pg, program, b, n_shards, placement)
            if b in assignment else
            lay.device_stub(b, spec.semiring, program.dtype, n_shards,
                            shardings=pl)
            for b, pl in (("pallas_tiles", t_pl),
                          ("pallas_windows", w_pl))) + (ids,)
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


def _each(f, *stacks):
    """``f`` over the leading partition axis of ``stacks``: ``vmap``'d over
    a stack of several partitions; on a stack of one (a shard_map device's
    block) called on that partition itself, so a device lowers the
    per-partition program and no batched form of it."""
    if jax.tree.leaves(stacks)[0].shape[0] > 1:
        return jax.vmap(f)(*stacks)
    out = f(*jax.tree.map(lambda x: x[0], stacks))
    return jax.tree.map(lambda x: x[None], out)


def _stack_product(backend: str, spec: SemiringSweep, sgs, lay_blk, v):
    """Stacked [P, v_max, K] semiring product on one backend: the vmapped
    COO reference product, or the flattened kernel launch over the stacked
    layout block (``lax.map``'d per partition for windows). A stack of one
    runs its partition's own product."""
    from repro.core.api import coo_semiring_product
    v_max = sgs.vmask.shape[-1]
    if backend == "coo":
        return _each(lambda sg, vv: coo_semiring_product(sg, spec, vv),
                     sgs, v)
    if v.shape[0] == 1:
        sgs, lay_blk, v = jax.tree.map(lambda x: x[0], (sgs, lay_blk, v))
    if backend == "pallas_tiles":
        agg = _tile_product(lay_blk, v, spec, v_max)
    else:
        agg = _window_product(lay_blk, v, spec, v_max, sgs.esrc, sgs.ew)
    return agg if agg.ndim == 3 else agg[None]


def _mixed_product(spec: SemiringSweep, groups, sgs, lay_blks, v):
    """Stacked [P, v_max, K] semiring product under a mixed per-partition
    backend assignment: one launch per backend group over its (static)
    partition sub-stack, scattered back into the full aggregate. Matches
    the uniform paths bit-for-bit per partition."""
    blks = {"coo": None, "pallas_tiles": lay_blks[0],
            "pallas_windows": lay_blks[1]}
    agg = jnp.zeros(v.shape, v.dtype)       # every row overwritten below
    for backend, gidx in groups:
        sub = jax.tree.map(lambda a: a[gidx], sgs)
        part = _stack_product(backend, spec, sub, blks[backend], v[gidx])
        agg = agg.at[jnp.asarray(gidx)].set(part)
    return agg


def _switch_product(spec: SemiringSweep, sgs, lay_blks, v):
    """The product of a shard_map device's one partition under a mixed
    assignment: a ``lax.switch`` on its backend id (``_BACKEND_IDS``) over
    the three products (``_auto_layout_blocks``' ``mixed_shard`` input)."""
    tiles, windows, bid = lay_blks
    blks = {"coo": None, "pallas_tiles": tiles, "pallas_windows": windows}
    return jax.lax.switch(
        bid[0], [partial(_stack_product, b, spec, sgs, blks[b])
                 for b in _BACKEND_IDS], v)


def _local_phase(program: VertexProgram, sg: DeviceSubgraph, params, state,
                 merged_v, ec: EdgeCombine, cfg: EngineConfig, first):
    """apply incoming -> sweep to local fixed point (or one hop: the
    partition's ``local_bound``), one partition, ``program.sweep``.

    ``first`` is True at superstep 0, where there are no incoming messages
    (paper Algorithm 1's ``if superstep = 0`` branch) and apply is skipped.
    """
    bound = local_bound(program, cfg, sg.local_paths)
    with scope("apply"):
        state = jax.lax.cond(
            first, lambda st: st,
            lambda st: program.apply_frontier(sg, params, st, merged_v,
                                              ec)[0],
            state)

    def cond(c):
        i, _, chg = c
        return (chg > 0) & (i < bound)

    def body(c):
        i, st, _ = c
        st, chg = program.sweep(sg, params, st, ec)
        return (i + 1, st, chg)

    with scope("sweep"):
        state, ch = program.sweep(sg, params, state, ec)
        i, state, last_ch = jax.lax.while_loop(cond, body,
                                               (jnp.int32(1), state, ch))
    with scope("pack"):
        out = program.frontier_out(sg, params, state)
    return state, out, i, last_ch


def _batched_local_phase(program: VertexProgram, sgs, lay_blk, params, state,
                         merged_v, ec: EdgeCombine, cfg: EngineConfig, first,
                         product):
    """Stacked local phase of a ``SemiringSweep`` program on the kernels:
    ``product(sgs, lay_blk, v)`` evaluates the semiring product of the
    whole stack (``_stack_product``, ``_mixed_product`` or
    ``_switch_product``), then the ``EdgeCombine`` epilogue merges the
    edge shards' partial aggregates (the identity without edge axes).

    The vmapped ``_local_phase`` cannot host a Pallas call (the batching
    rule would have to lift the kernel); instead the whole [P, ...] stack
    goes through ONE product per sweep and the while loop emulates
    vmap-of-while semantics by hand: a partition whose local fixed point is
    reached stops updating (its rows are select-frozen) while the others
    continue — identical results, per-partition sweep counts, and
    per-partition ``local_bound`` as the vmapped COO path."""
    spec = program.sweep_spec
    bound = local_bound(program, cfg, sgs.local_paths)
    with scope("apply"):
        state = jax.lax.cond(
            first, lambda st: st,
            lambda st: _each(
                lambda sg, s, m: program.apply_frontier(sg, params, s, m,
                                                        ec)[0],
                sgs, st, merged_v), state)

    def sweep_all(st):
        vals = _each(lambda sg, s: program.sweep_values(sg, params, s),
                     sgs, st)
        squeeze = vals.ndim == 2
        agg = product(sgs, lay_blk, vals[..., None] if squeeze else vals)
        agg = ec.min(agg) if spec.semiring == "min_plus" else ec.sum(agg)
        if squeeze:
            agg = agg[..., 0]
        return _each(lambda sg, s, a: program.sweep_fold(sg, params, s, a),
                     sgs, st, agg)

    n_parts = sgs.vmask.shape[0]

    def cond(c):
        i, _, chg = c
        return jnp.any((chg > 0) & (i < bound))

    def body(c):
        i, st, chg = c
        st2, ch2 = sweep_all(st)
        if n_parts == 1:           # the loop runs only while it is live
            return i + 1, st2, ch2
        live = (chg > 0) & (i < bound)
        st = jax.tree.map(
            lambda a, b: jnp.where(live.reshape((-1,) + (1,) * (b.ndim - 1)),
                                   b, a), st, st2)
        return (jnp.where(live, i + 1, i), st, jnp.where(live, ch2, chg))

    with scope("sweep"):
        state, ch = sweep_all(state)
        i0 = jnp.ones((n_parts,), jnp.int32)
        i, state, last_ch = jax.lax.while_loop(cond, body, (i0, state, ch))
    with scope("pack"):
        out = _each(lambda sg, s: program.frontier_out(sg, params, s),
                    sgs, state)
    return state, out, i, last_ch


def _exchange_dense(program: VertexProgram, ex, n_slots: int, capacity: int,
                    sgs, out, changed):
    """The SBS exchange over the dense ``[n_slots + 1, K]`` slot buffer:
    each partition scatters its changed frontier values into its buffer,
    ``ex`` combines the stack (an all-reduce over the subgraph axes under
    shard_map), and each partition gathers its merged view back. With a
    ``capacity`` (shard_map's ``sparse_sync_capacity``) the combine is the
    compacted all-gather of ``sbs.compact_allgather_exchange``."""
    comb, ident = program.combiner, program.identity
    with scope("pack"):
        bufs = _each(lambda sg, o, c: sbs.scatter_combine(
            o, sg.slot, c, n_slots, comb, ident), sgs, out, changed)
    with scope("exchange"):
        if capacity > 0:
            merged = sbs.compact_allgather_exchange(
                sbs.reduce_stack(bufs, comb), ident, comb, n_slots,
                capacity, ex.axis_names)
        else:
            merged = ex.combine_stack(bufs, comb)
        merged = merged.at[n_slots].set(ident)
    with scope("apply"):
        return _each(lambda sg: sbs.gather_merged(merged, sg.slot), sgs)


def _exchange_sharded_slots(program: VertexProgram, ex, ec: EdgeCombine,
                            n_slots: int, n_edge_shards: int, sgs, out,
                            changed):
    """Sharded SBS (``cfg.shard_slots``, DESIGN.md §7): frontier slots are
    owned by the edge-axis shard ``slot % n_edge_shards``; the subgraph-axes
    all-reduce runs on the 1/n_edge_shards slot slice, and the per-vertex
    merged view is rebuilt with an edge-axis combine — O(n_slots /
    n_edge_shards) state per device, which is what keeps the trillion-edge
    configuration within HBM."""
    comb, ident = program.combiner, program.identity
    n_loc = -(-(n_slots + 1) // n_edge_shards)
    rank = jax.lax.axis_index(ec.axis_names)

    def pack(sg, o, c):
        owned = c & (sg.slot % n_edge_shards == rank)
        slot_loc = jnp.where(owned, sg.slot // n_edge_shards, n_loc)
        return sbs.scatter_combine(o, slot_loc, owned, n_loc, comb, ident)

    def view(sg, merged):
        gather_own = sg.frontier & (sg.slot % n_edge_shards == rank)
        mv = jnp.where(gather_own[:, None],
                       merged[jnp.clip(sg.slot // n_edge_shards, 0, n_loc)],
                       ident)
        if comb == "min":
            return ec.min(mv)
        if comb == "max":
            return ec.max(mv)
        return ec.sum(jnp.where(gather_own[:, None], mv, 0).astype(mv.dtype))

    with scope("pack"):
        bufs = _each(pack, sgs, out, changed)
    with scope("exchange"):
        merged = ex.combine_stack(bufs, comb)
        return _each(lambda sg: view(sg, merged), sgs)


def _n_edge_shards(mesh: Mesh, cfg: EngineConfig) -> int:
    return int(np.prod([mesh.shape[a] for a in cfg.edge_axes])) \
        if cfg.edge_axes else 1


def _make_superstep(program: VertexProgram, cfg: EngineConfig, n_slots: int,
                    assignment=None, mesh: Optional[Mesh] = None):
    """One BSP superstep over a stacked local block [p, ...] — all P
    partitions in the simulator (``mesh=None``), one partition a device
    under shard_map — and the ``EdgeCombine`` it runs with:

        superstep(sgs, lay, params, state, last_out, merged_v, first) ->
            (state, out, merged_v, messages, active partitions, sweeps[p])

    Chosen once here: the local phase (``_local_phase`` through ``_each``
    for ``coo`` and hand-rolled sweeps; ``_batched_local_phase`` around the
    product of the kernel backends, per ``_effective_backend``), the
    exchange (``sbs.SimExchange`` reduces the stack; ``sbs.ShardExchange``
    reduces it and all-reduces over the subgraph axes) and its variant
    (dense; ``cfg.sparse_sync_capacity``'s compacted all-gather and
    ``cfg.shard_slots``' sharded slots, both shard_map only).
    ``cfg.lean_frontier`` compares the changed mask against ``merged_v``
    rather than ``last_out``."""
    edge_backend = _effective_backend(resolve_edge_backend(program, cfg),
                                      assignment)
    if mesh is None:
        ex, ec, n_edge = sbs.SimExchange(), EdgeCombine(()), 1
    else:
        ex = sbs.ShardExchange(tuple(cfg.subgraph_axes))
        ec = EdgeCombine(tuple(cfg.edge_axes))
        n_edge = _n_edge_shards(mesh, cfg)

    if edge_backend == "coo":
        def local_phase(sgs, lay, params, state, merged_v, first):
            return _each(lambda sg, st, m: _local_phase(
                program, sg, params, st, m, ec, cfg, first),
                sgs, state, merged_v)
    else:
        spec = program.sweep_spec
        if edge_backend != "auto":
            product = partial(_stack_product, edge_backend, spec)
        elif mesh is not None:
            product = partial(_switch_product, spec)
        else:
            product = partial(_mixed_product, spec,
                              _assignment_groups(assignment))

        def local_phase(sgs, lay, params, state, merged_v, first):
            return _batched_local_phase(program, sgs, lay, params, state,
                                        merged_v, ec, cfg, first, product)

    if mesh is not None and cfg.shard_slots and n_edge > 1:
        exchange = partial(_exchange_sharded_slots, program, ex, ec, n_slots,
                           n_edge)
    else:
        exchange = partial(_exchange_dense, program, ex, n_slots,
                           cfg.sparse_sync_capacity if mesh is not None
                           else 0)

    def superstep(sgs, lay, params, state, last_out, merged_v, first):
        state, out, sweeps, last_ch = local_phase(sgs, lay, params, state,
                                                  merged_v, first)
        ref = merged_v if cfg.lean_frontier else last_out
        with scope("pack"):
            changed = _each(
                lambda sg, o, r: program.changed_mask(o, r) & sg.frontier,
                sgs, out, ref)
        merged_v = exchange(sgs, out, changed)
        with scope("vote"):
            msgs = ex.all_sum_scalar(jnp.sum(changed, axis=-1,
                                             dtype=jnp.int32))
            active = ex.all_sum_scalar((last_ch > 0).astype(jnp.int32))
        return state, out, merged_v, msgs, active, sweeps

    return superstep, ec


def _loop_init(program: VertexProgram, ec: EdgeCombine, sgs, params, warm):
    """Initial state of a stacked block (``warm`` folded in through
    ``program.warm_init`` when given) and the identity-filled [p, v_max, K]
    block that starts ``last_out`` and ``merged_v``."""
    with scope("init"):
        state = _each(lambda sg: program.init(sg, params, ec), sgs)
        if warm is not None:
            state = _each(
                lambda sg, st, w: program.warm_init(sg, params, st, w),
                sgs, state, warm)
        fill = jnp.full(sgs.vmask.shape + (program.payload,),
                        program.identity, dtype=program.dtype)
    return state, fill


def _make_loop(program: VertexProgram, cfg: EngineConfig, n_slots: int,
               assignment=None, mesh: Optional[Mesh] = None):
    """The BSP loop over a stacked local block, init -> ``while_loop`` of
    ``_make_superstep``'s superstep -> result:

        run(sgs, lay, params, warm) ->
            (results[p, ...], supersteps, total_messages, sweeps[p])

    It halts once no partition changed anything and no message is pending
    (or at ``cfg.max_supersteps``). Under ``cfg.lean_frontier`` the carry's
    ``last_out`` is ``None``."""
    superstep, ec = _make_superstep(program, cfg, n_slots, assignment, mesh)

    def run(sgs, lay, params, warm):
        state, fill = _loop_init(program, ec, sgs, params, warm)

        def cond(c):
            step, msgs, active = c[0], c[-2], c[-1]
            return (step == 0) | (((msgs > 0) | (active > 0))
                                  & (step < cfg.max_supersteps))

        def body(c):
            step, state, last_out, merged_v, tot_msgs, tot_sweeps, _, _ = c
            state, out, merged_v, msgs, active, sweeps = superstep(
                sgs, lay, params, state, last_out, merged_v, step == 0)
            return (step + 1, state, None if cfg.lean_frontier else out,
                    merged_v, tot_msgs + msgs, tot_sweeps + sweeps, msgs,
                    active)

        carry = (jnp.int32(0), state, None if cfg.lean_frontier else fill,
                 fill, jnp.int32(0), jnp.zeros(sgs.vmask.shape[:1],
                                               jnp.int32),
                 jnp.int32(1), jnp.int32(1))
        steps, state, _, _, tot_msgs, tot_sweeps, _, _ = \
            jax.lax.while_loop(cond, body, carry)
        with scope("result"):
            results = _each(lambda sg, st: program.result(sg, params, st),
                            sgs, state)
        return results, steps, tot_msgs, tot_sweeps

    return run


def _runner_args(args, has_layout: bool, warm_start: bool):
    """``(sgs[, lay], params[, warm])`` -> ``(sgs, lay, params, warm)``."""
    n = 1 + has_layout
    assert len(args) == n + 1 + warm_start, \
        "a runner takes (sgs[, lay], params[, warm])"
    return (args[0], args[1] if has_layout else None, args[n],
            args[n + 1] if warm_start else None)


def _lanes(runner, n_shared: int, vmap: bool):
    """The micro-batching form of ``runner``: its first ``n_shared``
    inputs (graph, layout) are shared and the rest (params, warm block)
    carry a leading lane axis B, as do the outputs — ``vmap`` over the
    lanes, or a ``lax.scan`` through them inside one launch where a vmap
    would have to lift a Pallas call or a shard_map collective."""
    def batched(*args):
        shared, lanes = args[:n_shared], args[n_shared:]
        if vmap:
            return jax.vmap(lambda *x: runner(*shared, *x))(*lanes)
        _, out = jax.lax.scan(lambda c, x: (c, runner(*shared, *x)),
                              jnp.int32(0), lanes)
        return out
    return batched


def _check_assignment(edge_backend: str, partition_backends) -> None:
    if edge_backend == "auto" and partition_backends is None:
        raise ValueError("edge_backend='auto' runners need the resolved "
                         "partition_backends assignment "
                         "(resolve_partition_backends)")


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
    exchange variant the runner actually lowered (the simulator lowers only
    the dense one), so sparse-vs-dense benchmark comparisons measure real
    volume. Counts the inter-partition (subgraph-axes) collective only:
    intra-partition edge-axis combines (sweep reductions, the sharded
    merged-view rebuild) are excluded everywhere, like the paper's
    network-message metric."""
    itemsize = np.dtype(dtype).itemsize
    shard = cfg.backend == "shard_map"
    if shard and cfg.shard_slots and n_edge_shards > 1:
        # each of the n_edge_shards slot slices is all-reduced over the
        # subgraph axes: n_loc + 1 rows (incl. the dump row) per device,
        # n_parts * n_edge_shards devices
        n_loc = -(-(n_slots + 1) // n_edge_shards)
        return (n_loc + 1) * K * itemsize * n_parts * n_edge_shards
    if shard and cfg.sparse_sync_capacity > 0:
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


def execution_stats(program: VertexProgram, cfg: EngineConfig,
                    pg: PartitionedGraph, n_slots: int, steps, messages,
                    sweeps, *, n_edge_shards: int = 1,
                    assignment=None) -> ExecutionStats:
    """The bill of one BSP run from a runner's outputs (supersteps,
    messages, the [P] per-partition sweep counts): ``supersteps``,
    ``total_messages``, ``processed_edges``, ``total_bytes`` of the
    exchange the runner lowered on ``n_slots`` slot rows (the height it
    reduced: a session's bucketed capacity), ``backend_flops``, the
    ``'auto'`` ``assignment`` as ``partition_edge_backends``, the tile
    density on ``pallas_tiles``/``'auto'``, and the per-partition edge
    counts and flops. The caller adds the times."""
    edge_backend = resolve_edge_backend(program, cfg)
    sweeps = np.asarray(sweeps, dtype=np.int64)
    epp = pg.edges_per_part.astype(np.int64)
    lay = None if edge_backend == "coo" else pg.edge_layouts
    flops = sweeps * _flops_per_sweep(program, edge_backend, pg, lay,
                                      assignment, n_edge_shards)
    st = ExecutionStats(
        supersteps=int(steps), total_messages=int(messages),
        processed_edges=int((sweeps * epp).sum()),
        total_bytes=int(steps) * _exchange_bytes_per_step(
            cfg, n_slots, program.payload, program.dtype, pg.n_parts,
            n_edge_shards),
        edge_backend=edge_backend, backend_flops=int(flops.sum()),
        partition_edge_counts=[int(x) for x in epp],
        partition_flops=[int(x) for x in flops])
    if assignment is not None:
        st.partition_edge_backends = list(assignment)
    if edge_backend in ("pallas_tiles", "auto") and lay is not None:
        spec = program.sweep_spec
        st.tile_density = lay.density(pg, spec.semiring, spec.edge_values,
                                      program.dtype)
        st.partition_tile_density = [float(x) for x in lay.partition_density(
            pg, spec.semiring, spec.edge_values, program.dtype)]
    return st


def _one_shot_layouts(program: VertexProgram, cfg: EngineConfig,
                      pg: PartitionedGraph, *, mixed_shard: bool = False,
                      n_shards: int = 1,
                      placement: Optional[ShardSpecs] = None) -> tuple:
    """``(edge backend, 'auto' assignment, layout input)`` of a one-shot
    run: the layout input is ``()`` on ``coo`` and a one-tuple otherwise,
    ready to splice into the runner's arguments."""
    edge_backend = resolve_edge_backend(program, cfg)
    if edge_backend == "coo":
        return edge_backend, None, ()
    lay = pg.ensure_edge_layouts()
    if edge_backend != "auto":
        return edge_backend, None, (_layout_block_from(
            lay, pg, program, edge_backend, n_shards, placement),)
    assignment = resolve_partition_backends(program, cfg, pg, lay=lay)
    return edge_backend, assignment, (_auto_layout_blocks(
        lay, pg, program, assignment, mixed_shard, n_shards, placement),)


# --------------------------------------------------------------------------- #
# The two runners: the one loop, on a stack of every partition or, under
# shard_map, on one partition a device
# --------------------------------------------------------------------------- #
def make_sim_runner(program: VertexProgram, cfg: EngineConfig, n_slots: int,
                    *, warm_start=False, batch=False,
                    partition_backends=None):
    """Build the simulator BSP runner: the one loop (``_make_loop``) over
    the stacked [P, ...] pytree, with ``sbs.SimExchange`` reducing the
    stack, as a pure function of the runners' one protocol

        runner(sgs[, lay], params[, warm_block]) ->
            (results[P, ...], supersteps, total_messages, sweeps_per_part[P])

    ``sgs`` is the stacked [P, ...] DeviceSubgraph pytree; ``lay`` the
    device layout pytree, present exactly when
    ``resolve_edge_backend(program, cfg)`` is not ``'coo'``
    (``TileBlock``/``WindowBlock`` from ``_layout_block_from``; under
    ``'auto'``, which needs the ``partition_backends`` assignment of
    ``resolve_partition_backends``, the group-sliced ``(tiles, windows)``
    pair of ``_auto_layout_blocks``) — an explicit input, not a closure, so
    a serving session's compiled executable keeps working as the layouts
    evolve under streaming; ``params`` the program's parameter pytree
    (traced — one compilation serves every parameter value);
    ``warm_block`` (``warm_start=True``) a [P, v_max, K] previous-result
    block threaded into ``program.warm_init``.

    ``batch=True`` builds the cross-request micro-batching variant
    (serving/batcher.py): every params leaf — and the warm block — carries
    a leading batch axis B, the graph (and layout) inputs stay shared, and
    ONE launch returns per-lane ``(results[B], steps[B], msgs[B],
    sweeps[B, P])``. The COO path vmaps the whole loop over the lanes
    (vmap-of-while: a converged lane's carry is select-frozen while the
    rest run on, so per-lane math is identical to a singleton run); the
    Pallas backends cannot ride vmap's lifting of ``pallas_call``, so they
    scan the lanes inside the same single launch instead.

    ``run_sim`` calls the runner eagerly once per job; ``GraphSession``
    wraps it in ``jax.jit``, AOT-compiles it once per
    (program, config, padded shapes) key and reuses the executable across
    queries with zero retraces."""
    edge_backend = resolve_edge_backend(program, cfg)
    _check_assignment(edge_backend, partition_backends)
    run = _make_loop(program, cfg, n_slots, partition_backends)
    has_layout = edge_backend != "coo"

    def runner(*args):
        return run(*_runner_args(args, has_layout, warm_start))

    if not batch:
        return runner
    return _lanes(runner, 1 + has_layout, vmap=edge_backend == "coo")


def run_sim(program: VertexProgram, pg: PartitionedGraph, params=None,
            cfg: EngineConfig = EngineConfig(), *, resume_from=None,
            init_state=None):
    """One-shot simulator job: upload ``pg``, build the runner, execute.
    (Low-level layer — ``repro.session.GraphSession`` amortizes the upload
    and the compilation across queries.)

    ``cfg.trace`` steps the runner's superstep from the host, one launch a
    superstep, for per-step stats (``messages_per_step``,
    ``active_parts_per_step``) and checkpoints (``cfg.checkpoint_every``).
    ``resume_from``: path to a BSP checkpoint written by a previous trace
    run — restart mid-job (DESIGN.md §7).

    ``init_state``: global per-vertex values [n_vertices(, K)] from a
    previous *converged* run (e.g. before a stream delta was applied) — a
    warm start. Only sound for monotone programs (values tighten under the
    combiner; SSSP/MSSP/CC after edge/vertex growth): non-monotone programs
    (PageRank) silently fall back to a cold start. Shorter arrays (the graph
    grew) are padded with the combiner identity."""
    sgs = _device_subgraph(pg)
    warm = None
    if init_state is not None and program.monotone:
        warm = jnp.asarray(_warm_block(program, pg, init_state))
    edge_backend, assignment, lay_arg = _one_shot_layouts(program, cfg, pg)
    t0 = time.perf_counter()
    per_step = start_step = None

    if cfg.trace:
        superstep, ec = _make_superstep(program, cfg, pg.n_slots, assignment)
        state, fill = _loop_init(program, ec, sgs, params, warm)
        last_out = merged_v = fill
        start_step = 0
        if resume_from is not None:
            from repro.training.checkpoint import load_pytree
            ckpt, _ = load_pytree(
                resume_from, like=dict(state=state, last_out=fill,
                                       merged=fill, step=jnp.int32(0)))
            state, last_out, merged_v = (ckpt["state"], ckpt["last_out"],
                                         ckpt["merged"])
            start_step = int(ckpt["step"])
        lay = lay_arg[0] if lay_arg else None
        step_fn = jax.jit(lambda st, lo, mv, first: superstep(
            sgs, lay, params, st, lo, mv, first))
        per_step = ([], [])
        steps, tot_msgs = 0, 0
        tot_sweeps = np.zeros(pg.n_parts, np.int64)
        for step in range(start_step, cfg.max_supersteps):
            state, last_out, merged_v, msgs, active, sweeps = step_fn(
                state, last_out, merged_v, jnp.bool_(step == 0))
            msgs, active = int(msgs), int(active)
            per_step[0].append(msgs)
            per_step[1].append(active)
            steps, tot_msgs = steps + 1, tot_msgs + msgs
            tot_sweeps += np.asarray(sweeps, dtype=np.int64)
            if cfg.checkpoint_every and (step + 1) % cfg.checkpoint_every == 0 \
                    and cfg.checkpoint_dir:
                from repro.training.checkpoint import save_pytree
                os.makedirs(cfg.checkpoint_dir, exist_ok=True)
                save_pytree(f"{cfg.checkpoint_dir}/bsp_{step + 1:06d}.npz",
                            dict(state=state, last_out=last_out,
                                 merged=merged_v, step=step + 1))
            if msgs == 0 and active == 0:
                break
        results = _each(lambda sg, st: program.result(sg, params, st),
                        sgs, state)
    else:
        assert resume_from is None, "resume requires trace mode"
        runner = make_sim_runner(program, cfg, pg.n_slots,
                                 warm_start=warm is not None,
                                 partition_backends=assignment)
        results, steps, tot_msgs, tot_sweeps = runner(
            sgs, *lay_arg, params, *(() if warm is None else (warm,)))
    results = np.asarray(results)
    wall = time.perf_counter() - t0

    # the simulator lowers the dense exchange whatever cfg.backend says
    stats = execution_stats(program, dataclasses.replace(cfg, backend="sim"),
                            pg, pg.n_slots, steps, tot_msgs, tot_sweeps,
                            assignment=assignment)
    stats.wall_time = wall
    if per_step is not None:
        stats.messages_per_step, stats.active_parts_per_step = per_step
        stats.supersteps += start_step      # counted from the job's start
    return results, stats


def make_bsp_runner(program: VertexProgram, mesh: Mesh,
                    cfg: EngineConfig, n_slots: int, *,
                    has_vlabel=False, warm_start=False, batch=False,
                    partition_backends=None):
    """Build the shard_map BSP runner: the one loop (``_make_loop``) that
    the simulator runs, wrapped in ``shard_map`` over ``mesh`` with the
    ``shard_specs`` placement — each device runs it on its stack of one
    partition, and ``sbs.ShardExchange`` all-reduces over the subgraph
    axes. Shared by ``run_shard_map``, the graph-engine dry-run (which
    lowers it against ShapeDtypeStructs) and ``GraphSession``'s
    compiled-runner cache. It takes the simulator runner's protocol,

        runner(sgs[, lay], params[, warm_block]) ->
            (results[P, ...], supersteps, total_messages, sweeps_per_part[P])

    with ``sgs``, ``lay`` and ``warm_block`` sharded over the subgraph axes
    like the vertex tables, and ``params`` replicated (``P()``).

    ``warm_start=True`` adds the [P, v_max, K] warm-state block, threaded
    into ``program.warm_init`` right after on-device init — the incremental
    recompute path (docs/STREAMING.md). The caller owns the soundness check
    (monotone program, insert-only delta).

    ``lay`` is present exactly when ``resolve_edge_backend(program, cfg)``
    is not ``'coo'``. With ``cfg.edge_axes`` set, the tile/window lists are
    additionally sharded over the edge axes
    (``EdgeLayouts._sharded_geometry``): each edge shard runs the kernel
    product over its own per-shard lists and the ``EdgeCombine`` epilogue
    after the product (pmin for ``min_plus``, psum for ``plus_times``)
    reduces the partial per-vertex aggregates across the shards before the
    fold — bit-identical to the unsharded launch for min-combines,
    float-associativity-tolerant for sums, exactly like the COO path's
    sharded product. Under ``'auto'`` (``partition_backends`` required) a
    mixed assignment's layout input is ``(tiles, windows, backend_ids)``
    (``_auto_layout_blocks``: a stub for a backend no partition runs) and a
    ``lax.switch`` on the device's backend id picks the product; a uniform
    one takes its backend's own layout input (None for ``coo``).

    ``batch=True`` builds the micro-batching variant: the warm block (when
    present) and every params leaf carry a leading batch axis B, and the
    returned runner scans the lanes through the shard_map'd loop inside one
    launch — ``lax.scan`` rather than vmap, because a vmap would have to
    batch through the shard_map collectives. Outputs gain the same leading
    B."""
    edge_backend = resolve_edge_backend(program, cfg)
    _check_assignment(edge_backend, partition_backends)
    run = _make_loop(program, cfg, n_slots, partition_backends, mesh)
    specs = shard_specs(cfg, has_vlabel)
    has_layout = edge_backend != "coo"
    in_specs = (specs.graph,)
    if has_layout:
        in_specs += ({"coo": P(),           # the layout input is None
                      "pallas_tiles": specs.tiles,
                      "pallas_windows": specs.windows,
                      "auto": (specs.tiles, specs.windows, specs.part)}[
            _effective_backend(edge_backend, partition_backends)],)
    in_specs += (P(),) + ((specs.warm,) if warm_start else ())

    @partial(shard_map, mesh=mesh, in_specs=in_specs,
             out_specs=(P(tuple(cfg.subgraph_axes), None), P(), P(),
                        specs.part))
    def runner(*args):
        return run(*_runner_args(args, has_layout, warm_start))

    if not batch:
        return runner
    return _lanes(runner, 1 + has_layout, vmap=False)


def run_shard_map(program: VertexProgram, pg: PartitionedGraph, mesh: Mesh,
                  params=None, cfg: EngineConfig = EngineConfig(), *,
                  init_state=None):
    """``init_state``: global per-vertex values from a previous converged
    run, injected on-device through ``program.warm_init`` (same semantics as
    ``run_sim``: monotone programs only; non-monotone programs get an
    explicit cold start — the runner is built without the warm input, so the
    fallback is visible in the lowered program, never a silent drop)."""
    n_sub = int(np.prod([mesh.shape[a] for a in cfg.subgraph_axes]))
    n_edge = _n_edge_shards(mesh, cfg)
    assert pg.n_parts == n_sub, (pg.n_parts, n_sub)
    assert pg.e_max % n_edge == 0, "pad edges to a multiple of the edge axes"

    warm = init_state is not None and program.monotone
    placement = shard_placement(mesh, cfg, pg.vlabel is not None)
    sgs = _device_subgraph(pg, placement)
    edge_backend, assignment, lay_arg = _one_shot_layouts(
        program, cfg, pg, mixed_shard=True, n_shards=n_edge,
        placement=placement)
    runner = make_bsp_runner(program, mesh, cfg, pg.n_slots,
                             has_vlabel=pg.vlabel is not None,
                             warm_start=warm, partition_backends=assignment)

    t0 = time.perf_counter()
    with mesh:
        args = (sgs, *lay_arg, params)
        if warm:
            args += (jax.device_put(_warm_block(program, pg, init_state),
                                    placement.warm),)
        res, steps, tot_msgs, sweeps_per_part = runner(*args)
    res = np.asarray(res)
    wall = time.perf_counter() - t0
    stats = execution_stats(program, cfg, pg, pg.n_slots, steps, tot_msgs,
                            sweeps_per_part, n_edge_shards=n_edge,
                            assignment=assignment)
    stats.wall_time = wall
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
