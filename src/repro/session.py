"""GraphSession — the resident-graph serving API (ROADMAP north star).

DRONE's programming surface (paper §5.1) is "think like a graph" over a
long-lived partitioned state — the posture that distinguishes subgraph-
centric systems (GoFFish, the balanced vertex-cut line) from stateless
per-job engines. The low-level free functions (``run_sim``/``run_shard_map``)
are per-job: every call re-uploads the full ``PartitionedGraph`` and
rebuilds + retraces the BSP runner, and the streaming lifecycle makes
callers hand-thread ``StreamContext``/``DeltaBuffer``/``init_state`` between
five modules. ``GraphSession`` owns all of that:

  - the stacked ``DeviceSubgraph`` pytree stays **resident on device**
    across queries, re-uploaded only when the host graph actually changed;
  - ``query(program, params)`` goes through a **compiled-runner cache**
    keyed by (program static fields, parameter *structure*, EngineConfig,
    bucketed padded shapes P/v_max/e_max/slot_capacity) — repeated queries,
    multi-algorithm traffic and different parameter values (any SSSP
    source) all reuse one AOT-compiled executable with zero retraces;
  - each converged result of a monotone program is remembered and
    **auto-warm-starts** the next identical query after insert-only graph
    growth (``warm="auto"``);
  - the streaming lifecycle is folded in as methods: ``update`` routes
    through an internal coalescing ``DeltaBuffer``, ``flush`` applies the
    patch and refreshes the device pytree, ``compact`` shrinks the padded
    capacities; both log their row remap on a pending chain that each
    cached warm result replays lazily on its next use (a flush is O(1) in
    warm occupancy);
  - padded shapes follow a **bucketed ShapePolicy** (geometric rounding of
    ``v_max``/``e_max`` and of the SBS slot count, default growth 2x): a
    flush that stays inside the current bucket keeps the resident pytree
    layout and re-hits the compiled runner with zero retraces, so a growing
    graph compiles O(log growth) runners instead of O(flushes);
  - the runner cache is **bounded with LRU eviction** (``max_runners``):
    evicted entries recompile transparently on re-query, and eviction
    counts are surfaced in ``SessionStats`` / per-query
    ``ExecutionStats.evicted_runners`` / ``cache_info()``; warm-result
    memory is bounded the same way (``max_warm_entries``), and both caches
    take optional *byte* bounds (``max_runner_bytes``/``max_warm_bytes``)
    that count estimated device/host bytes per entry instead of slots;
  - ``EngineConfig.edge_backend`` picks the sweep's edge-compute backend
    (COO reference or the Pallas tile/window kernels); the device layouts
    ride as explicit runner inputs and their bucketed capacities join the
    cache key, so in-bucket streaming growth retraces nothing on any
    backend (docs/ARCHITECTURE.md "Edge-compute backends").

Monotone programs are always compiled with the warm input: a cold start is
served by a combiner-identity block (``warm_init`` tightening against the
identity is a no-op), so cold and warm queries share one executable and a
post-growth warm query retraces only when the padded shapes crossed a
bucket boundary.

    sess = GraphSession.from_graph(g, n_parts=16)         # or from_edge_log
    dist, st = sess.query(SSSP(), {"source": 0})          # compiles once
    dist, st = sess.query(SSSP(), {"source": 7})          # cache hit
    sess.update(adds=(src, dst, w))                       # buffered
    sess.flush()                                          # patch + re-upload
    dist, st = sess.query(SSSP(), {"source": 0})          # warm-auto restart

Backend selection is by mesh: construct with ``mesh=`` for the shard_map
production backend, without for the single-process simulator — the same
session code path serves both.

Invariants the session owns (docs/API.md "Caching rules" restates them):

  - **cache key fields** — a compiled runner is keyed by (program dataclass
    fields, param pytree *structure*, ``EngineConfig``, padded shape key
    ``(P, v_max, e_max, slot_capacity, has_vlabel)`` plus the Pallas
    layout shape-key when ``edge_backend`` is a kernel backend, warm-input
    flag); parameter *values* — and layout *contents* — are traced inputs
    and never key anything.
  - **warm entries are dtype-cast on entry** — a cached global result is
    cast to ``program.dtype`` before it reaches either backend
    (``engine._warm_block``), so a float64 numpy result can never leak its
    dtype into the compiled superstep loop and force a retrace.
  - **warm soundness** — insert-only flushes keep every cached converged
    result (values remain valid bounds, rows carried via
    ``DeltaStats.remap_state``); any deleting flush drops them all;
    ``compact`` changes layout, never the graph, so warm results survive it
    through ``CompactStats.remap_state``.
  - **slot-capacity padding is invisible** — runners are built with
    ``slot_capacity >= pg.n_slots``; the padded exchange rows only ever
    hold the combiner identity and are never gathered by a live vertex.
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import (EngineConfig, _auto_layout_blocks,
                               _device_subgraph, _layout_block_from,
                               _warm_block, execution_stats, local_bound,
                               make_bsp_runner, make_sim_runner,
                               normalize_edge_backend,
                               resolve_partition_backends, run_sim,
                               shard_placement)
from repro.core import autotune
from repro.core.api import VertexProgram
from repro.core.graph import Graph
from repro.core.metrics import ExecutionStats
from repro.core.partition import (PARTITIONERS, STREAM_ROUTERS,
                                  is_stateful_router)
from repro.core.subgraph import (PartitionedGraph, ShapePolicy,
                                 build_partitioned_graph)
from repro.obs import span
from repro.partition.monitor import LoadMonitor
from repro.partition.rebalance import (RebalanceStats, execute_rebalance,
                                       plan_rebalance)
from repro.serving.result_cache import ResultCache
from repro.serving.result_cache import result_key as _result_key
from repro.serving.runner_cache import RunnerCache
from repro.serving.runner_cache import RunnerEntry as _RunnerEntry
from repro.serving.runner_cache import canonical_params as _canonical_params
from repro.serving.runner_cache import params_fingerprint as \
    _params_fingerprint
from repro.serving.runner_cache import params_struct_key as _params_struct_key
from repro.serving.runner_cache import program_key as _program_key
from repro.serving.runner_cache import runner_nbytes as _runner_nbytes
from repro.stream.buffer import DeltaBuffer
from repro.stream.delta import CompactStats, DeltaStats, EdgeDelta
from repro.stream.delta import compact as _compact_pg
from repro.stream.ingest import StreamContext, streaming_ingest

__all__ = ["GraphSession", "SessionStats", "ShapePolicy"]


@dataclasses.dataclass
class _WarmEntry:
    """Last converged result of one (program, params) query.

    ``global_values`` ([n_vertices(, K)], combiner-identity filled) survives
    any membership change and is re-scattered through ``_warm_block`` when
    needed; ``device_block`` ([P, v_max, K], the program's own result
    layout) is the fast path — valid at ``device_epoch`` of the session's
    remap log: insert-only flushes and compactions do NOT eagerly remap it,
    they append to the log, and the pending chain is applied here on the
    entry's next use (``GraphSession._sync_warm_entry``).

    ``polarity`` is the program's ``warm_under`` declaration: the delta
    polarity this entry survives (``'inserts'``: SSSP/CC/BFS/LP results
    stay valid upper bounds while edges only appear; ``'deletes'``: the
    k-core peel stays valid while edges only disappear). ``_on_flush``
    drops exactly the entries whose polarity the applied patch violated."""
    global_values: np.ndarray
    device_block: Optional[np.ndarray]
    identity: Any
    supersteps: int
    device_epoch: int = 0
    polarity: str = "inserts"

    @property
    def nbytes(self) -> int:
        n = self.global_values.nbytes
        if self.device_block is not None:
            n += self.device_block.nbytes
        return n


@dataclasses.dataclass
class SessionStats:
    """Serving-side counters across the session lifetime."""
    queries: int = 0
    cache_hits: int = 0
    cache_misses: int = 0          # runner compilations
    warm_queries: int = 0          # queries served from a previous result
    flushes: int = 0               # delta batches applied to the host graph
    compactions: int = 0
    uploads: int = 0               # device pytree refreshes
    upload_bytes: int = 0          # bytes put on the device: the graph
                                   # pytree, layout blocks and warm blocks
    compile_time_total: float = 0.0
    cache_evictions_lru: int = 0   # runners dropped by the max_runners /
                                   # max_runner_bytes bounds
    cache_evictions_shape: int = 0  # runners dropped by a bucket change
    warm_evictions: int = 0        # warm results dropped by
                                   # max_warm_entries / max_warm_bytes
    runner_cache_bytes: int = 0    # estimated device bytes the compiled-
                                   # runner cache currently pins (outputs +
                                   # temps + code per executable)
    warm_cache_bytes: int = 0      # host bytes of the warm-result memory
    warm_remaps_applied: int = 0   # deferred warm-block remaps applied on
                                   # entry use (the lazy-flush counter: one
                                   # eager scheme would bill every entry
                                   # on every insert-only flush instead)
    device_launches: int = 0       # compiled-runner executions — a result-
                                   # cache hit serves with ZERO launches
    batches: int = 0               # micro-batched launches (query_batch)
    batched_queries: int = 0       # queries served inside those launches
    result_cache_l1_hits: int = 0  # converged results served from the
    result_cache_l2_hits: int = 0  # in-process / external tier
    result_cache_misses: int = 0   # result-cache consultations that ran
    rebalances: int = 0            # online migrations executed
    load_imbalance: float = 1.0    # the LoadMonitor's latest blended gauge
                                   # (1.0 when no monitor is attached)
    partition_edge_counts: list = dataclasses.field(default_factory=list)
                                   # latest per-partition resident edges
    partition_sweep_time: list = dataclasses.field(default_factory=list)
                                   # EWMA per-shard sweep seconds across
                                   # queries (the monitor's measured-work
                                   # signal, surfaced for benchmark tables)
    tile_density_min: float = 0.0  # spread of the per-partition tile
    tile_density_mean: float = 0.0  # densities from the latest Pallas/auto
    tile_density_max: float = 0.0  # query — the auto policy's raw input


class _SessionBuffer(DeltaBuffer):
    """DeltaBuffer whose flushes (manual *and* threshold-tripped) notify the
    owning session, so auto-flushes inside ``update`` never leave the device
    pytree or the warm cache stale."""

    def __init__(self, session: "GraphSession", *args, **kwargs):
        self._session = session
        super().__init__(*args, **kwargs)

    def _on_applied(self, st: DeltaStats) -> None:
        with span("session/on_flush"):
            self._session._on_flush(st)


def _nbytes(tree) -> int:
    """Bytes of the arrays in a pytree."""
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


# --------------------------------------------------------------------------- #
class GraphSession:
    """Resident-graph serving session over one ``PartitionedGraph``.

    Construct from an existing partitioned graph (``GraphSession(pg, ...)``),
    an in-memory ``Graph`` (``from_graph``) or an on-disk edge log
    (``from_edge_log``). Pass ``mesh=`` to serve on the shard_map backend;
    without a mesh the session transparently uses the simulator backend.

    ``ctx`` (a ``StreamContext``) enables the mutation methods
    (``update``/``flush``/``compact``); the factory constructors provide it
    whenever the partitioner is a pure streaming router. A session without a
    context is read-only (queries still cache and warm-start).

    ``shape_policy`` governs the padded device shapes (docs/ARCHITECTURE.md,
    "shape-bucket lifecycle"): the default is the bucketed
    ``ShapePolicy()`` (geometric 2x buckets), which keeps the compiled
    runners stable under streaming growth; pass ``ShapePolicy.exact()`` for
    the tightest possible padding (one-shot analysis jobs, parity tests
    against the low-level layer). Read-only sessions have frozen shapes, so
    they never over-provision the slot capacity (and ``from_graph`` with a
    non-streamable partitioner defaults to exact padding outright).
    ``pad_multiple`` is a convenience for the default policy's tiling only —
    an explicit ``shape_policy`` always wins (it carries its own
    ``pad_multiple``). ``max_runners`` bounds the compiled-runner cache and
    ``max_warm_entries`` the per-(program, params) warm-result memory, both
    with LRU eviction (``None`` = unbounded). ``max_runner_bytes`` /
    ``max_warm_bytes`` additionally bound the same caches by *estimated
    bytes per entry* (device footprint per executable via XLA's
    ``memory_analysis``; host bytes per warm result) — slots bound entry
    counts, bytes bound what the entries actually pin.

    Serving extras (docs/SERVING.md): ``runner_cache=`` injects a shared
    :class:`repro.serving.runner_cache.RunnerCache` (how a ``SessionPool``
    makes same-bucket tenants reuse one executable — ``max_runners`` /
    ``max_runner_bytes`` are ignored in favor of the shared bounds);
    ``result_cache=`` attaches a tiered
    :class:`repro.serving.result_cache.ResultCache` consulted by ``query``
    before launching anything; ``tenant=`` names this session in the shared
    caches' keys and pin accounting. ``close()`` (or the context-manager
    protocol) drops the resident device pytree and releases every shared-
    cache pin; a closed session raises ``RuntimeError`` on use.

    ``rebalance=`` wires in the online load rebalancer
    (docs/PARTITIONING.md): ``"auto"`` attaches a ``LoadMonitor`` (pass
    ``monitor=`` to configure it) that watches per-partition edge counts,
    frontier occupancy and measured per-shard sweep time, and — when its
    hysteresis gauge trips under streaming churn — migrates boundary edges
    off the overloaded partitions through the same remap machinery as
    ``compact()`` (warm state and in-bucket compiled runners survive; the
    graph-version bump invalidates result-cache entries). ``"manual"``
    keeps the gauge live but only ``session.rebalance()`` migrates;
    ``"off"`` (default) disables both. ``rebalance_target`` is the edge-
    balance the planner aims for (donors shed down to the mean).

    ``debug_sanitize=True`` arms the runtime retrace sanitizer
    (``repro.analysis.sanitizer``): every cache-hit launch runs under a
    ``retrace_guard``, so an AOT-compiled runner that silently re-enters
    the jax tracer raises ``RetraceError`` at the query that did it instead
    of degrading latency forever. ``debug_sanitize="warn"`` downgrades the
    failure to a ``RetraceWarning`` for production canaries.
    """

    def __init__(self, pg: PartitionedGraph, *, ctx: Optional[StreamContext]
                 = None, mesh=None, cfg: Optional[EngineConfig] = None,
                 max_buffer_edges: Optional[int] = 4096,
                 max_buffer_parts: Optional[int] = None,
                 pad_multiple: Optional[int] = None,
                 shape_policy: Optional[ShapePolicy] = None,
                 max_runners: Optional[int] = 32,
                 max_warm_entries: Optional[int] = 64,
                 max_runner_bytes: Optional[int] = None,
                 max_warm_bytes: Optional[int] = None,
                 runner_cache: Optional[RunnerCache] = None,
                 result_cache: Optional[ResultCache] = None,
                 tenant: Optional[str] = None,
                 rebalance: str = "off",
                 monitor: Optional[LoadMonitor] = None,
                 rebalance_target: float = 1.05,
                 debug_sanitize=False):
        self.pg = pg
        self.ctx = ctx
        self.mesh = mesh
        self.cfg = self._normalize_cfg(cfg or EngineConfig())
        self.shape_policy = self._resolve_policy(shape_policy, pad_multiple)
        self.pad_multiple = self.shape_policy.pad_multiple
        self.max_warm_entries = max_warm_entries
        self.max_warm_bytes = max_warm_bytes
        if rebalance not in ("off", "auto", "manual"):
            raise ValueError(
                f"rebalance={rebalance!r}: expected 'off', 'manual' or "
                "'auto'")
        self._rebalance_mode = rebalance
        self.rebalance_target = rebalance_target
        # "manual" keeps the monitor's gauge live without auto-triggering;
        # "off" attaches one only if the caller handed it in explicitly
        self.monitor = monitor if monitor is not None else (
            LoadMonitor() if rebalance != "off" else None)
        self._rebalancing = False      # re-entrancy guard (auto trigger
                                       # fires from _on_flush, and
                                       # rebalance() itself flushes)
        self.tenant = f"session-{id(self):x}" if tenant is None else tenant
        self._runner_cache = runner_cache if runner_cache is not None \
            else RunnerCache(max_runners, max_runner_bytes)
        self.result_cache = result_cache
        self.debug_sanitize = debug_sanitize
        self._closed = False
        self.stats = SessionStats()
        self.buffer = None if ctx is None else _SessionBuffer(
            self, pg, ctx, max_edges=max_buffer_edges,
            max_parts=max_buffer_parts, shape_policy=self.shape_policy)
        self._device = None            # resident stacked DeviceSubgraph
        self._device_version = -1
        self._host_version = 0         # bumped by every applied flush/compact
        self._warm: OrderedDict = OrderedDict()     # (pkey, params) -> entry
        self._identity_blocks: dict = {}  # cold-start [P,v_max,K] blocks
        self._auto_pin: dict = {}      # (sweep key, shape, tiles, windows
                                       # buckets) -> pinned 'auto' backend
                                       # assignment
        self._keepalive: dict = {}     # id-keyed programs pinned alive
        self._warm_epoch = 0           # advances per layout-moving event
        self._remap_log: list = []     # [(epoch, stats-with-remap_state)]:
                                       # pending warm-block remaps, applied
                                       # lazily on each entry's next use

    # ------------------------------------------------------------------ #
    # constructors
    # ------------------------------------------------------------------ #
    @classmethod
    def _resolve_policy(cls, shape_policy, pad_multiple) -> ShapePolicy:
        if shape_policy is not None:
            return shape_policy
        return ShapePolicy(pad_multiple=8 if pad_multiple is None
                           else pad_multiple)

    @classmethod
    def from_graph(cls, g: Graph, n_parts: int, partitioner: str = "cdbh",
                   *, seed: int = 0, mesh=None,
                   cfg: Optional[EngineConfig] = None,
                   pad_multiple: Optional[int] = None,
                   shape_policy: Optional[ShapePolicy] = None,
                   **kwargs) -> "GraphSession":
        """Partition + build + open a session in one call (the session-level
        ``partition_and_build``). Pure streaming partitioners also get a
        ``StreamContext`` so the update lifecycle works out of the box. The
        graph is padded by the session's (bucketed-by-default)
        ``shape_policy`` from the start, so the first flush already has
        in-bucket slack."""
        if shape_policy is None and partitioner not in STREAM_ROUTERS:
            # no StreamContext means no update/flush path: the shapes are
            # frozen for the session's lifetime, so buckets would only pay
            # padding overhead without ever amortizing a recompile
            shape_policy = ShapePolicy.exact(
                8 if pad_multiple is None else pad_multiple)
        policy = cls._resolve_policy(shape_policy, pad_multiple)
        entry = STREAM_ROUTERS.get(partitioner)
        router_state = None
        if is_stateful_router(entry):
            # stateful-streaming partitioner (EBV): the one-shot assignment
            # and the session's routing state must come from the SAME
            # streamed pass, or later deltas would not find resident edges
            router_state = entry.make_state(n_parts, g.n_vertices, seed)
            part = np.minimum(router_state.route_adds(g.src, g.dst),
                              n_parts - 1)
        else:
            part = PARTITIONERS[partitioner](g, n_parts, seed=seed)
        pg = build_partitioned_graph(g, part, n_parts, shape_policy=policy)
        ctx = None
        if partitioner in STREAM_ROUTERS:
            ctx = StreamContext(partitioner=partitioner, n_parts=n_parts,
                                seed=seed, n_vertices=g.n_vertices,
                                routing_degrees=g.total_degrees(),
                                router_state=router_state)
        return cls(pg, ctx=ctx, mesh=mesh, cfg=cfg, shape_policy=policy,
                   **kwargs)

    @classmethod
    def from_edge_log(cls, log, n_parts: int, partitioner: str = "cdbh",
                      *, seed: int = 0, mesh=None,
                      cfg: Optional[EngineConfig] = None,
                      pad_multiple: Optional[int] = None,
                      shape_policy: Optional[ShapePolicy] = None,
                      **kwargs) -> "GraphSession":
        """Open a session over a chunked on-disk edge log via the two-pass
        out-of-core ingest (docs/STREAMING.md). ``sess.ingest_stats`` holds
        the ingest throughput/memory accounting."""
        policy = cls._resolve_policy(shape_policy, pad_multiple)
        pg, ctx, stats = streaming_ingest(log, n_parts, partitioner,
                                          seed=seed, shape_policy=policy)
        sess = cls(pg, ctx=ctx, mesh=mesh, cfg=cfg, shape_policy=policy,
                   **kwargs)
        sess.ingest_stats = stats
        return sess

    # ------------------------------------------------------------------ #
    def _normalize_cfg(self, cfg: EngineConfig) -> EngineConfig:
        """The session picks the backend from mesh presence — a config asking
        for shard_map without a mesh falls back to the simulator
        transparently (and vice versa), so one call site serves both."""
        backend = "sim" if self.mesh is None else "shard_map"
        if cfg.backend != backend:
            cfg = dataclasses.replace(cfg, backend=backend)
        return cfg

    @property
    def slot_capacity(self) -> int:
        """SBS exchange-buffer height the runners are built with — the
        bucketed ``pg.n_slots``. Frontier re-elections that stay inside the
        slot bucket change nothing a compiled runner can see. A read-only
        session (no mutation path) has a frozen frontier, so it pads
        nothing."""
        if self.buffer is None:
            return int(self.pg.n_slots)
        return self.shape_policy.slot_capacity(self.pg.n_slots)

    @property
    def shape_key(self):
        """The padded device shapes a compiled runner is specialized to.
        All four dims are bucket values under the session's
        ``shape_policy``, so the key — and with it the runner cache — is
        stable across any flush that stays inside the current buckets."""
        pg = self.pg
        return (pg.n_parts, pg.v_max, pg.e_max, self.slot_capacity,
                pg.vlabel is not None)

    @property
    def _runners(self):
        """The compiled-runner entries (key -> ``RunnerEntry``, LRU order).
        On a pool-shared cache this is the WHOLE shared map — other tenants'
        entries included; on the default private cache it is exactly the old
        per-session ``OrderedDict``. Kept as a property for introspection
        back-compat; mutate through ``self._runner_cache``."""
        return self._runner_cache.entries

    # The runner-cache bounds live on the cache itself (shared in a pool);
    # these proxies keep the historical mutable-attribute surface — setting
    # one re-bounds the cache this session uses, applied on the next insert.
    # On a pool-shared cache that IS the shared bound.
    @property
    def max_runners(self) -> Optional[int]:
        return self._runner_cache.max_entries

    @max_runners.setter
    def max_runners(self, v: Optional[int]) -> None:
        self._runner_cache.max_entries = v

    @property
    def max_runner_bytes(self) -> Optional[int]:
        return self._runner_cache.max_bytes

    @max_runner_bytes.setter
    def max_runner_bytes(self, v: Optional[int]) -> None:
        self._runner_cache.max_bytes = v

    # ------------------------------------------------------------------ #
    # lifecycle
    # ------------------------------------------------------------------ #
    @property
    def closed(self) -> bool:
        return self._closed

    def close(self) -> None:
        """Release everything this session holds: the resident device
        pytree, its pins in the (possibly shared) runner cache, the warm-
        result memory, identity blocks and program pins. Idempotent; any
        subsequent query/mutation raises ``RuntimeError``. Without close,
        a dropped session keeps device memory alive until GC — the pool
        eviction path needs the deterministic version."""
        if self._closed:
            return
        self._closed = True
        self._runner_cache.release(self.tenant)
        self._warm.clear()
        self._remap_log.clear()
        self._identity_blocks.clear()
        self._keepalive.clear()
        self._device = None
        self._device_version = -1
        self._sync_warm_bytes()
        self._sync_runner_bytes()

    def __enter__(self) -> "GraphSession":
        self._check_open()
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _check_open(self) -> None:
        if self._closed:
            raise RuntimeError("GraphSession is closed")

    def _placement(self, cfg: Optional[EngineConfig] = None):
        """With a mesh, where every resident input (graph, layouts, warm
        blocks) of a query under ``cfg`` (default: the session's) goes: the
        in_specs of the runner that query launches, so a launch moves
        nothing."""
        if self.mesh is None:
            return None
        return shard_placement(self.mesh, cfg or self.cfg,
                               self.pg.vlabel is not None)

    def device_graph(self, cfg: Optional[EngineConfig] = None):
        """The resident stacked [P, ...] DeviceSubgraph pytree, re-uploaded
        only when the host graph changed since the last upload, or when a
        query's ``cfg`` shards it over other mesh axes. With a mesh each
        device holds its own partitions' share."""
        self._check_open()
        placement = self._placement(cfg)
        key = (self._host_version, placement and placement.graph)
        if self._device is None or self._device_version != key:
            with span("session/upload"):
                self._device = None    # free the old copy before uploading
                self._device = _device_subgraph(self.pg, placement)
                self._device_version = key
                self.stats.uploads += 1
                self.stats.upload_bytes += _nbytes(self._device)
        return self._device

    # ------------------------------------------------------------------ #
    # query path
    # ------------------------------------------------------------------ #
    def query(self, program: VertexProgram, params=None, *, warm="auto",
              cfg: Optional[EngineConfig] = None, use_result_cache=True):
        """Run ``program`` over the resident graph; returns
        ``(results, ExecutionStats)`` exactly like the low-level ``run``
        (results in the [P, v_max(, K)] local layout; ``self.pg.collect``
        maps them to global ids).

        ``warm`` — ``"auto"`` (default): monotone programs restart from this
        (program, params) pair's last converged result whenever one is still
        sound (every flush since was insert-only); ``False``: force a cold
        start; ``True``: require a warm start and raise ``ValueError`` when
        none is available (non-monotone program, no previous result, or a
        deleting flush invalidated it).

        ``cfg`` overrides the session config for this query (e.g. the
        vertex-centric baseline ``EngineConfig(mode="vc")``); the backend
        still follows the session's mesh. ``cfg.trace=True`` queries
        delegate to the uncached ``run_sim`` trace loop (per-superstep stats
        and checkpointing are job-level features, not serving features).

        When a ``result_cache`` is attached, the converged result of this
        exact ``(graph version, program, params, cfg)`` query may be served
        straight from the cache with **zero device launches**
        (``ExecutionStats.result_cache_tier`` says which tier answered);
        pass ``use_result_cache=False`` to force a device run. Keys carry
        the graph version, so any flush — including deleting ones —
        implicitly invalidates prior entries.

        Buffered updates are flushed first: a query always sees every
        mutation accepted by ``update``.

        The call is the span ``session/query``; its ``upload_bytes`` is what
        the query added to ``SessionStats.upload_bytes``, its
        ``pallas_edge_share`` the share of resident edges in partitions
        whose edge backend is a Pallas kernel, its ``one_sweep_share`` the
        share in partitions whose local phase sweeps once a superstep
        (``engine.local_bound``), and its ``exchange_bytes`` the query's
        ``ExecutionStats.total_bytes``.
        """
        with span("session/query") as sp:
            before = self.stats.upload_bytes
            out = self._query(program, params, warm, cfg, use_result_cache)
            sp.set_metadata(upload_bytes=self.stats.upload_bytes - before,
                            pallas_edge_share=self._pallas_edge_share(out[1]),
                            one_sweep_share=self._one_sweep_share(
                                program, cfg or self.cfg),
                            exchange_bytes=out[1].total_bytes)
        return out

    def _query(self, program, params, warm, cfg, use_result_cache):
        self._check_open()
        if self.buffer is not None and len(self.buffer):
            self.flush()
        cfg = self._normalize_cfg(cfg or self.cfg)
        params_c = _canonical_params(params)
        pkey = _program_key(program)
        if isinstance(pkey[1], int):
            # id()-based fallback key: pin the program object so a freed id
            # can never be reused by a different program and hit this entry
            self._keepalive[pkey[1]] = program

        entry = wkey = None
        if program.monotone:
            wkey = (pkey, _params_fingerprint(params_c))
            entry = self._warm.get(wkey)
            if entry is not None:
                self._warm.move_to_end(wkey)   # refresh LRU recency
        if warm is True:
            if not program.monotone:
                raise ValueError(
                    f"warm=True: {type(program).__name__} is not monotone — "
                    "warm starts are only sound for programs whose values "
                    "tighten under the combiner (program.monotone)")
            if entry is None:
                raise ValueError(
                    "warm=True but no previous converged result is cached "
                    "for this (program, params) query (or a deleting flush "
                    "invalidated it); use warm='auto' to fall back to cold")
        use_warm = entry is not None and warm in ("auto", True)

        if cfg.trace:
            init = entry.global_values if use_warm else None
            return run_sim(program, self.pg, params, cfg, init_state=init)

        self.stats.queries += 1
        # programs without a SemiringSweep always run COO: normalize the
        # config so their runners dedupe across edge_backend settings
        eb, cfg = normalize_edge_backend(program, cfg)

        use_rc = use_result_cache and self.result_cache is not None
        rkey = None
        if use_rc:
            rkey = _result_key(self.tenant, self._host_version, program,
                               params_c, cfg)
            t0 = time.perf_counter()
            val, tier = self.result_cache.get(rkey)
            if val is not None:
                # converged-result hit: no runner, no launch, no transfer
                if tier == "l1":
                    self.stats.result_cache_l1_hits += 1
                else:
                    self.stats.result_cache_l2_hits += 1
                st = ExecutionStats(
                    supersteps=int(val["supersteps"]),
                    wall_time=time.perf_counter() - t0,
                    edge_backend=str(val.get("edge_backend", eb)),
                    result_cache_tier=tier)
                return np.asarray(val["results"]), st
            self.stats.result_cache_misses += 1

        warm_in = bool(program.monotone)
        args = (self.device_graph(cfg),)
        if eb != "coo":
            args += (self._layout_arg(program, eb, cfg),)
        args += (params_c,)
        if warm_in:
            with span("session/warm"):
                args += (self._warm_arg(program, entry, use_warm, cfg),)
        with span("session/runner"):
            compiled, compile_time, evicted = self._get_runner(
                program, pkey, params_c, cfg, warm_in, args, eb)
        with span("session/launch"):
            t0 = time.perf_counter()
            out = self._launch(compiled, args, compile_time)
            self.stats.device_launches += 1
            res, steps, tot_msgs, sweeps = jax.block_until_ready(out)
            wall = time.perf_counter() - t0
        if use_warm:
            self.stats.warm_queries += 1

        with span("session/fetch"):
            res = np.asarray(res)
            stats = self._execution_stats(program, cfg, int(steps),
                                          int(tot_msgs), np.asarray(sweeps),
                                          wall, compile_time, eb)
            stats.evicted_runners = evicted
            if program.monotone:
                self._remember(program, wkey, res, stats.supersteps)
        if use_rc:
            stats.result_cache_tier = "miss"
            self.result_cache.put(rkey, dict(
                results=res, supersteps=stats.supersteps, edge_backend=eb))
        return res, stats

    def query_batch(self, program: VertexProgram, params_list, *,
                    warm="auto", cfg: Optional[EngineConfig] = None,
                    use_result_cache=True):
        """Serve ``len(params_list)`` queries of one program in a SINGLE
        device launch (the micro-batching engine entry point —
        ``serving/batcher.py`` coalesces live traffic into these). Returns
        ``[(results, ExecutionStats), ...]`` in input order, each exactly
        what ``query`` would have returned: the batched runner maps the
        same per-lane superstep loop over a stacked params pytree (COO
        simulator: ``jax.vmap`` — converged lanes are select-frozen, so
        per-lane results are bit-identical to singleton launches; Pallas /
        shard_map backends: ``lax.scan`` over lanes inside one executable).

        Every lane must share the program and the param *structure*
        (``ValueError`` otherwise — the batcher degrades mismatches to
        singleton ``query`` calls). Batch sizes are padded up to the next
        power of two (replicating lane 0) so the runner cache holds
        O(log max_batch) batched executables per program, not one per
        batch size; the pad lanes' outputs are discarded.

        Warm starts (``warm="auto"``) and the result cache work per lane:
        each lane looks up / stores its own warm entry and result-cache
        key. The result cache short-circuits only when EVERY lane hits —
        a partial hit still launches the full batch (the lanes that hit
        are simply recomputed; their entries refresh).

        The call is the span ``session/query_batch``, with the children of
        ``query``'s span and its ``upload_bytes``, ``pallas_edge_share``,
        ``one_sweep_share`` and ``exchange_bytes`` (summed over the
        lanes)."""
        with span("session/query_batch") as sp:
            before = self.stats.upload_bytes
            out = self._query_batch(program, params_list, warm, cfg,
                                    use_result_cache)
            meta = dict(upload_bytes=self.stats.upload_bytes - before)
            if out:
                meta["pallas_edge_share"] = self._pallas_edge_share(
                    out[0][1])
                meta["one_sweep_share"] = self._one_sweep_share(
                    program, cfg or self.cfg)
                meta["exchange_bytes"] = sum(st.total_bytes
                                             for _, st in out)
            sp.set_metadata(**meta)
        return out

    def _query_batch(self, program, params_list, warm, cfg,
                     use_result_cache):
        self._check_open()
        if self.buffer is not None and len(self.buffer):
            self.flush()
        B = len(params_list)
        if B == 0:
            return []
        cfg = self._normalize_cfg(cfg or self.cfg)
        if cfg.trace:
            raise ValueError("query_batch does not support cfg.trace — "
                             "trace one query at a time")
        params_cs = [_canonical_params(p) for p in params_list]
        skey = _params_struct_key(params_cs[0])
        for pc in params_cs[1:]:
            if _params_struct_key(pc) != skey:
                raise ValueError(
                    "query_batch needs an identical param structure on "
                    "every lane (same treedef, leaf shapes and dtypes); "
                    "mismatched requests must go through query()")
        if B == 1:
            res, st = self.query(program, params_list[0], warm=warm,
                                 cfg=cfg, use_result_cache=use_result_cache)
            return [(res, st)]
        if not jax.tree.leaves(params_cs[0]) and not program.monotone:
            # leafless lanes (no params, no warm input): nothing carries a
            # batch axis and every lane is the same computation — serve one
            # singleton and fan the result out
            res, st = self.query(program, params_list[0], warm=warm,
                                 cfg=cfg, use_result_cache=use_result_cache)
            return [(res, dataclasses.replace(st, batch_size=B))
                    for _ in range(B)]

        pkey = _program_key(program)
        if isinstance(pkey[1], int):
            self._keepalive[pkey[1]] = program
        eb, cfg = normalize_edge_backend(program, cfg)

        use_rc = use_result_cache and self.result_cache is not None
        rkeys = None
        if use_rc:
            rkeys = [_result_key(self.tenant, self._host_version, program,
                                 pc, cfg) for pc in params_cs]
            if all(self.result_cache.peek(k) is not None for k in rkeys):
                out = []
                for k in rkeys:
                    t0 = time.perf_counter()
                    val, tier = self.result_cache.get(k)
                    if tier == "l1":
                        self.stats.result_cache_l1_hits += 1
                    else:
                        self.stats.result_cache_l2_hits += 1
                    out.append((np.asarray(val["results"]), ExecutionStats(
                        supersteps=int(val["supersteps"]),
                        wall_time=time.perf_counter() - t0,
                        edge_backend=str(val.get("edge_backend", eb)),
                        result_cache_tier=tier, batch_size=B)))
                self.stats.queries += B
                return out
            self.stats.result_cache_misses += B

        # per-lane warm bookkeeping, same rules as query()
        entries, use_warms, wkeys = [], [], []
        for pc in params_cs:
            entry = wkey = None
            if program.monotone:
                wkey = (pkey, _params_fingerprint(pc))
                entry = self._warm.get(wkey)
                if entry is not None:
                    self._warm.move_to_end(wkey)
            if warm is True:
                if not program.monotone:
                    raise ValueError(
                        f"warm=True: {type(program).__name__} is not "
                        "monotone")
                if entry is None:
                    raise ValueError(
                        "warm=True but a lane has no cached converged "
                        "result; use warm='auto'")
            wkeys.append(wkey)
            entries.append(entry)
            use_warms.append(entry is not None and warm in ("auto", True))

        self.stats.queries += B
        self.stats.batches += 1
        self.stats.batched_queries += B
        warm_in = bool(program.monotone)
        Bp = 1 << (B - 1).bit_length()           # power-of-2 batch bucket
        pad = Bp - B
        params_pad = params_cs + [params_cs[0]] * pad
        batched_params = jax.tree.map(lambda *ls: jnp.stack(ls), *params_pad)
        args = (self.device_graph(cfg),)
        if eb != "coo":
            args += (self._layout_arg(program, eb, cfg),)
        args += (batched_params,)
        if warm_in:
            with span("session/warm"):
                blocks = [self._warm_arg(program, entries[i], use_warms[i],
                                         cfg) for i in range(B)]
                blocks += [blocks[0]] * pad
                args += (jnp.stack(blocks),)
        with span("session/runner"):
            compiled, compile_time, evicted = self._get_runner(
                program, pkey, batched_params, cfg, warm_in, args, eb,
                batch=Bp)
        with span("session/launch"):
            t0 = time.perf_counter()
            out = self._launch(compiled, args, compile_time)
            self.stats.device_launches += 1
            res_b, steps_b, msgs_b, sweeps_b = jax.block_until_ready(out)
            wall = time.perf_counter() - t0

        results = []
        with span("session/fetch"):
            for i in range(B):
                res = np.asarray(res_b[i])
                st = self._execution_stats(
                    program, cfg, int(steps_b[i]), int(msgs_b[i]),
                    np.asarray(sweeps_b[i]), wall, compile_time, eb)
                st.evicted_runners = evicted
                st.batch_size = B
                if use_warms[i]:
                    self.stats.warm_queries += 1
                if program.monotone:
                    self._remember(program, wkeys[i], res, st.supersteps)
                if use_rc:
                    st.result_cache_tier = "miss"
                    self.result_cache.put(rkeys[i], dict(
                        results=res, supersteps=st.supersteps,
                        edge_backend=eb))
                results.append((res, st))
        return results

    def result_key_for(self, program: VertexProgram, params=None,
                       cfg: Optional[EngineConfig] = None) -> str:
        """The tiered result-cache key ``query`` would consult for this
        request right now (tenant + current graph version + normalized
        config) — the batcher's fast path peeks it before queueing."""
        cfg = self._normalize_cfg(cfg or self.cfg)
        _, cfg = normalize_edge_backend(program, cfg)
        return _result_key(self.tenant, self._host_version, program,
                           _canonical_params(params), cfg)

    def _pallas_edge_share(self, st: ExecutionStats) -> float:
        """Share of the resident edges that the query's edge backend put
        in a Pallas kernel: 0 on ``coo``, 1 on ``pallas_*``, and under
        ``'auto'`` the edges of the partitions assigned a kernel."""
        if st.edge_backend != "auto":
            return 0.0 if st.edge_backend == "coo" else 1.0
        epp = self.pg.edges_per_part.astype(np.float64)
        kernel = np.array([b != "coo" for b in st.partition_edge_backends],
                          bool)
        if kernel.shape != epp.shape or epp.sum() == 0:
            return 0.0
        return float(epp[kernel].sum() / epp.sum())

    def _one_sweep_share(self, program: VertexProgram,
                         cfg: EngineConfig) -> float:
        """Share of the resident edges in partitions whose local phase
        ``local_bound`` holds to one sweep a superstep for ``program``."""
        epp = self.pg.edges_per_part.astype(np.float64)
        one = np.broadcast_to(
            np.asarray(local_bound(program, cfg, self.pg.local_paths)) == 1,
            epp.shape)
        return float(epp[one].sum() / epp.sum()) if epp.sum() else 0.0

    def _n_edge_shards(self, cfg) -> int:
        if cfg.backend != "shard_map" or not cfg.edge_axes \
                or self.mesh is None:
            return 1
        return int(np.prod([self.mesh.shape[a] for a in cfg.edge_axes]))

    def _pin_key(self, skey) -> tuple:
        lay = self.pg.ensure_edge_layouts(shape_policy=self.shape_policy)
        return (skey, self.shape_key, lay.shape_key("pallas_tiles"),
                lay.shape_key("pallas_windows"))

    def _resolve_assignment(self, program, cfg) -> tuple:
        """The per-partition backend assignment a ``'auto'`` query runs
        with, PINNED per (sweep key, padded-shape, layout-capacity) bucket:
        the policy is consulted once when a program's sweep key (semiring,
        edge values, dtype, engine backend) meets a bucket combination, and
        every later query of that key in the same buckets reuses the pick
        even though the measured densities drift with streaming growth —
        that is the zero-retrace guarantee ('auto' never flips a backend
        mid-bucket). Bucket crossings (flush past a capacity, compact,
        rebalance) naturally re-resolve under their new key."""
        lay = self.pg.ensure_edge_layouts(shape_policy=self.shape_policy)
        key = self._pin_key(autotune.sweep_key(program, cfg.backend))
        asg = self._auto_pin.get(key)
        if asg is None:
            asg = resolve_partition_backends(program, cfg, self.pg, lay=lay)
            self._auto_pin[key] = asg
        return asg

    def _layout_arg(self, program, eb, cfg):
        """Device layout pytree for a Pallas-backend query — an explicit
        runner input (like params), so the executable survives layout
        content changes and retraces only when the layout *capacities*
        cross a bucket (a new layout shape-key). ``'auto'`` passes the
        mixed-backend blocks (group-sliced pair on the simulator, full
        blocks + per-partition backend ids under shard_map); edge-axis
        sharding passes the per-shard geometry. The call is the span
        ``session/layouts``."""
        with span("session/layouts"):
            lay = self.pg.ensure_edge_layouts(shape_policy=self.shape_policy)
            before = lay.uploaded_bytes
            ns = self._n_edge_shards(cfg)
            if eb != "auto":
                blk = _layout_block_from(lay, self.pg, program, eb,
                                         n_shards=ns,
                                         placement=self._placement(cfg))
            elif cfg.backend == "shard_map":
                blk = _auto_layout_blocks(
                    lay, self.pg, program,
                    self._resolve_assignment(program, cfg), mixed_shard=True,
                    n_shards=ns, placement=self._placement(cfg))
            else:
                blk = _auto_layout_blocks(
                    lay, self.pg, program,
                    self._resolve_assignment(program, cfg))
            self.stats.upload_bytes += lay.uploaded_bytes - before
        return blk

    def _layout_key(self, program, eb, cfg):
        if eb == "coo":
            return None
        lay = self.pg.edge_layouts
        if lay is None:
            return None
        ns = self._n_edge_shards(cfg)
        if eb == "auto":
            # the pinned assignment joins the key: a re-resolution that
            # lands on different picks must compile a fresh runner (group
            # composition is baked into the traced argument structure)
            asg = self._resolve_assignment(program, cfg)
            return ("auto", autotune.sweep_key(program, cfg.backend), asg,
                    lay.shape_key("pallas_tiles", n_shards=ns, pg=self.pg),
                    lay.shape_key("pallas_windows", n_shards=ns, pg=self.pg))
        return lay.shape_key(eb, n_shards=ns, pg=self.pg)

    def _sync_warm_entry(self, entry: _WarmEntry) -> None:
        """Apply the pending remap chain to this entry's device block (lazy
        counterpart of the old eager per-flush remap): every insert-only
        flush / compaction since the entry was last touched is replayed in
        order. Entries never queried again never pay for any flush."""
        if entry.device_block is None \
                or entry.device_epoch == self._warm_epoch:
            return
        for ep, st in self._remap_log:
            if ep > entry.device_epoch:
                entry.device_block = st.remap_state(entry.device_block,
                                                    fill=entry.identity)
                self.stats.warm_remaps_applied += 1
        entry.device_epoch = self._warm_epoch
        self._sync_warm_bytes()

    def _prune_remap_log(self) -> None:
        """Drop log entries every live device block is already past. The
        log length is bounded by the slowest-moving warm entry; clearing
        the warm memory (deleting flush, evictions) empties it."""
        blocks = [e.device_epoch for e in self._warm.values()
                  if e.device_block is not None]
        if not blocks:
            self._remap_log.clear()
            return
        floor = min(blocks)
        self._remap_log = [(ep, st) for ep, st in self._remap_log
                           if ep > floor]

    def _sync_warm_bytes(self) -> None:
        self.stats.warm_cache_bytes = sum(e.nbytes
                                          for e in self._warm.values())

    def _warm_arg(self, program, entry, use_warm, cfg):
        """[P, v_max, K] warm block: the cached result when warming, the
        combiner identity (a structural no-op for ``warm_init``) when cold —
        so both paths share one compiled runner."""
        pg = self.pg
        K = program.payload
        placement = self._placement(cfg)
        sharding = placement and placement.warm
        if not use_warm:
            # constant per (shapes, dtype, identity, placement): keep it
            # resident so repeated cold queries skip the rebuild +
            # host->device transfer
            ikey = (pg.n_parts, pg.v_max, K, str(np.dtype(program.dtype)),
                    float(program.identity), sharding)
            blk = self._identity_blocks.get(ikey)
            if blk is None:
                blk = self._upload_warm(np.full(
                    (pg.n_parts, pg.v_max, K), program.identity,
                    dtype=program.dtype), sharding)
                self._identity_blocks[ikey] = blk
            return blk
        self._sync_warm_entry(entry)
        blk = entry.device_block
        if blk is not None and blk.shape == (pg.n_parts, pg.v_max, K):
            return self._upload_warm(blk, sharding)
        return self._upload_warm(
            _warm_block(program, pg, entry.global_values), sharding)

    def _upload_warm(self, blk: np.ndarray, sharding):
        """A [P, v_max, K] block on the device (each device's own
        partitions' rows under a mesh ``sharding``)."""
        out = jnp.asarray(blk) if sharding is None \
            else jax.device_put(blk, sharding)
        self.stats.upload_bytes += _nbytes(out)
        return out

    def _launch(self, compiled, args, compile_time):
        """Execute an AOT runner. With ``debug_sanitize`` armed, a *cache
        hit* (``compile_time == 0``) runs under ``retrace_guard``: the
        executable was traced long ago, so any tracer activity during the
        launch is a retrace bug and raises ``RetraceError`` (or warns for
        ``debug_sanitize="warn"``). Fresh compiles are exempt — their trace
        already happened, legitimately, inside ``_get_runner``."""
        if not self.debug_sanitize or compile_time > 0.0:
            return compiled(*args)
        from repro.analysis.sanitizer import retrace_guard
        action = "warn" if self.debug_sanitize == "warn" else "raise"
        with retrace_guard(action=action,
                           label=f"GraphSession[{self.tenant}] cache-hit "
                                 f"launch"):
            return compiled(*args)

    def _get_runner(self, program, pkey, params_c, cfg, warm_in, args, eb,
                    batch=0):
        """AOT-compile (trace + lower + compile, once) or fetch the cached
        executable for this (program, param structure, config, shapes).
        Returns ``(compiled, compile_time, n_lru_evictions)``; a hit
        refreshes the entry's LRU position. Runners are built against the
        bucketed ``slot_capacity``, not the exact ``pg.n_slots``; Pallas
        runners additionally key on the layout capacities (``shape_key`` of
        the ``EdgeLayouts``), which are bucketed and grow-only too.

        The cache may be shared across sessions (``SessionPool``): keys
        carry shapes and never the tenant, so a same-bucket lookup by a
        different tenant hits the same entry — that is the cross-tenant
        executable sharing. ``batch`` (a padded lane count from
        ``query_batch``) joins the key explicitly so a batched runner can
        never collide with a singleton runner whose params genuinely carry
        a leading axis of the same length."""
        lkey = self._layout_key(program, eb, cfg)
        full_shape = (self.shape_key, lkey)
        key = (pkey, _params_struct_key(params_c), cfg, full_shape, warm_in)
        if batch:
            key = key + (("batch", batch),)
        hit = self._runner_cache.lookup(key, self.tenant)
        if hit is not None:
            self.stats.cache_hits += 1
            return hit.compiled, 0.0, 0
        self.stats.cache_misses += 1
        n_slots = self.slot_capacity
        asg = self._resolve_assignment(program, cfg) if eb == "auto" \
            else None
        t0 = time.perf_counter()
        if cfg.backend == "sim":
            fn = make_sim_runner(program, cfg, n_slots, warm_start=warm_in,
                                 batch=bool(batch), partition_backends=asg)
            compiled = jax.jit(fn).lower(*args).compile()
        else:
            self._check_mesh(cfg)
            fn = make_bsp_runner(program, self.mesh, cfg, n_slots,
                                 has_vlabel=self.pg.vlabel is not None,
                                 warm_start=warm_in, batch=bool(batch),
                                 partition_backends=asg)
            with self.mesh:
                compiled = jax.jit(fn).lower(*args).compile()
        compile_time = time.perf_counter() - t0
        self.stats.compile_time_total += compile_time
        entry = _RunnerEntry(
            compiled=compiled, shape_key=full_shape,
            program=type(program).__name__, compile_time=compile_time,
            nbytes=_runner_nbytes(compiled))
        evicted = self._runner_cache.insert(key, entry, self.tenant)
        if evicted:
            self.stats.cache_evictions_lru += evicted
            self._prune_keepalive()
        self._sync_runner_bytes()
        return compiled, compile_time, evicted

    def _sync_runner_bytes(self) -> None:
        self.stats.runner_cache_bytes = self._runner_cache.total_bytes

    def _evict_lru(self, cache: OrderedDict, bound: Optional[int],
                   counter: str, max_bytes: Optional[int] = None) -> int:
        """Pop least-recently-used entries until ``cache`` fits ``bound``
        AND its estimated bytes fit ``max_bytes`` (the most recent entry is
        never evicted — a single over-budget entry must still serve),
        billing the named ``SessionStats`` counter and releasing any
        program pins the evictions orphaned."""
        evicted = 0
        if bound is not None:
            while len(cache) > bound:
                cache.popitem(last=False)
                evicted += 1
        if max_bytes is not None:
            total = sum(e.nbytes for e in cache.values())
            while total > max_bytes and len(cache) > 1:
                _, e = cache.popitem(last=False)
                total -= e.nbytes
                evicted += 1
        if evicted:
            setattr(self.stats, counter,
                    getattr(self.stats, counter) + evicted)
            self._prune_keepalive()
        return evicted

    def _prune_keepalive(self) -> None:
        """Release id-keyed program pins whose id no longer appears in any
        runner-cache or warm-memory key: once nothing can look the id up,
        the id-reuse hazard the pin guards against is gone, and keeping the
        object would leak host memory on a bounded cache."""
        if not self._keepalive:
            return
        live = {k[0][1] for k in self._runners} | \
               {wk[0][1] for wk in self._warm}
        self._keepalive = {i: p for i, p in self._keepalive.items()
                           if i in live}

    def _check_mesh(self, cfg: EngineConfig):
        n_sub = int(np.prod([self.mesh.shape[a] for a in cfg.subgraph_axes]))
        assert self.pg.n_parts == n_sub, (self.pg.n_parts, n_sub)
        assert self.pg.e_max % self._n_edge_shards(cfg) == 0, \
            "pad edges to a multiple of the edge axes"

    def _execution_stats(self, program, cfg, steps, msgs, sweeps, wall,
                         compile_time, eb="coo") -> ExecutionStats:
        """The engine's bill of the launch, on the bucketed exchange height
        the runner reduces (``slot_capacity``), plus what is the session's
        own: the times, the per-partition sweep times and ``SessionStats``."""
        pg = self.pg
        st = execution_stats(
            program, cfg, pg, self.slot_capacity, steps, msgs, sweeps,
            n_edge_shards=self._n_edge_shards(cfg),
            assignment=self._resolve_assignment(program, cfg)
            if eb == "auto" else None)
        st.wall_time, st.compile_time = wall, compile_time
        # per-shard sweep time: the launch wall time apportioned by each
        # shard's flops share (shards run lock-step supersteps, so the
        # flops skew IS the critical-path skew the monitor cares about)
        flops = np.asarray(st.partition_flops, np.float64)
        share = (flops / st.backend_flops if st.backend_flops
                 else np.full(pg.n_parts, 1.0 / max(pg.n_parts, 1)))
        st.partition_sweep_time = [float(x) for x in wall * share]
        if st.partition_tile_density:
            dens = np.asarray(st.partition_tile_density)
            self.stats.tile_density_min = float(dens.min())
            self.stats.tile_density_mean = float(dens.mean())
            self.stats.tile_density_max = float(dens.max())
        # surface the load gauges on SessionStats (EWMA for the measured
        # signal) and feed the monitor's measured-work input
        self.stats.partition_edge_counts = list(st.partition_edge_counts)
        prev = self.stats.partition_sweep_time
        cur = st.partition_sweep_time
        if len(prev) != len(cur):
            self.stats.partition_sweep_time = list(cur)
        else:
            a = self.monitor.cfg.ema if self.monitor is not None else 0.5
            self.stats.partition_sweep_time = [
                a * n + (1.0 - a) * o for n, o in zip(cur, prev)]
        if self.monitor is not None:
            self.monitor.observe_query(st)
            self.stats.load_imbalance = self.monitor.gauge
        return st

    def _remember(self, program, wkey, res, supersteps):
        """Cache this converged result as the warm seed for the next
        identical query (padded rows sanitized to the combiner identity),
        evicting the least-recently-used result beyond
        ``max_warm_entries`` — the bound that keeps warm host memory and
        the per-flush remap cost independent of how many distinct queries
        the session has ever served."""
        pg = self.pg
        blk = res if res.ndim == 3 else res[..., None]
        blk = np.where(pg.vmask[..., None], blk,
                       np.asarray(program.identity, blk.dtype))
        self._warm[wkey] = _WarmEntry(
            global_values=pg.collect(res, fill=program.identity),
            device_block=blk, identity=program.identity,
            supersteps=supersteps, device_epoch=self._warm_epoch,
            polarity=program.warm_under)
        self._warm.move_to_end(wkey)
        self._evict_lru(self._warm, self.max_warm_entries, "warm_evictions",
                        max_bytes=self.max_warm_bytes)
        self._prune_remap_log()
        self._sync_warm_bytes()

    # ------------------------------------------------------------------ #
    # streaming lifecycle
    # ------------------------------------------------------------------ #
    def _require_buffer(self, what: str) -> DeltaBuffer:
        self._check_open()
        if self.buffer is None:
            raise ValueError(
                f"{what} needs a StreamContext (this session was opened "
                "from a bare PartitionedGraph, or with a non-streamable "
                "partitioner); use GraphSession.from_graph/from_edge_log "
                "with a pure routing partitioner, or pass ctx=")
        return self.buffer

    def update(self, adds=None, deletes=None) -> None:
        """Enqueue edge mutations. ``adds`` is ``(src, dst)`` or
        ``(src, dst, w)`` (array-likes of global ids), ``deletes`` is
        ``(src, dst)``; an ``EdgeDelta`` is accepted for either role via
        ``push``. Ops coalesce in the internal ``DeltaBuffer`` and are
        applied on ``flush()`` (or automatically when a buffer threshold
        trips — the session notices either way). The call is the span
        ``stream/update``."""
        buf = self._require_buffer("update()")
        if isinstance(adds, EdgeDelta) or isinstance(deletes, EdgeDelta):
            raise TypeError("pass an EdgeDelta through session.push()")
        with span("stream/update"):
            if deletes is not None:
                buf.delete(*deletes[:2])
            if adds is not None:
                buf.add(*adds[:3])

    def push(self, delta: EdgeDelta) -> None:
        """Enqueue a whole producer ``EdgeDelta`` (deletes-then-adds)."""
        self._require_buffer("push()").push(delta)

    def flush(self) -> Optional[DeltaStats]:
        """Apply every buffered mutation as one coalesced patch. Returns the
        applied patch's ``DeltaStats`` — if a buffer threshold already
        auto-flushed everything during ``update``, the stats of that last
        applied patch (never None once any patch has been applied; None only
        when nothing was ever buffered). The device pytree refreshes lazily
        on the next query; compiled runners survive unless the padded shapes
        crossed a bucket boundary."""
        buf = self._require_buffer("flush()")
        st = buf.flush()
        return st if st is not None else buf.last_flush

    def _on_flush(self, st: DeltaStats) -> None:
        self._host_version += 1
        self.stats.flushes += 1
        # A warm entry survives a flush only when the applied patch matches
        # its program's declared polarity (VertexProgram.warm_under):
        # 'inserts' entries survive insert-only patches (no delete was even
        # attempted — the historical warm_start_safe bit), 'deletes' entries
        # survive patches that added no edge. Membership is grow-only under
        # both, so one shared remap log serves whichever side survives.
        keep = {"inserts": st.warm_start_safe, "deletes": st.n_added == 0}
        if any(keep.values()):
            # Local rows reshuffle (and v_max may cross a bucket), but the
            # remap is only LOGGED here — each warm entry replays the
            # pending chain on its next use (_sync_warm_entry), so a flush
            # costs O(1) regardless of warm occupancy and entries that are
            # never queried again never pay at all.
            self._warm_epoch += 1
            self._remap_log.append((self._warm_epoch, st))
        if not all(keep.values()):
            # the patch loosened values for the other polarity: those
            # cached results are not sound anymore
            for wkey in [k for k, e in self._warm.items()
                         if not keep.get(e.polarity, False)]:
                del self._warm[wkey]
        self._prune_remap_log()
        self._sync_warm_bytes()
        self._evict_stale_runners()
        # streaming churn drives the load monitor; under rebalance="auto" a
        # tripped hysteresis gauge migrates right here, before the flush's
        # caller sees the new graph version
        if self.monitor is not None and not self._rebalancing:
            self.stats.load_imbalance = self.monitor.observe_graph(self.pg)
            if (self._rebalance_mode == "auto"
                    and self.monitor.should_rebalance()):
                self.rebalance()

    def rebalance(self, *, target: Optional[float] = None
                  ) -> Optional[RebalanceStats]:
        """Migrate boundary edges off overloaded partitions
        (docs/PARTITIONING.md). Plans a minimal cheapest-first move set
        (``repro.partition.rebalance``), executes it through the same
        ``repack_partitions`` remap machinery as ``compact`` — warm results
        ride the remap chain, in-bucket runners survive, the version bump
        invalidates result-cache entries — and records the moved pairs in
        the routing context so later deletes/re-adds find them. Returns
        the ``RebalanceStats``, or None when the plan is empty (already
        balanced). Needs a ``StreamContext`` like every mutation path."""
        self._check_open()
        self._require_buffer("rebalance()")
        if self._rebalancing:
            return None
        self._rebalancing = True
        try:
            if len(self.buffer):
                self.flush()
            # donor selection weights by the monitor's BLENDED load vector
            # (measured sweep time + frontier churn, not just edge counts)
            # when one is live — the moved objects are still edges
            loads = self.monitor.blended_loads(self.pg.n_parts) \
                if self.monitor is not None else None
            plan = plan_rebalance(
                self.pg, target=self.rebalance_target
                if target is None else target, loads=loads)
            if plan.n_moves == 0:
                return None
            rs = execute_rebalance(self.pg, self.ctx, plan,
                                   shape_policy=self.shape_policy)
            self._host_version += 1
            self.stats.rebalances += 1
            # migration deliberately reshaped the per-partition densities:
            # drop the pinned 'auto' assignments so the next query
            # re-consults the policy against the new geometry
            self._auto_pin.clear()
            # migration changes layout (membership moved), never values:
            # joins the pending-remap chain exactly like a compaction
            self._warm_epoch += 1
            self._remap_log.append((self._warm_epoch, rs))
            self._prune_remap_log()
            self._evict_stale_runners()
            if self.monitor is not None:
                self.monitor.notify_rebalanced()
                self.stats.load_imbalance = self.monitor.observe_graph(
                    self.pg)
            return rs
        finally:
            self._rebalancing = False

    def compact(self) -> CompactStats:
        """Evict edge-less members, shrink the padded capacities to the
        session policy's **bucket floor**, and carry every cached warm
        result across the re-layout (global values are layout-independent;
        device blocks move through ``remap_state``). When the compacted
        content still fits the current buckets the padded shapes — and every
        compiled runner — survive untouched."""
        self._check_open()
        if self.ctx is None:
            self._require_buffer("compact()")
        if self.buffer is not None and len(self.buffer):
            self.flush()
        cs = _compact_pg(self.pg, self.ctx, shape_policy=self.shape_policy)
        self._host_version += 1
        self.stats.compactions += 1
        self._auto_pin.clear()     # compaction re-lays the geometry: let
                                   # the next 'auto' query re-resolve
        # compaction changes layout, never values: joins the pending-remap
        # chain like an insert-only flush (applied on each entry's next use)
        self._warm_epoch += 1
        self._remap_log.append((self._warm_epoch, cs))
        self._prune_remap_log()
        self._evict_stale_runners()
        return cs

    def _evict_stale_runners(self) -> None:
        """Drop executables specialized to padded shapes the graph no longer
        has (bucket growth via flush, bucket shrink via compact). Any patch
        that stays inside the current buckets evicts nothing — the whole
        point of the bucketed cache. Pallas runners also check their layout
        capacities: a tile/block cap crossing its bucket stales only the
        runners of that backend, never the COO ones.

        On a shared cache this RELEASES the session's pins rather than
        deleting entries outright: a tenant crossing a bucket must never
        invalidate the runners its same-shaped neighbors still serve from.
        Entries nobody pins anymore are dropped; on a private cache that is
        every stale entry — exactly the old behavior."""
        cur = self.shape_key
        lay = self.pg.edge_layouts
        have_lay = lay is not None and lay.matches(self.pg)

        def lay_key_now(backend, ns):
            # the entry's layout key recomputed against the CURRENT layout
            # at the entry's own shard count; None (can't realize, e.g.
            # e_max no longer divides the shards) means stale
            try:
                return lay.shape_key(backend, n_shards=ns, pg=self.pg)
            except AssertionError:
                return None

        def stale_entry(e):
            base, lkey = e.shape_key
            if base != cur:
                return True
            if lkey is None:
                return False
            if not have_lay:
                return True
            if lkey[0] == "auto":
                _, skey, asg, tk, wk = lkey
                ns = tk[1] if len(tk) == 5 else 1
                if tk != lay_key_now("pallas_tiles", ns) \
                        or wk != lay_key_now("pallas_windows", ns):
                    return True
                # a re-resolved pin that landed on different picks stales
                # the old mixed-backend executable
                pin = self._auto_pin.get(self._pin_key(skey))
                return pin is not None and pin != asg
            ns = lkey[1] if len(lkey) == 5 else 1
            backend = "pallas_tiles" if lkey[0] == "tiles" \
                else "pallas_windows"
            return lkey != lay_key_now(backend, ns)

        released = self._runner_cache.release_stale(self.tenant, stale_entry)
        self.stats.cache_evictions_shape += released
        self._sync_runner_bytes()
        # flush/compact may also have dropped warm entries — release any
        # id-keyed program pins nothing references anymore
        self._prune_keepalive()
        self._identity_blocks = {
            k: v for k, v in self._identity_blocks.items()
            if k[:2] == (self.pg.n_parts, self.pg.v_max)}

    # ------------------------------------------------------------------ #
    # introspection
    # ------------------------------------------------------------------ #
    def cache_info(self) -> list:
        """Snapshot of the compiled-runner cache in LRU order (oldest —
        next to be evicted — first): one dict per entry with the program
        type name, the (padded-shape, layout) key it was specialized to,
        its hit count, what its compilation cost, the estimated device
        bytes it pins (what ``max_runner_bytes`` evicts against), and the
        tenants pinning it (``owners`` — more than one on a pool-shared
        cache)."""
        return self._runner_cache.info()
