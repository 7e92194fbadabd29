"""Partitioning + execution metrics (paper §6.2).

Partitioning metrics:
  - Imbalance         = max_i |E_i| / (|E| / n)
  - Replication Factor = sum_i |V_i| / |V|

Execution metrics (gathered by the engine): supersteps, network messages
((key,value) pairs, i.e. changed frontier slots per superstep), bytes moved,
PEPS (processed edges per second, paper Fig 9). The per-phase time split
(sweep, exchange, ...) comes from a profile: the engine's superstep phases
carry named scopes (``repro.obs``, docs/SERVING.md "Tracing a session").
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.subgraph import PartitionedGraph

__all__ = ["PartitionMetrics", "partition_metrics", "ExecutionStats"]


@dataclasses.dataclass
class PartitionMetrics:
    n_parts: int
    imbalance: float
    replication_factor: float
    edges_per_part_max: int
    edges_per_part_min: int
    n_frontier: int
    master_balance: float  # max masters per part / mean (SBS aggregation balance)

    def __str__(self):
        return (f"P={self.n_parts} imbalance={self.imbalance:.4f} "
                f"RF={self.replication_factor:.4f} frontier={self.n_frontier} "
                f"master_balance={self.master_balance:.3f}")


def partition_metrics(pg: PartitionedGraph) -> PartitionMetrics:
    epp = pg.edges_per_part
    vpp = pg.vertices_per_part
    masters = (pg.is_master & pg.vmask & (pg.slot < pg.n_slots)).sum(axis=1)
    mmean = masters.mean() if pg.n_slots else 1.0
    return PartitionMetrics(
        n_parts=pg.n_parts,
        imbalance=float(epp.max() / max(epp.mean(), 1e-12)),
        replication_factor=float(vpp.sum() / max(pg.n_vertices, 1)),
        edges_per_part_max=int(epp.max()),
        edges_per_part_min=int(epp.min()),
        n_frontier=pg.n_slots,
        master_balance=float(masters.max() / max(mmean, 1e-12)) if pg.n_slots else 1.0,
    )


@dataclasses.dataclass
class ExecutionStats:
    """Filled in by the engine; one entry per superstep when tracing."""
    supersteps: int = 0
    total_messages: int = 0            # changed (key,value) pairs, paper metric
    total_bytes: int = 0               # dense SBS buffer bytes actually reduced
    messages_per_step: list = dataclasses.field(default_factory=list)
    active_parts_per_step: list = dataclasses.field(default_factory=list)
    wall_time: float = 0.0             # execution only — compile billed apart
    compile_time: float = 0.0          # trace+compile on a GraphSession
                                       # runner-cache miss; 0.0 on a hit, so
                                       # steady-state serving latency is
                                       # wall_time alone (one-shot run_* pay
                                       # trace cost inside wall_time as ever)
    evicted_runners: int = 0           # LRU evictions this query's cache
                                       # admission forced (GraphSession only)
    processed_edges: int = 0
    edge_backend: str = "coo"          # which edge-compute backend ran the
                                       # local sweeps ('coo' also for
                                       # programs without a SemiringSweep)
    backend_flops: int = 0             # semiring ops the backend issued:
                                       # 2*K per resident edge on COO; the
                                       # dense tile/block work (identity
                                       # padding included) on Pallas
    tile_density: float = 0.0          # non-identity fraction of the real
                                       # tiles ('pallas_tiles' only): the
                                       # MXU utilization of the dense path
                                       # — low density says use windows/COO
    queue_time: float = 0.0            # admission-queue dwell before launch
                                       # (serving/batcher.py fills it in)
    batch_size: int = 1                # lanes in the micro-batched launch
                                       # that served this query (1 = a
                                       # singleton launch)
    result_cache_tier: str = ""        # '' when no result cache consulted;
                                       # 'l1'/'l2' when the converged result
                                       # was served without a device launch,
                                       # 'miss' when it ran and was stored
    # Per-partition (per-shard) load gauges — the LoadMonitor's measured-
    # work inputs, and independently useful in benchmark tables. Empty
    # lists when the run path did not fill them (result-cache hits, trace
    # mode).
    partition_edge_counts: list = dataclasses.field(default_factory=list)
    partition_flops: list = dataclasses.field(default_factory=list)
                                       # backend_flops split per shard:
                                       # sweeps[p] * flops-per-sweep[p]
    partition_sweep_time: list = dataclasses.field(default_factory=list)
                                       # wall_time apportioned by each
                                       # shard's flops share — the realized
                                       # per-shard sweep-time estimate
    partition_tile_density: list = dataclasses.field(default_factory=list)
                                       # per-partition non-identity tile
                                       # fraction — the auto policy's input
                                       # (filled on pallas_tiles and auto)
    partition_edge_backends: list = dataclasses.field(default_factory=list)
                                       # edge_backend='auto' only: the
                                       # resolved concrete backend billed to
                                       # each partition this run

    @property
    def peps(self) -> float:
        """Actual processed edges per second (paper §8.5, [25])."""
        return self.processed_edges / self.wall_time if self.wall_time else 0.0

    @property
    def total_time(self) -> float:
        """wall_time + compile_time — what the first (cold) query costs."""
        return self.wall_time + self.compile_time
