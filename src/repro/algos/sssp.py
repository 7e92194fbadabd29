"""Single-Source Shortest Path (paper §7.1).

The paper keeps sequential Dijkstra inside each subgraph. A priority queue is
hostile to a vector unit, so the TPU-native local solver is Bellman–Ford
iterated to the partition-local fixed point (min-plus semiring sweeps) — the
superstep/communication behaviour is identical to the paper's SC model
(distances propagate arbitrarily far inside a partition per superstep), and
the SBS Aggregate operator is ``min``, as in the paper. A partition without
interior paths sweeps once a superstep instead (``engine.local_bound``).

Weights must be non-negative. Distances are float32.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.core.api import DeviceSubgraph, SemiringSweep, VertexProgram

INF = jnp.float32(jnp.inf)


@dataclasses.dataclass
class SSSP(VertexProgram):
    combiner: str = "min"
    payload: int = 1
    dtype: object = jnp.float32
    delta_based: bool = False
    monotone: bool = True          # distances only tighten -> warm-startable
    value_key: str = "dist"

    # declarative sweep: min-plus relax over the edge weights; the engine
    # routes the product through the configured edge-compute backend
    sweep_spec = SemiringSweep("min_plus", "weight")

    def init(self, sg: DeviceSubgraph, params, ec):
        src = params["source"]  # global vertex id (replicated scalar)
        dist = jnp.where(sg.vid32 == src, 0.0, INF).astype(jnp.float32)
        return {"dist": jnp.where(sg.vmask, dist, INF)}

    def apply_frontier(self, sg, params, state, merged, ec):
        m = merged[:, 0]
        new = jnp.where(sg.frontier, jnp.minimum(state["dist"], m),
                        state["dist"])
        changed = jnp.sum(new < state["dist"], dtype=jnp.int32)
        return {"dist": new}, changed

    def sweep_values(self, sg, params, state):
        return state["dist"]

    def sweep_fold(self, sg, params, state, agg):
        d = state["dist"]
        new = jnp.where(sg.vmask, jnp.minimum(d, agg), d)
        changed = jnp.sum(new < d, dtype=jnp.int32)
        return {"dist": new}, changed

    def frontier_out(self, sg, params, state):
        return state["dist"][:, None]

    def result(self, sg, params, state):
        return state["dist"]
