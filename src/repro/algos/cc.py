"""Connected Components (paper §5.1 Algorithm 1, §5.2 Fig. 3).

Label propagation: every vertex starts labelled with its own global id; one
local sweep takes the min label over in-neighbours (the graph must be stored
undirected, i.e. both edge directions present, so this is symmetric). The
engine iterates sweeps to the partition-local fixed point — the vectorized
equivalent of the paper's ``SequentialCC`` per subgraph; once a superstep
on a partition without interior paths, ``engine.local_bound`` — and SBS
merges frontier labels with ``min`` (the paper's Aggregate operator for
CC).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.core.api import DeviceSubgraph, SemiringSweep, VertexProgram

_IMAX = 2**31 - 1


@dataclasses.dataclass
class ConnectedComponents(VertexProgram):
    combiner: str = "min"
    payload: int = 1
    dtype: object = jnp.int32
    delta_based: bool = False
    monotone: bool = True       # labels only decrease -> warm-startable
    value_key: str = "label"

    # min-plus over zero-valued edges == min-label propagation; int32 all
    # the way through every backend (the Pallas kernels honor the dtype)
    sweep_spec = SemiringSweep("min_plus", "zero")

    def init(self, sg: DeviceSubgraph, params, ec):
        return {"label": jnp.where(sg.vmask, sg.vid32, _IMAX)}

    def apply_frontier(self, sg, params, state, merged, ec):
        m = merged[:, 0]
        new = jnp.where(sg.frontier, jnp.minimum(state["label"], m),
                        state["label"])
        changed = jnp.sum(new < state["label"], dtype=jnp.int32)
        return {"label": new}, changed

    def sweep_values(self, sg, params, state):
        return state["label"]

    def sweep_fold(self, sg, params, state, agg):
        lab = state["label"]
        new = jnp.where(sg.vmask, jnp.minimum(lab, agg), lab)
        changed = jnp.sum(new < lab, dtype=jnp.int32)
        return {"label": new}, changed

    def frontier_out(self, sg, params, state):
        return state["label"][:, None]

    def result(self, sg, params, state):
        return state["label"]
