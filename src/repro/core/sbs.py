"""Subgraph Boundary Synchronization (paper §4.3) — TPU-native.

The paper's SBS routes (key,value) pairs mirror->master, Aggregates with a
user combiner, then Disseminates master->mirrors. On a TPU mesh this entire
protocol *is* an all-reduce with that combiner over a dense frontier-slot
vector (DESIGN.md §2): the reduction tree takes the role of the master (the
paper itself notes masters are "randomly elected ... aggregation workload is
evenly distributed", i.e. a balanced reduction).

Two exchange contexts share one scatter/gather implementation:

  - ``SimExchange``    — single-process simulator: the per-partition buffers
    are stacked on a leading P axis and reduced with jnp over axis 0.
  - ``ShardExchange``  — shard_map backend: each device holds its own stack
    of one partition's buffer; it is reduced, then combined with
    ``jax.lax.psum/pmin/pmax`` over the subgraph mesh axes (pod, data).

The engine's one superstep (``engine._make_superstep``) calls
``combine_stack`` and ``all_sum_scalar`` of either on its stacked block.

A sparse compacted exchange (``compact_exchange``) is provided as the
beyond-paper optimization for frontier-sparse supersteps: the changed slots
are compacted to the top-C (idx, val) pairs and all-gathered, cutting
collective bytes when #changed << n_slots (see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp

__all__ = ["scatter_combine", "gather_merged", "reduce_stack", "SimExchange",
           "ShardExchange", "compact_allgather_exchange"]


def scatter_combine(out, slot, vmask, n_slots: int, combiner: str, identity):
    """[v_max, K] contributions -> [n_slots + 1, K] partition-local buffer.

    Row ``n_slots`` is the dump row for non-frontier vertices.
    """
    k = out.shape[-1]
    mask = vmask[:, None]
    contrib = jnp.where(mask, out, identity)
    buf = jnp.full((n_slots + 1, k), identity, dtype=out.dtype)
    if combiner == "min":
        return buf.at[slot].min(contrib, mode="drop")
    if combiner == "max":
        return buf.at[slot].max(contrib, mode="drop")
    if combiner == "sum":
        return buf.at[slot].add(contrib, mode="drop")
    raise ValueError(combiner)


def gather_merged(buf, slot):
    """[n_slots + 1, K] merged buffer -> [v_max, K] per-vertex view
    (identity-valued dump row lands on non-frontier vertices)."""
    return buf[slot]


def reduce_stack(bufs: jnp.ndarray, combiner: str) -> jnp.ndarray:
    """Combine stacked buffers [p, n_slots+1, K] over the leading axis."""
    if combiner == "min":
        return jnp.min(bufs, axis=0)
    if combiner == "max":
        return jnp.max(bufs, axis=0)
    if combiner == "sum":
        return jnp.sum(bufs, axis=0)
    raise ValueError(combiner)


@dataclasses.dataclass(frozen=True)
class SimExchange:
    """Reduce stacked buffers [P, n_slots+1, K] over axis 0."""

    def all_combine(self, bufs: jnp.ndarray, combiner: str) -> jnp.ndarray:
        return reduce_stack(bufs, combiner)

    def combine_stack(self, bufs: jnp.ndarray, combiner: str) -> jnp.ndarray:
        """The engine's exchange: every partition is in the stack."""
        return self.all_combine(bufs, combiner)

    def all_sum_scalar(self, x):
        """Sum of a [P] per-partition vector."""
        return jnp.sum(x, axis=0)


@dataclasses.dataclass(frozen=True)
class ShardExchange:
    """lax collectives over the subgraph mesh axes (inside shard_map)."""

    axis_names: Sequence[str]

    def all_combine(self, buf: jnp.ndarray, combiner: str) -> jnp.ndarray:
        ax = tuple(self.axis_names)
        if combiner == "min":
            return jax.lax.pmin(buf, ax)
        if combiner == "max":
            return jax.lax.pmax(buf, ax)
        if combiner == "sum":
            return jax.lax.psum(buf, ax)
        raise ValueError(combiner)

    def combine_stack(self, bufs: jnp.ndarray, combiner: str) -> jnp.ndarray:
        """The engine's exchange: the device's local stack [p, n_slots+1,
        K] reduced, then combined across the devices."""
        return self.all_combine(reduce_stack(bufs, combiner), combiner)

    def all_sum_scalar(self, x):
        """Sum of the local [p] per-partition vector over every device."""
        return jax.lax.psum(jnp.sum(x, axis=0), tuple(self.axis_names))


# --------------------------------------------------------------------------- #
# Beyond-paper: compacted sparse exchange
# --------------------------------------------------------------------------- #
@partial(jax.jit, static_argnames=("capacity", "combiner", "n_slots"))
def _compact_local(buf, changed_slots_mask, *, capacity: int, combiner: str,
                   n_slots: int):
    """Select up to ``capacity`` changed slots into (idx, val) pairs."""
    scores = changed_slots_mask.astype(jnp.int32)
    idx = jnp.argsort(-scores)[:capacity]
    valid = scores[idx] > 0
    idx = jnp.where(valid, idx, n_slots)
    return idx.astype(jnp.int32), buf[idx]


def compact_allgather_exchange(buf, identity, combiner: str, n_slots: int,
                               capacity: int, axis_names):
    """All-gather compacted (idx, val) pairs and re-combine locally.

    Collective bytes: P * capacity * (4 + K*itemsize) instead of
    n_slots * K * itemsize * ring-factor — a win when the active frontier is
    small (late CC/SSSP supersteps). Falls back to correctness (not volume)
    when capacity < #changed is violated by the caller's capacity policy.
    """
    changed = jnp.any(buf[:-1] != identity, axis=-1)
    idx, vals = _compact_local(buf, changed, capacity=capacity,
                               combiner=combiner, n_slots=n_slots)
    all_idx = jax.lax.all_gather(idx, axis_names, tiled=True)     # [P*C]
    all_vals = jax.lax.all_gather(vals, axis_names, tiled=True)   # [P*C, K]
    merged = jnp.full_like(buf, identity)
    if combiner == "min":
        merged = merged.at[all_idx].min(all_vals, mode="drop")
    elif combiner == "max":
        merged = merged.at[all_idx].max(all_vals, mode="drop")
    else:
        merged = merged.at[all_idx].add(all_vals, mode="drop")
    merged = merged.at[n_slots].set(identity)
    return merged
