"""The benchmark's own Graph500 Kronecker (R-MAT) generator.

Graph500 v3.0, kernel 1 input: ``2**scale`` vertices, ``edge_factor *
2**scale`` sampled edge tuples, quadrant probabilities A, B, C = .57, .19,
.19 (D = .05) at each of ``scale`` bit levels, vertex labels permuted so
that degree does not follow the id. The edge list is made undirected: each
sampled tuple is put in canonical order (lower id first), self-loops and
repeats are dropped (the first sample of a pair keeps its weight), and both
directions are stored with the same weight. Weights are uniform in
``[1, 10)`` (Graph500 draws ``[0, 1)``; the configuration lists the range
under ``assumed``).

The sampling, the permutation and the sort that finds repeats run in one
jitted call on the default device, from ``jax.random.key(seed)``; the host
only drops the marked tuples. Insert batches for the streaming mix are drawn
from the same distribution and the same label permutation with numpy, on a
separate seed stream (``rmat_batch``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

A, B, C = 0.57, 0.19, 0.19


def _quadrant_thresholds(a: float, b: float, c: float):
    ab = a + b
    return ab, c / (1.0 - ab), a / ab


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _sample_on_device(key, scale: int, n_edges: int, a: float, b: float,
                      c: float):
    ab, c_norm, a_norm = _quadrant_thresholds(a, b, c)
    k_bits, k_perm, k_w = jax.random.split(key, 3)

    def bit_level(bit, carry):
        src, dst = carry
        r = jax.random.uniform(jax.random.fold_in(k_bits, bit),
                               (2, n_edges))
        ii = r[0] > ab
        jj = r[1] > jnp.where(ii, c_norm, a_norm)
        return (src | (ii.astype(jnp.int32) << bit),
                dst | (jj.astype(jnp.int32) << bit))

    zeros = jnp.zeros((n_edges,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, bit_level, (zeros, zeros))
    perm = jax.random.permutation(k_perm, 1 << scale).astype(jnp.int32)
    src, dst = perm[src], perm[dst]
    w = jax.random.uniform(k_w, (n_edges,), jnp.float32, 1.0, 10.0)
    lo, hi = jnp.minimum(src, dst), jnp.maximum(src, dst)
    lo, hi, w = jax.lax.sort((lo, hi, w), num_keys=2, is_stable=True)
    first = jnp.concatenate([jnp.ones((1,), bool),
                             (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])])
    return lo, hi, w, first & (lo != hi), perm


def kronecker_edges(scale: int, edge_factor: int, seed: int):
    """``(n_vertices, lo, hi, w, perm)``: the undirected edges once each
    (``lo < hi``, int64 on the host) with their float32 weights, and the
    label permutation the sample used."""
    n_edges = edge_factor << scale
    out = _sample_on_device(jax.random.key(seed), scale, n_edges, A, B, C)
    lo, hi, w, keep, perm = jax.device_get(out)
    return (1 << scale, lo[keep].astype(np.int64), hi[keep].astype(np.int64),
            w[keep], perm.astype(np.int64))


def both_directions(lo, hi, w):
    """The directed edge list of an undirected one: each edge both ways."""
    return np.concatenate([lo, hi]), np.concatenate([hi, lo]), \
        np.concatenate([w, w])


def rmat_batch(rng: np.random.Generator, scale: int, perm: np.ndarray,
               n_edges: int):
    """``n_edges`` undirected R-MAT edges (no self-loops) under the graph's
    label permutation ``perm``, with uniform ``[1, 10)`` weights: what a
    stream of inserts from the same distribution adds. A pair may repeat
    an edge the graph already holds."""
    ab, c_norm, a_norm = _quadrant_thresholds(A, B, C)
    us, vs = [], []
    have = 0
    while have < n_edges:
        m = 2 * (n_edges - have) + 16
        r = rng.random((scale, 2, m))
        ii = r[:, 0] > ab
        jj = r[:, 1] > np.where(ii, c_norm, a_norm)
        bits = np.int64(1) << np.arange(scale, dtype=np.int64)[:, None]
        u = perm[(ii * bits).sum(0)]
        v = perm[(jj * bits).sum(0)]
        keep = u != v
        us.append(u[keep])
        vs.append(v[keep])
        have += int(keep.sum())
    u = np.concatenate(us)[:n_edges]
    v = np.concatenate(vs)[:n_edges]
    w = rng.uniform(1.0, 10.0, n_edges).astype(np.float32)
    return u, v, w
