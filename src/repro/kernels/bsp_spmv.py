"""Block-sparse semiring SpMV — the SVHM local-sweep hot loop as a Pallas TPU
kernel (DESIGN.md §5).

TPU adaptation of the paper's per-subgraph sequential relaxation: the
partition's adjacency is decomposed into dense (tm x tn) = (128 x 128) tiles
listed in *dst-major* order. The kernel walks the tile list with
scalar-prefetched (tile_dst, tile_src) routing arrays
(``pltpu.PrefetchScalarGridSpec``): BlockSpec index maps pull the right value
block per tile, the output block stays resident in VMEM while consecutive
grid steps visit tiles of the same dst row (revisit-accumulate pattern,
``@pl.when`` on the first visit), and

  - ``plus_times`` rides the MXU: tile @ vals_block  (128x128 @ 128xK)
  - ``min_plus``   rides the VPU: min over src of (tile + vals)

Requirements (enforced by ``ops.build_tiles`` and ``core.layouts``):
  - tile list sorted by (tile_dst, tile_src); every dst tile row appears at
    least once (identity filler tiles), so every output block is initialized;
  - tiles dense with the semiring's absorbing pad (0 / +inf / INT_MAX).

Dtype: the kernel computes in the dtype of ``tiles``/``vals`` (they must
agree). ``min_plus`` supports any ordered dtype — float32 for SSSP
distances, int32 for CC min-label propagation (whose identity is
``iinfo(int32).max``, not +inf). ``plus_times`` requires a float dtype (the
MXU path accumulates through ``preferred_element_type``).

``interpret=None`` (the default) auto-selects: compiled on TPU, interpret
mode everywhere else (``ops.default_interpret``) — overridable per call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TM = 128   # dst rows per tile (MXU-aligned)
TN = 128   # src cols per tile


def default_interpret() -> bool:
    """Pallas interpret mode unless we are actually on a TPU backend."""
    return jax.default_backend() != "tpu"


def _kernel(tile_dst_ref, tile_src_ref, tiles_ref, vals_ref, out_ref, *,
            semiring: str):
    i = pl.program_id(0)
    prev = tile_dst_ref[jnp.maximum(i - 1, 0)]
    first = (i == 0) | (tile_dst_ref[i] != prev)

    t = tiles_ref[0]                                     # [TM, TN]
    v = vals_ref[0]                                      # [TN, K]

    if semiring == "plus_times":
        # full f32 precision: the MXU's default single bf16 pass leaves
        # ~1e-3 relative error, which PageRank on the chip showed
        part = jnp.dot(t, v, preferred_element_type=v.dtype,
                       precision=jax.lax.Precision.HIGHEST)          # MXU

        @pl.when(first)
        def _init():
            out_ref[0] = part

        @pl.when(jnp.logical_not(first))
        def _acc():
            out_ref[0] += part
    else:  # min_plus
        cand = t[:, :, None] + v[None, :, :]             # [TM, TN, K]
        part = jnp.min(cand, axis=1)                     # [TM, K]

        @pl.when(first)
        def _init():
            out_ref[0] = part

        @pl.when(jnp.logical_not(first))
        def _acc():
            out_ref[0] = jnp.minimum(out_ref[0], part)


@functools.partial(jax.jit, static_argnames=("n_dst_tiles", "semiring",
                                             "interpret"))
def bsp_spmv(tiles, tile_dst, tile_src, vals, *, n_dst_tiles: int,
             semiring: str = "plus_times", interpret=None):
    """tiles [T,TM,TN], tile_dst/src [T] i32 (dst-major sorted),
    vals [n_src_tiles, TN, K]  ->  [n_dst_tiles, TM, K] (dtype of vals)."""
    if interpret is None:
        interpret = default_interpret()
    T, tm, tn = tiles.shape
    K = vals.shape[-1]
    assert (tm, tn) == (TM, TN)
    assert tiles.dtype == vals.dtype, (tiles.dtype, vals.dtype)
    if semiring == "plus_times" and not jnp.issubdtype(vals.dtype,
                                                       jnp.floating):
        raise ValueError(
            f"plus_times rides the MXU and needs a float dtype, got "
            f"{vals.dtype}; min_plus is the integer-friendly semiring")

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, TM, TN), lambda i, td, ts: (i, 0, 0)),
            pl.BlockSpec((1, TN, K), lambda i, td, ts: (ts[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, TM, K), lambda i, td, ts: (td[i], 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, semiring=semiring),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_dst_tiles, TM, K), vals.dtype),
        interpret=interpret,
    )(tile_dst, tile_src, tiles, vals)
