"""Windowed one-hot segment combine — general-case SpMV reduce as a Pallas
TPU kernel (DESIGN.md §5).

The XLA fallback for the SVHM sweep's reduce-by-destination is a scatter,
which serializes badly on TPU. This kernel instead processes *edge blocks*
whose destinations are confined to one 128-row output window (a layout
produced by ``ops.window_align_edges`` — edges sorted by dst, padded per
window to a multiple of the block size, empty windows given one identity
block). No dynamic gather/scatter is needed inside the kernel:

  onehot[w, e] = (local_dst[e] == w)        # iota compare, VPU
  sum:  out_window += sum_e where(onehot, msgs, 0)
  min:  out_window = min(out_window, min_e where(onehot, msgs, +inf))

Inside the kernel a block's ``Be`` edges are laid out as ``[Be/128, 128]``
rows of lanes, so no operand has a unit minor or second-minor dim: Mosaic
refuses such a block, and XLA would pad each unit row to 8 in HBM.

Scalar-prefetched ``block_window[b]`` routes each edge block to its output
window; consecutive blocks of the same window accumulate in VMEM.

Dtype: the kernel computes in ``msgs.dtype``. ``sum`` requires a float
dtype; ``min``/``max`` work on any ordered dtype, with the identity
taken from ``ref.combine_identity`` (int32 min-combine pads with
``iinfo(int32).max``). ``interpret=None`` auto-selects compiled-on-TPU /
interpret-elsewhere, overridable per call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bsp_spmv import default_interpret
from repro.kernels.ref import combine_identity

W = 128       # output rows per window
LANES = 128   # edges per row of a block as the kernel sees it


def _kernel(block_window_ref, msgs_ref, ldst_ref, out_ref, *, combiner: str):
    b = pl.program_id(0)
    prev = block_window_ref[jnp.maximum(b - 1, 0)]
    first = (b == 0) | (block_window_ref[b] != prev)

    ldst = ldst_ref[0]                                   # [R, 128]
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (W,) + ldst.shape, 0)
              == ldst[None])                             # [W, R, 128]
    K = msgs_ref.shape[1]
    if combiner == "sum":
        ident, red, comb = 0, jnp.sum, jnp.add
    else:
        ident = combine_identity(combiner, msgs_ref.dtype)
        red = jnp.min if combiner == "min" else jnp.max
        comb = jnp.minimum if combiner == "min" else jnp.maximum
    # one [W, 1] column per payload lane: reduce the block's edges (lanes,
    # then rows) under each window row's one-hot mask
    cols = []
    for k in range(K):
        cand = jnp.where(onehot, msgs_ref[0, k][None], ident)
        cols.append(red(red(cand, axis=2), axis=1, keepdims=True))

    @pl.when(first)
    def _init():
        for k, c in enumerate(cols):
            out_ref[0, :, k:k + 1] = c.astype(out_ref.dtype)

    @pl.when(jnp.logical_not(first))
    def _acc():
        for k, c in enumerate(cols):
            out_ref[0, :, k:k + 1] = comb(out_ref[0, :, k:k + 1],
                                          c.astype(out_ref.dtype))


@functools.partial(jax.jit, static_argnames=("n_windows", "combiner",
                                             "interpret"))
def segment_combine_windowed(msgs, local_dst, block_window, *, n_windows: int,
                             combiner: str = "sum", interpret=None):
    """msgs [B*Be, K] (identity-padded), local_dst [B*Be] i32 in [0, W),
    block_window [B] i32 sorted ascending covering every window
    ->  [n_windows, W, K] in msgs.dtype. ``Be`` must be a multiple of 128.
    The prefetched ``block_window`` lives in SMEM (1 MiB on a v5e), which
    bounds one launch to about 2**18 blocks."""
    if interpret is None:
        interpret = default_interpret()
    if combiner == "sum" and not jnp.issubdtype(msgs.dtype, jnp.floating):
        raise ValueError(
            f"sum-combine needs a float dtype, got {msgs.dtype}; min/max "
            f"are the integer-friendly combiners")
    B = block_window.shape[0]
    Be = msgs.shape[0] // B
    K = msgs.shape[-1]
    assert Be % LANES == 0, f"block of {Be} edges is not a multiple of 128"
    R = Be // LANES
    # a block's edges as [R, 128] (payload lanes leading): every block dim
    # Mosaic tiles is then lane-dense — a unit second-minor dim would pad
    # each edge row 8x in HBM and is refused as a block shape anyway
    msgs = msgs.reshape(B, Be, K).transpose(0, 2, 1).reshape(B, K, R, LANES)
    local_dst = local_dst.reshape(B, R, LANES)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, K, R, LANES), lambda b, bw: (b, 0, 0, 0)),
            pl.BlockSpec((1, R, LANES), lambda b, bw: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, W, K), lambda b, bw: (bw[b], 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, combiner=combiner),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_windows, W, K), msgs.dtype),
        interpret=interpret,
    )(block_window, msgs, local_dst)
