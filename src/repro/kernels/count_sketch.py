"""Pallas TPU kernel: Count-Sketch apply  A_tilde_k = S_k^T A  for K blocks.

TPU adaptation (see DESIGN.md §2): Count-Sketch is a scatter-add on CPUs/GPUs;
TPUs have no efficient scatter but a 128x128 systolic MXU.  We therefore
materialize, per (row-tile, sketch-block), the signed one-hot bucket matrix
``O[r, c] = sigma_r * 1{h_r == c}`` in VMEM via ``broadcasted_iota`` and
compute ``A_tilde_k += O^T @ A_tile`` as an MXU matmul.  Arithmetic intensity
rises from O(1) (scatter) to O(b) and the op becomes MXU-bound.

Grid: (K, d_tiles, n_tiles) with the n (reduction) dimension innermost so each
(K, d_tile) output block stays resident in VMEM across its accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_TILE_N = 256
DEFAULT_TILE_D = 256


def _kernel(h_ref, sigma_ref, a_ref, out_ref, *, block_size: int,
            n_rows: int):
    i = pl.program_id(2)  # innermost: reduction over row tiles

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    h = h_ref[0]                          # (1, tn) int32 lane row
    sigma = sigma_ref[0]                  # (1, tn)
    a = a_ref[...]                        # (tn, td)
    tn = h.shape[1]
    if n_rows % tn:
        # The last row panel overhangs A (A is not padded in HBM): zero
        # the rows past n, whose contents are undefined (0 * NaN != 0).
        row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0) + i * tn
        a = jnp.where(row < n_rows, a, 0.0).astype(a.dtype)
    # Signed one-hot bucket matrix in VMEM, transposed: (b, tn).
    iota = jax.lax.broadcasted_iota(jnp.int32, (block_size, tn), 0)
    onehot = jnp.where(h == iota, sigma, 0.0).astype(a.dtype)
    # MXU: (b, tn) @ (tn, td) -> (b, td)
    out_ref[0] += jnp.dot(onehot, a, preferred_element_type=out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_size", "tile_n", "tile_d",
                                             "interpret"))
def count_sketch_apply(h: jax.Array, sigma: jax.Array, a: jax.Array,
                       block_size: int, *, tile_n: int = DEFAULT_TILE_N,
                       tile_d: int = DEFAULT_TILE_D,
                       interpret: bool = False) -> jax.Array:
    """(K, n) x (K, n) x (n, d) -> (K, block_size, d).

    Only the (K, n) bucket/sign rows are padded to the row tile (padded
    rows get sigma 0, bucket 0); A's overhanging edge panels are read in
    place, their rows past n zeroed in-kernel and their columns past d
    sliced off the output, so no padded copy of A is made."""
    k, n = h.shape
    d = a.shape[1]
    tn = min(tile_n, max(8, n))
    td = min(tile_d, max(128, d))
    n_pad = (-n) % tn
    d_pad = (-d) % td
    if n_pad:
        h = jnp.pad(h, ((0, 0), (0, n_pad)))
        sigma = jnp.pad(sigma, ((0, 0), (0, n_pad)))
    n_t, d_t = (n + n_pad) // tn, (d + d_pad) // td

    # h/sigma ride as (K, 1, n) so each block is a (1, tn) lane row, which
    # meets the TPU's (8, 128) block rule (the unit sublane dim is whole).
    out = pl.pallas_call(
        functools.partial(_kernel, block_size=block_size, n_rows=n),
        grid=(k, d_t, n_t),
        in_specs=[
            pl.BlockSpec((1, 1, tn), lambda kk, j, i: (kk, 0, i)),
            pl.BlockSpec((1, 1, tn), lambda kk, j, i: (kk, 0, i)),
            pl.BlockSpec((tn, td), lambda kk, j, i: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, block_size, td), lambda kk, j, i: (kk, 0, j)),
        out_shape=jax.ShapeDtypeStruct((k, block_size, d + d_pad),
                                       jnp.float32),
        interpret=interpret,
    )(h[:, None, :], sigma.astype(jnp.float32)[:, None, :],
      a.astype(jnp.float32))
    return out[:, :, :d]
