"""Pallas TPU kernel: coded block mat-vec (paper Alg. 1 worker compute).

Each coded row-block (systematic or parity) is multiplied with the replicated
vector; the straggler-erasure mask is fused so erased workers never write.
This is memory-bound (one pass over the encoded matrix); the kernel's job is
to keep it at streaming bandwidth with VMEM-tiled row blocks and to avoid a
separate masking pass over the output.

Grid: (W, s_tiles) with the reduction over the vector innermost.

``parity_residuals`` is the kernel's master-side companion: one fused
masked pass over the (g+1, g+1, b) product grid computing every row/column
single-parity-check residual at once — the corruption detector's inner
loop (``core.coded.detect_corrupted``), kept here with the worker kernel
because both are the per-phase hot path over the same coded layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_TILE_S = 512


def _kernel(er_ref, enc_ref, x_ref, out_ref, *, s_cols: int):
    w = pl.program_id(0)
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    keep = (1 - er_ref[w]).astype(out_ref.dtype)
    enc = enc_ref[0]                     # (b, ts)
    x = x_ref[...]                       # (1, ts)
    if s_cols % enc.shape[1]:
        # The last column tile overhangs enc (not padded in HBM): zero its
        # columns past s, whose contents are undefined (0 * NaN != 0).
        col = jax.lax.broadcasted_iota(jnp.int32, enc.shape, 1) \
            + s * enc.shape[1]
        enc = jnp.where(col < s_cols, enc, 0.0)
    # (1, ts) . (b, ts)^T -> (1, b): the block product as a lane row.
    out_ref[0] += keep * jax.lax.dot_general(
        x, enc, (((1,), (1,)), ((), ())),
        preferred_element_type=out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_s", "interpret"))
def coded_block_matvec(enc: jax.Array, x: jax.Array, erased: jax.Array, *,
                       tile_s: int = DEFAULT_TILE_S,
                       interpret: bool = False) -> jax.Array:
    """(W, b, s) x (s,) x (W,) bool -> (W, b) masked block products."""
    w, b, s = enc.shape
    ts = min(tile_s, max(128, s))
    s_pad = (-s) % ts
    if s_pad:
        # Only x is padded; enc's overhanging tile is masked in-kernel, so
        # no padded copy of the encoded matrix is made.
        x = jnp.pad(x, (0, s_pad))
    st = (s + s_pad) // ts

    # The erasure mask is one whole (W,) int32 array in SMEM; x is a
    # (1, s) lane row and each worker writes a (1, b) row of (W, 1, b),
    # so every VMEM block meets the TPU's (8, 128) rule.
    out = pl.pallas_call(
        functools.partial(_kernel, s_cols=s),
        grid=(w, st),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, b, ts), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, ts), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, b), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((w, 1, b), jnp.float32),
        interpret=interpret,
    )(erased.astype(jnp.int32), enc.astype(jnp.float32),
      x.astype(jnp.float32)[None, :])
    return out[:, 0, :]


@jax.jit
def parity_residuals(products: jax.Array, known: jax.Array):
    """Per-line parity-check residuals of a coded product grid.

    products: ((g+1), (g+1), b) block products (erased cells arbitrary);
    known: ((g+1), (g+1)) bool arrival mask.  Every row and column of the
    extended grid satisfies sum(systematic) - parity = 0, so over known
    cells the signed line sums are exact-zero residual vectors unless a
    known cell's value is corrupted.  Returns ``(row_res, row_mag,
    col_res, col_mag)``: the L2 residual of each line's constraint and
    the L2 magnitude of the line's known values (the relative-tolerance
    scale).  Unknown cells contribute zero to both, so the caller must
    gate on line completeness — a line with a missing cell has no
    checkable constraint.
    """
    n = products.shape[0]
    sgn = jnp.where(jnp.arange(n) == n - 1, -1.0, 1.0)
    vals = jnp.where(known[..., None], products, 0.0).astype(jnp.float32)
    row_res = jnp.linalg.norm(jnp.einsum("c,rcb->rb", sgn, vals), axis=-1)
    col_res = jnp.linalg.norm(jnp.einsum("r,rcb->cb", sgn, vals), axis=-1)
    row_mag = jnp.sqrt((vals ** 2).sum(axis=(1, 2)))
    col_mag = jnp.sqrt((vals ** 2).sum(axis=(0, 2)))
    return row_res, row_mag, col_res, col_mag
