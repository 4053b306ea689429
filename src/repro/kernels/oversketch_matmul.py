"""Pallas TPU kernel: straggler-masked blocked Gram accumulation
``G = sum_k m_k * A_tilde_k^T @ A_tilde_k`` (OverSketch computation+reduction
phases, paper Alg. 2 steps 3-5, fused).

The survivor mask is applied *inside* the accumulation loop, so a straggling
block's contribution is never read from HBM into the MXU — on real hardware
the mask also gates the DMA.  The caller divides by the survivor count
(keeping the kernel a pure masked sum keeps it reusable for the distributed
resilient-psum path, where the rescale happens after the cross-chip
reduction).

Grid: (d_i, d_j, K*b_tiles) with the fused (block, row-tile) reduction
innermost so each (d_i, d_j) output tile accumulates in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_TILE_D = 256
DEFAULT_TILE_B = 256


def _kernel(mask_ref, ai_ref, aj_ref, out_ref, *, b_tiles: int):
    r = pl.program_id(2)

    @pl.when(r == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    m = mask_ref[r // b_tiles]            # scalar mask for this sketch block
    ai = m * ai_ref[0]                    # (tb, tdi)
    aj = aj_ref[0]                        # (tb, tdj)
    out_ref[...] += jax.lax.dot_general(
        ai, aj, (((0,), (0,)), ((), ())),
        preferred_element_type=out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_d", "tile_b", "interpret"))
def oversketch_gram(a_tilde: jax.Array, survivors: jax.Array, *,
                    tile_d: int = DEFAULT_TILE_D,
                    tile_b: int = DEFAULT_TILE_B,
                    interpret: bool = False) -> jax.Array:
    """(K, b, d) x (K,) bool -> (d, d) masked Gram / survivor count."""
    k, b, d = a_tilde.shape
    tb = min(tile_b, max(8, b))
    td = min(tile_d, max(128, d))
    b_pad, d_pad = (-b) % tb, (-d) % td
    if b_pad or d_pad:
        a_tilde = jnp.pad(a_tilde, ((0, 0), (0, b_pad), (0, d_pad)))
    bt, dt = (b + b_pad) // tb, (d + d_pad) // td
    mask = survivors.astype(jnp.float32)

    out = pl.pallas_call(
        functools.partial(_kernel, b_tiles=bt),
        grid=(dt, dt, k * bt),
        in_specs=[
            # The (K,) survivor mask is one whole SMEM array, read as a
            # scalar per block.
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, tb, td), lambda i, j, r: (r // bt, r % bt, i)),
            pl.BlockSpec((1, tb, td), lambda i, j, r: (r // bt, r % bt, j)),
        ],
        out_specs=pl.BlockSpec((td, td), lambda i, j, r: (i, j)),
        out_shape=jax.ShapeDtypeStruct((d + d_pad, d + d_pad), jnp.float32),
        interpret=interpret,
    )(mask, a_tilde.astype(jnp.float32), a_tilde.astype(jnp.float32))
    n_avail = jnp.maximum(mask.sum(), 1.0)
    return out[:d, :d] / n_avail
