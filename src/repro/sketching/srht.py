"""SRHT family: blocked subsampled randomized Hadamard transform.

Each block is an independent SRHT  ``S_i^T = sqrt(n_pad/b) P_i H_norm D_i``:
Rademacher signs D_i, the orthonormal Walsh-Hadamard mix H_norm (length
padded to n_pad = next power of two), and b rows sampled uniformly with
replacement (P_i).  Per-block unbiasedness: H_norm D_i is orthogonal on the
zero-padded embedding, and E[P_i^T P_i] = (b/n_pad) I, so
``E[S_i S_i^T] = I`` — the property the OverSketch Eq. 4 survivor rescale
needs.  Tighter embedding constants than Count-Sketch at equal m (Tropp
2011), at an O(n log n) mixing cost per block.

The Hadamard mix is the radix-2 butterfly ``repro.kernels.ref.fwht``.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.kernels.ref import fwht
from repro.sketching.base import SketchFamily, next_pow2
from repro.sketching.registry import register


@register("srht")
@dataclasses.dataclass(frozen=True)
class SRHTFamily(SketchFamily):

    def sample(self, key: jax.Array, num_rows: int) -> dict:
        ks, kp = jax.random.split(key)
        blocks = self.cfg.total_blocks
        n_pad = next_pow2(num_rows)
        sigma = jax.random.rademacher(ks, (blocks, num_rows),
                                      dtype=jnp.float32)
        rows = jax.random.randint(kp, (blocks, self.cfg.block_size), 0, n_pad,
                                  dtype=jnp.int32)
        return {"sigma": sigma, "rows": rows}

    def apply(self, state: dict, a: jax.Array) -> jax.Array:
        n, d = a.shape
        n_pad = next_pow2(n)
        scale = jnp.sqrt(jnp.asarray(n_pad / self.cfg.block_size, a.dtype))

        # lax.map streams blocks so peak memory is ONE (n_pad, d) panel
        # (plus output), not the (K, n_pad, d) tensor a vmap would build —
        # only block_size of the n_pad mixed rows survive the gather anyway.
        def one(args):
            sigma, rows = args
            x = sigma[:, None] * a
            if n_pad != n:
                x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
            return fwht(x[None])[0][rows] * scale

        return jax.lax.map(one, (state["sigma"], state["rows"]))

    def apply_flops(self, num_rows: int, d: int) -> float:
        n_pad = next_pow2(num_rows)
        return float(n_pad * max(1, int(math.log2(n_pad))) * d)
