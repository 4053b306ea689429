"""SJLT family: sparse Johnson-Lindenstrauss transform (blocked OSNAP).

Each block S_i has s nonzeros of value +-1/sqrt(s) per row of A (Count-
Sketch is the s=1 special case), applied as s signed segment-sums.  Per-
block unbiasedness: diagonal entries of S_i S_i^T sum s slots of 1/s each
and cross-slot sign products are zero-mean, so ``E[S_i S_i^T] = I`` even
with intra-row bucket collisions.  s > 1 buys Count-Sketch's O(nnz) apply
cost a better distortion-vs-m trade (Nelson & Nguyen 2013) — the middle
ground between "oversketch" and "srht".
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

import repro.core.sketch as core_sketch
from repro.sketching.base import SketchFamily
from repro.sketching.registry import register


@register("sjlt")
@dataclasses.dataclass(frozen=True)
class SJLTFamily(SketchFamily):

    nnz_per_row: int = 4

    def sample(self, key: jax.Array, num_rows: int) -> dict:
        kh, ks = jax.random.split(key)
        shape = (self.cfg.total_blocks, self.nnz_per_row, num_rows)
        h = jax.random.randint(kh, shape, 0, self.cfg.block_size,
                               dtype=jnp.int32)
        sigma = jax.random.rademacher(ks, shape, dtype=jnp.float32)
        return {"h": h, "sigma": sigma}

    def apply(self, state: dict, a: jax.Array) -> jax.Array:
        b = self.cfg.block_size

        def one_block(h_b, s_b):
            slots = jax.vmap(
                lambda h, s: core_sketch.apply_block(h, s, b, a))(h_b, s_b)
            return slots.sum(axis=0)

        out = jax.vmap(one_block)(state["h"], state["sigma"])
        return out / jnp.sqrt(jnp.asarray(float(self.nnz_per_row), out.dtype))

    def apply_flops(self, num_rows: int, d: int) -> float:
        return 2.0 * self.nnz_per_row * num_rows * d
