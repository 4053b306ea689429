"""Nystrom / row-sampling family: uniform row subsampling of hess_sqrt.

Each block samples b rows of A uniformly with replacement and rescales by
sqrt(n/b):  ``S_i^T = sqrt(n/b) P_i``.  Then ``E[S_i S_i^T] = (n/b)
E[P_i^T P_i] = I``, and the per-block Gram ``(S_i^T A)^T (S_i^T A)`` is the
classic Nystrom / subsampled-Newton estimate of A^T A.  No mixing at all:
apply is a gather, the cheapest family and the weakest on rows with
non-uniform leverage — the far end of the accuracy/cost axis from
"gaussian", which is exactly why the fig7 family sweep includes it.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.sketching.base import SketchFamily
from repro.sketching.registry import register


@register("nystrom")
@dataclasses.dataclass(frozen=True)
class NystromFamily(SketchFamily):

    def sample(self, key: jax.Array, num_rows: int) -> dict:
        rows = jax.random.randint(
            key, (self.cfg.total_blocks, self.cfg.block_size), 0, num_rows,
            dtype=jnp.int32)
        return {"rows": rows}

    def apply(self, state: dict, a: jax.Array) -> jax.Array:
        n = a.shape[0]
        scale = jnp.sqrt(jnp.asarray(n / self.cfg.block_size, a.dtype))
        return jax.vmap(lambda r: a[r])(state["rows"]) * scale

    def apply_flops(self, num_rows: int, d: int) -> float:
        return float(self.cfg.block_size * d)
