"""Dense Gaussian family: blocks of iid N(0, 1/b) entries.

``S_i in R^{n x b}`` with entries N(0, 1/b) gives ``E[S_i S_i^T] = I``
exactly, and the sketched Gram of a single block is Wishart — the setting
where the Marchenko-Pastur inverse bias of ``sketching.debias`` is exact
(E[(S^T A)^+ ...] inflates by m/(m-d-1), Romanov, Zhang & Pilanci 2024,
Sec. 2).  The most accurate family per sketched row and the reference
point for the debiasing tests, but the only one with a dense O(n b d)
apply per block — the straggler clock charges that honestly, which is why
it loses the simulated wall-clock race it wins on epsilon.

The state stores per-block PRNG keys, not the n x b matrices: blocks are
regenerated inside the jitted Gram (cheaper than shipping them, exactly
like serverless workers re-deriving their sketch from a seed).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.sketching.base import SketchFamily
from repro.sketching.registry import register


@register("gaussian")
@dataclasses.dataclass(frozen=True)
class GaussianFamily(SketchFamily):

    def sample(self, key: jax.Array, num_rows: int) -> dict:
        return {"keys": jax.random.split(key, self.cfg.total_blocks)}

    def apply(self, state: dict, a: jax.Array) -> jax.Array:
        n = a.shape[0]
        b = self.cfg.block_size
        inv_sqrt_b = 1.0 / jnp.sqrt(jnp.asarray(float(b), a.dtype))

        # lax.map streams blocks: one (n, b) sketch lives at a time, keeping
        # the regenerate-from-seed memory story (a vmap would materialize
        # all K blocks at once).
        def one(k):
            g = jax.random.normal(k, (n, b), dtype=a.dtype) * inv_sqrt_b
            return g.T @ a

        return jax.lax.map(one, state["keys"])

    def apply_flops(self, num_rows: int, d: int) -> float:
        return 2.0 * num_rows * self.cfg.block_size * d
