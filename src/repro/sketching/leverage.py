"""Leverage-score row sampling: importance-weighted row subsampling.

Upgrades the uniform ``nystrom`` family on data with non-uniform leverage:
each block samples b rows with replacement from ``p_i = l_i / d`` where
``l_i = ||q_i||^2`` are the exact leverage scores of A (row norms of its
thin-QR Q factor), and rescales row i by ``1 / sqrt(b p_i)``.  Then

    E[S_i S_i^T] = b * E[e_r e_r^T / (b p_r)] = sum_r p_r e_r e_r^T / p_r
                 = I    (restricted to rows with l_i > 0),

so the per-block Gram is unbiased for A^T A and the family inherits Alg. 2's
k-of-n survivor semantics like every other registry entry.  Sampling by
leverage is the optimal importance distribution for row-sampled Grams
(Drineas-Mahoney-Muthukrishnan): rows that matter are kept, so spiky
matrices that break uniform Nystrom are handled at the same per-worker
cost.

The QR pass to get the scores is a one-time master-side O(n d^2) — the same
price as one exact Gram, amortized across the N+e blocks in the cost hook.
Because the scores depend on A, sampling happens lazily in ``apply`` (the
protocol's ``sample`` never sees A); the state carries only the key, so the
realization is still deterministic per Newton iteration.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.sketching.base import SketchFamily
from repro.sketching.registry import register


@register("leverage")
@dataclasses.dataclass(frozen=True)
class LeverageFamily(SketchFamily):

    def sample(self, key: jax.Array, num_rows: int) -> dict:
        # Scores depend on A, which apply() sees and sample() does not:
        # defer the draw, keep the key (one realization per iteration).
        return {"key": key}

    def apply(self, state: dict, a: jax.Array) -> jax.Array:
        n, d = a.shape
        q, _ = jnp.linalg.qr(a)                      # thin QR, (n, d)
        lev = jnp.sum(q * q, axis=1)                 # leverage scores, sum=d
        p = lev / jnp.maximum(jnp.sum(lev), 1e-30)
        shape = (self.cfg.total_blocks, self.cfg.block_size)
        rows = jax.random.choice(state["key"], n, shape, replace=True, p=p)
        scale = 1.0 / jnp.sqrt(
            jnp.maximum(self.cfg.block_size * p[rows], 1e-30))
        return a[rows] * scale[..., None]

    def apply_flops(self, num_rows: int, d: int) -> float:
        # Master-side QR amortized over the fleet + the per-block gather.
        qr = 2.0 * num_rows * d * d / max(self.cfg.total_blocks, 1)
        return qr + float(self.cfg.block_size * d)
