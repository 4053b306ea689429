"""OverSketch family: the paper's stacked Count-Sketch blocks (Eq. 4).

This is the seed implementation from ``repro.core.sketch`` migrated behind
the ``SketchFamily`` protocol; ``repro.core`` re-exports are untouched, and
``apply`` is ``core.sketch.apply_sketch``, which picks its implementation
by platform and block size (``apply_path``); ``gram`` passes it the
survivor mask (``apply_live``), so dropped blocks are not sketched.  Per-block unbiasedness E[S_i S_i^T] = I is the Count-Sketch
property the paper's Lemma 6.1 builds on.

Cost model: sketching is folded into the coded matmul workers (paper
Sec. 4.1 amortizes encoding), so ``apply_flops`` stays 0 and a block worker
is charged only its Gram tile — matching the seed's clock accounting.
"""
from __future__ import annotations

import dataclasses

import jax

import repro.core.sketch as core_sketch
from repro.sketching.base import SketchFamily
from repro.sketching.registry import register


@register("oversketch")
@dataclasses.dataclass(frozen=True)
class OverSketchFamily(SketchFamily):

    def sample(self, key: jax.Array, num_rows: int) -> core_sketch.CountSketch:
        return core_sketch.sample_countsketch(key, num_rows, self.cfg)

    def apply(self, state: core_sketch.CountSketch,
              a: jax.Array) -> jax.Array:
        return core_sketch.apply_sketch(state, a)

    def apply_live(self, state: core_sketch.CountSketch, a: jax.Array,
                   survivors) -> jax.Array:
        # The apply skips the blocks the straggler mask drops (on a TPU the
        # MXU kernel does no matmul for them): the Gram weighs them by 0,
        # so H_hat is what every block's sketch gives.
        return core_sketch.apply_sketch(state, a, survivors)

    def apply_path(self, platform: str) -> str:
        return core_sketch.sketch_impl(platform, self.cfg.block_size)
