"""SketchFamily protocol: the pluggable randomized-sketch axis.

The paper fixes one sketch family — stacked Count-Sketch blocks (Eq. 4) —
but the straggler-resilience argument only needs the *block structure*: a
sketch ``S = [S_1, ..., S_{N+e}]`` whose blocks ``S_i in R^{n x b}`` are
independent and satisfy ``E[S_i S_i^T] = I``.  Any such family gives an
unbiased sketched Gram ``H_hat = (1/N_avail) sum_{i in survivors} (S_i^T A)^T
(S_i^T A)`` under k-of-n block survival, so Alg. 2's "wait for any N of N+e"
semantics carry over verbatim.

This module defines the protocol every family implements:

  sample(key, num_rows) -> state     pytree of arrays (jit-transparent)
  apply(state, a)       -> (total_blocks, b, d) per-block  S_i^T A
  apply_live(state, a, survivors) -> the same, what ``gram`` calls; a
      family may zero (and skip) the blocks the mask drops
  gram(state, a, survivors) -> (d, d) masked, rescaled Gram estimate:
      ``apply_live`` then ``core.sketch.sketched_gram``
  apply_path(platform)  -> str       which implementation ``apply`` lowers
      to on a platform (the OverSketch family's selects by platform and
      block size; the others have one jnp form, "unfused")
  block_flops(num_rows, d) -> float  per-worker cost for the straggler clock
  comm_units(d)         -> float     per-worker master-I/O units

Families are frozen dataclasses (hashable) so jitted closures keyed on a
family instance can be lru_cached, mirroring ``newton._jitted_*``.

References: OverSketched Newton Eq. 4 / Alg. 2 (block semantics); Romanov,
Zhang & Pilanci 2024 "Newton Meets Marchenko-Pastur" (family-agnostic
debiasing, see ``sketching.debias``); Bartan & Pilanci 2020 "Distributed
Averaging Methods for Randomized Second Order Optimization" (per-worker
independent sketches, see ``newton`` sketch_mode="distributed-avg").
"""
from __future__ import annotations

import abc
import dataclasses
from typing import Any, Optional

import jax

import repro.core.sketch as core_sketch
from repro.core.sketch import OverSketchConfig
from repro.obs import wall

SketchState = Any  # pytree of arrays; structure is family-specific


@dataclasses.dataclass(frozen=True)
class SketchFamily(abc.ABC):
    """A configured block-structured sketch family (see module docstring).

    ``cfg`` carries the shared dimension accounting — sketch_dim m = N*b,
    block_size b, straggler_tolerance zeta => total_blocks N+e — reused
    across families so any family drops into the Alg. 2 worker layout.
    """

    cfg: OverSketchConfig

    # Subclasses set this; used as the registry key and in benchmark rows.
    name = "abstract"

    @abc.abstractmethod
    def sample(self, key: jax.Array, num_rows: int) -> SketchState:
        """Draw an independent realization of all N+e blocks (fresh per
        Newton iteration, like the paper's per-iteration sketch)."""

    @abc.abstractmethod
    def apply(self, state: SketchState, a: jax.Array) -> jax.Array:
        """Per-block application A (n, d) -> (total_blocks, b, d), unscaled
        by 1/sqrt(N) (the survivor rescale in ``gram`` absorbs it)."""

    def apply_live(self, state: SketchState, a: jax.Array,
                   survivors: Optional[jax.Array]) -> jax.Array:
        """``apply`` for a Gram over ``survivors`` (None = every block).  A
        family may leave the blocks the mask drops unsketched, reading 0,
        since the Gram weighs them by 0; the default sketches them all."""
        return self.apply(state, a)

    def apply_path(self, platform: str) -> str:
        """Which implementation ``apply`` takes when lowered for
        ``platform``: ``"unfused"`` (the family's jnp form) unless the
        family selects by platform and block size."""
        return "unfused"

    def gram(self, state: SketchState, a: jax.Array,
             survivors: Optional[jax.Array] = None) -> jax.Array:
        """Masked H_hat = (1/N_avail) sum_i A_tilde_i^T A_tilde_i.

        Shared across families: per-block unbiasedness (E[S_i S_i^T] = I)
        makes dropping blocks + rescaling exact for every family.

        The sketch runs under the device scope ``osn_sketch`` and the Gram
        under ``osn_gram`` (``repro.obs.wall``).
        """
        with jax.named_scope(wall.SKETCH):
            a_t = self.apply_live(state, a, survivors)
        with jax.named_scope(wall.GRAM):
            return core_sketch.sketched_gram(a_t, survivors)

    # ------------------------------------------------------------------ cost
    # Hooks for the straggler SimClock: per-worker flops and master-I/O for
    # one sketch-block worker (Alg. 2 step 3).  The default charges only the
    # Gram-tile matmul — the OverSketch family folds sketching into the coded
    # matmul workers (paper Sec. 4.1), so its apply cost is amortized.
    # Families whose apply is a separate pass override ``apply_flops``.

    def apply_flops(self, num_rows: int, d: int) -> float:
        """Per-block cost of forming A_tilde_i, in flops (0 if amortized)."""
        return 0.0

    def block_flops(self, num_rows: int, d: int) -> float:
        b = self.cfg.block_size
        gram_tile = 2.0 * b * min(d, b) ** 2
        return gram_tile + self.apply_flops(num_rows, d)

    def comm_units(self, d: int) -> float:
        """Master-I/O units per worker (one b x min(d,b) output tile)."""
        return 0.05


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (Hadamard sizes; static under jit)."""
    return 1 << max(0, (n - 1).bit_length())
