"""OverSketch: straggler-resilient Count-Sketch based randomized matrix products.

The paper's Eq. (4) sketch is ``S = (1/sqrt(N)) [S_1, ..., S_{N+e}]`` where each
``S_i in R^{n x b}`` is an independent Count-Sketch.  The sketched Gram
``H_hat = A^T S S^T A = (1/N) sum_i (S_i^T A)^T (S_i^T A)`` tolerates up to
``e`` straggling blocks: any surviving subset of blocks gives an unbiased
estimate after rescaling by the survivor count (``E[S_i S_i^T] = I``).

We never materialize S.  A Count-Sketch block is two integer/sign vectors
``(h, sigma)``; ``S_i^T A`` is a signed segment-sum of A's rows into b buckets
(``apply_block``).  ``apply_sketch``, the optimizer's path, lowers to the
segment sums, or on a TPU at b <= ``MXU_MAX_BLOCK_SIZE`` to the one-hot MXU
matmul of ``repro.kernels.count_sketch``; the kernels' oracle is
``repro.kernels.ref``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.kernels import count_sketch


@dataclasses.dataclass(frozen=True)
class OverSketchConfig:
    """Configuration for the OverSketch sketch of Eq. (4).

    Attributes:
      sketch_dim: target sketch dimension m = N*b (excluding over-provision).
      block_size: b, the width of each Count-Sketch block (worker tile size).
      straggler_tolerance: zeta; e = ceil(zeta * N) extra blocks are added.
    """

    sketch_dim: int
    block_size: int
    straggler_tolerance: float = 0.25

    def __post_init__(self):
        if self.sketch_dim % self.block_size != 0:
            raise ValueError(
                f"sketch_dim {self.sketch_dim} must be divisible by "
                f"block_size {self.block_size}")

    @property
    def num_blocks(self) -> int:
        """N = m / b."""
        return self.sketch_dim // self.block_size

    @property
    def num_redundant(self) -> int:
        """e = ceil(zeta * N) over-provisioned blocks."""
        return int(math.ceil(self.straggler_tolerance * self.num_blocks))

    @property
    def total_blocks(self) -> int:
        return self.num_blocks + self.num_redundant

    @property
    def total_dim(self) -> int:
        return self.total_blocks * self.block_size


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CountSketch:
    """(N+e) independent Count-Sketch blocks over n rows.

    h:     int32 (total_blocks, n)  bucket index in [0, b) per row per block.
    sigma: float (total_blocks, n)  Rademacher signs.
    block_size: static b.
    """

    h: jax.Array
    sigma: jax.Array
    block_size: int

    def tree_flatten(self):
        return (self.h, self.sigma), self.block_size

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux)

    @property
    def total_blocks(self) -> int:
        return self.h.shape[0]

    @property
    def num_rows(self) -> int:
        return self.h.shape[1]


def sample_countsketch(key: jax.Array, num_rows: int,
                       cfg: OverSketchConfig) -> CountSketch:
    """Draw an independent realization of the Eq. (4) sketch (fresh per iter)."""
    kh, ks = jax.random.split(key)
    h = jax.random.randint(kh, (cfg.total_blocks, num_rows), 0, cfg.block_size,
                           dtype=jnp.int32)
    sigma = jax.random.rademacher(
        ks, (cfg.total_blocks, num_rows), dtype=jnp.float32)
    return CountSketch(h=h, sigma=sigma, block_size=cfg.block_size)


def apply_block(h: jax.Array, sigma: jax.Array, block_size: int,
                a: jax.Array) -> jax.Array:
    """S_i^T A for one Count-Sketch block: (n,) x (n,) x (n, d) -> (b, d)."""
    signed = a * sigma[:, None].astype(a.dtype)
    return jax.ops.segment_sum(signed, h, num_segments=block_size)


# The widest sketch block ``apply_sketch`` gives the MXU kernel on a TPU.
# The kernel's work per block grows with b (6 b n d flop for its three
# bfloat16 passes) and the segment sums' barely does: on a v5e the two
# cross between b = 1024 and 2048 (PERF.md section 6 has the readings).
MXU_MAX_BLOCK_SIZE = 1024


def sketch_impl(platform: str, block_size: int) -> str:
    """Which implementation ``apply_sketch`` lowers to on ``platform`` (a
    JAX platform name such as "tpu" or "cpu") for blocks of ``block_size``:
    "mxu_count_sketch" (the Pallas MXU kernel, ``kernels/count_sketch.py``)
    or "segment_sum"."""
    if platform == "tpu" and block_size <= MXU_MAX_BLOCK_SIZE:
        return "mxu_count_sketch"
    return "segment_sum"


def _apply_segment_sum(h: jax.Array, sigma: jax.Array, a: jax.Array,
                       block_size: int,
                       live: Optional[jax.Array] = None) -> jax.Array:
    """``lax.map`` streams the blocks, so peak memory is one signed (n, d)
    panel plus the (K, b, d) output — never the (K, n, d) tensor a vmap
    over blocks would build (242 GB at epsilon's n = 200k, d = 2000).
    Blocks that ``live`` marks dead are sketched, then zeroed."""
    out = jax.lax.map(
        lambda hs: apply_block(hs[0], hs[1], block_size, a), (h, sigma))
    if live is None:
        return out
    return jnp.where(live.astype(bool)[:, None, None], out, 0.0)


def apply_sketch(cs: CountSketch, a: jax.Array,
                 live: Optional[jax.Array] = None) -> jax.Array:
    """All blocks: A (n, d) -> A_tilde (total_blocks, b, d).  Unscaled.

    The 1/sqrt(N) scale of Eq. (4) is folded into the Gram rescale (we divide
    by the survivor count there), which is what makes dropping blocks exact.
    The implementation follows the platform the program is lowered for
    and the block size (``sketch_impl``): the MXU kernel on a TPU, which
    has no fast scatter, and the segment sums elsewhere or for wide blocks.
    Both give the f32 segment sum up to the order of its additions.

    ``live``, a (total_blocks,) mask such as the straggler survivors, marks
    the blocks whose sketch is wanted: the others read exactly 0 on every
    platform (the MXU kernel does no matmul for them), and the live ones
    are what ``live=None`` gives, bit for bit.
    """
    b = cs.block_size
    if sketch_impl("tpu", b) == "segment_sum":
        return _apply_segment_sum(cs.h, cs.sigma, a, b, live)
    return jax.lax.platform_dependent(
        cs.h, cs.sigma, a, live,
        tpu=lambda h, sigma, a, live: count_sketch.count_sketch_apply(
            h, sigma, a, b, live=live, interpret=False),
        default=lambda h, sigma, a, live: _apply_segment_sum(
            h, sigma, a, b, live))


def apply_sketch_chunked(cs: CountSketch, a_fn: Callable[[int], jax.Array],
                         num_chunks: int, chunk_rows: int,
                         d: int) -> jax.Array:
    """Streaming S^T A for tall A that should not be materialized.

    ``a_fn(c)`` returns chunk c of A with ``chunk_rows`` rows.  Row j of chunk
    c corresponds to global row ``c*chunk_rows + j`` of A (and of the sketch).
    """
    def body(c, acc):
        rows = a_fn(c)
        start = c * chunk_rows
        h_c = jax.lax.dynamic_slice_in_dim(cs.h, start, chunk_rows, axis=1)
        s_c = jax.lax.dynamic_slice_in_dim(cs.sigma, start, chunk_rows, axis=1)
        part = jax.vmap(
            lambda h, s: apply_block(h, s, cs.block_size, rows))(h_c, s_c)
        return acc + part

    init = jnp.zeros((cs.total_blocks, cs.block_size, d), dtype=jnp.float32)
    return jax.lax.fori_loop(0, num_chunks, body, init)


def sketched_gram(a_tilde: jax.Array,
                  survivors: Optional[jax.Array] = None) -> jax.Array:
    """H_hat = (1/N_avail) sum_{i in survivors} A_tilde_i^T A_tilde_i.

    a_tilde:   (total_blocks, b, d) sketched square root blocks.
    survivors: bool (total_blocks,) mask of non-straggling blocks; None = all.

    Dropping a block and rescaling keeps the estimator unbiased — this is the
    paper's "over"-sketching straggler resiliency, done as a masked reduction.
    """
    if survivors is None:
        survivors = jnp.ones((a_tilde.shape[0],), dtype=bool)
    m = survivors.astype(a_tilde.dtype)
    n_avail = jnp.maximum(m.sum(), 1.0)
    grams = jnp.einsum("kbd,kbe->kde", a_tilde, a_tilde)
    return jnp.einsum("k,kde->de", m, grams) / n_avail


def oversketched_gram(key: jax.Array, a: jax.Array, cfg: OverSketchConfig,
                      survivors: Optional[jax.Array] = None) -> jax.Array:
    """One-shot H_hat ~= A^T A with straggler resiliency (single device)."""
    cs = sample_countsketch(key, a.shape[0], cfg)
    return sketched_gram(apply_sketch(cs, a), survivors)


# ---------------------------------------------------------------------------
# Distributed (shard_map) path: sketch blocks spread over a mesh axis.
# ---------------------------------------------------------------------------

def distributed_sketched_gram(a: jax.Array, cs: CountSketch,
                              survivors: jax.Array, *,
                              mesh: jax.sharding.Mesh,
                              block_axis: str) -> jax.Array:
    """H_hat over a mesh: each ``block_axis`` shard owns total_blocks/axis
    sketch blocks, computes its local masked Gram contribution, and the
    result is a straggler-masked all-reduce (`resilient psum`).

    a is replicated (or row-sharded and pre-reduced by the caller); h/sigma/
    survivors are sharded on their leading block dimension.
    """
    from jax.sharding import PartitionSpec as P

    def local(a_l, h_l, s_l, m_l):
        a_t = jax.vmap(
            lambda h, s: apply_block(h, s, cs.block_size, a_l))(h_l, s_l)
        mf = m_l.astype(a_t.dtype)
        gram = jnp.einsum("k,kbd,kbe->de", mf, a_t, a_t)
        n_local = mf.sum()
        gram = jax.lax.psum(gram, block_axis)
        n_avail = jax.lax.psum(n_local, block_axis)
        return gram / jnp.maximum(n_avail, 1.0)

    spec_blocks = P(block_axis)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), spec_blocks, spec_blocks, spec_blocks),
        out_specs=P())(a, cs.h, cs.sigma, survivors)
