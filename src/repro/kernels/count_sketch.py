"""Pallas TPU kernel: Count-Sketch apply  A_tilde_k = S_k^T A  for K blocks.

TPU adaptation: Count-Sketch is a scatter-add on CPUs/GPUs;
TPUs have no efficient scatter but a 128x128 systolic MXU.  We therefore
materialize, per (sketch block, row panel), the signed one-hot bucket matrix
``O[c, r] = sigma_r * 1{h_r == c}`` (b, tn) in VMEM via ``broadcasted_iota``
and compute ``A_tilde_k += O @ A_panel`` as an MXU matmul.

Loop order.  Grid ``(block group g, d tile j, row panel r)`` with the row
reduction innermost: the ``(G, b, td)`` output block of a group of G sketch
blocks stays resident in VMEM across r, and each program step applies all G
blocks of its group to the one ``(tn, td)`` panel of A it loaded.  So A
crosses HBM ceil(K / G) times per call, not K times.

Exactness.  The MXU multiplies bfloat16.  The one-hot entries (+-1, 0) are
exact in bfloat16, and an f32 panel is split in VMEM into three bfloat16
parts ``hi + mid + lo == a`` (8 significand bits each, 24 in all), so three
passes ``O @ hi + O @ mid + O @ lo`` accumulated in f32 give the f32
segment sum up to the order of its additions.  A bfloat16 A takes one pass.

Live blocks.  ``live`` (K,) marks the blocks whose sketch is wanted; it
rides in SMEM, padded with zeros to whole groups, and a block it marks
dead builds no one-hot and runs no pass, so its output reads exactly 0.
The Hessian passes the straggler survivors: the Gram weighs a dropped
block by 0, so the MXU skips work the master would discard.  Tiles and
pass order do not depend on the mask, so a live block's sketch is the
same either way.

No operand but the (K,) mask is padded in HBM.  The last group overhangs
K, and its blocks past K read dead; the last row panel overhangs A and
the bucket/sign rows, and its rows past n are zeroed in the kernel;
columns past d reach only output columns past d, whose writes are
dropped.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Scoped VMEM the tiles are sized to (v5e has 128 MiB per core, 16 MiB of
# it scoped by default); each call asks for its counted working set.
VMEM_BUDGET_BYTES = 48 * 1024 * 1024
VMEM_HEADROOM_BYTES = 4 * 1024 * 1024
MAX_GROUP = 16          # sketch blocks applied to each loaded A panel
MAX_TILE_N = 512        # rows of A per panel: the matmuls' contraction
MAX_TILE_D = 512        # columns of A per panel


def _round_up(x: int, m: int) -> int:
    return x + (-x) % m


def vmem_bytes(group: int, block_size: int, tile_n: int,
               tile_d: int) -> int:
    """VMEM one program step holds with an f32 A: the double-buffered
    output block and A panel, the double-buffered bucket/sign rows, the
    panel's f32 copy and its three bfloat16 parts, one block's one-hot
    (f32 and bfloat16) and its four f32 products."""
    return (2 * 4 * group * block_size * tile_d
            + 2 * 4 * tile_n * tile_d
            + 2 * 2 * 4 * group * tile_n
            + (4 + 2 * 3) * tile_n * tile_d
            + 6 * block_size * tile_n
            + 4 * 4 * block_size * tile_d)


def pick_tiles(num_blocks: int, block_size: int, n: int, d: int):
    """(group, tile_n, tile_d) for a (K, b, n, d) call, whose working set
    ``vmem_bytes`` fits ``VMEM_BUDGET_BYTES``.  It starts from the widest
    panel up to ``MAX_TILE_N`` x ``MAX_TILE_D`` (lane- and sublane-aligned,
    no wider than the padded array) and the largest group of sketch blocks,
    a multiple of 8 up to ``MAX_GROUP``; while the working set is over the
    budget it shrinks the group by 8, then halves the panel's columns, then
    its rows, down to 8 blocks of 128 x 128.  Raises ValueError where even
    that does not fit (b past about 4500)."""
    tn = min(MAX_TILE_N, _round_up(n, 128))
    td = min(MAX_TILE_D, _round_up(d, 128))
    group = min(MAX_GROUP, _round_up(num_blocks, 8))
    while vmem_bytes(group, block_size, tn, td) > VMEM_BUDGET_BYTES:
        if group > 8:
            group -= 8
        elif td > 128:
            td = _round_up(td // 2, 128)
        elif tn > 128:
            tn = _round_up(tn // 2, 128)
        else:
            raise ValueError(
                f"count_sketch_apply: block_size {block_size} does not fit "
                f"{VMEM_BUDGET_BYTES} bytes of VMEM at the smallest tiles")
    return group, tn, td


def _split(a: jax.Array):
    """bfloat16 parts whose f32 sum is ``a`` exactly."""
    if a.dtype == jnp.bfloat16:
        return [a]
    hi = a.astype(jnp.bfloat16)
    rest = a - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return [hi, mid, lo]


def _kernel(live_ref, h_ref, sigma_ref, a_ref, out_ref, *,
            block_size: int, n_rows: int):
    g = pl.program_id(0)
    r = pl.program_id(2)   # innermost: reduction over row panels
    group, tn = h_ref.shape

    @pl.when(r == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[...]                                    # (tn, td)
    sigma = sigma_ref[...]                            # (group, tn)
    if n_rows % tn:
        # The last row panel overhangs A and the bucket/sign rows: zero
        # its rows past n, whose contents are undefined (0 * NaN != 0).
        limit = n_rows - r * tn
        row = jax.lax.broadcasted_iota(jnp.int32, (tn, 1), 0)
        a = jnp.where(row < limit, a, 0).astype(a.dtype)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, tn), 1)
        sigma = jnp.where(col < limit, sigma, 0.0)
    parts = _split(a)
    iota = jax.lax.broadcasted_iota(jnp.int32, (block_size, tn), 0)
    for i in range(group):
        # Dead blocks, and those past K (the last group's overhang, padded
        # dead), are skipped: their output keeps the zeros of _init.
        @pl.when(live_ref[g * group + i] != 0)
        def _apply(i=i):
            # Block i's signed one-hot bucket matrix, (b, tn).
            onehot = jnp.where(h_ref[i:i + 1, :] == iota, sigma[i:i + 1, :],
                               0.0).astype(jnp.bfloat16)
            acc = out_ref[i]
            for p in parts:
                # bfloat16 operands: exact products at the MXU's own
                # precision, whatever the caller's default matmul precision.
                acc += jnp.dot(onehot, p, precision=jax.lax.Precision.DEFAULT,
                               preferred_element_type=jnp.float32)
            out_ref[i] = acc


@functools.partial(jax.jit, static_argnames=("block_size", "group", "tile_n",
                                             "tile_d", "interpret"))
def _count_sketch_apply(h, sigma, a, live, *, block_size: int, group: int,
                        tile_n: int, tile_d: int, interpret: bool):
    k, n = h.shape
    d = a.shape[1]
    if a.dtype != jnp.bfloat16:
        a = a.astype(jnp.float32)
    num_groups = pl.cdiv(k, group)
    live = jnp.pad(live.astype(jnp.int32), (0, num_groups * group - k))
    vmem = vmem_bytes(group, block_size, tile_n, tile_d)
    # Every edge block overhangs its array (only the mask is padded):
    # reads past an edge are masked above or skipped, writes past it
    # dropped.  The mask is one whole array in SMEM, read per block.
    return pl.pallas_call(
        functools.partial(_kernel, block_size=block_size, n_rows=n),
        grid=(num_groups, pl.cdiv(d, tile_d), pl.cdiv(n, tile_n)),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((group, tile_n), lambda g, j, r: (g, r)),
            pl.BlockSpec((group, tile_n), lambda g, j, r: (g, r)),
            pl.BlockSpec((tile_n, tile_d), lambda g, j, r: (r, j)),
        ],
        out_specs=pl.BlockSpec((group, block_size, tile_d),
                               lambda g, j, r: (g, 0, j)),
        out_shape=jax.ShapeDtypeStruct((k, block_size, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem + vmem // 2 + VMEM_HEADROOM_BYTES),
        interpret=interpret,
        name="mxu_count_sketch",
    )(live, h.astype(jnp.int32), sigma.astype(jnp.float32), a)


def count_sketch_apply(h: jax.Array, sigma: jax.Array, a: jax.Array,
                       block_size: int, *, live: Optional[jax.Array] = None,
                       interpret: bool = False) -> jax.Array:
    """(K, n) x (K, n) x (n, d) -> (K, block_size, d) float32.

    ``live`` is a (K,) bool or int mask of the blocks to sketch (default
    every block).  A block with ``live`` false reads exactly 0 and costs
    no matmul; a live block's sketch is bit for bit the one ``live=None``
    gives.  Tiles come from ``pick_tiles``: groups of up to 16 sketch
    blocks share each loaded panel of A, so A is read ceil(K / group)
    times."""
    k, n = h.shape
    if live is None:
        live = jnp.ones((k,), jnp.int32)
    group, tn, td = pick_tiles(k, block_size, n, a.shape[1])
    return _count_sketch_apply(h, sigma, a, live, block_size=block_size,
                               group=group, tile_n=tn, tile_d=td,
                               interpret=interpret)
