"""Pallas TPU kernel: fused sketch->Gram streaming pipeline, d-tiled.

The paper's per-iteration hot path (Alg. 2 steps 3-5) is "sketch then
multiply": form ``A_tilde_k = S_k^T A`` for every sketch block, then
accumulate the survivor-masked Gram ``G = (1/N_avail) sum_k m_k
A_tilde_k^T A_tilde_k``.  The unfused pipeline costs two HBM round-trips —
``A_tilde`` (K, b, d) is written by the apply kernel and re-read by the
Gram kernel.  This kernel fuses the two: it streams row-panels of A,
applies the sketch block-locally, keeps running ``A_tilde_k`` column
panels in VMEM accumulators, and folds the masked Gram contribution into
the output tile when a block's reduction completes.  ``A_tilde`` never
touches HBM.

All supported families reduce to the same structure — a per-(block,
row-tile) *encode matrix* ``E in R^{tn x b}`` materialized in VMEM from
``broadcasted_iota`` (no host constants), followed by an MXU matmul:

  count-sketch:  E[r, c] = sigma_r * 1{h_r == c}
                 (the signed one-hot bucket matrix of ``count_sketch.py``)
  SJLT/OSNAP:    E[r, c] = (1/sqrt(s)) sum_t sigma_{t,r} * 1{h_{t,r} == c}
                 (s signed one-hot layers summed; count-sketch is s = 1,
                 intra-row bucket collisions sum exactly like the
                 segment-sum reference)
  SRHT:          E[r, c] = sigma_r * (-1)^popcount((o + r) & rows_c) / sqrt(b)
                 (the sampled-row slice of the Hadamard mix: H is symmetric,
                 so gathering b rows of H D A is a matmul with b *columns*
                 of H, each regenerated from the global row index o + r.
                 The SRHT scale sqrt(n_pad/b) * 1/sqrt(n_pad) collapses to
                 1/sqrt(b), so n_pad appears only through the bit pattern,
                 and zero rows past n never need to be streamed.)

Grid: ``(d_i, d_j, K, n_tiles)`` with the row-panel reduction innermost.
Each program owns one ``(d_tile, d_tile)`` block of the Gram output and
two ``(b, d_tile)`` VMEM scratch accumulators holding the column panels
``A_tilde_k[:, i_tile]`` and ``A_tilde_k[:, j_tile]``; the resident
working set is a function of ``d_tile`` — never of d — so the fused path
compiles for ANY d.  ``pick_d_tile`` chooses the largest tile that fits
``MAX_FUSED_VMEM_BYTES`` (``d_tile == d_pad`` recovers the single-tile
kernel exactly: one program, no encode recompute).  Past one tile, with
t tiles per side, the encode matmul is recomputed (2t - 1)x and A's
column panels are re-read 2t x — the price of never materializing
``A_tilde`` (see kernels/README.md for the budget table and the
recompute accounting).  The caller divides by the survivor count (same
convention as ``oversketch_matmul``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_TILE_N = 256
# Budget for the kernel's VMEM working set as ``fused_vmem_bytes`` counts
# it.  Since the grid tiles the output, the budget is a function of
# d_tile, not d: it bounds the TILE, never declines the call — pick_d_tile
# shrinks the tile until the working set fits.  Each call asks the
# compiler for ``vmem_limit`` of its counted working set (the v5e default
# scope is 16 MiB of the core's 128 MiB), so every tile within the budget
# compiles at default and at highest matmul precision.
MAX_FUSED_VMEM_BYTES = 20 * 1024 * 1024
VMEM_HEADROOM_BYTES = 2 * 1024 * 1024
MIN_D_TILE = 128


def fused_vmem_bytes(block_size: int, d_tile: int,
                     tile_n: int = DEFAULT_TILE_N, nnz: int = 1,
                     single: bool = False) -> int:
    """VMEM bytes one (d_i, d_j) program allocates, f32: the
    double-buffered A column panels (one on the single-tile grid, two on
    the tiled grid), the double-buffered output tile, the A_tilde scratch
    accumulators, the double-buffered sign/bucket blocks (nnz layers,
    sublane-padded to 8) and the in-kernel temporaries (three (b, tn)
    encode-sized arrays, one (b, td) panel product).  An upper bound on
    the smallest scoped-VMEM limit the v5e compiler accepts at default
    precision: within 5% at epsilon's tiled width, loose on the
    single-tile grid (table in kernels/README.md)."""
    td = d_tile + ((-d_tile) % 128)
    panels = 1 if single else 2
    sub = 8 * -(-nnz // 8)
    return 4 * (2 * panels * tile_n * td + 2 * td * td
                + (panels + 1) * block_size * td
                + 4 * sub * tile_n + 3 * block_size * tile_n)


def vmem_limit(counted: int) -> int:
    """Scoped VMEM to ask the compiler for, given a counted working set.
    Highest matmul precision splits each f32 matmul operand into bf16
    parts in VMEM (19.8 MiB against 15.8 MiB counted at epsilon's tile,
    b = 256, d_tile = 1024); the extra half covers that, the headroom
    Mosaic's internal scratch."""
    return counted + counted // 2 + VMEM_HEADROOM_BYTES


def fits_fused_vmem(block_size: int, d_tile: int,
                    tile_n: int = DEFAULT_TILE_N, nnz: int = 1,
                    single: bool = False) -> bool:
    """Does a (d_tile, d_tile) output tile's working set fit the budget?
    Used only to PICK d_tile (pick_d_tile) — no caller declines on it."""
    return fused_vmem_bytes(block_size, d_tile, tile_n, nnz,
                            single) <= MAX_FUSED_VMEM_BYTES


def pick_d_tile(block_size: int, d: int, tile_n: int = DEFAULT_TILE_N,
                nnz: int = 1) -> int:
    """Largest output tile within the VMEM budget: d_pad itself when the
    whole (d_pad, d_pad) output fits (single-tile grid, zero recompute),
    otherwise the largest power-of-two multiple of 128 that fits (floor
    MIN_D_TILE, the lane width — below it the MXU runs padded anyway)."""
    d_pad = d + ((-d) % 128)
    if fits_fused_vmem(block_size, d_pad, tile_n, nnz, single=True):
        return d_pad
    td = MIN_D_TILE
    while 2 * td < d_pad and fits_fused_vmem(block_size, 2 * td, tile_n, nnz):
        td *= 2
    return td


def fused_path(block_size: int, d: int, tile_n: int = DEFAULT_TILE_N,
               nnz: int = 1) -> str:
    """Which fused grid a (b, d) problem gets: ``"fused"`` (one resident
    output tile — the pre-tiling kernel, zero encode recompute) or
    ``"fused_tiled"`` (multi-tile (d_i, d_j) grid).  Families without an
    encode-matrix form report ``"unfused"`` via SketchFamily.fused_path."""
    d_pad = d + ((-d) % 128)
    return "fused" if pick_d_tile(block_size, d, tile_n, nnz) >= d_pad \
        else "fused_tiled"


def _encode_count(meta, sigma, offset, block_size):
    """Summed signed one-hot layers, transposed (b, tn): meta/sigma are
    (s, tn) slices, one lane row per layer (s = 1 is plain count-sketch;
    s > 1 is SJLT, scaled by 1/sqrt(s))."""
    s, tn = sigma.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (block_size, tn), 0)
    enc = jnp.zeros((block_size, tn), jnp.float32)
    for t in range(s):   # s is static and tiny (1..8): unrolled layers
        enc = enc + jnp.where(meta[t:t + 1, :] == iota,
                              sigma[t:t + 1, :], 0.0)
    if s > 1:
        enc = enc * (1.0 / math.sqrt(float(s)))
    return enc


def _encode_srht(meta, sigma, offset, block_size):
    """Sampled Hadamard mix, transposed (b, tn): meta is the (b, 1)
    sampled-row column, sigma the (1, tn) sign row."""
    tn = sigma.shape[-1]
    g = jax.lax.broadcasted_iota(jnp.int32, (block_size, tn), 1) + offset
    bits = jax.lax.population_count(jnp.bitwise_and(g, meta))
    had = jnp.where(jnp.bitwise_and(bits, 1) == 0, 1.0, -1.0)
    return sigma * had * (1.0 / math.sqrt(float(block_size)))


_ENCODERS = {"count": _encode_count, "srht": _encode_srht}


def _panel(a_ref, r, tile_n, n_rows):
    """Row panel r of A.  A is not padded in HBM: when tile_n does not
    divide n the last panel overhangs the array, and its rows past n hold
    whatever the buffer held, so they are zeroed here (their sign is 0
    too, but 0 * NaN is not 0).  Columns past d only reach output rows and
    columns past d, which the caller slices off."""
    a = a_ref[...]
    if n_rows % tile_n:
        row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0) + r * tile_n
        a = jnp.where(row < n_rows, a, 0.0).astype(a.dtype)
    return a


def _gram_fold(at_i, at_j, m):
    """m * at_i^T at_j for (b, td) panels -> (td, td); the survivor weight
    scales the small panel, not the output tile."""
    return jax.lax.dot_general(m * at_i, at_j, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _kernel_single(mask_ref, meta_ref, sigma_ref, a_ref, out_ref, acc_ref, *,
                   mode: str, block_size: int, tile_n: int, n_rows: int):
    """Single-tile specialization (d_t == 1): the whole (d_pad, d_pad)
    output is resident, A streams once per block, zero encode recompute."""
    kk = pl.program_id(2)
    r = pl.program_id(3)

    @pl.when((kk == 0) & (r == 0))
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(r == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = _panel(a_ref, r, tile_n, n_rows)      # (tn, d_pad)
    enc = _ENCODERS[mode](meta_ref[0], sigma_ref[0], r * tile_n, block_size)
    # MXU: (b, tn) @ (tn, d_pad) accumulated into the resident panel.
    acc_ref[...] += jnp.dot(enc.astype(a.dtype), a,
                            preferred_element_type=acc_ref.dtype)

    @pl.when(r == pl.num_programs(3) - 1)
    def _fold_gram():
        at = acc_ref[...]                     # (b, d_pad) complete A_tilde_k
        out_ref[...] += _gram_fold(at, at, mask_ref[kk])


def _kernel_tiled(mask_ref, meta_ref, sigma_ref, ai_ref, aj_ref, out_ref,
                  acc_i_ref, acc_j_ref, *, mode: str, block_size: int,
                  tile_n: int, n_rows: int):
    """General d-tiled grid: each program owns one (td, td) output tile and
    two (b, td) A_tilde column-panel accumulators.  On diagonal tiles
    (i == j) the j-panel is the i-panel, so its matmul is skipped and the
    fold contracts acc_i with itself."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    kk = pl.program_id(2)
    r = pl.program_id(3)

    @pl.when((kk == 0) & (r == 0))
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(r == 0)
    def _init_acc():
        acc_i_ref[...] = jnp.zeros_like(acc_i_ref)
        acc_j_ref[...] = jnp.zeros_like(acc_j_ref)

    # (b, tn) encode matrix for this (block, row-panel); padded rows carry
    # sigma 0 so they contribute nothing.
    enc = _ENCODERS[mode](meta_ref[0], sigma_ref[0], r * tile_n, block_size)
    ai = _panel(ai_ref, r, tile_n, n_rows)    # (tn, td) column panel i
    enc = enc.astype(ai.dtype)
    acc_i_ref[...] += jnp.dot(enc, ai, preferred_element_type=acc_i_ref.dtype)

    @pl.when(i != j)
    def _acc_j():
        acc_j_ref[...] += jnp.dot(enc, _panel(aj_ref, r, tile_n, n_rows),
                                  preferred_element_type=acc_j_ref.dtype)

    @pl.when(r == pl.num_programs(3) - 1)
    def _fold_gram():
        # Block k's panels are complete: fold its masked Gram tile.
        at_i = acc_i_ref[...]
        at_j = jnp.where(i == j, at_i, acc_j_ref[...])
        out_ref[...] += _gram_fold(at_i, at_j, mask_ref[kk])


@functools.partial(jax.jit,
                   static_argnames=("mode", "block_size", "tile_n", "d_tile",
                                    "interpret"))
def _sketch_gram(mask: jax.Array, meta: jax.Array, sigma: jax.Array,
                 a: jax.Array, *, mode: str, block_size: int, tile_n: int,
                 d_tile: int, interpret: bool) -> jax.Array:
    k, s, n = sigma.shape
    d = a.shape[1]
    tn = min(tile_n, max(8, n))
    td = max(MIN_D_TILE, d_tile + ((-d_tile) % 128))
    d_pad128 = d + ((-d) % 128)
    single = td >= d_pad128          # whole output fits one resident tile
    if single:
        td = d_pad128
    n_pad, d_pad = (-n) % tn, (-d) % td
    if n_pad:
        # Only the small (K, s, n) sign/bucket rows are padded (sign 0 =>
        # no contribution); A's overhanging panel is masked in-kernel, so
        # no padded copy of the (n, d) operand is ever made.
        sigma = jnp.pad(sigma, ((0, 0), (0, 0), (0, n_pad)))
        if mode == "count":
            meta = jnp.pad(meta, ((0, 0), (0, 0), (0, n_pad)))
    n_t, d_t = (n + n_pad) // tn, (d + d_pad) // td
    # Sign/bucket rows are (1, s, tn) lane blocks of (K, s, n); the SRHT
    # sampled rows are a (b, 1) sublane column of (K, b, 1).  The survivor
    # mask is one whole (K,) array in SMEM, read as a scalar per block.
    meta_spec = (pl.BlockSpec((1, s, tn), lambda i, j, kk, r: (kk, 0, r))
                 if mode == "count"
                 else pl.BlockSpec((1, block_size, 1),
                                   lambda i, j, kk, r: (kk, 0, 0)))
    if mode != "count":
        meta = meta[:, :, None]
    common = dict(mode=mode, block_size=block_size, tile_n=tn, n_rows=n)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        meta_spec,
        pl.BlockSpec((1, s, tn), lambda i, j, kk, r: (kk, 0, r)),
        pl.BlockSpec((tn, td), lambda i, j, kk, r: (r, i)),
    ]
    operands = [mask, meta, sigma.astype(jnp.float32),
                a.astype(jnp.float32)]
    if single:
        kernel = functools.partial(_kernel_single, **common)
        scratch = [pltpu.VMEM((block_size, td), jnp.float32)]
    else:
        kernel = functools.partial(_kernel_tiled, **common)
        in_specs.append(pl.BlockSpec((tn, td), lambda i, j, kk, r: (r, j)))
        operands.append(a.astype(jnp.float32))
        scratch = [pltpu.VMEM((block_size, td), jnp.float32),
                   pltpu.VMEM((block_size, td), jnp.float32)]

    vmem = fused_vmem_bytes(block_size, td, tn, s, single)
    out = pl.pallas_call(
        kernel,
        grid=(d_t, d_t, k, n_t),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((td, td), lambda i, j, kk, r: (i, j)),
        out_shape=jax.ShapeDtypeStruct((d + d_pad, d + d_pad), jnp.float32),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(vmem)),
        interpret=interpret,
    )(*operands)
    n_avail = jnp.maximum(mask.sum(), 1.0)
    return out[:d, :d] / n_avail


@functools.partial(jax.jit, static_argnames=("block_size", "tile_n",
                                             "d_tile", "interpret"))
def sketch_gram_count(h: jax.Array, sigma: jax.Array, a: jax.Array,
                      block_size: int, survivors: jax.Array, *,
                      tile_n: int = DEFAULT_TILE_N,
                      d_tile: int = None,
                      interpret: bool = False) -> jax.Array:
    """Fused count-sketch Gram: (K,n),(K,n),(n,d),(K,) -> (d,d).

    Equivalent to ``oversketch_gram(count_sketch_apply(h, sigma, a, b),
    survivors)`` with ``A_tilde`` kept in VMEM.  ``d_tile`` defaults to
    ``pick_d_tile`` (the largest output tile within the VMEM budget).
    """
    if d_tile is None:
        d_tile = pick_d_tile(block_size, a.shape[1], tile_n)
    return _sketch_gram(survivors.astype(jnp.float32), h[:, None, :],
                        sigma[:, None, :], a, mode="count",
                        block_size=block_size, tile_n=tile_n, d_tile=d_tile,
                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_size", "tile_n",
                                             "d_tile", "interpret"))
def sketch_gram_sjlt(h: jax.Array, sigma: jax.Array, a: jax.Array,
                     block_size: int, survivors: jax.Array, *,
                     tile_n: int = DEFAULT_TILE_N,
                     d_tile: int = None,
                     interpret: bool = False) -> jax.Array:
    """Fused SJLT Gram: (K,s,n),(K,s,n),(n,d),(K,) -> (d,d).

    h/sigma carry s bucket/sign layers per block (OSNAP, s nonzeros per
    row of A); the encode matrix sums the s signed one-hot layers in VMEM
    and scales by 1/sqrt(s), so intra-row collisions add exactly like the
    slot-summed segment-sum reference (``ref.sjlt_apply``).
    """
    if d_tile is None:
        d_tile = pick_d_tile(block_size, a.shape[1], tile_n,
                             nnz=h.shape[1])
    return _sketch_gram(survivors.astype(jnp.float32), h, sigma, a,
                        mode="count", block_size=block_size, tile_n=tile_n,
                        d_tile=d_tile, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tile_n", "d_tile",
                                             "interpret"))
def sketch_gram_srht(rows: jax.Array, sigma: jax.Array, a: jax.Array,
                     survivors: jax.Array, *,
                     tile_n: int = DEFAULT_TILE_N,
                     d_tile: int = None,
                     interpret: bool = False) -> jax.Array:
    """Fused SRHT Gram: (K,b),(K,n),(n,d),(K,) -> (d,d).

    rows are the b sampled Hadamard-row indices per block (in [0, n_pad));
    equivalent to the SRHT family's sign -> pad -> FWHT -> gather -> Gram
    chain, but block-local: the b needed mix rows are regenerated per
    row-panel so the (n_pad, d) mixed panel never exists.
    """
    b = rows.shape[1]
    if d_tile is None:
        d_tile = pick_d_tile(b, a.shape[1], tile_n)
    return _sketch_gram(survivors.astype(jnp.float32), rows,
                        sigma[:, None, :], a, mode="srht", block_size=b,
                        tile_n=tile_n, d_tile=d_tile, interpret=interpret)
