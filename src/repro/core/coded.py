"""Coded matrix-vector multiplication with a 2-D product code (paper Alg. 1).

The data matrix's row-blocks are laid out on a g x g grid and extended with a
parity column (row sums), a parity row (column sums) and a corner (total sum),
giving (g+1)^2 worker tasks for T = g^2 systematic blocks.  Every row and
column of the extended grid satisfies a single-parity-check constraint, so a
*peeling decoder* recovers any erasure pattern with at most one missing cell
per row xor column per round (and most patterns with up to 2g+1 erasures).

The systematic blocks are the data itself, zero-padded, so the code keeps
only its 2g+1 parity blocks (``encode_2d``): the products of the systematic
cells are read from A v, and those of the parity cells from the parity
blocks.  The code of A^T is encoded from A's column blocks, with no
transpose of A.  ``encode_full`` builds the whole grid, which the
distributed path takes and the tests compare against.

Encoding happens once (the paper amortizes it across iterations since the data
matrix is fixed); decode is a cheap `lax.fori_loop` of vectorized peel rounds.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.obs import wall


@dataclasses.dataclass(frozen=True)
class ProductCode:
    """Static geometry of the 2-D product code."""

    num_blocks: int   # T systematic row blocks (pre-padding)
    block_rows: int   # b rows per block
    grid: int         # g, where g*g >= T

    @property
    def num_workers(self) -> int:
        return (self.grid + 1) ** 2

    @property
    def padded_blocks(self) -> int:
        return self.grid * self.grid


def make_code(num_rows: int, block_rows: int) -> ProductCode:
    t = -(-num_rows // block_rows)
    g = int(math.ceil(math.sqrt(t)))
    return ProductCode(num_blocks=t, block_rows=block_rows, grid=g)


def _row_block_parity(a: jax.Array, code: ProductCode) -> jax.Array:
    """The parity of the grid of A's row blocks, A zero-padded to g^2 b
    rows: (2g+1, b, s).  The whole grid rows are read in place, one per
    step of a scan; only the last, ragged one is padded, as a copy of
    fewer than g b rows."""
    g, b = code.grid, code.block_rows
    rows, s = a.shape
    q = rows // (g * b)                   # grid rows of whole blocks

    def grid_row(col_sums, r):
        blocks = jax.lax.dynamic_slice_in_dim(a, r * g * b, g * b)
        blocks = blocks.reshape(g, b, s)
        return col_sums + blocks, blocks.sum(axis=0)

    col_sums, row_sums = jax.lax.scan(
        grid_row, jnp.zeros((g, b, s), a.dtype), jnp.arange(q))
    if q < g:
        rest = a[q * g * b:]
        last = jnp.pad(rest, ((0, g * b - rest.shape[0]), (0, 0)))
        last = last.reshape(g, b, s)
        row_sums = jnp.concatenate([
            row_sums, last.sum(axis=0, keepdims=True),
            jnp.zeros((g - q - 1, b, s), a.dtype)])
        col_sums = col_sums + last
    return jnp.concatenate([row_sums, col_sums,
                            row_sums.sum(axis=0, keepdims=True)])


def _column_block_parity(a: jax.Array, code: ProductCode) -> jax.Array:
    """The parity of the grid of A's column blocks, each parity block an
    (n, b) block in A's layout: (2g+1, n, b), each summed straight from
    A's columns."""
    g, b, t = code.grid, code.block_rows, code.num_blocks
    n = a.shape[0]
    blocks = [a[:, k * b:(k + 1) * b] for k in range(t)]
    blocks[-1] = jnp.pad(blocks[-1], ((0, 0), (0, b - blocks[-1].shape[1])))

    def total(ks):
        return functools.reduce(jnp.add, [blocks[k] for k in ks],
                                jnp.zeros((n, b), a.dtype))

    return jnp.stack([total(range(r * g, min(r * g + g, t)))
                      for r in range(g)]
                     + [total(range(c, t, g)) for c in range(g)]
                     + [total(range(t))])


@functools.partial(jax.jit, static_argnames=("code", "transpose"))
def encode_2d(a: jax.Array, code: ProductCode,
              transpose: bool = False) -> jax.Array:
    """The 2g+1 parity blocks of the code of A's rows: (rows, s) ->
    (2g+1, b, s), the g row parities, the g column parities, the corner.

    With ``transpose`` the code is of A^T, whose row blocks are A's column
    blocks: (n, cols) -> (2g+1, n, b), each parity kept in A's layout
    (block p of the code of A^T is ``parity[p].T``), summed from A's
    columns with no transpose of A.
    """
    if transpose:
        return _column_block_parity(a, code)
    return _row_block_parity(a, code)


@functools.partial(jax.jit, static_argnames=("code",))
def encode_full(a: jax.Array, code: ProductCode) -> jax.Array:
    """A (rows, s) -> the whole code ((g+1), (g+1), b, s): the systematic
    blocks (A zero-padded to g^2 * b rows) and their parities, one block
    per worker, as ``distributed_coded_matvec`` takes it."""
    g, b = code.grid, code.block_rows
    rows, s = a.shape
    pad = code.padded_blocks * b - rows
    a_pad = jnp.pad(a, ((0, pad), (0, 0)))
    blocks = a_pad.reshape(g, g, b, s)
    row_par = blocks.sum(axis=1, keepdims=True)            # (g, 1, b, s)
    top = jnp.concatenate([blocks, row_par], axis=1)       # (g, g+1, b, s)
    col_par = top.sum(axis=0, keepdims=True)               # (1, g+1, b, s)
    return jnp.concatenate([top, col_par], axis=0)         # (g+1, g+1, b, s)


def coded_block_products(enc: jax.Array, x: jax.Array) -> jax.Array:
    """Every worker's task on the whole code: its block times x.
    ((g+1),(g+1),b,s) -> (...,b)."""
    return jnp.einsum("rcbs,s->rcb", enc, x)


def block_products(a: jax.Array, parity: jax.Array, v: jax.Array,
                   code: ProductCode, transpose: bool = False) -> jax.Array:
    """Every worker's task, ((g+1), (g+1), b), from A and its parity blocks
    (``encode_2d``): the systematic cells are A v (A^T v with
    ``transpose``) zero-padded to g^2 b and cut into blocks, the parity
    cells the parity blocks times v."""
    g, b = code.grid, code.block_rows
    if transpose:
        sys = v @ a
        par = jnp.einsum("pnb,n->pb", parity, v)
    else:
        sys = a @ v
        par = jnp.einsum("pbs,s->pb", parity, v)
    sys = jnp.pad(sys, (0, code.padded_blocks * b - sys.shape[0]))
    top = jnp.concatenate([sys.reshape(g, g, b), par[:g, None]], axis=1)
    # The last grid row: the column parities, then the corner.
    return jnp.concatenate([top, par[None, g:]], axis=0)


def _peel_axis(vals: jax.Array, known: jax.Array, axis: int) -> Tuple[jax.Array, jax.Array]:
    """One peel round along rows (axis=0 constraints iterate over columns) or
    columns.  Constraint per line: sum(systematic) - parity_cell = 0."""
    n = vals.shape[0]  # (g+1, g+1, b), square
    sgn = jnp.where(jnp.arange(n) == n - 1, -1.0, 1.0)
    if axis == 0:   # row constraints: sum over c of sgn[c] * v[r, c] = 0
        sgn_rc = sgn[None, :]
        reduce_axis = 1
    else:           # column constraints: sum over r of sgn[r] * v[r, c] = 0
        sgn_rc = sgn[:, None]
        reduce_axis = 0
    kf = known.astype(vals.dtype)
    line_sum = (vals * (sgn_rc * kf)[..., None]).sum(axis=reduce_axis,
                                                     keepdims=True)
    missing = (~known).sum(axis=reduce_axis, keepdims=True)
    recover_line = missing == 1
    candidate = -line_sum * sgn_rc[..., None]
    rec_mask = recover_line & (~known)
    vals = jnp.where(rec_mask[..., None], candidate, vals)
    known = known | rec_mask
    return vals, known


def peel_decode(products: jax.Array, known: jax.Array,
                code: ProductCode) -> Tuple[jax.Array, jax.Array]:
    """Peeling decoder.  products ((g+1),(g+1),b) with erased cells arbitrary,
    known ((g+1),(g+1)) bool.  Returns (systematic blocks (g,g,b), success)."""
    vals = jnp.where(known[..., None], products, 0.0)

    def round_fn(_, carry):
        v, k = carry
        v, k = _peel_axis(v, k, axis=0)
        v, k = _peel_axis(v, k, axis=1)
        return v, k

    vals, known = jax.lax.fori_loop(0, code.grid + 1, round_fn, (vals, known))
    g = code.grid
    success = known[:g, :g].all()
    return vals[:g, :g], success


@jax.jit
def parity_residuals(products: jax.Array, known: jax.Array):
    """Per-line parity-check residuals of a coded product grid.

    products: ((g+1), (g+1), b) block products (erased cells arbitrary);
    known: ((g+1), (g+1)) bool arrival mask.  Every row and column of the
    extended grid satisfies sum(systematic) - parity = 0, so over known
    cells the signed line sums are exact-zero residual vectors unless a
    known cell's value is corrupted.  Returns ``(row_res, row_mag,
    col_res, col_mag)``: the L2 residual of each line's constraint and
    the L2 magnitude of the line's known values (the relative-tolerance
    scale).  Unknown cells contribute zero to both, so the caller must
    gate on line completeness — a line with a missing cell has no
    checkable constraint.
    """
    n = products.shape[0]
    sgn = jnp.where(jnp.arange(n) == n - 1, -1.0, 1.0)
    vals = jnp.where(known[..., None], products, 0.0).astype(jnp.float32)
    row_res = jnp.linalg.norm(jnp.einsum("c,rcb->rb", sgn, vals), axis=-1)
    col_res = jnp.linalg.norm(jnp.einsum("r,rcb->cb", sgn, vals), axis=-1)
    row_mag = jnp.sqrt((vals ** 2).sum(axis=(1, 2)))
    col_mag = jnp.sqrt((vals ** 2).sum(axis=(0, 2)))
    return row_res, row_mag, col_res, col_mag


def decode_matvec(products: jax.Array, known: jax.Array, code: ProductCode,
                  out_rows: int) -> Tuple[jax.Array, jax.Array]:
    """Full decode back to y = A @ x of length out_rows."""
    sys_blocks, ok = peel_decode(products, known, code)
    y = sys_blocks.reshape(code.padded_blocks * code.block_rows)
    return y[:out_rows], ok


def detect_corrupted(products: jax.Array, known: jax.Array,
                     code: ProductCode, rtol: float = 1e-3) -> jax.Array:
    """Parity-check detection of corrupted (not merely missing) products.

    The same single-parity-check constraints the peeling decoder uses for
    erasures double as integrity checks: a *corrupted* known cell violates
    both its row and its column constraint, while an erased cell merely
    makes its two lines uncheckable (a constraint needs every cell of the
    line).  A known cell is flagged when at least one of its checks fires
    and the other fires or is uncheckable — exact for a single corrupted
    cell in a fully-known grid, conservative when corruption shares lines
    with erasures (over-flagging demotes innocents to erasures; an
    undecodable pattern then falls through to the master's billed full
    relaunch, never to a silently wrong result).

    Returns a ((g+1), (g+1)) bool grid of cells to demote to erasures,
    feeding the existing ``peel_decode`` path unchanged.
    """
    del code  # geometry is carried by the grid shape itself
    row_res, row_mag, col_res, col_mag = parity_residuals(products, known)
    full_rows = known.all(axis=1)
    full_cols = known.all(axis=0)
    tiny = jnp.finfo(jnp.float32).tiny
    rows_bad = full_rows & (row_res > rtol * (row_mag + tiny))
    cols_bad = full_cols & (col_res > rtol * (col_mag + tiny))
    flagged = ((rows_bad[:, None] & cols_bad[None, :])
               | (rows_bad[:, None] & ~full_cols[None, :])
               | (cols_bad[None, :] & ~full_rows[:, None]))
    return known & flagged


def verified_decode(products: jax.Array, arrived: jax.Array,
                    code: ProductCode, out_rows: int, rtol: float = 1e-3
                    ) -> Tuple[Optional[jax.Array], bool, int]:
    """Corruption-tolerant decode: detect, erase, peel, then verify.

    1. ``detect_corrupted`` localizes corrupted cells with at least one
       checkable line and demotes them to erasures.
    2. The peeling decoder runs on the surviving cells (undecodable
       pattern => give up).
    3. Verification: the decoded systematic blocks extend to a *unique*
       codeword grid (parities are exact sums of block products — the
       products are linear in the blocks); any surviving arrived cell
       that disagrees with that extension witnesses corruption the
       detector could not localize, so the decode is rejected rather
       than silently wrong.

    Returns ``(y, ok, flagged)``: the decoded matvec (None when
    rejected), whether it is trustworthy, and how many cells the
    detector demoted.  The one blind spot is fundamental, not a decoder
    weakness: a corrupted systematic cell whose three witnesses (its row
    parity, its column parity, the corner) are all erased leaves the
    arrived data exactly consistent with a valid codeword carrying the
    corrupted value — no decoder can tell the difference.  Callers
    relaunch on ``ok=False`` (the paper's straggler fallback, reused).
    """
    flagged = detect_corrupted(products, arrived, code, rtol)
    n_flagged = jnp.sum(flagged)
    with TraceAnnotation(wall.SYNC_DECODE):
        n_flagged = int(n_flagged)
    known = arrived & ~flagged
    sys_blocks, ok = peel_decode(products, known, code)
    with TraceAnnotation(wall.SYNC_DECODE):
        ok = bool(ok)
    if not ok:
        return None, False, n_flagged
    # Unique codeword extension of the decoded systematic part.
    row_par = sys_blocks.sum(axis=1, keepdims=True)
    top = jnp.concatenate([sys_blocks, row_par], axis=1)
    col_par = top.sum(axis=0, keepdims=True)
    full = jnp.concatenate([top, col_par], axis=0)     # (g+1, g+1, b)
    resid = jnp.linalg.norm(full - products, axis=-1)
    mag = jnp.linalg.norm(full, axis=-1) + jnp.finfo(jnp.float32).tiny
    mismatch = (known & (resid > rtol * mag)).any()
    with TraceAnnotation(wall.SYNC_DECODE):
        mismatch = bool(mismatch)
    if mismatch:
        return None, False, n_flagged
    y = sys_blocks.reshape(code.padded_blocks * code.block_rows)
    return y[:out_rows], True, n_flagged


@functools.partial(jax.jit, static_argnames=("code", "transpose"))
def coded_matvec(a: jax.Array, parity: jax.Array, v: jax.Array,
                 code: ProductCode, erased: Optional[jax.Array] = None,
                 transpose: bool = False) -> Tuple[jax.Array, jax.Array]:
    """End-to-end straggler-resilient A v (A^T v with ``transpose``) from A
    and its parity blocks (``encode_2d``).

    erased: bool ((g+1),(g+1)) straggler mask (True = missing).  None = none.
    """
    prods = block_products(a, parity, v, code, transpose)
    if erased is None:
        known = jnp.ones(prods.shape[:2], dtype=bool)
    else:
        known = ~erased
    return decode_matvec(prods, known, code,
                         a.shape[1] if transpose else a.shape[0])


# ---------------------------------------------------------------------------
# Distributed (shard_map) path: one coded block per device slot.
# ---------------------------------------------------------------------------

def distributed_coded_matvec(enc_flat: jax.Array, x: jax.Array,
                             erased_flat: jax.Array, code: ProductCode,
                             out_rows: int, *, mesh: jax.sharding.Mesh,
                             worker_axis: str) -> Tuple[jax.Array, jax.Array]:
    """Coded matvec with worker tasks sharded over ``worker_axis``.

    enc_flat: (W_pad, b, s) blocks of the whole code (``encode_full``)
       flattened row-major and zero-padded to a multiple of the axis size
       (W_pad >= (g+1)^2).
    erased_flat: (W_pad,) straggler erasures.  Erased workers' products are
       masked before the gather — simulating "the master never saw them".
    """
    from jax.sharding import PartitionSpec as P

    def local(enc_l, x_l, er_l):
        prod = jnp.einsum("wbs,s->wb", enc_l, x_l)
        prod = jnp.where(er_l[:, None], 0.0, prod)
        return jax.lax.all_gather(prod, worker_axis, tiled=True)

    prods_flat = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(worker_axis), P(), P(worker_axis)),
        out_specs=P(), check_vma=False)(enc_flat, x, erased_flat)
    w = code.num_workers
    g1 = code.grid + 1
    prods = prods_flat[:w].reshape(g1, g1, code.block_rows)
    known = (~erased_flat[:w]).reshape(g1, g1)
    return decode_matvec(prods, known, code, out_rows)
