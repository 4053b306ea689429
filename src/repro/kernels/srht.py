"""Pallas TPU kernels: batched blocked fast Walsh-Hadamard transform (FWHT).

The SRHT sketch block is ``S_i^T A = sqrt(n_pad/b) * P_i H_norm (D_i A)``:
random signs, an orthonormal Hadamard mix, then b sampled rows.  The mix is
the hot loop.  A butterfly FWHT is O(n log n) but VPU-bound scalar shuffling;
on TPU we instead use the Sylvester identity ``H_{n1*n2} = H_{n1} (x) H_{n2}``
(x = Kronecker) to express the transform of a (n1*n2, td) panel as TWO MXU
matmuls with small dense Hadamard matrices:

    X = reshape(x, (n1, n2, td));   Y = H_{n1} @_1 X;   Y = H_{n2} @_2 Y

The Hadamard factors are materialized in VMEM from ``broadcasted_iota`` via
``H[i, j] = (-1)^popcount(i & j)`` — no host constants, same trick as the
count-sketch one-hot kernel.  Arithmetic intensity rises from O(1) to
O(sqrt(n)) and the op becomes MXU-bound.

Two kernels share that identity:

* ``_fwht_panel`` (monolithic): grid (K, d_tiles), each invocation holds one
  full (n, td) panel in VMEM and does both contractions.  VMEM ~
  2 * n * td * 4 bytes (in + out blocks) + (n1^2 + n2^2) * 4 for the
  factors — fine up to n ~ 4096 at td = 256, but n >> VMEM cannot compile.

* ``fwht_two_pass`` (tiled): the same Kronecker split executed as two
  pallas_calls that never hold a full panel.  Split the row index
  g = q * n2 + r (q = high bits, r = low bits); then
  ``H_n[g, g'] = H_{n1}[q, q'] * H_{n2}[r, r']`` and the transform
  factorizes into a LOCAL pass (contract r' with H_{n2} inside each
  contiguous n2-row chunk; grid (K, n1, d_tiles), VMEM ~ 2 * n2 * td * 4)
  and an ACROSS pass (contract q' with H_{n1}, a strided matmul over the
  chunk axis; grid (K, n2/tr, d_tiles), VMEM ~ 2 * n1 * tr * td * 4).
  Peak VMEM drops from O(n * td) to O(sqrt(n) * td) and any power-of-two n
  compiles.  The intermediate makes one HBM round-trip — the price of
  streaming; the factor matrices stay O(n1^2 + n2^2) = O(n).

``fwht`` dispatches between them on the documented VMEM panel budget.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sketch_gram import vmem_limit


DEFAULT_TILE_D = 256
DEFAULT_TILE_R = 8
# VMEM budget for one FWHT kernel, as ``panel_vmem_bytes`` /
# ``two_pass_vmem_bytes`` count it.  The monolithic kernel runs while its
# panel fits; past that the dispatcher switches to the two-pass kernel,
# whose column tile shrinks until it fits.  Each call asks the compiler
# for ``sketch_gram.vmem_limit`` of its counted bytes.
MAX_PANEL_BYTES = 16 * 1024 * 1024


def _split_pow2(n: int):
    log = int(math.log2(n)) if n > 1 else 0
    n1 = 1 << (log // 2)
    return n1, n // n1


def _hadamard(n: int, dtype) -> jax.Array:
    """Unnormalized Sylvester-Hadamard matrix H[i,j] = (-1)^popcount(i&j)."""
    i = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    bits = jax.lax.population_count(jnp.bitwise_and(i, j))
    return jnp.where(bits % 2 == 0, 1.0, -1.0).astype(dtype)


def _panel_kernel(x_ref, out_ref, *, n1: int, n2: int):
    x = x_ref[0]                                    # (n1*n2, td)
    td = x.shape[1]
    h1 = _hadamard(n1, x.dtype)
    h2 = _hadamard(n2, x.dtype)
    # Contract the n1 (high-bit) index: (n1, n1) @ (n1, n2*td).
    y = jax.lax.dot_general(h1, x.reshape(n1, n2 * td),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # Contract the n2 (low-bit) index: (n2, n2) x (n1, n2, td) -> (n2, n1, td).
    y = jax.lax.dot_general(h2, y.reshape(n1, n2, td),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y = y.transpose(1, 0, 2).reshape(n1 * n2, td)
    out_ref[0] = y * (1.0 / math.sqrt(float(n1 * n2)))


def _local_kernel(x_ref, out_ref, *, n2: int):
    """Pass A: one contiguous (n2, td) chunk, contract r' with H_{n2}."""
    x = x_ref[0, 0]                                 # (n2, td)
    h2 = _hadamard(n2, x.dtype)
    out_ref[0, 0] = jax.lax.dot_general(
        h2, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _across_kernel(x_ref, out_ref, *, n1: int, scale: float):
    """Pass B: a strided (n1, tr, td) slab, contract q' with H_{n1}."""
    x = x_ref[0]                                    # (n1, tr, td)
    tr, td = x.shape[1], x.shape[2]
    h1 = _hadamard(n1, x.dtype)
    y = jax.lax.dot_general(h1, x.reshape(n1, tr * td),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    out_ref[0] = y.reshape(n1, tr, td) * scale


def _check_pow2(n: int) -> None:
    if n & (n - 1):
        raise ValueError(f"fwht length {n} must be a power of two")


def _tile_d(tile_d: int, d: int) -> int:
    """Column tile: a multiple of the 128-lane width, at most tile_d.  The
    last tile may overhang d; columns transform independently, so the
    overhang only reaches output columns past d, which are never stored."""
    return max(128, min(tile_d, d + ((-d) % 128)) // 128 * 128)


def _params(vmem_bytes: int):
    # Same rule as the fused Gram: counted bytes, half again for highest
    # precision's bf16 operand splits, plus headroom.
    return pltpu.CompilerParams(vmem_limit_bytes=vmem_limit(vmem_bytes))


def two_pass_vmem_bytes(n: int, tile_d: int,
                        tile_r: int = DEFAULT_TILE_R) -> int:
    """VMEM of the two-pass kernel's larger pass, f32: double-buffered in
    and out blocks, three block-sized temporaries and the Hadamard factor
    — (n2, td) blocks for the local pass, (n1, tr, td) slabs for the
    across pass.  An upper bound on the smallest scoped-VMEM limit the
    v5e compiler accepts (15.0 against 13.0 MiB at n = 2^18, d = 2000)."""
    n1, n2 = _split_pow2(max(n, 1))
    tr = min(tile_r, n2)
    return 4 * max(7 * n2 * tile_d + n2 * n2,
                   7 * n1 * tr * tile_d + n1 * n1)


def _two_pass_tile_d(n: int, tile_d: int, d: int, tile_r: int,
                     max_bytes: int) -> int:
    """Widest column tile whose two-pass working set fits max_bytes
    (floor 128, the lane width)."""
    td = _tile_d(tile_d, d)
    while td > 128 and two_pass_vmem_bytes(n, td, tile_r) > max_bytes:
        td -= 128
    return td


@functools.partial(jax.jit, static_argnames=("tile_d", "tile_r", "interpret"))
def fwht_two_pass(x: jax.Array, *, tile_d: int = DEFAULT_TILE_D,
                  tile_r: int = DEFAULT_TILE_R,
                  interpret: bool = False) -> jax.Array:
    """Two-pass tiled orthonormal FWHT along axis 1 of (K, n, d).

    Kronecker decomposition streamed as local + across passes so VMEM
    holds O(sqrt(n) * tile) instead of a full (n, tile_d) panel; any
    power-of-two n compiles.  Matches ``fwht`` / the butterfly oracle.
    """
    k, n, d = x.shape
    _check_pow2(n)
    n1, n2 = _split_pow2(n)
    tr = min(tile_r, n2)                 # both powers of two => tr | n2
    td = _two_pass_tile_d(n, tile_d, d, tile_r, MAX_PANEL_BYTES)
    d_t = pl.cdiv(d, td)
    params = _params(two_pass_vmem_bytes(n, td, tile_r))
    x4 = x.astype(jnp.float32).reshape(k, n1, n2, d)

    mid = pl.pallas_call(
        functools.partial(_local_kernel, n2=n2),
        grid=(k, n1, d_t),
        in_specs=[pl.BlockSpec((1, 1, n2, td), lambda kk, q, j: (kk, q, 0, j))],
        out_specs=pl.BlockSpec((1, 1, n2, td), lambda kk, q, j: (kk, q, 0, j)),
        out_shape=jax.ShapeDtypeStruct((k, n1, n2, d), jnp.float32),
        compiler_params=params,
        interpret=interpret,
    )(x4)

    out = pl.pallas_call(
        functools.partial(_across_kernel, n1=n1,
                          scale=1.0 / math.sqrt(float(n))),
        grid=(k, n2 // tr, d_t),
        in_specs=[pl.BlockSpec((1, n1, tr, td),
                               lambda kk, m, j: (kk, 0, m, j))],
        out_specs=pl.BlockSpec((1, n1, tr, td),
                               lambda kk, m, j: (kk, 0, m, j)),
        out_shape=jax.ShapeDtypeStruct((k, n1, n2, d), jnp.float32),
        compiler_params=params,
        interpret=interpret,
    )(mid)
    return out.reshape(k, n, d)


def panel_vmem_bytes(n: int, tile_d: int = DEFAULT_TILE_D,
                     d: int = DEFAULT_TILE_D) -> int:
    """VMEM the monolithic kernel allocates, f32: double-buffered in and
    out (n, td) panels, two panel-sized matmul temporaries and the two
    Hadamard factors (the dispatch quantity; see kernels/README.md)."""
    td = _tile_d(tile_d, d)
    n1, n2 = _split_pow2(max(n, 1))
    return 4 * (6 * n * td + n1 * n1 + n2 * n2)


@functools.partial(jax.jit, static_argnames=("tile_d", "interpret",
                                             "max_panel_bytes"))
def fwht(x: jax.Array, *, tile_d: int = DEFAULT_TILE_D,
         interpret: bool = False,
         max_panel_bytes: int = MAX_PANEL_BYTES) -> jax.Array:
    """Orthonormal Walsh-Hadamard transform along axis 1 of (K, n, d).

    n must be a power of two (callers zero-pad; padded rows mix harmlessly
    since the transform is linear).  Satisfies fwht(fwht(x)) == x.
    Dispatches to the monolithic panel kernel while its panel fits
    ``max_panel_bytes`` of VMEM, else to the two-pass tiled kernel.
    """
    k, n, d = x.shape
    _check_pow2(n)
    vmem = panel_vmem_bytes(n, tile_d, d)
    if vmem > max_panel_bytes:
        return fwht_two_pass(x, tile_d=tile_d, interpret=interpret)
    n1, n2 = _split_pow2(n)
    td = _tile_d(tile_d, d)

    return pl.pallas_call(
        functools.partial(_panel_kernel, n1=n1, n2=n2),
        grid=(k, pl.cdiv(d, td)),
        in_specs=[pl.BlockSpec((1, n, td), lambda kk, j: (kk, 0, j))],
        out_specs=pl.BlockSpec((1, n, td), lambda kk, j: (kk, 0, j)),
        out_shape=jax.ShapeDtypeStruct((k, n, d), jnp.float32),
        compiler_params=_params(vmem),
        interpret=interpret,
    )(x.astype(jnp.float32))
