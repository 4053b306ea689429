"""Public jitted wrappers for the Pallas kernels.

On a TPU the kernels are compiled by Mosaic; on any other backend they
run under the Pallas interpreter (``interpret=True``), which executes the
kernel body with jax ops and checks results, not speed.  The mode follows
``jax.default_backend()``; callers can force either.  A test that compiles
for a described chip from a CPU process must pass ``interpret=False``
itself (see ``tests/test_tpu_compile.py``).

Kernel time is read from the device trace: the Hessian's kernels run
under the device scopes that ``repro.obs.wall`` names.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import coded_matvec as _cmv
from repro.kernels import count_sketch as _cs
from repro.kernels import oversketch_matmul as _og
from repro.kernels import sketch_gram as _sg
from repro.kernels import srht as _srht


def _interpret(explicit: Optional[bool]) -> bool:
    if explicit is not None:
        return explicit
    return jax.default_backend() != "tpu"


def count_sketch_apply(h: jax.Array, sigma: jax.Array, a: jax.Array,
                       block_size: int,
                       interpret: Optional[bool] = None) -> jax.Array:
    """S^T A for all K sketch blocks: (K,n),(K,n),(n,d) -> (K,b,d)."""
    return _cs.count_sketch_apply(h, sigma, a, block_size,
                                  interpret=_interpret(interpret))


def oversketch_gram(a_tilde: jax.Array, survivors: jax.Array,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Masked Gram (K,b,d),(K,) -> (d,d), rescaled by survivor count."""
    return _og.oversketch_gram(a_tilde, survivors,
                               interpret=_interpret(interpret))


def sketch_gram_count(h: jax.Array, sigma: jax.Array, a: jax.Array,
                      block_size: int, survivors: jax.Array,
                      interpret: Optional[bool] = None,
                      tile_n: int = _sg.DEFAULT_TILE_N,
                      d_tile: Optional[int] = None) -> jax.Array:
    """Fused count-sketch Gram (K,n),(K,n),(n,d),(K,) -> (d,d); A_tilde
    never hits HBM (streaming apply + in-register masked Gram).  The
    output is d-tiled past the VMEM budget (``d_tile`` defaults to
    ``pick_d_tile``; see ``fused_path`` for which grid a shape gets)."""
    return _sg.sketch_gram_count(h, sigma, a, block_size, survivors,
                                 tile_n=tile_n, d_tile=d_tile,
                                 interpret=_interpret(interpret))


def sketch_gram_sjlt(h: jax.Array, sigma: jax.Array, a: jax.Array,
                     block_size: int, survivors: jax.Array,
                     interpret: Optional[bool] = None,
                     tile_n: int = _sg.DEFAULT_TILE_N,
                     d_tile: Optional[int] = None) -> jax.Array:
    """Fused SJLT Gram (K,s,n),(K,s,n),(n,d),(K,) -> (d,d); the s signed
    one-hot layers are summed into the encode matrix in VMEM."""
    return _sg.sketch_gram_sjlt(h, sigma, a, block_size, survivors,
                                tile_n=tile_n, d_tile=d_tile,
                                interpret=_interpret(interpret))


def sketch_gram_srht(rows: jax.Array, sigma: jax.Array, a: jax.Array,
                     survivors: jax.Array,
                     interpret: Optional[bool] = None,
                     tile_n: int = _sg.DEFAULT_TILE_N,
                     d_tile: Optional[int] = None) -> jax.Array:
    """Fused SRHT Gram (K,b),(K,n),(n,d),(K,) -> (d,d); the Hadamard mix
    rows are regenerated block-locally so the mixed panel never exists."""
    return _sg.sketch_gram_srht(rows, sigma, a, survivors,
                                tile_n=tile_n, d_tile=d_tile,
                                interpret=_interpret(interpret))


# Grid-choice helpers, re-exported for benchmarks and tests: which fused
# grid a (block_size, d) problem gets ("fused" single-tile vs
# "fused_tiled") and the d_tile the default routing picks.
fused_path = _sg.fused_path
pick_d_tile = _sg.pick_d_tile


def fwht(x: jax.Array, interpret: Optional[bool] = None) -> jax.Array:
    """Orthonormal Walsh-Hadamard transform along axis 1 of (K, n, d).
    Dispatches monolithic-panel vs two-pass tiled on the VMEM budget."""
    return _srht.fwht(x, interpret=_interpret(interpret))


def fwht_two_pass(x: jax.Array,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Force the two-pass tiled FWHT (local + across Kronecker passes)."""
    return _srht.fwht_two_pass(x, interpret=_interpret(interpret))


def coded_block_matvec(enc: jax.Array, x: jax.Array, erased: jax.Array,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Masked coded block products (W,b,s),(s,),(W,) -> (W,b)."""
    return _cmv.coded_block_matvec(enc, x, erased,
                                   interpret=_interpret(interpret))
