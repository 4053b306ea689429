"""Compile the main path's kernels for a TPU v5e chip that is described,
not attached, at real widths.

Nothing runs here: a pass means the chip's compiler accepts each Pallas
kernel compiled with ``interpret=False`` (its block tiling, its scoped-VMEM
request) and that the program fits one chip's 16 GiB of HBM.  The widths
are epsilon's published d = 2000 with n cut to 200,000 rows (the fig7
sketch: K = 148 blocks of b = 256) and a9a at its full 32,000 x 123 (the
fig8 sketch: K = 13 blocks of b = 128).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the worker that runs this file
keeps it until it exits.  Keep every such compile in this one file.
"""
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)            # chip_smoke.py lives at the repo root

from chip_smoke import PHASES  # noqa: E402
from repro.core.coded import make_code  # noqa: E402
from repro.core.sketch import CountSketch, apply_sketch  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.sketching.base import next_pow2  # noqa: E402

HBM_BYTES = 16 * 2 ** 30
# The phases chip_smoke.py runs on the chip: n, d, sketch, coded rows.
WIDTHS = {ph.name: ph for ph in PHASES}
SJLT_NNZ = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # Keep the TPU compiler's logs out of the temp directory.
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def _hbm_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


def _kernel_cases(width):
    ph = WIDTHS[width]
    n, d, br = ph.n, ph.d, ph.coded_block_rows
    k, b = ph.sketch.total_blocks, ph.sketch.block_size
    f32, i32, b1 = jnp.float32, jnp.int32, jnp.bool_
    code_x, code_xt = make_code(n, min(br, n)), make_code(d, min(br, d))
    return {
        "sketch_gram_count": (
            lambda h, s, a, m: ops.sketch_gram_count(h, s, a, b, m,
                                                     interpret=False),
            ((k, n), i32), ((k, n), f32), ((n, d), f32), ((k,), b1)),
        "sketch_gram_sjlt": (
            lambda h, s, a, m: ops.sketch_gram_sjlt(h, s, a, b, m,
                                                    interpret=False),
            ((k, SJLT_NNZ, n), i32), ((k, SJLT_NNZ, n), f32), ((n, d), f32),
            ((k,), b1)),
        "sketch_gram_srht": (
            lambda r, s, a, m: ops.sketch_gram_srht(r, s, a, m,
                                                    interpret=False),
            ((k, b), i32), ((k, n), f32), ((n, d), f32), ((k,), b1)),
        "count_sketch_apply": (
            lambda h, s, a: ops.count_sketch_apply(h, s, a, b,
                                                   interpret=False),
            ((k, n), i32), ((k, n), f32), ((n, d), f32)),
        "oversketch_gram": (
            lambda at, m: ops.oversketch_gram(at, m, interpret=False),
            ((k, b, d), f32), ((k,), b1)),
        # One streamed SRHT block, as sketching/srht.py applies it.
        "fwht": (
            lambda x: ops.fwht(x, interpret=False),
            ((1, next_pow2(n), d), f32)),
        # The product codes of X and X^T the gradient's matvecs read.
        "coded_block_matvec_x": (
            lambda e, x, er: ops.coded_block_matvec(e, x, er,
                                                    interpret=False),
            ((code_x.num_workers, code_x.block_rows, d), f32), ((d,), f32),
            ((code_x.num_workers,), b1)),
        "coded_block_matvec_xt": (
            lambda e, x, er: ops.coded_block_matvec(e, x, er,
                                                    interpret=False),
            ((code_xt.num_workers, code_xt.block_rows, n), f32),
            ((n,), f32), ((code_xt.num_workers,), b1)),
    }


KERNELS = list(_kernel_cases("a9a"))


# Highest precision splits f32 matmul operands into bf16 parts in VMEM,
# so it needs more scoped VMEM than the default (chip_smoke.py checks
# the Hessian at highest precision).
@pytest.mark.parametrize("precision", [None, "highest"])
@pytest.mark.parametrize("width", list(WIDTHS))
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, kernel, width, precision):
    fn, *shapes = _kernel_cases(width)[kernel]
    with jax.default_matmul_precision(precision):
        compiled = _compile(fn, one_chip, *shapes)
    # interpret=False lowers the kernel through Mosaic; an interpreted
    # kernel would compile too, but as plain HLO with no custom call.
    assert "tpu_custom_call" in compiled.as_text()
    assert _hbm_bytes(compiled) <= HBM_BYTES


@pytest.mark.parametrize("width", list(WIDTHS))
def test_streamed_apply_sketch_fits_one_chip(one_chip, width):
    """The default (use_kernels=False) sketch path: blocks stream through
    lax.map, so it holds one signed (n, d) panel, never (K, n, d)."""
    ph = WIDTHS[width]
    n, d = ph.n, ph.d
    k, b = ph.sketch.total_blocks, ph.sketch.block_size

    def fn(h, s, a):
        return apply_sketch(CountSketch(h=h, sigma=s, block_size=b), a)

    compiled = _compile(fn, one_chip, ((k, n), jnp.int32),
                        ((k, n), jnp.float32), ((n, d), jnp.float32))
    assert _hbm_bytes(compiled) <= HBM_BYTES
    # Temporaries stay within a few (n, d) panels, not K of them.
    assert compiled.memory_analysis().temp_size_in_bytes <= 4 * n * d * 4
