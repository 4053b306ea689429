"""Compile the solve's device programs for a TPU v5e chip that is
described, not attached, at real widths.

Nothing runs here: a pass means the chip's compiler accepts each program,
the count-sketch Pallas kernel compiled with ``interpret=False`` among
them (its block tiling, its scoped-VMEM request), and that the program
fits one chip's 16 GiB of HBM.  The widths are epsilon's published
d = 2000 with n cut to 200,000 rows (the fig7 sketch: K = 148 blocks of
b = 256), a9a at its full 32,000 x 123 (the fig8 sketch: K = 13 blocks of
b = 128) and the paper's synthetic problem at its full 300,000 x 3000
(its sketch: K = 148 blocks of b = 256), with the product codes held as
parity only.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the worker that runs this file
keeps it until it exits.  Keep every such compile in this one file.
"""
import base64
import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)            # chip_smoke.py lives at the repo root

from chip_smoke import PHASES, Phase  # noqa: E402
from repro import sketching  # noqa: E402
from repro.core import coded  # noqa: E402
from repro.core.coded import make_code  # noqa: E402
from repro.core.newton import (_jitted_distavg_direction,  # noqa: E402
                               _jitted_sketched_hessian)
from repro.core.objectives import Dataset, LogisticRegression  # noqa: E402
from repro.core.sketch import (MXU_MAX_BLOCK_SIZE, CountSketch,  # noqa: E402
                               OverSketchConfig, apply_sketch)
from repro.kernels.count_sketch import (VMEM_BUDGET_BYTES,  # noqa: E402
                                        VMEM_HEADROOM_BYTES,
                                        count_sketch_apply, pick_tiles,
                                        vmem_bytes)

HBM_BYTES = 16 * 2 ** 30
# The paper's synthetic logistic problem (arXiv:1903.08857, Sec. 5) at its
# published size, as bench/configs/synthetic.json runs it.
SYNTHETIC = dict(n=300_000, d=3000, coded_block_rows=256,
                 sketch=OverSketchConfig(30208, 256, 0.25))
# The phases chip_smoke.py runs on the chip, and the synthetic problem:
# n, d, sketch, coded rows.
WIDTHS = {ph.name: ph for ph in PHASES}
WIDTHS["synthetic"] = Phase("synthetic", SYNTHETIC["n"], SYNTHETIC["d"],
                            100_000, SYNTHETIC["sketch"],
                            SYNTHETIC["coded_block_rows"])


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


def _programs(width):
    """The solve's device programs at a width: (program, its argument
    shapes, whether the MXU count-sketch kernel is in it)."""
    ph = WIDTHS[width]
    n, d, br = ph.n, ph.d, ph.coded_block_rows
    k, b = ph.sketch.total_blocks, ph.sketch.block_size
    f32, i32, b1 = jnp.float32, jnp.int32, jnp.bool_
    code_x, code_xt = make_code(n, min(br, n)), make_code(d, min(br, d))
    hessian = _jitted_sketched_hessian(
        LogisticRegression(lam=1e-5), sketching.get("oversketch", ph.sketch))
    return {
        "count_sketch_apply": (
            lambda h, s, a: count_sketch_apply(h, s, a, b, interpret=False),
            True, ((k, n), i32), ((k, n), f32), ((n, d), f32)),
        # With the straggler survivors as its live mask, as the Hessian
        # calls it (an SMEM operand).
        "count_sketch_apply_live": (
            lambda h, s, a, m: count_sketch_apply(h, s, a, b, live=m,
                                                  interpret=False),
            True, ((k, n), i32), ((k, n), f32), ((n, d), f32), ((k,), b1)),
        "hessian": (
            lambda w, x, y, h, s, m: hessian(
                w, Dataset(x, y), CountSketch(h=h, sigma=s, block_size=b),
                m),
            True, ((d,), f32), ((n, d), f32), ((n,), f32), ((k, n), i32),
            ((k, n), f32), ((k,), b1)),
        # The gradient's coded matvecs: X v and X^T v from X and the
        # parity of its code.
        "coded_matvec_x": (
            lambda x, p, v, e: coded.coded_matvec(x, p, v, code_x, e),
            False, ((n, d), f32),
            ((2 * code_x.grid + 1, code_x.block_rows, d), f32),
            ((d,), f32), ((code_x.grid + 1,) * 2, b1)),
        "coded_matvec_xt": (
            lambda x, p, v, e: coded.coded_matvec(x, p, v, code_xt, e, True),
            False, ((n, d), f32),
            ((2 * code_xt.grid + 1, n, code_xt.block_rows), f32),
            ((n,), f32), ((code_xt.grid + 1,) * 2, b1)),
    }


PROGRAMS = list(_programs("a9a"))


# Highest precision splits f32 matmul operands into bf16 parts in VMEM,
# so it needs more scoped VMEM than the default (chip_smoke.py checks
# the Hessian at highest precision).
@pytest.mark.parametrize("precision", [None, "highest"])
@pytest.mark.parametrize("width", list(WIDTHS))
@pytest.mark.parametrize("kernel", PROGRAMS)
def test_kernel_compiles_for_v5e(one_chip, kernel, width, precision):
    fn, mxu, *shapes = _programs(width)[kernel]
    with jax.default_matmul_precision(precision):
        compiled = _compile(fn, one_chip, *shapes)
    # interpret=False lowers the kernel through Mosaic; an interpreted
    # kernel would compile too, but as plain HLO with no custom call.
    assert ("tpu_custom_call" in compiled.as_text()) == mxu
    assert _hbm_bytes(compiled) <= HBM_BYTES


def _apply_sketch_compiled(sharding, width):
    """apply_sketch compiled for the chip at a width, with A and the output
    in the row-major layout the program hands them over in (left to
    itself, the compiler may pick a transposed layout for a bare parameter
    and add a copy of A to meet the kernel's)."""
    ph = WIDTHS[width]
    n, d = ph.n, ph.d
    k, b = ph.sketch.total_blocks, ph.sketch.block_size

    def fn(h, s, a):
        return apply_sketch(CountSketch(h=h, sigma=s, block_size=b), a)

    rows = Format(Layout(major_to_minor=(0, 1)), sharding)
    args = [jax.ShapeDtypeStruct((k, n), jnp.int32, sharding=sharding),
            jax.ShapeDtypeStruct((k, n), jnp.float32, sharding=sharding),
            jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=rows)]
    out = Format(Layout(major_to_minor=(0, 1, 2)), sharding)
    return jax.jit(fn, out_shardings=out).lower(*args).compile()


def _mxu_count_sketch_call(text):
    """The compiled ``mxu_count_sketch`` custom call in an HLO text: its
    operands' shapes, its scoped-VMEM request in bytes, and its Mosaic
    body (MLIR bytecode, whose string table names the memory spaces)."""
    line = next(ln for ln in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in ln
                and "mxu_count_sketch" in ln.split("=", 1)[0])
    operands = re.search(r"operand_layout_constraints=\{(.*?)\}\}",
                         line).group(1)
    vmem = re.search(r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"',
                     line).group(1)
    body = re.search(r'"body":"([^"]+)"', line).group(1)
    return (re.findall(r"[a-z0-9]+\[[0-9,]*\]", operands), int(vmem),
            base64.b64decode(body))


@pytest.mark.parametrize("width", list(WIDTHS))
def test_apply_sketch_lowers_to_the_mxu_kernel(one_chip, width):
    """Lowered for a TPU, the platform branch of apply_sketch is the MXU
    count-sketch kernel (a Mosaic custom call), not the segment sums.  In
    the Hessian program it takes the survivor mask, padded to whole block
    groups, as an SMEM operand, and asks for the VMEM it asks for with
    every block live."""
    compiled = _apply_sketch_compiled(one_chip, width)
    assert "tpu_custom_call" in compiled.as_text()
    ph = WIDTHS[width]
    n, d = ph.n, ph.d
    k, b = ph.sketch.total_blocks, ph.sketch.block_size
    group, tn, td = pick_tiles(k, b, n, d)
    vmem = vmem_bytes(group, b, tn, td)
    k_pad = k + (-k) % group
    hessian = _jitted_sketched_hessian(
        LogisticRegression(), sketching.get("oversketch", ph.sketch))
    arg = functools.partial(_arg, one_chip)
    text = hessian.lower(
        arg((d,)), Dataset(arg((n, d)), arg((n,))),
        CountSketch(h=arg((k, n), jnp.int32), sigma=arg((k, n)),
                    block_size=b), arg((k,), jnp.bool_)).compile().as_text()
    operands, request, body = _mxu_count_sketch_call(text)
    assert operands == [f"s32[{k_pad}]", f"s32[{k},{n}]", f"f32[{k},{n}]",
                        f"f32[{n},{d}]"]
    assert b"#tpu.memory_space<smem>" in body
    every = _mxu_count_sketch_call(compiled.as_text())
    assert request == every[1] == vmem + vmem // 2 + VMEM_HEADROOM_BYTES


@pytest.mark.parametrize("width", list(WIDTHS))
def test_streamed_apply_sketch_fits_one_chip(one_chip, width):
    """The sketch on the chip: the MXU kernel streams panels of A through VMEM, so besides its (K, b, d)
    output it holds no more than the (K, n) bucket and sign rows rounded
    up to the kernel's tiles: never an (n, d) panel, never (K, n, d)."""
    ph = WIDTHS[width]
    n, d = ph.n, ph.d
    k, b = ph.sketch.total_blocks, ph.sketch.block_size
    compiled = _apply_sketch_compiled(one_chip, width)
    assert _hbm_bytes(compiled) <= HBM_BYTES
    group, tn, td = pick_tiles(k, b, n, d)
    k_pad, n_pad = k + (-k) % group, n + (-n) % tn
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= 4 * 2 * k_pad * n_pad


# sketch_mode="distributed-avg" needs b > d, so at epsilon's d = 2000 it
# sketches with b = 2048: K = 19 blocks for 15 of them.
DISTAVG_SKETCH = OverSketchConfig(15 * 2048, 2048, 0.25)


def _arg(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_distavg_direction_compiles_at_epsilon_width(one_chip):
    """Past MXU_MAX_BLOCK_SIZE apply_sketch keeps the segment sums on a
    TPU: the distributed-average direction at b = 2048 holds no Mosaic
    call, and fits the chip."""
    ph, cfg = WIDTHS["epsilon"], DISTAVG_SKETCH
    n, d, k, b = ph.n, ph.d, cfg.total_blocks, cfg.block_size
    assert b > MXU_MAX_BLOCK_SIZE
    fn = _jitted_distavg_direction(LogisticRegression(),
                                   sketching.get("oversketch", cfg), True,
                                   "chol", 64)
    arg = functools.partial(_arg, one_chip)
    compiled = fn.lower(
        arg((d,)), Dataset(arg((n, d)), arg((n,))), arg((d,)),
        CountSketch(h=arg((k, n), jnp.int32), sigma=arg((k, n)),
                    block_size=b),
        arg((k,), jnp.bool_)).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    assert _hbm_bytes(compiled) <= HBM_BYTES


def test_count_sketch_apply_compiles_at_distavg_width(one_chip):
    """The kernel at b = 2048, whose tiles pick_tiles shrinks until its
    working set fits the VMEM budget, compiles for the chip."""
    ph, cfg = WIDTHS["epsilon"], DISTAVG_SKETCH
    n, d, k, b = ph.n, ph.d, cfg.total_blocks, cfg.block_size
    group, tn, td = pick_tiles(k, b, n, d)
    assert vmem_bytes(group, b, tn, td) <= VMEM_BUDGET_BYTES
    compiled = _compile(
        lambda h, s, a: count_sketch_apply(h, s, a, b, interpret=False),
        one_chip, ((k, n), jnp.int32), ((k, n), jnp.float32),
        ((n, d), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()
    assert _hbm_bytes(compiled) <= HBM_BYTES


def _row_major(sharding, ndim):
    return Format(Layout(major_to_minor=tuple(range(ndim))), sharding)


def test_synthetic_solve_programs_fit_one_chip_beside_x_and_parity(one_chip):
    """At 300,000 x 3000 the two parity encodes, both coded matvecs and the
    Hessian program compile for the chip, and each program's arguments,
    output and temporaries, with X and both parity stacks held beside
    them, fit its 16 GiB.  Arrays are row-major, as the program hands
    them over.  The whole codes would not fit: they alone take 11.7 GB
    beside X's 3.6."""
    n, d, br = SYNTHETIC["n"], SYNTHETIC["d"], SYNTHETIC["coded_block_rows"]
    cfg = SYNTHETIC["sketch"]
    k, b = cfg.total_blocks, cfg.block_size
    code_x, code_xt = make_code(n, br), make_code(d, br)

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=_row_major(one_chip, len(shape)))

    def program(fn, *out_ndims):
        out = tuple(_row_major(one_chip, k) for k in out_ndims)
        return jax.jit(fn, out_shardings=out[0] if len(out) == 1 else out)

    x, v_d, v_n = arg((n, d)), arg((d,)), arg((n,))
    par_x = jax.eval_shape(lambda a: coded.encode_2d(a, code_x), x)
    par_xt = jax.eval_shape(
        lambda a: coded.encode_2d(a, code_xt, transpose=True), x)
    assert par_x.shape == (2 * code_x.grid + 1, br, d)
    assert par_xt.shape == (2 * code_xt.grid + 1, n, br)
    fam = sketching.get("oversketch", cfg)
    hessian = _jitted_sketched_hessian(LogisticRegression(lam=1e-5), fam)
    compiled = {
        "encode_x": program(lambda a: coded.encode_2d(a, code_x), 3
                            ).lower(x),
        "encode_xt": program(lambda a: coded.encode_2d(
            a, code_xt, transpose=True), 3).lower(x),
        "coded_matvec_x": program(
            lambda a, p, v, e: coded.coded_matvec(a, p, v, code_x, e),
            1, 0).lower(x, arg(par_x.shape), v_d,
                        arg((code_x.grid + 1,) * 2, jnp.bool_)),
        "coded_matvec_xt": program(
            lambda a, p, v, e: coded.coded_matvec(a, p, v, code_xt, e, True),
            1, 0).lower(x, arg(par_xt.shape), v_n,
                        arg((code_xt.grid + 1,) * 2, jnp.bool_)),
        "hessian": program(hessian, 2).lower(
            v_d, Dataset(x, v_n),
            CountSketch(h=arg((k, n), jnp.int32), sigma=arg((k, n)),
                        block_size=b), arg((k,), jnp.bool_)),
    }
    compiled = {name: low.compile() for name, low in compiled.items()}
    assert "tpu_custom_call" in compiled["hessian"].as_text()
    # Bytes on the device, row-major tiles included, of what the solve
    # holds, and of each held array that is among a program's arguments.
    held = {"x": compiled["encode_x"].memory_analysis()
            .argument_size_in_bytes,
            "par_x": compiled["encode_x"].memory_analysis()
            .output_size_in_bytes,
            "par_xt": compiled["encode_xt"].memory_analysis()
            .output_size_in_bytes}
    assert held["par_x"] + held["par_xt"] < 3.1e9
    among_args = {"encode_x": ("x",), "encode_xt": ("x",),
                  "coded_matvec_x": ("x", "par_x"),
                  "coded_matvec_xt": ("x", "par_xt"), "hessian": ("x",)}
    for name, c in compiled.items():
        total = sum(held.values()) + _hbm_bytes(c) - sum(
            held[a] for a in among_args[name])
        assert total <= HBM_BYTES, (name, total)
    # The encodes copy no X: their temporaries are under a fifth of it.
    for name in ("encode_x", "encode_xt"):
        assert compiled[name].memory_analysis().temp_size_in_bytes \
            < held["x"] / 5, name
