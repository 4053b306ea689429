"""Kernel microbenchmarks: Pallas (interpret on CPU) vs pure-jnp reference.

On this container the interpreter dominates wall-clock, so the *reference*
implementations provide the meaningful CPU numbers and the Pallas variants
are validated for correctness+shape coverage; on TPU the same harness times
the compiled kernels.  Derived column reports achieved GFLOP/s of the ref.

Every row carries a ``path`` field naming what actually executed, so the
persisted BENCH_kernels.json trajectory is attributable row-by-row:

  ref         pure-jnp oracle timing
  pallas      Pallas entry point checked against the oracle (no timing)
  unfused     the two-kernel apply+gram baseline the fusion replaces
  fused       fused sketch->Gram, single resident output tile
  fused_tiled fused sketch->Gram, d-tiled (d_i, d_j) output grid

Pre-path-field BENCH files (before the d-tiled kernel) labelled the
``*_fused`` rows by entry point alone; see kernels/README.md ("Reading
BENCH_kernels.json") for the discontinuity note.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.common import time_fn
from repro.kernels import ops, ref


def _fused_inputs(key, kg, ng, dg, bg, s=None):
    """Shared draw for the fused sketch->Gram rows; the 1/sqrt(n) row scale
    keeps Gram entries O(1) so max_err is an absolute float32 figure."""
    kh, ks, ka, kr, kj = jax.random.split(key, 5)
    h = jax.random.randint(kh, (kg, ng), 0, bg, dtype=jnp.int32)
    sg = jax.random.rademacher(ks, (kg, ng), dtype=jnp.float32)
    a = jax.random.normal(ka, (ng, dg)) / math.sqrt(ng)
    n_pad = 1 << (ng - 1).bit_length()
    rows = jax.random.randint(kr, (kg, bg), 0, n_pad, dtype=jnp.int32)
    sjlt = None
    if s is not None:
        hj = jax.random.randint(kj, (kg, s, ng), 0, bg, dtype=jnp.int32)
        sj = jax.random.rademacher(jax.random.fold_in(kj, 1), (kg, s, ng),
                                   dtype=jnp.float32)
        sjlt = (hj, sj)
    surv = jnp.ones((kg,), bool).at[0].set(False)
    return h, sg, a, rows, sjlt, surv, n_pad


def _fused_rows(rows, tag, key, kg, ng, dg, bg, s, iters):
    """Unfused-ref + fused rows for all three encode families at one shape.

    Flop counts match what each implementation actually executes: fused
    kernel = dense encode matmul + gram, recomputed once per output
    row/column of d tiles; scatter-style count ref = one signed add per
    element; FWHT ref = butterfly.
    """
    h, sg, a, rws, (hj, sj), surv, n_pad = _fused_inputs(
        key, kg, ng, dg, bg, s=s)
    gram_fl = 2.0 * kg * bg * dg * dg
    d_tile = ops.pick_d_tile(bg, dg)
    d_tiles = -(-dg // d_tile)
    path = ops.fused_path(bg, dg)
    # Tiled grid recomputes the encode matmul once per off-diagonal panel:
    # (2*d_tiles - 1) x the single-tile encode work (see kernels/README.md).
    flops_fused = 2.0 * kg * ng * bg * dg * (2.0 * d_tiles - 1.0) + gram_fl
    shape = f"shape=({kg},{ng},{dg},{bg})"

    cases = [
        ("count", lambda: ref.sketch_gram_count(h, sg, a, bg, surv),
         lambda: ops.sketch_gram_count(h, sg, a, bg, surv),
         2.0 * kg * ng * dg + gram_fl),
        ("srht", lambda: ref.sketch_gram_srht(rws, sg, a, surv),
         lambda: ops.sketch_gram_srht(rws, sg, a, surv),
         kg * n_pad * math.log2(n_pad) * dg + gram_fl),
        ("sjlt", lambda: ref.sketch_gram_sjlt(hj, sj, a, bg, surv),
         lambda: ops.sketch_gram_sjlt(hj, sj, a, bg, surv),
         2.0 * kg * s * ng * dg + gram_fl),
    ]
    for fam, f_ref, f_fus, flops_ref in cases:
        f_unf = jax.jit(f_ref)
        us_unf = time_fn(f_unf)
        rows.append({"name": f"kernel_sketch_gram_{fam}_unfused_ref{tag}",
                     "us": us_unf, "path": "unfused",
                     "derived": (f"gflops={flops_ref/us_unf/1e3:.2f};"
                                 f"{shape}")})
        us_fus = time_fn(f_fus, iters=iters, warmup=1)
        err = float(jnp.abs(f_fus() - f_unf()).max())
        rows.append({"name": f"kernel_sketch_gram_{fam}_fused{tag}",
                     "us": us_fus, "path": path,
                     "derived": (f"gflops={flops_fused/us_fus/1e3:.2f};"
                                 f"max_err={err:.2e};d_tile={d_tile};"
                                 f"{shape}")})


def run(quick: bool = True):
    key = jax.random.PRNGKey(0)
    rows = []

    # count-sketch apply
    k, n, d, b = (8, 4096, 256, 256) if quick else (10, 20_000, 1000, 512)
    kh, ks, ka = jax.random.split(key, 3)
    h = jax.random.randint(kh, (k, n), 0, b, dtype=jnp.int32)
    sg = jax.random.rademacher(ks, (k, n), dtype=jnp.float32)
    a = jax.random.normal(ka, (n, d))
    f_ref = jax.jit(lambda: ref.count_sketch_apply(h, sg, a, b))
    us = time_fn(f_ref)
    flops = 2.0 * k * n * d
    rows.append({"name": "kernel_count_sketch_ref", "us": us, "path": "ref",
                 "derived": f"gflops={flops/us/1e3:.2f};shape=({k},{n},{d})"})
    out_p = ops.count_sketch_apply(h, sg, a, b)
    out_r = f_ref()
    err = float(jnp.abs(out_p - out_r).max())
    rows.append({"name": "kernel_count_sketch_pallas_check", "us": 0.0,
                 "path": "pallas", "derived": f"max_err={err:.2e}"})

    # oversketch gram
    a_t = jax.random.normal(key, (k, b, d))
    surv = jnp.ones((k,), bool).at[0].set(False)
    f_ref2 = jax.jit(lambda: ref.oversketch_gram(a_t, surv))
    us2 = time_fn(f_ref2)
    flops2 = 2.0 * k * b * d * d
    rows.append({"name": "kernel_oversketch_gram_ref", "us": us2,
                 "path": "ref", "derived": f"gflops={flops2/us2/1e3:.2f}"})
    err2 = float(jnp.abs(ops.oversketch_gram(a_t, surv) - f_ref2()).max())
    rows.append({"name": "kernel_oversketch_gram_pallas_check", "us": 0.0,
                 "path": "pallas", "derived": f"max_err={err2:.2e}"})

    # fused sketch->gram streaming kernel vs unfused apply+gram (the
    # two-HBM-round-trip baseline it replaces), all three encode families.
    # First shape fits one resident output tile (path=fused); the second
    # puts d above the old single-tile budget so the d-tiled grid runs
    # (path=fused_tiled) — pre-tiling code silently never fused there.
    s = 4
    if quick:
        _fused_rows(rows, "", jax.random.fold_in(key, 2),
                    6, 4096, 256, 256, s, iters=3)
        _fused_rows(rows, "_bigd", jax.random.fold_in(key, 3),
                    2, 1024, 1536, 128, s, iters=2)
    else:
        _fused_rows(rows, "", jax.random.fold_in(key, 2),
                    10, 20_000, 512, 512, s, iters=3)
        _fused_rows(rows, "_bigd", jax.random.fold_in(key, 3),
                    4, 4096, 2048, 256, s, iters=2)

    # srht fwht (blocked Kronecker-matmul kernel vs butterfly oracle)
    kf, nf, df = (4, 1024, 256) if quick else (8, 8192, 1000)
    xf = jax.random.normal(ks, (kf, nf, df))
    f_ref_f = jax.jit(lambda: ref.fwht(xf))
    usf = time_fn(f_ref_f)
    flopsf = kf * nf * math.log2(nf) * df
    rows.append({"name": "kernel_fwht_ref", "us": usf, "path": "ref",
                 "derived": f"gflops={flopsf/usf/1e3:.2f};shape=({kf},{nf},{df})"})
    errf = float(jnp.abs(ops.fwht(xf) - f_ref_f()).max())
    rows.append({"name": "kernel_fwht_pallas_check", "us": 0.0,
                 "path": "pallas", "derived": f"max_err={errf:.2e}"})

    # two-pass tiled fwht (streams O(sqrt(n)) VMEM panels; the compile
    # path for n beyond the monolithic kernel's panel budget)
    k2p, n2p, d2p = (2, 4096, 256) if quick else (4, 16384, 256)
    x2p = jax.random.normal(jax.random.fold_in(ks, 3), (k2p, n2p, d2p))
    f_2p = lambda: ops.fwht_two_pass(x2p)
    us2p = time_fn(f_2p, iters=3, warmup=1)
    err2p = float(jnp.abs(f_2p() - ref.fwht(x2p)).max())
    rows.append({"name": "kernel_fwht_two_pass", "us": us2p,
                 "path": "pallas",
                 "derived": (f"max_err={err2p:.2e};"
                             f"shape=({k2p},{n2p},{d2p})")})

    # coded matvec
    w, bb, ss = (25, 128, 2048) if quick else (64, 256, 8192)
    enc = jax.random.normal(key, (w, bb, ss))
    x = jax.random.normal(kh, (ss,))
    er = jnp.zeros((w,), bool).at[3].set(True)
    f_ref3 = jax.jit(lambda: ref.coded_block_matvec(enc, x, er))
    us3 = time_fn(f_ref3)
    gb = enc.size * 4 / 1e9
    rows.append({"name": "kernel_coded_matvec_ref", "us": us3, "path": "ref",
                 "derived": f"gbps={gb/(us3/1e6):.2f}"})
    err3 = float(jnp.abs(ops.coded_block_matvec(enc, x, er) - f_ref3()).max())
    rows.append({"name": "kernel_coded_matvec_pallas_check", "us": 0.0,
                 "path": "pallas", "derived": f"max_err={err3:.2e}"})

    return rows
