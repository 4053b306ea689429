"""Kernel microbenchmark: the count-sketch Pallas kernel (interpreted on
CPU) vs its pure-jnp reference.

On this container the interpreter dominates wall-clock, so the *reference*
implementation provides the meaningful CPU number and the Pallas kernel is
checked for correctness against it; on TPU the kernel is compiled.  Derived
column reports achieved GFLOP/s of the ref.

Every row carries a ``path`` field naming what actually executed, so the
persisted BENCH_kernels.json trajectory is attributable row-by-row:

  ref         pure-jnp oracle timing
  pallas      Pallas entry point checked against the oracle (no timing)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import time_fn
from repro.kernels import count_sketch, ref


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
    out_p = count_sketch.count_sketch_apply(
        h, sg, a, b, interpret=jax.default_backend() != "tpu")
    out_r = f_ref()
    err = float(jnp.abs(out_p - out_r).max())
    rows.append({"name": "kernel_count_sketch_pallas_check", "us": 0.0,
                 "path": "pallas", "derived": f"max_err={err:.2e}"})
    return rows
