#!/usr/bin/env python3
"""Run OverSketched Newton end to end on one TPU chip, and check it.

    python3 chip_smoke.py [--seed S]

One process, one chip.  For each phase below the data is made on the
device from ``--seed`` (``make_logistic_dataset``, cond = 10, rows in
sorted-margin layout, as ``data.profile_dataset`` makes paper profiles),
and ``oversketched_newton`` solves ``LogisticRegression(lam=1e-5)`` through
its normal path: coded gradient matvecs under the default straggler
clock, the sketched Hessian (the MXU count-sketch kernel on a TPU), the
Cholesky direction and the line search.  Each phase solves once that
way, and once more with a plain exact-Newton reference.

  epsilon  d = 2000 (epsilon's published width), n = 200,000 (cut from
           400,000 to fit 16 GiB of HBM beside the product codes of X and
           X^T), n_test = 50,000; the fig7 sketch (K = 148 blocks of
           b = 256), coded_block_rows = 256.
  a9a      32,000 x 123, n_test = 16,000 (the published size); the fig8
           sketch (K = 13 blocks of b = 128), coded_block_rows = 128.

Checks, each a hard failure: finite objectives ending below f(w0) = log 2;
at the first iterate the solve's Hessian matches the ``kernels/ref.py``
oracle under highest matmul precision (HESSIAN_RTOL, relative Frobenius);
the solve ends within 1% (``benchmarks.common.best_f``) of the exact-Newton
reference; the compiled Hessian holds a ``tpu_custom_call`` (the
count-sketch kernel was compiled for the chip, not interpreted).

Every line but the last is a JSON record for information: compile and
per-iteration wall seconds, peak device memory, the sketch's
implementation.  The last line is ``{"ok": true, "device": {...}}``.
Without a TPU the script exits nonzero before any phase runs.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(REPO, "src"), REPO]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import best_f  # noqa: E402
from repro import sketching  # noqa: E402
from repro.core import (LogisticRegression, NewtonConfig,  # noqa: E402
                        OverSketchConfig, oversketched_newton)
from repro.core.newton import _jitted_sketched_hessian  # noqa: E402
from repro.core.straggler import StragglerModel  # noqa: E402
from repro.data.synthetic import make_logistic_dataset  # noqa: E402
from repro.kernels import ref  # noqa: E402

# Solve vs oracle Hessian, both at highest precision: float32 sums of
# n rows in two different orders, expected near 1e-6.
HESSIAN_RTOL = 1e-4
# OSN's final objective against the exact-Newton reference (best_f).
REF_REL = 0.01


@dataclasses.dataclass(frozen=True)
class Phase:
    name: str
    n: int
    d: int
    n_test: int
    sketch: OverSketchConfig
    coded_block_rows: int
    iters: int = 8


PHASES = (
    Phase("epsilon", 200_000, 2000, 50_000,
          OverSketchConfig(((15 * 2000) // 256 + 1) * 256, 256, 0.25),
          256),
    Phase("a9a", 32_000, 123, 16_000,
          OverSketchConfig(((10 * 123) // 128 + 1) * 128, 128, 0.25),
          128),
)


def compile_cache_dir(environ) -> str:
    """JAX_COMPILATION_CACHE_DIR where it is set, else a fixed directory
    in the checkout (a path that never moves, so later runs hit it)."""
    return environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        REPO, ".jax_cache")


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _solve(obj, data, cfg, model):
    t0 = time.perf_counter()
    res = oversketched_newton(obj, data, jnp.zeros(data.x.shape[1]), cfg,
                              model=model)
    jax.block_until_ready(res.w)
    return res, time.perf_counter() - t0


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _ref_hessian(obj, w, data, state, survivors):
    """The oracle: kernels/ref.py's segment-sum apply, one block at a time
    (all K at once would hold a (K, n, d) tensor), and its masked Gram."""
    a = obj.hess_sqrt(w, data)
    b = state.block_size
    a_t = jax.lax.map(
        lambda hs: ref.count_sketch_apply(hs[0][None], hs[1][None], a, b)[0],
        (state.h, state.sigma))
    eye = jnp.eye(a.shape[1], dtype=a.dtype)
    return ref.oversketch_gram(a_t, survivors) + obj.hess_reg * eye


def run_phase(ph: Phase, seed: int) -> dict:
    """Solve one phase on the default device and with the reference; raise
    AssertionError on a failed check.  Returns the readings,
    ``custom_call`` among them (whether the compiled Hessian holds a
    Mosaic kernel)."""
    key = jax.random.PRNGKey(seed)
    t0 = time.perf_counter()
    data = make_logistic_dataset(key, ph.n, ph.d, ph.n_test, cond=10.0,
                                 sorted_layout=True)
    jax.block_until_ready(data)
    out = {"phase": ph.name, "n": ph.n, "d": ph.d, "n_test": ph.n_test,
           "sketch_blocks": ph.sketch.total_blocks,
           "block_size": ph.sketch.block_size,
           "data_s": time.perf_counter() - t0}
    obj = LogisticRegression(lam=1e-5)
    fam = sketching.get("oversketch", ph.sketch)
    out["sketch_impl"] = fam.apply_path(jax.devices()[0].platform)

    cfg = NewtonConfig(iters=ph.iters, sketch=ph.sketch,
                       coded_block_rows=ph.coded_block_rows, seed=seed)
    # One iteration first: it compiles every program the solve runs and
    # gives the first iterate; the full solve then runs warm.
    first, warm_s = _solve(obj, data, dataclasses.replace(cfg, iters=1),
                           StragglerModel())
    res, wall_s = _solve(obj, data, cfg, StragglerModel())
    w1 = first.w
    f = np.asarray(res.history["fval"], np.float64)
    out["fvals"] = f.tolist()
    out["compile_s"] = warm_s - wall_s / ph.iters
    out["s_per_iter"] = wall_s / ph.iters
    out["solve_peak_bytes"] = _peak_bytes()
    _check(bool(np.isfinite(f).all()), f"{ph.name}: f not finite")
    _check(f[-1] < math.log(2.0),
           f"{ph.name}: final f {f[-1]} >= f(w0) = log 2")
    history = res.history
    del first, res

    # The solve's Hessian at the first iterate against the oracle, with
    # the redundant blocks dropped as stragglers.
    state = fam.sample(jax.random.fold_in(key, 7), ph.n)
    survivors = jnp.arange(ph.sketch.total_blocks) < ph.sketch.num_blocks
    hess = _jitted_sketched_hessian(obj, fam)
    out["custom_call"] = "tpu_custom_call" in hess.lower(
        w1, data, state, survivors).compile().as_text()
    with jax.default_matmul_precision("highest"):
        h_osn = hess(w1, data, state, survivors)
        h_ref = jax.jit(_ref_hessian, static_argnums=0)(obj, w1, data, state,
                                                        survivors)
    h_def = hess(w1, data, state, survivors)       # default precision
    ref_norm = jnp.linalg.norm(h_ref)
    out["hessian_rel"] = float(jnp.linalg.norm(h_osn - h_ref) / ref_norm)
    out["hessian_rel_default_precision"] = float(
        jnp.linalg.norm(h_def - h_ref) / ref_norm)
    _check(out["hessian_rel"] <= HESSIAN_RTOL,
           f"{ph.name}: Hessian rel err {out['hessian_rel']}")
    del h_osn, h_ref, h_def

    # Plain exact Newton (full Hessian, exact gradient, no straggler
    # clock) at highest precision, for the same iterations.
    with jax.default_matmul_precision("highest"):
        ref_res, ref_s = _solve(obj, data, NewtonConfig(
            iters=ph.iters, hessian_policy="exact", seed=seed), None)
    f_ref = ref_res.history["fval"]
    out["ref_fvals"] = list(f_ref)
    out["ref_wall_s"] = ref_s
    target = best_f(ref_res.history, history, rel=REF_REL)
    out["vs_ref_rel"] = (f[-1] - f_ref[-1]) / abs(f_ref[-1])
    _check(f[-1] <= target,
           f"{ph.name}: final f {f[-1]} not within 1% of the exact-Newton "
           f"reference {f_ref[-1]}")
    out["peak_bytes"] = _peak_bytes()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    jax.config.update("jax_compilation_cache_dir",
                      compile_cache_dir(os.environ))
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    emit({"device": device, "jax": jax.__version__})
    for ph in PHASES:
        out = run_phase(ph, args.seed)
        _check(out["custom_call"],
               f"{ph.name}: no tpu_custom_call in the Hessian")
        emit(out)
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
