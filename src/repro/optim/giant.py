"""GIANT: Globally Improved Approximate Newton Direction (Wang et al., 2018)
— the paper's main second-order serverful baseline (Fig. 4).

Two distributed stages per iteration:
  1. workers compute local gradients from their shard; master sums -> full g;
  2. workers compute a local Newton direction p_i = H_i^{-1} g from their
     *local* Hessian; master averages -> p.

Straggler handling variants (paper Fig. 6): wait_all (uncoded), gcode
(gradient coding on stage 1), ignore (drop stragglers in both stages — the
"mini-batch" curve).  Both stages are scored on the simulated clock.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from repro import obs, scheduler
from repro.core import solvers, straggler
from repro.core.objectives import Dataset
from repro.optim.gradient_coding import gradient_coding_phase
from repro.runtime.faults import PhaseExhaustedError


@dataclasses.dataclass(frozen=True)
class GiantConfig:
    iters: int = 20
    num_workers: int = 60
    policy: str = "wait_all"     # wait_all | gcode | ignore
    gcode_redundancy: int = 2
    unit_step: bool = True
    cg_iters: int = 30
    # Phase dispatch through the repro.scheduler DAG layer.  GIANT's two
    # stages have a true data edge (the local Newton solves consume the
    # summed gradient), so its iteration DAG is a chain and the DAG
    # schedule reproduces the sequential one bit-for-bit — the degenerate
    # end of the DAG-vs-sequential spectrum, kept as a schedule-equality
    # regression anchor.  Per-phase memory sizing still applies.
    schedule: str = "dag"        # dag | sequential
    phase_memory: bool = False   # bill each stage at its shard working set
    seed: int = 0
    track_test_error: bool = False


def _shard_bounds(n: int, w: int):
    per = -(-n // w)
    return [(i * per, min((i + 1) * per, n)) for i in range(w)]


def giant(objective, data: Dataset, w0: jax.Array, cfg: GiantConfig,
          model: Optional[straggler.StragglerModel] = straggler.StragglerModel()
          ) -> Dict[str, List[float]]:
    """Runs GIANT; requires objective.hess_sqrt + gradient on sub-datasets.

    ``model`` may also be a prebuilt ``straggler.SimClock`` (custom fleet /
    cost / trace config, see ``repro.runtime``)."""
    if cfg.schedule not in ("dag", "sequential"):
        raise ValueError(f"unknown schedule {cfg.schedule!r}")
    key = straggler.host_key(cfg.seed)   # only the fleet's phases draw on it
    if isinstance(model, straggler.SimClock):
        clock = model
    else:
        clock = straggler.SimClock(model) if model is not None else None
    n, d = data.x.shape
    bounds = _shard_bounds(n, cfg.num_workers)

    # Pad shards to equal size for a stacked vmap (last shard may be short).
    per = bounds[0][1] - bounds[0][0]
    xs, ys, wts = [], [], []
    for lo, hi in bounds:
        pad = per - (hi - lo)
        xs.append(jnp.pad(data.x[lo:hi], ((0, pad), (0, 0))))
        ys.append(jnp.pad(data.y[lo:hi], ((0, pad),) + ((0, 0),) * (data.y.ndim - 1)))
        wts.append(jnp.pad(jnp.ones(hi - lo), (0, pad)))
    xs, ys, wts = jnp.stack(xs), jnp.stack(ys), jnp.stack(wts)

    def local_grad(x_i, y_i, wt_i, w_vec):
        return jax.grad(lambda wv: objective.masked_value(
            wv, Dataset(x=x_i, y=y_i), wt_i))(w_vec)

    def local_newton(x_i, y_i, wt_i, w_vec, g):
        # Local Hessian via the shard's hess_sqrt (masked rows zeroed).
        a_i = objective.hess_sqrt(w_vec, Dataset(x=x_i, y=y_i))
        a_i = a_i * wt_i[: a_i.shape[0], None] if a_i.shape[0] == x_i.shape[0] \
            else a_i  # softmax hess_sqrt has n*K rows; mask repeats
        scale = x_i.shape[0] / jnp.maximum(wt_i.sum(), 1.0)
        h_i = scale * (a_i.T @ a_i) + \
            (objective.hess_reg + 1e-8) * jnp.eye(d, dtype=a_i.dtype)
        return solvers.psd_solve(h_i, g)

    lg = jax.jit(jax.vmap(local_grad, in_axes=(0, 0, 0, None)))
    ln = jax.jit(jax.vmap(local_newton, in_axes=(0, 0, 0, None, None)))
    val_fn = jax.jit(objective.value)
    grad_fn = jax.jit(objective.gradient)

    hist: Dict[str, List[float]] = {k: [] for k in (
        "iter", "fval", "gnorm", "step", "time", "cost", "test_error")}
    w = jnp.asarray(w0, jnp.float32)

    tel = clock.telemetry if clock is not None else obs.NULL
    run_span = tel.trace.begin(
        "giant", "run", clock.time if clock is not None else 0.0,
        policy=cfg.policy, workers=cfg.num_workers, schedule=cfg.schedule)
    if tel.enabled:
        tel.metrics.gauge("giant.cg_iters").set(cfg.cg_iters)

    grad_flops = 2.0 * per * d                    # local gradient pass
    # GIANT's local solves are CG / Hessian-free (Wang et al.): cg_iters
    # Hessian-vector products over the local shard per iteration.
    newton_flops = 2.0 * per * d * cfg.cg_iters
    # Both stages stream the same (per x d) shard; CG adds a few d-vectors.
    shard_bytes = scheduler.matvec_worker_bytes(per, d)
    shard_mem = (scheduler.lambda_memory_gb(shard_bytes)
                 if cfg.phase_memory else None)
    # True working set, declared unconditionally: inert billing-wise, but
    # an attached fault plan with an OomSpec kills undersized attempts.
    shard_ws = float(shard_bytes) / 2.0 ** 30
    for t in range(cfg.iters):
        key, k1, k2, k3 = jax.random.split(key, 4)
        it_span = tel.trace.begin(
            f"iter{t}", "iteration",
            clock.time if clock is not None else float(t))
        dag = (scheduler.DagRun(clock)
               if cfg.schedule == "dag" and clock is not None else None)

        def phase(k, name, deps, *, policy, kk=None, flops, comm):
            try:
                if dag is not None:
                    # Every dep here is the previous stage — the chain
                    # resolves to the engine's exact sequential path.  A
                    # dep that ran on the direct clock (the gcode round)
                    # has no DAG node; the barrier at the current clock
                    # stands in for its edge.
                    known = tuple(dd for dd in deps if dd in dag.results)
                    return dag.dispatch(scheduler.PhaseSpec(
                        name=name, workers=cfg.num_workers, policy=policy,
                        k=kk, flops_per_worker=flops, comm_units=comm,
                        memory_gb=shard_mem, working_set_gb=shard_ws,
                        deps=known), key=k,
                        sequential=len(known) < len(deps)).mask
                _, mask = clock.phase(k, cfg.num_workers, policy=policy,
                                      k=kk, flops_per_worker=flops,
                                      comm_units=comm, memory_gb=shard_mem,
                                      working_set_gb=shard_ws,
                                      phase_name=name)
                return mask
            except PhaseExhaustedError as e:
                # Fault plan exhausted the retry budget: attempts are
                # billed, the dead shards' results never arrive.  GIANT's
                # stages both average shard-local quantities, so the
                # finite-finisher mask gives honest drop semantics (the
                # "ignore" policy's math, forced by the fleet).
                tel.metrics.counter("giant.exhausted_phases").inc()
                return jnp.asarray(e.mask)

        # --- stage 1: gradient -------------------------------------------
        shard_sizes = wts.sum(axis=1)
        if cfg.policy == "ignore" and clock is not None:
            fin = phase(k1, "grad", (), policy="k_of_n",
                        kk=max(1, int(0.95 * cfg.num_workers)),
                        flops=grad_flops, comm=1.0)
        else:
            fin = jnp.ones((cfg.num_workers,), bool)
            if clock is not None:
                if cfg.policy == "gcode":
                    # Coded gradient round: stays on the direct clock (its
                    # internal schedule predates the DAG layer); the next
                    # stage launches after it either way.
                    gradient_coding_phase(clock, k1, cfg.num_workers,
                                          cfg.gcode_redundancy,
                                          flops_per_worker=grad_flops)
                else:
                    # wait_all's mask is all-True on a healthy fleet; under
                    # an exhausted fault plan it is the finite-finisher
                    # mask and the dead shards drop out of the average.
                    fin = phase(k1, "grad", (), policy="wait_all",
                                flops=grad_flops, comm=1.0)
        g_locals = lg(xs, ys, wts, w)
        finf = fin.astype(jnp.float32)
        weights = finf * shard_sizes
        g = (weights[:, None] * g_locals).sum(0) / jnp.maximum(
            weights.sum(), 1.0)
        # masked_value includes the regularizer per shard; averaging keeps it.

        # --- stage 2: local second-order directions -----------------------
        if cfg.policy == "ignore" and clock is not None:
            fin2 = phase(k2, "local-newton", ("grad",), policy="k_of_n",
                         kk=max(1, int(0.95 * cfg.num_workers)),
                         flops=newton_flops, comm=1.0)
        else:
            fin2 = jnp.ones((cfg.num_workers,), bool)
            if clock is not None:
                fin2 = phase(k2, "local-newton", ("grad",),
                             policy="wait_all", flops=newton_flops,
                             comm=1.0)
        p_locals = ln(xs, ys, wts, w, g)
        fin2f = fin2.astype(jnp.float32)
        p = -(fin2f[:, None] * p_locals).sum(0) / jnp.maximum(fin2f.sum(), 1.0)

        step = 1.0
        if not cfg.unit_step:
            from repro.core import linesearch
            step = float(linesearch.linesearch_strongly_convex(
                objective, data, w, p, g))
            if clock is not None:
                phase(k3, "linesearch", ("local-newton",),
                      policy="wait_all", flops=grad_flops * 6, comm=0.3)
        w = w + step * p

        hist["iter"].append(t)
        hist["fval"].append(float(val_fn(w, data)))
        hist["gnorm"].append(float(jnp.linalg.norm(grad_fn(w, data))))
        hist["step"].append(float(step))
        hist["time"].append(clock.time if clock is not None else float(t + 1))
        hist["cost"].append(clock.dollars if clock is not None else 0.0)
        if tel.enabled and dag is not None and dag.results:
            rep = dag.critical_path()
            tel.trace.set_attrs(it_span,
                                critical_path=list(rep.critical_path),
                                dag_makespan=rep.makespan)
        tel.trace.end(it_span,
                      clock.time if clock is not None else float(t + 1))
        if cfg.track_test_error and data.x_test is not None:
            hist["test_error"].append(
                float(objective.error(w, data.x_test, data.y_test)))
        else:
            hist["test_error"].append(float("nan"))
    hist["w"] = w
    tel.trace.end(run_span,
                  clock.time if clock is not None else float(cfg.iters))
    return hist
