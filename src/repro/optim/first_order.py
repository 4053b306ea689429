"""First-order baselines from the paper's experiments (Sec. 5.4):
gradient descent, Nesterov accelerated gradient, mini-batch SGD — each with a
straggler policy and the same simulated-wall-clock accounting as OverSketched
Newton, so convergence-vs-time plots are directly comparable (Fig. 11).

Straggler policies for the gradient phase:
  wait_all   — uncoded, wait for every worker;
  ignore     — mini-batch gradient: drop stragglers' shards (Fig. 5c);
  gcode      — gradient coding (Tandon et al.): exact gradient from any
               W-(r-1) workers at the cost of r-fold data replication
               (Fig. 5b) — modelled by `repro.optim.gradient_coding`.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.core import straggler
from repro.core.objectives import Dataset
from repro.optim.gradient_coding import gradient_coding_phase


@dataclasses.dataclass(frozen=True)
class FirstOrderConfig:
    iters: int = 100
    lr: float = 1.0
    method: str = "gd"              # gd | nag | sgd
    policy: str = "ignore"          # wait_all | ignore | gcode
    num_workers: int = 60
    gcode_redundancy: int = 2       # r: data repeated r times per worker
    momentum: float = 0.9           # NAG
    batch_fraction: float = 0.2     # sgd
    backtracking: bool = True       # backtracking line search (Fig. 11 setup)
    bt_shrink: float = 0.5
    bt_c: float = 1e-4
    bt_max: int = 20
    seed: int = 0
    track_test_error: bool = False


def _worker_shards(n: int, w: int) -> jax.Array:
    """Row -> worker assignment, contiguous shards."""
    per = -(-n // w)
    return jnp.minimum(jnp.arange(n) // per, w - 1)


def _masked_gradient(objective, data: Dataset, w_vec: jax.Array,
                     shard_of_row: jax.Array, finished: jax.Array):
    """Mean gradient over the rows owned by finished workers (mini-batch /
    ignore-stragglers scheme).  Regularizer term included analytically."""
    row_ok = finished[shard_of_row]
    # Weighted data gradient: reuse gradient_via by masking rows via a scaled
    # dataset is wrong for nonlinear objectives; instead compute row-masked.
    g_fn = getattr(objective, "masked_gradient", None)
    if g_fn is not None:
        return g_fn(w_vec, data, row_ok)
    # Generic fallback: autodiff on the masked mean objective.
    def masked_value(wv):
        return objective.masked_value(wv, data, row_ok)
    return jax.grad(masked_value)(w_vec)


def _backtrack(objective, data, w, g, direction, cfg):
    f0 = objective.value(w, data)
    gtd = g @ direction
    t = cfg.lr
    for _ in range(cfg.bt_max):
        if float(objective.value(w + t * direction, data)) <= \
                float(f0 + cfg.bt_c * t * gtd):
            return t
        t *= cfg.bt_shrink
    return t


def first_order(objective, data: Dataset, w0: jax.Array,
                cfg: FirstOrderConfig,
                model: Optional[straggler.StragglerModel] = straggler.StragglerModel()
                ) -> Dict[str, List[float]]:
    key = straggler.host_key(cfg.seed)   # the fleet draws on the host
    if isinstance(model, straggler.SimClock):
        clock, model = model, model.model
    else:
        clock = straggler.SimClock(model) if model is not None else None
    n = data.x.shape[0]
    shard_of_row = _worker_shards(n, cfg.num_workers)

    grad_fn = jax.jit(objective.gradient)
    val_fn = jax.jit(objective.value)
    masked_grad_fn = jax.jit(
        lambda wv, ok: _masked_gradient(objective, data, wv, shard_of_row, ok))

    hist: Dict[str, List[float]] = {k: [] for k in (
        "iter", "fval", "gnorm", "step", "time", "cost", "test_error")}
    w = jnp.asarray(w0, jnp.float32)
    velocity = jnp.zeros_like(w)
    d = data.x.shape[1]
    grad_flops = 2.0 * (n / cfg.num_workers) * d

    for t in range(cfg.iters):
        key, kp, kb = jax.random.split(key, 3)
        # Gradient evaluation point (NAG looks ahead).
        w_eval = w + cfg.momentum * velocity if cfg.method == "nag" else w

        if cfg.method == "sgd":
            nb = max(1, int(cfg.batch_fraction * n))
            idx = jax.random.choice(straggler.on_device_of(kb, data.x), n,
                                    (nb,), replace=False)
            sub = Dataset(x=data.x[idx], y=data.y[idx])
            g = objective.gradient(w_eval, sub)
            if clock is not None:
                clock.phase(kp, cfg.num_workers, policy="wait_all",
                            flops_per_worker=grad_flops * cfg.batch_fraction,
                            comm_units=0.5)
        elif cfg.policy == "wait_all" or model is None:
            g = grad_fn(w_eval, data)
            if clock is not None:
                clock.phase(kp, cfg.num_workers, policy="wait_all",
                            flops_per_worker=grad_flops, comm_units=1.0)
        elif cfg.policy == "ignore":
            _, finished = clock.phase(
                kp, cfg.num_workers, policy="k_of_n",
                k=max(1, int(0.95 * cfg.num_workers)),
                flops_per_worker=grad_flops, comm_units=1.0)
            g = masked_grad_fn(w_eval, finished)
        elif cfg.policy == "gcode":
            g = grad_fn(w_eval, data)   # gradient coding recovers it exactly
            gradient_coding_phase(clock, kp, cfg.num_workers,
                                  cfg.gcode_redundancy,
                                  flops_per_worker=grad_flops)
        else:
            raise ValueError(cfg.policy)

        if cfg.backtracking:
            step = _backtrack(objective, data, w_eval, g, -g, cfg)
            if clock is not None:   # line search costs a round (Fig.11 note)
                clock.phase(jax.random.fold_in(kp, 3), cfg.num_workers,
                            policy="wait_all",
                            flops_per_worker=grad_flops * 3, comm_units=0.3)
        else:
            step = cfg.lr

        if cfg.method == "nag":
            velocity = cfg.momentum * velocity - step * g
            w = w + velocity
        else:
            w = w - step * g

        hist["iter"].append(t)
        hist["fval"].append(float(val_fn(w, data)))
        hist["gnorm"].append(float(jnp.linalg.norm(grad_fn(w, data))))
        hist["step"].append(float(step))
        hist["time"].append(clock.time if clock is not None else float(t + 1))
        hist["cost"].append(clock.dollars if clock is not None else 0.0)
        if cfg.track_test_error and data.x_test is not None:
            hist["test_error"].append(
                float(objective.error(w, data.x_test, data.y_test)))
        else:
            hist["test_error"].append(float("nan"))
    hist["w"] = w
    return hist
