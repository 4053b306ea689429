"""L2-regularised logistic regression: its data, the program's objective,
and the plain reference.

    f(w) = (1/n) sum_i log(1 + exp(-y_i x_i.w)) + (lam/2) ||w||^2

A configuration names this file by ``"objective": "logistic"``.  What the
harness takes from it:

- ``PROGRAM`` and ``program_args``: the ``repro.core`` class that the timed
  path solves with, and its arguments from the configuration;
- ``start``: the iterate every solve starts from, w0 = 0 of length d;
- ``work``: the least work of one Newton iteration on this objective, the
  yardstick of the roofline metrics (logistic's counts live in
  ``bench/work.py``);
- ``make_data``: the benchmark's own jitted copy of the repository's
  synthetic generator (``repro.data.synthetic.make_logistic_dataset``, no
  test set): features uniform on [-1, 1]^d with columns scaled by a
  geometric spectrum from 1 down to 1/cond, labels +-1 from a random
  ground-truth model, rows optionally stored sorted by margin.  A copy, so
  that no change to the program can change the benchmark's inputs;
- the reference: ``optimum`` and ``value_at``, at full float32
  precision, and ``control``, the same exact Newton in the precision
  below the configuration's.

The reference is written from the formula and is independent of the
program: no sketch, no coded matvec, no straggler clock, nothing imported
from ``repro``.  Every pass over X goes through fixed blocks of rows, so it
never holds more than one block's temporaries beside X.  The float32
reference runs under ``default_matmul_precision("highest")``: on a TPU a
float32 matrix product otherwise rounds its inputs to bfloat16.  In
bfloat16 the products accumulate in float32, as the matrix unit does, and
only the Cholesky factorisation, which has no bfloat16 form, is computed in
float32 from the rounded Hessian and rounded back.
"""
from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp

from bench import work as counts

PROGRAM = "LogisticRegression"

# Armijo sufficient-decrease constant and the step candidates 4^0..4^-5.
ARMIJO = 0.1
STEPS = tuple(4.0 ** -i for i in range(6))
# Rows per block: 25,000 x 2000 float32 is 200 MB of temporaries.
BLOCK_ELEMS = 50_000_000
# The float32 reference runs Newton until the decrement g.H^-1.g is below
# this share of f (f - f* is half the decrement), or MAX_ITERS.
DECREMENT_TOL = 1e-11
MAX_ITERS = 40


def program_args(config: dict) -> dict:
    return {"lam": float(config["lam"])}


def start(x, y, config: dict) -> jax.Array:
    """w0 = 0: float32 zeros, one weight per feature."""
    return jnp.zeros((x.shape[1],), jnp.float32)


def work(config: dict) -> dict:
    """{"hessian", "gradient", "iteration": ``bench.work.Work``}: the d x d
    sketched Hessian over n rows, the gradient's two reads of X, and the
    whole iteration, at the configuration's shapes and sketch."""
    n, d = config["n"], config["d"]
    blocks = counts.sketch_blocks(config["newton"]["sketch"])
    return {"hessian": counts.hessian(n, d, *blocks),
            "gradient": counts.gradient(n, d),
            "iteration": counts.iteration(n, d, *blocks)}


@functools.partial(jax.jit,
                   static_argnames=("n", "d", "cond", "sorted_layout"))
def generate(key: jax.Array, n: int, d: int, cond: float,
             sorted_layout: bool):
    """(x (n, d) float32, y (n,) float32 in {-1, +1})."""
    kx, kw, kb, ky, _, _ = jax.random.split(key, 6)
    w = jax.random.normal(kw, (d,))
    b = jax.random.normal(kb, ())
    scales = jnp.geomspace(1.0, 1.0 / max(cond, 1.0), d)
    x = jax.random.uniform(kx, (n, d), minval=-1.0, maxval=1.0) * scales
    p = jax.nn.sigmoid(x @ w + b)
    y = jnp.where(jax.random.uniform(ky, (n,)) < p, 1.0, -1.0)
    if sorted_layout:
        order = jnp.argsort(x @ w)
        x, y = x[order], y[order]
    return x, y


def make_data(key: jax.Array, config: dict):
    return generate(key, int(config["n"]), int(config["d"]),
                    float(config["cond"]), bool(config["sorted_layout"]))


def row_blocks(n: int, d: int) -> List[Tuple[int, int]]:
    rows = max(1, min(n, BLOCK_ELEMS // max(d, 1)))
    return [(a, min(a + rows, n)) for a in range(0, n, rows)]


def _dot(a, b):
    """Product accumulated in float32, stored in the inputs' dtype."""
    return jnp.matmul(a, b, preferred_element_type=jnp.float32).astype(a.dtype)


@jax.jit
def value(x, y, w, lam):
    n, d = x.shape
    total = jnp.zeros((), jnp.float32)
    for a, b in row_blocks(n, d):
        m = y[a:b] * _dot(x[a:b], w)
        total += jnp.sum(jax.nn.softplus(-m), dtype=jnp.float32)
    reg = 0.5 * lam * jnp.sum(w.astype(jnp.float32) ** 2)
    return (total / n + reg).astype(x.dtype)


@jax.jit
def gradient(x, y, w, lam):
    n, d = x.shape
    g = jnp.zeros((d,), jnp.float32)
    for a, b in row_blocks(n, d):
        m = y[a:b] * _dot(x[a:b], w)
        r = -y[a:b] * jax.nn.sigmoid(-m)
        g += jnp.matmul(r, x[a:b], preferred_element_type=jnp.float32)
    return (g / n + lam * w.astype(jnp.float32)).astype(x.dtype)


@jax.jit
def hessian(x, y, w, lam):
    n, d = x.shape
    h = jnp.zeros((d, d), jnp.float32)
    for a, b in row_blocks(n, d):
        s = jax.nn.sigmoid(y[a:b] * _dot(x[a:b], w))
        xs = x[a:b] * (s * (1 - s))[:, None]
        h += jnp.matmul(xs.T, x[a:b], preferred_element_type=jnp.float32)
    return (h / n + lam * jnp.eye(d, dtype=jnp.float32)).astype(x.dtype)


@jax.jit
def direction(h, g):
    """-H^-1 g by Cholesky, factorised in float32 and stored in g's dtype."""
    c = jax.scipy.linalg.cho_factor(h.astype(jnp.float32), lower=True)
    c = (c[0].astype(h.dtype).astype(jnp.float32), c[1])
    return (-jax.scipy.linalg.cho_solve(c, g.astype(jnp.float32))).astype(
        g.dtype)


def newton(x, y, lam: float, iters: int | None = None):
    """Exact Newton with an Armijo search over ``STEPS`` from w = 0.

    ``iters=None`` runs to convergence (the float32 reference optimum);
    otherwise exactly ``iters`` iterations, as the program runs them.
    Returns (w, f(w) as this precision computes it, iterations run)."""
    dt = x.dtype
    w = jnp.zeros((x.shape[1],), dt)
    f = float(value(x, y, w, lam))
    t = 0
    while t < (MAX_ITERS if iters is None else iters):
        g = gradient(x, y, w, lam)
        p = direction(hessian(x, y, w, lam), g)
        gp = float(jnp.vdot(g.astype(jnp.float32), p.astype(jnp.float32)))
        if iters is None and -gp <= DECREMENT_TOL * abs(f):
            break
        for step in STEPS:
            w_new = (w + jnp.asarray(step, dt) * p).astype(dt)
            f_new = float(value(x, y, w_new, lam))
            if f_new <= f + ARMIJO * step * gp:
                break
        w, f = w_new, f_new
        t += 1
    return w, f, t


def with_precision(fn):
    """Run ``fn`` with float32 products at full float32 precision."""
    @functools.wraps(fn)
    def wrapped(*args, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kw)
    return wrapped


@with_precision
def optimum(x, y, config: dict):
    """(w*, f(w*)) of the float32 reference, run to convergence."""
    w, f, _ = newton(x, y, float(config["lam"]))
    return w, f


@with_precision
def value_at(x, y, w, config: dict) -> float:
    return float(value(x, y, w.astype(x.dtype), float(config["lam"])))


def control(x, y, config: dict, iters: int, dtype):
    """The reference in the program's place, in ``dtype``: its iterate, in
    float32, and the objective it computed for it."""
    xb, yb = x.astype(dtype), y.astype(dtype)
    w, f, _ = newton(xb, yb, float(config["lam"]), iters=iters)
    del xb, yb
    return w.astype(jnp.float32), float(f)
