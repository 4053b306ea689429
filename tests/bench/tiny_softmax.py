"""A second objective for the harness's tests: unregularised softmax
regression, whose iterate has K d weights where logistic's has d.

    f(w) = (1/n) sum_i [logsumexp(x_i W^T) - y_i . x_i W^T]

with W = w.reshape(K, d) and y_i one-hot.

The tests copy this file to ``bench/objectives/tiny_softmax.py`` of a
scratch checkout and name it from a configuration, as a new objective is
added: the harness finds it by name and takes from it ``PROGRAM``,
``program_args``, ``make_data``, ``start``, ``work``, ``optimum`` and
``value_at``.

The reference is exact Newton written from the formula: the K d x K d
Hessian (1/n) sum_i (diag(p_i) - p_i p_i^T) kron x_i x_i^T, singular along
the shift of every class by one vector, so the direction is its
pseudo-inverse times the gradient; an Armijo search over 4^0..4^-5; all
under ``default_matmul_precision("highest")``.  Nothing is imported from
``repro``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import work as counts

PROGRAM = "SoftmaxRegression"

ARMIJO = 0.1
STEPS = tuple(4.0 ** -i for i in range(6))
DECREMENT_TOL = 1e-12
MAX_ITERS = 50


def program_args(config: dict) -> dict:
    return {"num_classes": int(config["classes"])}


@functools.partial(jax.jit, static_argnames=("n", "d", "k"))
def generate(key: jax.Array, n: int, d: int, k: int):
    """(x (n, d) uniform on [-1, 1], y (n, k) one-hot labels drawn from a
    random ground-truth softmax model)."""
    kx, kw, ky = jax.random.split(key, 3)
    x = jax.random.uniform(kx, (n, d), minval=-1.0, maxval=1.0)
    w = jax.random.normal(kw, (k, d))
    labels = jax.random.categorical(ky, x @ w.T, axis=1)
    return x, jax.nn.one_hot(labels, k, dtype=jnp.float32)


def make_data(key: jax.Array, config: dict):
    return generate(key, int(config["n"]), int(config["d"]),
                    int(config["classes"]))


def start(x, y, config: dict) -> jax.Array:
    """w0 = 0: float32 zeros, d weights for each of the K classes."""
    return jnp.zeros((int(config["classes"]) * x.shape[1],), jnp.float32)


def work(config: dict) -> dict:
    """The sketched K d x K d Hessian over the n K rows of its square root,
    a gradient of K columns per product over two reads of X, one more read
    for the line search, and a factorisation of the K d x K d Hessian."""
    n, d, k = config["n"], config["d"], config["classes"]
    blocks = counts.sketch_blocks(config["newton"]["sketch"])
    hessian = counts.hessian(n * k, k * d, *blocks)
    gradient = counts.Work(ops=4.0 * n * d * k,
                           bytes=2.0 * counts.F32 * n * d)
    search = counts.Work(ops=2.0 * n * d * k, bytes=counts.F32 * n * d)
    return {"hessian": hessian, "gradient": gradient,
            "iteration": hessian + gradient + search
            + counts.direction(k * d)}


def _probs(x, y, w):
    logits = x @ w.reshape(y.shape[1], x.shape[1]).T
    return logits, jax.nn.softmax(logits, axis=1)


@jax.jit
def value(x, y, w):
    logits, _ = _probs(x, y, w)
    return jnp.mean(jax.nn.logsumexp(logits, axis=1)
                    - jnp.sum(logits * y, axis=1))


@jax.jit
def gradient(x, y, w):
    _, p = _probs(x, y, w)
    return ((p - y).T @ x / x.shape[0]).reshape(-1)


@jax.jit
def hessian(x, y, w):
    _, p = _probs(x, y, w)
    k, d = y.shape[1], x.shape[1]
    b = jnp.eye(k)[None] * p[:, None, :] - p[:, :, None] * p[:, None, :]
    h = jnp.einsum("nkl,nj,nm->kjlm", b, x, x) / x.shape[0]
    return h.reshape(k * d, k * d)


def with_precision(fn):
    @functools.wraps(fn)
    def wrapped(*args, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kw)
    return wrapped


@with_precision
def optimum(x, y, config: dict):
    """(w*, f(w*)): exact Newton with a pseudo-inverse direction from
    w = 0, until the decrement is below ``DECREMENT_TOL`` of f."""
    w = start(x, y, config)
    f = float(value(x, y, w))
    for _ in range(MAX_ITERS):
        g = gradient(x, y, w)
        p = -jnp.linalg.pinv(hessian(x, y, w), hermitian=True) @ g
        gp = float(g @ p)
        if -gp <= DECREMENT_TOL * abs(f):
            break
        for step in STEPS:
            w_new = w + step * p
            f_new = float(value(x, y, w_new))
            if f_new <= f + ARMIJO * step * gp:
                break
        w, f = w_new, f_new
    return w, f


@with_precision
def value_at(x, y, w, config: dict) -> float:
    return float(value(x, y, w))
