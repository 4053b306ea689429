"""OverSketched Newton end-to-end behaviour: convergence on strongly and
weakly convex problems, straggler policies, theory-flavoured checks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Dataset, LogisticRegression, NewtonConfig,
                        OverSketchConfig, RidgeRegression, SoftmaxRegression,
                        StragglerModel, oversketched_newton)


def _logistic(key, n=1500, d=30):
    kx, kw, ky = jax.random.split(key, 3)
    x = jax.random.uniform(kx, (n, d), minval=-1, maxval=1)
    wstar = jax.random.normal(kw, (d,))
    y = jnp.where(jax.random.uniform(ky, (n,)) < jax.nn.sigmoid(x @ wstar),
                  1.0, -1.0)
    return Dataset(x=x, y=y), wstar


def test_strongly_convex_converges_to_tolerance():
    data, _ = _logistic(jax.random.PRNGKey(0))
    obj = LogisticRegression(lam=1e-4)
    cfg = NewtonConfig(iters=10, sketch=OverSketchConfig(512, 64, 0.25),
                       coded_block_rows=128)
    res = oversketched_newton(obj, data, jnp.zeros(data.x.shape[1]), cfg)
    assert res.history["gnorm"][-1] < 1e-3
    # monotone decrease of f
    f = res.history["fval"]
    assert all(f[i + 1] <= f[i] + 1e-6 for i in range(len(f) - 1))


def test_matches_exact_newton_iterate_count():
    """Sketched Newton should need a similar number of iterations to exact
    Newton (paper Fig. 6 observation) on a well-conditioned problem."""
    data, _ = _logistic(jax.random.PRNGKey(1), n=1200, d=20)
    obj = LogisticRegression(lam=1e-3)
    common = dict(iters=8, coded_block_rows=128)
    sk_cfg = NewtonConfig(sketch=OverSketchConfig(1024, 128, 0.25), **common)
    ex_cfg = NewtonConfig(hessian_policy="exact",
                          sketch=OverSketchConfig(1024, 128, 0.25), **common)
    r_sk = oversketched_newton(obj, data, jnp.zeros(20), sk_cfg, model=None)
    r_ex = oversketched_newton(obj, data, jnp.zeros(20), ex_cfg, model=None)
    it_sk = next(i for i, g in enumerate(r_sk.history["gnorm"]) if g < 1e-4)
    it_ex = next(i for i, g in enumerate(r_ex.history["gnorm"]) if g < 1e-4)
    assert it_sk <= it_ex + 3


def test_weakly_convex_gradnorm_linear_decrease():
    """Thm 3.3: ||grad f||^2 decreases linearly for softmax (weakly convex)."""
    key = jax.random.PRNGKey(2)
    n, d, k = 900, 12, 4
    kx, kw, ky = jax.random.split(key, 3)
    x = jax.random.normal(kx, (n, d))
    w = jax.random.normal(kw, (k, d))
    y = jax.nn.one_hot(jax.random.categorical(ky, x @ w.T), k)
    obj = SoftmaxRegression(num_classes=k)
    cfg = NewtonConfig(iters=7, sketch=OverSketchConfig(1024, 128, 0.25),
                       coded_block_rows=128, solver="pinv")
    res = oversketched_newton(obj, Dataset(x=x, y=y), jnp.zeros(k * d), cfg)
    g = res.history["gnorm"]
    assert g[-1] < 0.3 * g[0]
    assert all(g[i + 1] <= g[i] * 1.01 for i in range(len(g) - 1))


def test_straggler_sim_makes_coded_faster_than_wait_all():
    """Coded gradients must beat wait-all in simulated time (Fig. 6)."""
    data, _ = _logistic(jax.random.PRNGKey(3), n=2000, d=25)
    obj = LogisticRegression(lam=1e-4)
    # aggressive-but-decodable tail (the 2-D product code targets the
    # paper's ~2-5% straggler regime)
    model = StragglerModel(p_tail=0.08, tail_hi=3.0)
    base = dict(iters=5, sketch=OverSketchConfig(512, 64, 0.25),
                coded_block_rows=64)
    t_coded = oversketched_newton(
        obj, data, jnp.zeros(25),
        NewtonConfig(gradient_policy="coded", **base),
        model=model).history["time"][-1]
    t_wait = oversketched_newton(
        obj, data, jnp.zeros(25),
        NewtonConfig(gradient_policy="wait_all", **base),
        model=model).history["time"][-1]
    assert t_coded < t_wait


def test_unit_step_works():
    """Paper footnote 9: unit step-size suffices in practice."""
    data, _ = _logistic(jax.random.PRNGKey(4))
    obj = LogisticRegression(lam=1e-4)
    cfg = NewtonConfig(iters=8, unit_step=True,
                       sketch=OverSketchConfig(512, 64, 0.25),
                       coded_block_rows=128)
    res = oversketched_newton(obj, data, jnp.zeros(data.x.shape[1]), cfg,
                              model=None)
    assert res.history["gnorm"][-1] < 1e-3


def test_cg_solver_path():
    data, _ = _logistic(jax.random.PRNGKey(5), n=800, d=15)
    obj = LogisticRegression(lam=1e-3)
    cfg = NewtonConfig(iters=6, solver="cg", cg_iters=40,
                       sketch=OverSketchConfig(512, 64, 0.25),
                       coded_block_rows=128)
    res = oversketched_newton(obj, data, jnp.zeros(15), cfg, model=None)
    assert res.history["gnorm"][-1] < 1e-3


def test_ridge_closed_form_agreement():
    """Sketched Newton on ridge must land near the closed-form optimum."""
    key = jax.random.PRNGKey(6)
    n, d = 1000, 20
    x = jax.random.normal(key, (n, d))
    y = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    lam = 0.1
    data = Dataset(x=x, y=y)
    obj = RidgeRegression(lam=lam)
    cfg = NewtonConfig(iters=12, sketch=OverSketchConfig(2048, 256, 0.25),
                       coded_block_rows=128)
    res = oversketched_newton(obj, data, jnp.zeros(d), cfg, model=None)
    w_closed = jnp.linalg.solve(x.T @ x / n + lam * jnp.eye(d), x.T @ y / n)
    np.testing.assert_allclose(np.asarray(res.w), np.asarray(w_closed),
                               rtol=2e-2, atol=2e-3)


def test_history_schema():
    data, _ = _logistic(jax.random.PRNGKey(7), n=400, d=10)
    obj = LogisticRegression()
    cfg = NewtonConfig(iters=3, sketch=OverSketchConfig(256, 64, 0.25),
                       coded_block_rows=64)
    res = oversketched_newton(obj, data, jnp.zeros(10), cfg)
    for k in ("iter", "fval", "gnorm", "step", "time"):
        assert len(res.history[k]) == 3
    assert res.history["time"] == sorted(res.history["time"])


def test_sketch_draw_takes_the_seed_keys_bits_on_the_datas_device(
        monkeypatch):
    """The key chain runs on the host, yet every iteration's sketch
    (its hashes and signs) is the one that PRNGKey(seed), split four ways
    per iteration and folded with 7, draws; it is drawn on the data's
    device."""
    from repro import sketching
    from repro.core import newton
    data, _ = _logistic(jax.random.PRNGKey(8), n=600, d=12)
    obj = LogisticRegression(lam=1e-4)
    cfg = NewtonConfig(iters=3, sketch=OverSketchConfig(256, 64, 0.25),
                       coded_block_rows=64, seed=1600000013)
    seen = []
    jitted = newton._jitted_sketched_hessian

    def spy(objective, family):
        fn = jitted(objective, family)

        def call(w, data, state, survivors):
            seen.append((state, survivors))
            return fn(w, data, state, survivors)
        return call
    monkeypatch.setattr(newton, "_jitted_sketched_hessian", spy)
    oversketched_newton(obj, data, jnp.zeros(12), cfg)
    assert len(seen) == cfg.iters
    fam = sketching.get(cfg.sketch_family, cfg.sketch)
    key = jax.random.PRNGKey(cfg.seed)
    device, = data.x.devices()
    for state, survivors in seen:
        key, _, kh, _ = jax.random.split(key, 4)
        want = fam.sample(jax.random.fold_in(kh, 7), data.x.shape[0])
        for got, ref in zip(jax.tree.leaves(state), jax.tree.leaves(want)):
            assert got.devices() == {device}
            assert np.array_equal(np.asarray(got), np.asarray(ref))
        assert survivors.shape == (cfg.sketch.total_blocks,)
