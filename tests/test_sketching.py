"""Pluggable sketching subsystem: registry round-trips, per-family
unbiasedness of the sketched Gram, survivor-subset rescaling, each
family's apply and Gram against its oracle and its dense formula, the FWHT
butterfly, Marchenko-Pastur debiasing, and end-to-end Newton convergence
for every family (incl. distributed-avg)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sketching
from repro.core import (Dataset, LogisticRegression, NewtonConfig,
                        OverSketchConfig, oversketched_newton)
from repro.core.sketch import sketched_gram
from repro.kernels import ref

FAMILIES = ("oversketch", "srht", "sjlt", "gaussian", "nystrom", "leverage")


def _cfg(m=256, b=64, zeta=0.25):
    return OverSketchConfig(m, b, zeta)


def _logistic(key, n=1200, d=20):
    kx, kw, ky = jax.random.split(key, 3)
    x = jax.random.uniform(kx, (n, d), minval=-1, maxval=1)
    wstar = jax.random.normal(kw, (d,))
    y = jnp.where(jax.random.uniform(ky, (n,)) < jax.nn.sigmoid(x @ wstar),
                  1.0, -1.0)
    return Dataset(x=x, y=y)


# ------------------------------------------------------------------ registry
def test_registry_round_trip():
    cfg = _cfg()
    for name in FAMILIES:
        fam = sketching.get(name, cfg)
        assert fam.name == name
        assert fam.cfg is cfg
    assert set(FAMILIES) <= set(sketching.available())


def test_registry_unknown_family_raises():
    with pytest.raises(KeyError, match="unknown sketch family"):
        sketching.get("fourier", _cfg())


def test_families_are_hashable_and_cacheable():
    """jit-closure caching in newton keys on family instances."""
    cfg = _cfg()
    for name in FAMILIES:
        assert sketching.get(name, cfg) == sketching.get(name, cfg)
        assert hash(sketching.get(name, cfg)) == hash(sketching.get(name, cfg))


# ------------------------------------------------------- per-family statistics
@pytest.mark.parametrize("name", FAMILIES)
def test_gram_unbiased(name):
    """E[A^T S S^T A] = A^T A per family, within Monte-Carlo tolerance."""
    key = jax.random.PRNGKey(3)
    n, d, reps = 300, 12, 60
    a = jax.random.normal(key, (n, d)) / np.sqrt(n)
    fam = sketching.get(name, _cfg(256, 64, 0.25))
    grams = []
    for r in range(reps):
        state = fam.sample(jax.random.fold_in(key, r), n)
        grams.append(fam.gram(state, a))
    avg = jnp.stack(grams).mean(axis=0)
    true = a.T @ a
    rel = float(jnp.linalg.norm(avg - true) / jnp.linalg.norm(true))
    assert rel < 0.08, f"{name}: mean sketched Gram off by {rel:.3f}"


@pytest.mark.parametrize("name", FAMILIES)
def test_survivor_subset_rescaling(name):
    """Masked gram == mean of the surviving per-block grams, exactly."""
    key = jax.random.PRNGKey(4)
    n, d = 200, 10
    a = jax.random.normal(key, (n, d))
    fam = sketching.get(name, _cfg(256, 64, 0.5))
    state = fam.sample(jax.random.fold_in(key, 1), n)
    a_t = fam.apply(state, a)                    # (K, b, d)
    surv = jnp.arange(fam.cfg.total_blocks) % 3 != 0
    got = fam.gram(state, a, surv)
    keep = np.asarray(a_t)[np.asarray(surv)]
    want = np.einsum("kbd,kbe->de", keep, keep) / keep.shape[0]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("name", ("oversketch", "srht", "sjlt"))
def test_apply_matches_the_oracle(name):
    """Each family's apply against its ``kernels/ref.py`` oracle on the
    same draws (the OverSketch family's is ``apply_sketch``'s CPU branch,
    the ``lax.map`` of segment sums)."""
    key = jax.random.PRNGKey(5)
    a = jax.random.normal(key, (200, 20))
    fam = sketching.get(name, _cfg(256, 64, 0.25))
    state = fam.sample(jax.random.fold_in(key, 2), 200)
    b = fam.cfg.block_size
    expect = {
        "oversketch": lambda: ref.count_sketch_apply(state.h, state.sigma,
                                                     a, b),
        "srht": lambda: ref.srht_apply(state["rows"], state["sigma"], a),
        "sjlt": lambda: ref.sjlt_apply(state["h"], state["sigma"], a, b),
    }[name]()
    np.testing.assert_allclose(np.asarray(jax.jit(fam.apply)(state, a)),
                               np.asarray(expect), rtol=1e-5, atol=1e-5)


def _dense_sketch(fam, state, a):
    """The (K, n, b) sketch matrices S_k the family's draw stands for,
    written out entry by entry (S_k^T A is what ``apply`` returns)."""
    n = a.shape[0]
    k, b = fam.cfg.total_blocks, fam.cfg.block_size
    if fam.name in ("oversketch", "sjlt"):
        h, sigma = ((state.h[:, None], state.sigma[:, None])
                    if fam.name == "oversketch"
                    else (state["h"], state["sigma"]))
        one_hot = jax.nn.one_hot(h, b) * sigma[..., None]   # (K, s, n, b)
        return one_hot.sum(axis=1) / np.sqrt(h.shape[1])
    if fam.name == "srht":
        n_pad = 1 << max(0, (n - 1).bit_length())
        had = ref.fwht(jnp.eye(n_pad)[None])[0][:n]          # (n, n_pad)
        cols = had[:, state["rows"]].transpose(1, 0, 2)      # (K, n, b)
        return (cols * state["sigma"][..., None]
                * np.sqrt(n_pad / b))
    if fam.name == "gaussian":
        return jax.vmap(lambda kk: jax.random.normal(kk, (n, b))
                        / np.sqrt(b))(state["keys"])
    if fam.name == "nystrom":
        return (jax.nn.one_hot(state["rows"], n).transpose(0, 2, 1)
                * np.sqrt(n / b))
    # leverage: rows drawn by A's leverage scores, each scaled by
    # 1/sqrt(b p_row).
    q, _ = jnp.linalg.qr(a)
    lev = jnp.sum(q * q, axis=1)
    p = lev / jnp.sum(lev)
    rows = jax.random.choice(state["key"], n, (k, b), replace=True, p=p)
    return (jax.nn.one_hot(rows, n).transpose(0, 2, 1)
            / jnp.sqrt(b * p[rows])[:, None, :])


@pytest.mark.parametrize("d", [640, 2048])
@pytest.mark.parametrize("name", FAMILIES)
def test_gram_matches_the_dense_formula(name, d):
    """Each family's masked Gram against (1/N_avail) sum_k m_k
    (S_k^T A)^T (S_k^T A) from its written-out sketch matrices, at d past
    512."""
    key = jax.random.PRNGKey(9)
    n = 64
    a = jax.random.normal(key, (n, d)) / np.sqrt(n)
    fam = sketching.get(name, _cfg(256, 64, 0.25))
    state = fam.sample(jax.random.fold_in(key, 1), n)
    surv = jnp.arange(fam.cfg.total_blocks) % 2 == 0
    a_t = jnp.einsum("knb,nd->kbd", _dense_sketch(fam, state, a), a,
                     precision="highest")
    keep = np.asarray(a_t, np.float64)[np.asarray(surv)]
    want = np.einsum("kbd,kbe->de", keep, keep) / keep.shape[0]
    np.testing.assert_allclose(np.asarray(jax.jit(fam.gram)(state, a, surv)),
                               want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("masked", [False, True])
def test_core_oversketched_gram_is_the_sketched_gram_of_apply_sketch(masked):
    """core.sketch.oversketched_gram draws its sketch from the key and is
    sketched_gram(apply_sketch(...)) on that draw, bit for bit."""
    from repro.core import sketch as core_sketch
    key = jax.random.PRNGKey(8)
    n = 400
    a = jax.random.normal(key, (n, 16)) / np.sqrt(n)
    cfg = _cfg(256, 64, 0.25)
    kf = jax.random.fold_in(key, 1)
    surv = (jnp.ones((cfg.total_blocks,), bool).at[1].set(False)
            if masked else None)
    cs = core_sketch.sample_countsketch(kf, n, cfg)
    np.testing.assert_array_equal(
        np.asarray(core_sketch.oversketched_gram(kf, a, cfg, surv)),
        np.asarray(sketched_gram(core_sketch.apply_sketch(cs, a), surv)))


# --------------------------------------------------------------- FWHT
@pytest.mark.parametrize("k,n,d,dtype", [
    (2, 128, 9, jnp.float32),
    (2, 8, 5, jnp.float32),
    (3, 256, 17, jnp.float32),
    (1, 512, 130, jnp.float32),
    (2, 64, 20, jnp.float32),
    (1, 1024, 130, jnp.float32),
    (2, 2048, 17, jnp.float32),
    (1, 4096, 256, jnp.float32),
    (1, 32768, 8, jnp.float32),       # past the old kernel's panel budget
    (1, 512, 64, jnp.float32),
    (2, 256, 40, jnp.float32),
    (2, 256, 40, jnp.bfloat16),
    (1, 512, 64, jnp.bfloat16),
])
def test_fwht_is_orthonormal_involution(k, n, d, dtype):
    """The butterfly SRHT runs: norms kept, and H_norm^2 = I (Sylvester H
    is symmetric).  bfloat16 rounds at each of the log2(n) stages, so its
    bound is a few roundings (2^-8 each) at the largest magnitude."""
    x = jax.random.normal(jax.random.PRNGKey(n + d), (k, n, d)).astype(dtype)
    x32 = np.asarray(x, np.float32)
    rtol = atol = 1e-5
    if dtype == jnp.bfloat16:
        rtol, atol = 2.0 ** -6, 2.0 ** -5 * np.abs(x32).max()
    y = jax.jit(ref.fwht)(x)
    assert y.dtype == dtype
    np.testing.assert_allclose(float(jnp.linalg.norm(y.astype(jnp.float32))),
                               float(np.linalg.norm(x32)), rtol=rtol)
    np.testing.assert_allclose(np.asarray(jax.jit(ref.fwht)(y), np.float32),
                               x32, rtol=rtol, atol=atol)


def test_fwht_rejects_non_pow2():
    with pytest.raises(ValueError):
        ref.fwht(jnp.zeros((1, 100, 4)))


# ----------------------------------------------------------------- leverage
def test_leverage_beats_uniform_sampling_on_spiky_rows():
    """On a matrix whose mass sits in a few high-leverage rows, uniform
    Nystrom sampling mostly misses them; leverage-score sampling keeps
    them (Drineas-Mahoney-Muthukrishnan) at the same per-worker cost."""
    key = jax.random.PRNGKey(11)
    a = jax.random.normal(key, (400, 10)) * 0.05
    a = a.at[:8].mul(40.0)                  # 8 dominant rows
    cfg = _cfg(256, 64, 0.25)
    true = a.T @ a

    def mean_err(name):
        fam = sketching.get(name, cfg)
        errs = []
        for r in range(20):
            state = fam.sample(jax.random.fold_in(key, r), 400)
            g = fam.gram(state, a)
            errs.append(float(jnp.linalg.norm(g - true)
                              / jnp.linalg.norm(true)))
        return np.mean(errs)

    assert mean_err("leverage") < 0.5 * mean_err("nystrom")


# ------------------------------------------------------------------- debias
def test_mp_factor_values():
    assert float(sketching.mp_factor(20, 80)) == pytest.approx(0.75)
    # clamped far outside the m > d regime
    assert float(sketching.mp_factor(64, 4)) == pytest.approx(
        sketching.debias.MIN_FACTOR)


def test_debias_reduces_direction_bias():
    """E[gamma * H_hat^{-1} g] is much closer to H^{-1} g than the plain
    sketched direction (inverse-Wishart inflation m/(m-d-1) vs MP's 1-d/m)."""
    key = jax.random.PRNGKey(6)
    n, d, m, reps = 400, 20, 64, 200
    a = jax.random.normal(key, (n, d)) / np.sqrt(n)
    g = jax.random.normal(jax.random.fold_in(key, 1), (d,))
    h_true = a.T @ a
    p_exact = jnp.linalg.solve(h_true, g)
    fam = sketching.get("gaussian", OverSketchConfig(m, m, 0.0))

    def one(k):
        a_t = fam.apply(fam.sample(k, n), a)
        return jnp.linalg.solve(sketched_gram(a_t), g)

    p_all = jax.vmap(one)(jax.random.split(jax.random.fold_in(key, 2), reps))
    p_plain = p_all.mean(axis=0)
    p_deb = sketching.debias_direction(p_plain, d, m)
    err_plain = float(jnp.linalg.norm(p_plain - p_exact))
    err_deb = float(jnp.linalg.norm(p_deb - p_exact))
    assert err_deb < 0.35 * err_plain, (err_plain, err_deb)


# --------------------------------------------------------------- end to end
@pytest.mark.parametrize("name", FAMILIES)
def test_newton_converges_for_every_family(name):
    """Acceptance: all five families hit the same tolerance on logistic."""
    data = _logistic(jax.random.PRNGKey(7))
    obj = LogisticRegression(lam=1e-4)
    cfg = NewtonConfig(iters=10, sketch=_cfg(512, 64, 0.25),
                       coded_block_rows=128, sketch_family=name)
    res = oversketched_newton(obj, data, jnp.zeros(data.x.shape[1]), cfg)
    assert res.history["gnorm"][-1] < 1e-3


def test_debiased_beats_plain_unit_step_newton():
    """With unit steps and a tight sketch (m = 2d), the plain sketched
    direction is ~2x too long in expectation; MP debiasing restores
    convergence (Romanov-Zhang-Pilanci 2024 motivation)."""
    data = _logistic(jax.random.PRNGKey(8), n=1000, d=24)
    obj = LogisticRegression(lam=1e-3)
    base = dict(iters=8, sketch=OverSketchConfig(48, 48, 0.0),
                coded_block_rows=128, sketch_family="gaussian",
                unit_step=True)
    f_plain = oversketched_newton(
        obj, data, jnp.zeros(24), NewtonConfig(debias=False, **base),
        model=None).history["fval"][-1]
    f_deb = oversketched_newton(
        obj, data, jnp.zeros(24), NewtonConfig(debias=True, **base),
        model=None).history["fval"][-1]
    assert f_deb < f_plain


def test_distributed_avg_mode_converges():
    """Bartan-Pilanci direction averaging under the straggler clock."""
    data = _logistic(jax.random.PRNGKey(9))
    obj = LogisticRegression(lam=1e-4)
    cfg = NewtonConfig(iters=10, sketch=OverSketchConfig(512, 128, 0.25),
                       coded_block_rows=128, sketch_family="gaussian",
                       sketch_mode="distributed-avg", debias=True)
    res = oversketched_newton(obj, data, jnp.zeros(data.x.shape[1]), cfg)
    assert res.history["gnorm"][-1] < 1e-3
    assert res.history["time"] == sorted(res.history["time"])


def test_distavg_cg_agrees_with_dense_solve():
    """distavg_solver='cg' (matvec-only per-block solves, for d beyond
    master-factorization scale) must track the dense Cholesky path."""
    data = _logistic(jax.random.PRNGKey(12))
    obj = LogisticRegression(lam=1e-4)
    base = dict(iters=6, sketch=OverSketchConfig(512, 128, 0.25),
                coded_block_rows=128, sketch_family="gaussian",
                sketch_mode="distributed-avg", debias=True)
    r_chol = oversketched_newton(obj, data, jnp.zeros(data.x.shape[1]),
                                 NewtonConfig(distavg_solver="chol", **base))
    r_cg = oversketched_newton(obj, data, jnp.zeros(data.x.shape[1]),
                               NewtonConfig(distavg_solver="cg", **base))
    np.testing.assert_allclose(np.asarray(r_chol.w), np.asarray(r_cg.w),
                               rtol=1e-3, atol=1e-4)
    assert r_cg.history["gnorm"][-1] < 1e-3


def test_unknown_distavg_solver_raises():
    data = _logistic(jax.random.PRNGKey(14), n=200, d=8)
    with pytest.raises(ValueError, match="distavg_solver"):
        oversketched_newton(LogisticRegression(), data, jnp.zeros(8),
                            NewtonConfig(iters=1,
                                         sketch=_cfg(128, 64, 0.25),
                                         distavg_solver="qr"))


def test_distavg_requires_block_size_above_dim():
    data = _logistic(jax.random.PRNGKey(11), n=200, d=30)
    with pytest.raises(ValueError, match="block_size"):
        oversketched_newton(
            LogisticRegression(), data, jnp.zeros(30),
            NewtonConfig(iters=1, sketch=OverSketchConfig(64, 16, 0.25),
                         sketch_mode="distributed-avg"))
    with pytest.raises(ValueError, match="hessian_policy"):
        oversketched_newton(
            LogisticRegression(), data, jnp.zeros(30),
            NewtonConfig(iters=1, sketch=OverSketchConfig(128, 64, 0.25),
                         sketch_mode="distributed-avg",
                         hessian_policy="exact"))


def test_unknown_sketch_mode_raises():
    data = _logistic(jax.random.PRNGKey(10), n=200, d=8)
    with pytest.raises(ValueError, match="sketch_mode"):
        oversketched_newton(LogisticRegression(), data, jnp.zeros(8),
                            NewtonConfig(iters=1, sketch=_cfg(128, 64, 0.25),
                                         sketch_mode="bogus"))
