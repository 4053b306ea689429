"""The benchmark's data generator copy, its plain reference and the
comparison that decides ``correct``, on the CPU at tiny sizes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cell as cells, check
from bench.objectives import logistic

LAM = 1e-5
CONFIG = {"lam": LAM}
LIMITS = {"f_gap": 1e-4, "f_report": 1e-5}


@pytest.fixture(scope="module")
def problem():
    x, y = logistic.generate(jax.random.PRNGKey(3), 1200, 16, 10.0, True)
    return x, y


@pytest.mark.parametrize("sorted_layout", [True, False])
def test_generator_copy_matches_the_program_generator(sorted_layout):
    from repro.data.synthetic import make_logistic_dataset
    key = jax.random.PRNGKey(7)
    want = make_logistic_dataset(key, 300, 12, 0, cond=10.0,
                                 sorted_layout=sorted_layout)
    x, y = logistic.make_data(key, {"n": 300, "d": 12, "cond": 10.0,
                                    "sorted_layout": sorted_layout})
    np.testing.assert_array_equal(np.asarray(x), np.asarray(want.x))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want.y))


def test_derived_seeds_are_31_bit_and_repeatable():
    big = 2 ** 40 + 17
    s = cells.derive_seed(big, "solve", 3)
    assert s == cells.derive_seed(big, "solve", 3)
    assert 0 <= s < 2 ** 31
    assert s != cells.derive_seed(big, "solve", 4)
    assert s != cells.derive_seed(big + 1, "solve", 3)


def test_reference_derivatives_match_autodiff(problem):
    x, y = problem
    w = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (x.shape[1],))
    with jax.default_matmul_precision("highest"):
        g = logistic.gradient(x, y, w, LAM)
        h = logistic.hessian(x, y, w, LAM)
        g_ad = jax.grad(lambda v: logistic.value(x, y, v, LAM))(w)
        h_ad = jax.hessian(lambda v: logistic.value(x, y, v, LAM))(w)
    np.testing.assert_allclose(g, g_ad, rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(h, h_ad, rtol=1e-4, atol=1e-7)


def test_reference_blocks_do_not_change_the_sums(problem, monkeypatch):
    x, y = problem
    w = jnp.full((x.shape[1],), 0.1)
    whole = float(logistic.value(x, y, w, LAM))
    monkeypatch.setattr(logistic, "BLOCK_ELEMS", 16 * 100)
    assert len(logistic.row_blocks(*x.shape)) == 12
    blocked = float(jax.jit(logistic.value.__wrapped__)(x, y, w, LAM))
    assert blocked == pytest.approx(whole, rel=1e-6)


def test_reference_converges_to_a_stationary_point(problem):
    x, y = problem
    w, f = logistic.optimum(x, y, CONFIG)
    with jax.default_matmul_precision("highest"):
        g = logistic.gradient(x, y, w, LAM)
        g0 = logistic.gradient(x, y, jnp.zeros_like(w), LAM)
    assert float(jnp.linalg.norm(g)) < 1e-3 * float(jnp.linalg.norm(g0))
    assert f < float(np.log(2.0))


def test_the_control_runs_in_bfloat16(problem):
    x, y = problem
    w, f = logistic.control(x, y, CONFIG, 3, jnp.bfloat16)
    assert w.dtype == jnp.float32 and np.isfinite(f)
    # Its values are bfloat16's: rounding them again changes nothing.
    np.testing.assert_array_equal(
        np.asarray(w), np.asarray(w.astype(jnp.bfloat16).astype(
            jnp.float32)))
    assert f == float(jnp.asarray(f, jnp.bfloat16))


def _answer(w, f):
    return cells.Answer(w=jnp.asarray(w, jnp.float32), f=float(f),
                        seconds=0.0)


def test_the_optimum_passes_and_faults_fail(problem):
    x, y = problem
    w, f = logistic.optimum(x, y, CONFIG)

    def judge(*answers):
        return check.judge(logistic, x, y, CONFIG, list(answers), LIMITS)

    ok = judge(_answer(w, f))
    assert ok["failed"] == 0 and ok["numbers"]["f_gap"] < 1e-6
    unmoved = judge(_answer(jnp.zeros_like(w), np.log(2)))
    assert unmoved["failed"] == 1
    assert unmoved["numbers"]["f_gap"] > 0.1
    misreported = judge(_answer(w, f * (1 + 1e-3)))
    assert misreported["failed"] == 1
    assert misreported["numbers"]["f_report"] == pytest.approx(1e-3,
                                                               rel=1e-2)
    nan = judge(_answer(w * np.nan, f))
    assert nan["failed"] == 1


def test_lines_print_each_number_beside_its_limit():
    out = check.lines({"f_gap": 1.5e-5, "f_report": 2e-7},
                      {"f_gap": 1e-3, "f_report": 1e-4})
    assert out == ["f_gap 1.5e-05 limit 0.001", "f_report 2e-07 limit 0.0001"]
