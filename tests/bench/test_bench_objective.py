"""What the harness takes from an objective file: the iterate a solve starts
from (``start``) and the work the roofline metrics divide by (``work``).

Logistic's are the harness's former built-ins, to the bit.  A second
objective, softmax regression with an iterate of K d weights
(``tiny_softmax.py``), runs through the unchanged harness as new files
and ``BENCHMARK.json`` entries only, and is judged against its own plain
reference."""
import json
import os
import shutil

import jax.numpy as jnp
import pytest

from bench import work
from bench.objectives import logistic
from conftest import REPO, add_cell

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = ("epsilon", "a9a", "synthetic")

TINY_SOFTMAX = {
    "name": "tiny_softmax", "objective": "tiny_softmax", "classes": 3,
    "n": 1536, "d": 8, "n_test": 0, "precision": "float32",
    "newton": {"sketch": {"sketch_dim": 512, "block_size": 64,
                          "straggler_tolerance": 0.25},
               "coded_block_rows": 64},
}
# Six iterations bring the program within about 2e-7 of the reference
# optimum on the CPU (8 seeds), three only within about 1e-3.
SOFTMAX_ITERS = 6
SOFTMAX_LIMITS = {"f_gap": {"limit": 1e-5}, "f_report": {"limit": 1e-4}}


def _config(name):
    with open(os.path.join(REPO, "bench", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", CONFIGS)
def test_logistic_work_is_what_the_readers_counted_before(name):
    """The readers called ``bench.work`` with the configuration's n, d and
    sketch blocks; the objective's ``work`` gives the same counts."""
    cfg = _config(name)
    blocks = work.sketch_blocks(cfg["newton"]["sketch"])
    got = logistic.work(cfg)
    assert set(got) == {"hessian", "gradient", "iteration"}
    assert got["hessian"] == work.hessian(cfg["n"], cfg["d"], *blocks)
    assert got["gradient"] == work.gradient(cfg["n"], cfg["d"])
    assert got["iteration"] == work.iteration(cfg["n"], cfg["d"], *blocks)


def test_logistic_starts_at_float32_zeros_of_length_d():
    x = jnp.ones((5, 7), jnp.float32)
    w0 = logistic.start(x, jnp.ones((5,), jnp.float32), _config("a9a"))
    assert w0.dtype == jnp.float32 and w0.shape == (7,)
    assert not bool(jnp.any(w0))


def _softmax_cell(checkout):
    shutil.copy(os.path.join(HERE, "tiny_softmax.py"),
                os.path.join(checkout, "bench", "objectives",
                             "tiny_softmax.py"))
    name = add_cell(checkout, name="tiny-softmax", config=TINY_SOFTMAX,
                    limits=SOFTMAX_LIMITS,
                    traffic=("solves6", {"loop": "closed",
                                         "iters": SOFTMAX_ITERS}),
                    metric=("iter_roofline.softmax", None))
    return name


@pytest.mark.parametrize("trace", [0, 1])
def test_a_softmax_objective_runs_through_the_harness(checkout, harness,
                                                      trace):
    """Its solves start at K d zeros and return K d weights, and each is
    correct against the softmax reference."""
    from repro.core import oversketched_newton
    name = _softmax_cell(checkout)
    shapes = []

    def recorded(objective, data, w0, cfg):
        res = oversketched_newton(objective, data, w0, cfg)
        shapes.append((w0.shape, res.w.shape))
        return res

    rc, res, err = harness(checkout, name, trace=trace, newton=recorded)
    assert rc == 0 and res["correct"] is True, err
    assert res["failed"] == 0 and res["attempted"] >= 1
    kd = TINY_SOFTMAX["classes"] * TINY_SOFTMAX["d"]
    assert set(shapes) == {((kd,), (kd,))}
    if trace:
        # iter_roofline divides the softmax objective's own iteration work
        # by the window's seconds per iteration.
        from bench import cell as cells
        objective = cells.load(checkout, name).objective
        least, _ = work.least_time(
            objective.work(TINY_SOFTMAX)["iteration"],
            work.peaks("TPU v5 lite"))
        per_iter = res["device"]["window_s"] / (
            SOFTMAX_ITERS * res["attempted"])
        assert res["metrics"]["iter_roofline.softmax"]["value"] == \
            pytest.approx(least / per_iter * 100, rel=1e-12)
    else:
        assert set(res["metrics"]) == {"solve_s", "setup_s"}
