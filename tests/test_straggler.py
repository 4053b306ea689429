"""Straggler model + simulation clock invariants."""
import hashlib
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import straggler as sg


def test_sample_shapes_and_positivity():
    m = sg.StragglerModel()
    t = m.sample_times(jax.random.PRNGKey(0), 100)
    assert t.shape == (100,)
    assert (np.asarray(t) > 0).all()


def test_tail_fraction_close_to_p():
    """~2% of workers straggle (Fig. 1)."""
    m = sg.StragglerModel(p_tail=0.02, body_sigma=0.01)
    t = np.asarray(m.sample_times(jax.random.PRNGKey(1), 20000))
    med = np.median(t)
    frac = (t > 1.25 * med).mean()
    assert 0.005 < frac < 0.05


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 999), k_frac=st.floats(0.1, 1.0))
def test_policy_ordering(seed, k_frac):
    """k-of-n <= wait-all, and k-of-n monotone in k."""
    m = sg.StragglerModel(p_tail=0.1)
    t = m.sample_times(jax.random.PRNGKey(seed), 64)
    k = max(1, int(64 * k_frac))
    assert float(sg.k_of_n_time(t, k)) <= float(sg.wait_all_time(t)) + 1e-6
    if k > 1:
        assert float(sg.k_of_n_time(t, k - 1)) <= float(sg.k_of_n_time(t, k)) + 1e-6


def test_k_of_n_mask_has_at_least_k():
    m = sg.StragglerModel(p_tail=0.2)
    t = m.sample_times(jax.random.PRNGKey(3), 50)
    mask = sg.k_of_n_mask(t, 30)
    assert int(mask.sum()) >= 30


def test_speculative_beats_wait_all_with_heavy_tail():
    m = sg.StragglerModel(p_tail=0.3, tail_lo=3.0, tail_hi=6.0)
    wins = 0
    for s in range(20):
        t = m.sample_times(jax.random.PRNGKey(s), 100)
        spec = float(sg.speculative_time(t, jax.random.PRNGKey(1000 + s), m))
        if spec <= float(sg.wait_all_time(t)) + 1e-6:
            wins += 1
    assert wins >= 15


def test_speculative_relaunch_does_the_phase_work():
    """Regression: relaunched stragglers must redo the phase's ACTUAL work.
    The old default re-sampled with work_per_worker=1.0, so heavy phases
    got unrealistically fast relaunches and speculative baselines looked
    optimistic (fig10)."""
    work = 50.0
    m = sg.StragglerModel(p_tail=0.2, tail_lo=5.0, tail_hi=5.0,
                          invoke_overhead=0.0)
    key = jax.random.PRNGKey(21)
    times = m.sample_times(key, 100, work_per_worker=work)
    deadline = float(jnp.sort(times)[89])   # watch_fraction=0.9 deadline
    spec = float(sg.speculative_time(times, jax.random.PRNGKey(1021), m,
                                     work_per_worker=work))
    # A relaunch doing the real work needs ~`work` more seconds; the buggy
    # unit-work relaunch finished ~1s after the deadline.
    assert spec > deadline + 0.5 * work
    # and relaunching never does worse than waiting (a relaunch can
    # straggle too, in which case the original's finish is kept)
    assert spec <= float(sg.wait_all_time(times)) + 1e-6


def test_clock_phase_speculative_threads_work():
    """The engine's speculative policy relaunches with the phase work too:
    a heavy phase's elapsed must reflect work-scaled relaunches."""
    m = sg.StragglerModel(p_tail=0.2, tail_lo=5.0, tail_hi=5.0,
                          invoke_overhead=0.0)
    work = 50.0
    clock = sg.SimClock(m)
    elapsed, _ = clock.phase(jax.random.PRNGKey(22), 100,
                             work_per_worker=work, policy="speculative")
    body_time = work * 1.3     # generous bound on a non-straggler's time
    assert float(elapsed) > body_time + 0.5 * work


def test_clock_accumulates():
    clock = sg.SimClock(sg.StragglerModel())
    e1, m1 = clock.phase(jax.random.PRNGKey(0), 16, policy="wait_all")
    e2, m2 = clock.phase(jax.random.PRNGKey(1), 16, policy="k_of_n", k=12)
    assert clock.time == float(e1) + float(e2)
    assert m1.all()
    assert int(m2.sum()) >= 12


# ----------------------------------------------------- the clock on the host
DRAWS_GOLDEN = json.loads(
    (pathlib.Path(__file__).parent / "fixtures"
     / "straggler_draws_golden.json").read_text())["cases"]


@pytest.mark.parametrize("case", DRAWS_GOLDEN,
                         ids=lambda c: f"{c['seed']}x{c['workers']}")
def test_host_draws_match_the_eager_golden_bit_for_bit(case):
    """The worker-time draws from a host key are the eager op sequence's
    bits, recorded on the CPU backend: a jitted draw would fuse the float
    ops and move them, and with them every simulated second."""
    n = case["workers"]
    key = sg.on_host(jax.random.fold_in(jax.random.PRNGKey(case["seed"]), n))
    t = sg.StragglerModel().sample_times(key, n)
    assert t.devices() == {sg.host_device()}
    t = np.asarray(t)
    assert t.dtype == np.float32 and t.shape == (n,)
    assert hashlib.sha256(t.astype("<f4").tobytes()).hexdigest() \
        == case["sha256"]
    assert [float(v) for v in t[:3]] == case["first"]


def test_host_keys_are_committed_to_the_cpu_with_the_same_bits():
    assert sg.host_device().platform == "cpu"
    for seed in (0, 1600000013):
        key = sg.host_key(seed)
        assert key.committed and key.devices() == {sg.host_device()}
        assert np.array_equal(np.asarray(key),
                              np.asarray(jax.random.PRNGKey(seed)))
        assert sg.on_host(key) is key
        child = jax.random.fold_in(jax.random.split(key, 4)[2], 7)
        assert child.devices() == {sg.host_device()}


def test_phase_counts_one_host_draw_per_failure_free_phase():
    from repro import obs
    tel = obs.Telemetry()
    clock = sg.SimClock(sg.StragglerModel(), telemetry=tel)
    policies = (("wait_all", None), ("k_of_n", 12), ("speculative", None))
    for i, (policy, k) in enumerate(policies):
        _, mask = clock.phase(jax.random.PRNGKey(i), 16, policy=policy, k=k)
        assert mask.dtype == bool and mask.shape == (16,)
    counters = tel.metrics.snapshot()["counters"]
    draws = {n: v for n, v in counters.items()
             if n.startswith("fleet.draws.")}
    # One round per failure-free phase; the speculative phase's relaunches
    # draw one more.
    assert draws == {"fleet.draws.cpu": float(len(policies) + 1)}


# A second CPU device stands in for the accelerator: the default device
# and the data are on cpu:1, the host clock on cpu:0.  Every draw that
# feeds a device program has to reach the data's device, and every fleet
# draw has to stay on the host, or a jitted program meets operands
# committed to two devices.
TWO_DEVICES = """
import jax, jax.numpy as jnp, numpy as np
cpu0, cpu1 = jax.devices("cpu")
jax.config.update("jax_default_device", cpu1)
from repro.core import (Dataset, LogisticRegression, NewtonConfig,
                        OverSketchConfig, StragglerModel, newton,
                        oversketched_newton, straggler)
from repro.optim.first_order import FirstOrderConfig, first_order
from repro.optim.giant import GiantConfig, giant
from repro.runtime import get_scenario
assert straggler.host_device() == cpu0

draws, placed, states = set(), [], []
sample_times = straggler.StragglerModel.sample_times
def spy_draw(self, key, *a, **k):
    t = sample_times(self, key, *a, **k)
    draws.update(t.devices())
    return t
straggler.StragglerModel.sample_times = spy_draw
on_device_of = straggler.on_device_of
def spy_place(key, x):
    out = on_device_of(key, x)
    placed.append((np.ndim(x), out.devices()))
    return out
straggler.on_device_of = spy_place
for name in ("_jitted_sketched_hessian", "_jitted_distavg_direction"):
    def spy_jit(*args, _jit=getattr(newton, name)):
        fn = _jit(*args)
        def call(*ops):
            states.extend(jax.tree.leaves(ops[-2]))
            return fn(*ops)
        return call
    setattr(newton, name, spy_jit)

k = jax.random.PRNGKey(0)
x = jax.random.normal(k, (256, 8))
y = jnp.sign(x @ jax.random.normal(jax.random.fold_in(k, 1), (8,)))
data = Dataset(x=jax.device_put(x, cpu1),
               y=jax.device_put(y, cpu1))
obj, w0 = LogisticRegression(lam=1e-3), jnp.zeros(8)
sk = OverSketchConfig(sketch_dim=64, block_size=16, straggler_tolerance=0.25)
clock = straggler.SimClock(StragglerModel(),
                           faults=get_scenario("corruption", prob=0.3))
res = oversketched_newton(obj, data, w0, NewtonConfig(
    iters=4, sketch=sk, coded_block_rows=32, corruption_detection=True),
    clock)
assert res.history["gnorm"][-1] < 0.1 * res.history["gnorm"][0]
oversketched_newton(obj, data, w0, NewtonConfig(
    iters=2, sketch=sk, coded_block_rows=32, sketch_mode="distributed-avg"))
giant(obj, data, w0, GiantConfig(iters=2, num_workers=8, cg_iters=4))
first_order(obj, data, w0, FirstOrderConfig(iters=2, method="sgd",
                                            num_workers=8))
assert draws == {cpu0}, draws
assert states and all(s.devices() == {cpu1} for s in states)
# The sketch draws (on X, two dimensions), the corruption noise (on the
# product grid, three) and the SGD row sample all land on the data.
assert {nd for nd, _ in placed} == {2, 3}, placed
assert all(devs == {cpu1} for _, devs in placed), placed
print("ok")
"""


def test_host_clock_feeds_device_draws_on_the_datas_device():
    import os
    import subprocess
    import sys
    repo = pathlib.Path(__file__).parent.parent
    env = dict(os.environ, PYTHONPATH=str(repo / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run([sys.executable, "-c", TWO_DEVICES],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert out.stdout.strip().endswith("ok")
