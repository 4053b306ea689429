"""Chaos plane: seeded fault injection + graceful degradation.

Contracts pinned here, layer by layer:

1. Registry: every shipped scenario is constructible with knob overrides,
   specs validate their knobs, unknown names fail loudly.
2. Determinism: a fault plan's randomness comes from its own seeded
   stream — same plan, same drive => bit-identical totals; a plan whose
   windows never open leaves the healthy clock bit-identical (the
   fault plane cannot perturb the historical stream).
3. Trace: every scenario's signature lands in the additive v3 ``faults``
   row object, and a recorded chaotic run replays bit-identically with
   NO plan attached.
4. Billing honesty: throttle rejections, OOM escalations, burst retries,
   and hedged/speculative relaunches that die all bill; a truly
   exhausted phase (``fail_open=False``) raises a typed error AFTER
   billing every attempt, and the raise itself record/replays.
5. Detection: a corrupted coded-matvec product is localized by the
   parity checks and decoded EXACTLY; blind decode returns garbage.
6. Degradation: under every registry scenario (and a real retry budget)
   the Newton solve still converges; strict mode propagates the typed
   error instead.
7. Alerting: each scenario fires its expected ``obs.health`` metric
   while a healthy monitored drive stays silent, and the alerts render
   in the ``make_report --trace`` pipeline.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs, scheduler
from repro.core import coded
from repro.core.straggler import SimClock, StragglerModel
from repro.runtime import (FaultPlan, FleetConfig, PhaseExhaustedError,
                           S3Spec, ThrottleSpec, TraceRecorder,
                           available_scenarios, get_scenario, load_trace)
from repro.runtime.faults import BurstSpec, CorruptionSpec, PoolDeathSpec

ALL_SCENARIOS = ("az_burst", "corruption", "oom", "pool_death",
                 "s3_transient", "throttle")


def _drive(faults=None, *, rounds=6, workers=16, policy="wait_all", k=None,
           fleet=None, pool=None, recorder=None, replay=None, telemetry=None,
           memory_gb=None, working_set_gb=None, flops=3e5, key0=100):
    """The fixed chaos test workload: ``rounds`` identical fan-outs."""
    clock = SimClock(StragglerModel(p_tail=0.05, tail_hi=3.0),
                     fleet=fleet if fleet is not None
                     else FleetConfig(cold_start_prob=0.1),
                     pool=pool, faults=faults, recorder=recorder,
                     replay=replay, telemetry=telemetry)
    for r in range(rounds):
        clock.phase(jax.random.PRNGKey(key0 + r), workers, policy=policy,
                    k=k, flops_per_worker=flops, comm_units=1.0,
                    memory_gb=memory_gb, working_set_gb=working_set_gb)
    return clock


# --------------------------------------------------------------- registry
def test_registry_lists_every_scenario():
    assert tuple(available_scenarios()) == ALL_SCENARIOS


def test_scenario_knob_overrides():
    plan = get_scenario("az_burst", kill_fraction=0.9, t_end=3.0, seed=4)
    assert plan.burst.kill_fraction == 0.9
    assert plan.burst.t_end == 3.0
    assert plan.seed == 4
    assert plan.active()
    assert not FaultPlan().active()


def test_unknown_scenario_fails_loudly():
    with pytest.raises(ValueError, match="unknown scenario"):
        get_scenario("meteor_strike")


def test_spec_validation():
    with pytest.raises(ValueError):
        BurstSpec(kill_fraction=1.5)
    with pytest.raises(ValueError):
        BurstSpec(t_start=2.0, t_end=1.0)
    with pytest.raises(ValueError):
        ThrottleSpec(max_concurrent=0)
    with pytest.raises(ValueError):
        S3Spec(get_fail_prob=-0.1)
    with pytest.raises(ValueError):
        CorruptionSpec(prob=2.0)
    with pytest.raises(ValueError):
        PoolDeathSpec(fraction=1.5)


# ----------------------------------------------------------- determinism
def test_same_plan_is_bit_deterministic():
    a = _drive(get_scenario("az_burst"))
    b = _drive(get_scenario("az_burst"))
    assert a.time == b.time
    assert a.dollars == b.dollars


def test_plan_seed_changes_the_fault_stream():
    a = _drive(get_scenario("s3_transient", get_fail_prob=0.5))
    b = _drive(get_scenario("s3_transient", get_fail_prob=0.5, seed=1))
    assert a.time != b.time


def test_dormant_plan_leaves_healthy_clock_bit_identical():
    """A plan whose windows never open draws from its own stream only —
    the main lifecycle RNG never sees it, so totals are bit-identical
    to a plan-less run (pre-chaos traces replay unchanged for the same
    reason)."""
    healthy = _drive(None)
    dormant = _drive(FaultPlan(
        burst=BurstSpec(t_start=1e9, kill_fraction=1.0),
        throttle=ThrottleSpec(max_concurrent=1, t_start=1e9),
        s3=S3Spec(get_fail_prob=0.9, put_fail_prob=0.9, t_start=1e9),
        corruption=CorruptionSpec(prob=1.0, t_start=1e9)))
    assert dormant.time == healthy.time
    assert dormant.dollars == healthy.dollars


# ------------------------------------- per-scenario signature + replay
#: scenario -> (drive kwargs for its raw cell, fault-stat keys it must
#: leave in the trace's ``faults`` rows).
_SCENARIO_DRIVES = {
    "az_burst": (dict(), ("burst_kills", "burst_exposed")),
    "throttle": (dict(), ("throttled", "peak_concurrency")),
    "s3_transient": (dict(), ("s3_get_retries", "s3_put_retries")),
    "oom": (dict(memory_gb=0.25, working_set_gb=0.5),
            ("oom_kills", "oom_escalations")),
    "pool_death": (dict(pool=True), ("pool_killed",)),
}


def _scenario_drive(scen, faults, **kw):
    drive_kw, _ = _SCENARIO_DRIVES[scen]
    drive_kw = dict(drive_kw, **kw)
    if drive_kw.pop("pool", False):
        drive_kw["pool"] = scheduler.WarmPool(ttl=300.0, prewarmed=32)
    return _drive(faults, **drive_kw)


@pytest.mark.parametrize("scen", sorted(_SCENARIO_DRIVES))
def test_scenario_leaves_signature_and_replays(scen, tmp_path):
    rec = TraceRecorder(lifecycle=True)
    recorded = _scenario_drive(scen, get_scenario(scen), recorder=rec)
    totals: dict = {}
    for row in rec.rows:
        for key, v in (row.get("faults") or {}).items():
            if isinstance(v, (int, float)):
                totals[key] = totals.get(key, 0) + v
    _, want_keys = _SCENARIO_DRIVES[scen]
    for key in want_keys:
        assert totals.get(key, 0) > 0, \
            f"{scen} left no {key} in the trace: {totals}"
    path = tmp_path / f"{scen}.jsonl"
    rec.dump(path)
    # Replay with NO fault plan: the trace alone carries the chaos.
    replayed = _scenario_drive(scen, None, replay=load_trace(path))
    assert replayed.time == recorded.time
    assert replayed.dollars == recorded.dollars


# ------------------------------------------------------- billing honesty
def test_throttle_bills_rejected_invocations():
    healthy = _drive(None)
    throttled = _drive(FaultPlan(throttle=ThrottleSpec(max_concurrent=4)))
    assert throttled.ledger.invocations > healthy.ledger.invocations
    assert throttled.time > healthy.time


def test_oom_escalation_bills_bigger_lambdas_and_sizing_mitigates():
    plan = get_scenario("oom")
    plain = _drive(None, memory_gb=0.25, working_set_gb=0.5)
    oom = _drive(plan, memory_gb=0.25, working_set_gb=0.5)
    # Killed 90%-wasted attempts plus doubled-memory retries: strictly
    # more gb-seconds and wall time than the same drive without the plan.
    assert oom.ledger.gb_seconds > plain.ledger.gb_seconds
    assert oom.time > plain.time
    # The mitigation is sizing at the declared working set: the plan
    # stays attached but never fires.
    rec = TraceRecorder()
    sized = _drive(plan, memory_gb=0.5, working_set_gb=0.5, recorder=rec)
    assert all(not (r.get("faults") or {}).get("oom_kills")
               for r in rec.rows)
    assert sized.time < oom.time


@pytest.mark.parametrize("policy", ("hedged", "speculative"))
def test_relaunch_policies_bill_their_failures(policy, tmp_path):
    """Satellite: hedged/speculative duplicates are exposed to the same
    faults as first launches — dead duplicates and throttled relaunches
    still bill, and the billed totals record/replay bit-identically."""
    healthy = _drive(None, policy=policy, rounds=4)
    burst = get_scenario("az_burst", kill_fraction=0.8, t_end=30.0)
    burst_run = _drive(burst, policy=policy, rounds=4)
    assert burst_run.ledger.invocations > healthy.ledger.invocations
    assert burst_run.dollars > healthy.dollars
    throttled = _drive(FaultPlan(throttle=ThrottleSpec(max_concurrent=6)),
                       policy=policy, rounds=4)
    assert throttled.ledger.invocations > healthy.ledger.invocations
    rec = TraceRecorder()
    recorded = _drive(burst, policy=policy, rounds=4, recorder=rec)
    path = tmp_path / "relaunch.jsonl"
    rec.dump(path)
    replayed = _drive(None, policy=policy, rounds=4,
                      replay=load_trace(path))
    assert replayed.time == recorded.time
    assert replayed.dollars == recorded.dollars


# ----------------------------------------------------- typed exhaustion
_LETHAL = FaultPlan(burst=BurstSpec(t_start=0.0, kill_fraction=1.0))
_STRICT_FLEET = FleetConfig(fail_open=False, max_retries=1,
                            cold_start_prob=0.0)


def test_exhaustion_raises_typed_error_after_billing(tmp_path):
    rec = TraceRecorder()
    clock = SimClock(StragglerModel(), fleet=_STRICT_FLEET, recorder=rec,
                     faults=_LETHAL)
    with pytest.raises(PhaseExhaustedError) as ei:
        clock.phase(jax.random.PRNGKey(0), 8, policy="wait_all",
                    flops_per_worker=3e5, comm_units=1.0)
    e = ei.value
    assert e.num_workers == 8
    assert int(e.mask.sum()) == 0
    assert e.elapsed > 0.0
    # Every attempt billed (8 workers x 2 attempts), clock advanced to
    # the last observed event — the caller resumes on a consistent line.
    assert clock.ledger.invocations == 16.0
    assert clock.time == pytest.approx(e.elapsed)
    assert clock.dollars > 0.0
    row = rec.rows[-1]
    assert row["raised"]
    assert row["exhausted"] == 8
    # The raise itself replays: same error, same totals, no plan needed.
    path = tmp_path / "exhausted.jsonl"
    rec.dump(path)
    rclock = SimClock(StragglerModel(), replay=load_trace(path))
    with pytest.raises(PhaseExhaustedError) as rei:
        rclock.phase(jax.random.PRNGKey(0), 8, policy="wait_all",
                     flops_per_worker=3e5, comm_units=1.0)
    assert rei.value.elapsed == e.elapsed
    assert np.array_equal(rei.value.mask, e.mask)
    assert rclock.time == clock.time
    assert rclock.dollars == clock.dollars


def test_k_of_n_survives_partial_exhaustion():
    """A partial-wait phase under the same hard budget completes from
    survivors instead of raising — the paper's redundancy thesis applied
    to real (non-fail-open) retry budgets."""
    plan = FaultPlan(burst=BurstSpec(t_start=0.0, kill_fraction=0.5))
    clock = SimClock(StragglerModel(), fleet=_STRICT_FLEET, faults=plan)
    _, mask = clock.phase(jax.random.PRNGKey(1), 8, policy="k_of_n", k=4,
                          flops_per_worker=3e5, comm_units=1.0)
    assert int(np.asarray(mask).sum()) >= 4


def test_fail_open_default_never_raises():
    clock = SimClock(StragglerModel(),
                     fleet=FleetConfig(max_retries=1, cold_start_prob=0.0),
                     faults=_LETHAL)
    _, mask = clock.phase(jax.random.PRNGKey(0), 8, policy="wait_all",
                          flops_per_worker=3e5, comm_units=1.0)
    assert int(np.asarray(mask).sum()) == 8   # final attempts immune


# ------------------------------------------- corruption detect + decode
def _coded_setup(key=3, rows=32, cols=12, block=8):
    k = jax.random.PRNGKey(key)
    a = jax.random.normal(k, (rows, cols))
    v = jax.random.normal(jax.random.fold_in(k, 1), (cols,))
    code = coded.make_code(rows, block)
    prods = coded.coded_block_products(coded.encode_full(a, code), v)
    return a @ v, prods, code, rows


# make_code(32, 8) -> 4 blocks on a 2x2 systematic grid; row/col index 2
# are the parity lines of the 3x3 worker grid.
@pytest.mark.parametrize("cell", [(1, 1), (2, 1), (1, 2)],
                         ids=["systematic", "col_parity", "row_parity"])
def test_corrupted_cell_detected_and_decoded_exactly(cell):
    exact, prods, code, rows = _coded_setup()
    g1 = code.grid + 1
    known = jnp.ones((g1, g1), bool)
    bad = prods.at[cell[0], cell[1]].add(7.5)
    flagged = coded.detect_corrupted(bad, known, code)
    assert bool(flagged[cell])
    y, ok, n_flagged = coded.verified_decode(bad, known, code, rows)
    assert n_flagged >= 1
    assert bool(ok)
    np.testing.assert_allclose(np.asarray(y), np.asarray(exact),
                               rtol=1e-4, atol=1e-4)


def test_blind_decode_returns_the_corruption():
    exact, prods, code, rows = _coded_setup()
    g1 = code.grid + 1
    known = jnp.ones((g1, g1), bool)
    bad = prods.at[1, 1].add(7.5)   # a systematic cell
    y, ok = coded.decode_matvec(bad, known, code, rows)
    assert bool(ok)
    assert not np.allclose(np.asarray(y), np.asarray(exact),
                           rtol=1e-4, atol=1e-4)


def test_clean_grid_flags_nothing():
    _, prods, code, _ = _coded_setup()
    g1 = code.grid + 1
    known = jnp.ones((g1, g1), bool)
    assert not bool(coded.detect_corrupted(prods, known, code).any())


# ------------------------------------------------ end-to-end degradation
def _newton_solve(faults=None, *, fleet=None, pool=None, telemetry=None,
                  detection=True, fallback="degrade", iters=8):
    from repro.core.newton import NewtonConfig, oversketched_newton
    from repro.core.objectives import Dataset, LogisticRegression
    from repro.core.sketch import OverSketchConfig

    key = jax.random.PRNGKey(0)
    n, d = 256, 8
    x = jax.random.normal(key, (n, d))
    y = jnp.sign(x @ jax.random.normal(jax.random.fold_in(key, 1), (d,)))
    cfg = NewtonConfig(iters=iters,
                       sketch=OverSketchConfig(sketch_dim=64, block_size=16,
                                               straggler_tolerance=0.25),
                       coded_block_rows=32, corruption_detection=detection,
                       fault_fallback=fallback)
    clock = SimClock(StragglerModel(), fleet=fleet, pool=pool, faults=faults,
                     telemetry=telemetry)
    res = oversketched_newton(LogisticRegression(lam=1e-3),
                              Dataset(x=x, y=y), jnp.zeros((d,)), cfg, clock)
    return float(res.history["gnorm"][-1]), clock


@pytest.mark.parametrize("scen", ALL_SCENARIOS)
def test_newton_converges_under_every_scenario(scen):
    """Graceful degradation, end to end: each registry scenario under a
    REAL retry budget still reaches a converged solve (the corruption
    scenario additionally needs the parity-check detection on, which is
    the default)."""
    gn, clock = _newton_solve(
        get_scenario(scen),
        fleet=FleetConfig(cold_start_prob=0.1, fail_open=False,
                          max_retries=2),
        pool=scheduler.WarmPool(ttl=300.0, prewarmed=32))
    assert np.isfinite(gn)
    assert gn < 1e-2
    assert np.isfinite(clock.time) and np.isfinite(clock.dollars)


def test_corruption_detection_recovers_what_blind_decode_loses():
    plan = get_scenario("corruption", prob=0.3)
    gn_healthy, _ = _newton_solve(None)
    gn_blind, _ = _newton_solve(plan, detection=False)
    gn_detected, _ = _newton_solve(plan, detection=True)
    assert gn_healthy < 1e-3
    assert gn_detected < 1e-3
    assert gn_blind > 10.0 * gn_detected


def test_strict_mode_propagates_exhaustion():
    with pytest.raises(PhaseExhaustedError):
        _newton_solve(_LETHAL, fleet=_STRICT_FLEET, fallback="raise",
                      iters=2)


def test_degrade_mode_survives_what_strict_mode_raises_on():
    gn, clock = _newton_solve(
        FaultPlan(burst=BurstSpec(t_start=0.5, t_end=2.0,
                                  kill_fraction=0.9)),
        fleet=FleetConfig(fail_open=False, max_retries=1), iters=6)
    assert np.isfinite(gn)
    assert np.isfinite(clock.time) and clock.dollars > 0.0


# ------------------------------------------------------- health alerting
def _monitored_drive(faults=None, *, rounds=14, pool=None,
                     schedule=None):
    """The alert-test workload: enough healthy rounds to freeze every
    detector baseline before any fault window opens."""
    tel = obs.Telemetry(monitors=True)
    clock = SimClock(StragglerModel(p_tail=0.05, tail_hi=3.0),
                     fleet=FleetConfig(cold_start_prob=0.2),
                     pool=pool, faults=faults, telemetry=tel)
    for r in range(rounds):
        mem, ws = (schedule(r) if schedule is not None else (None, None))
        clock.phase(jax.random.PRNGKey(600 + r), 24, policy="wait_all",
                    flops_per_worker=3e5, comm_units=1.0,
                    memory_gb=mem, working_set_gb=ws)
    return tel, clock


def _healthy_midpoint(rounds=7, pool=False):
    p = scheduler.WarmPool(ttl=300.0, prewarmed=48) if pool else None
    clock = SimClock(StragglerModel(p_tail=0.05, tail_hi=3.0),
                     fleet=FleetConfig(cold_start_prob=0.2), pool=p)
    for r in range(rounds):
        clock.phase(jax.random.PRNGKey(600 + r), 24, policy="wait_all",
                    flops_per_worker=3e5, comm_units=1.0)
    return clock.time


def test_healthy_monitored_drive_stays_silent():
    tel, _ = _monitored_drive(None)
    assert tel.health.alerts == []
    tel, _ = _monitored_drive(
        None, pool=scheduler.WarmPool(ttl=300.0, prewarmed=48))
    assert tel.health.alerts == []


def _fleet_alert_plan(scen, t_mid):
    """The scenario windowed to open only after the detector baselines
    froze on healthy samples."""
    if scen == "az_burst":
        return FaultPlan(burst=BurstSpec(t_start=t_mid,
                                         kill_fraction=0.9))
    if scen == "throttle":
        return FaultPlan(throttle=ThrottleSpec(max_concurrent=4,
                                               t_start=t_mid))
    if scen == "s3_transient":
        return FaultPlan(s3=S3Spec(get_fail_prob=0.7, put_fail_prob=0.3,
                                   retry_delay=0.2, t_start=t_mid))
    raise KeyError(scen)


@pytest.mark.parametrize("scen", ("az_burst", "throttle", "s3_transient"))
def test_scenario_fires_straggler_alerts(scen):
    """Bursts, throttling, and S3 retry chains all fatten the completion
    stream mid-run — the straggler detectors must notice."""
    plan = _fleet_alert_plan(scen, _healthy_midpoint())
    tel, _ = _monitored_drive(plan)
    metrics = {a.metric for a in tel.health.alerts}
    assert metrics & {"worker.completion_s", "phase.tail_p95_s"}, \
        f"{scen} fired no straggler alert (got {metrics})"


def test_oom_fires_straggler_alerts():
    """Right-sized early rounds freeze the baseline; undersized later
    rounds OOM at 90% of the run and retry escalated — roughly doubled
    completions, a textbook drift."""
    tel, _ = _monitored_drive(
        get_scenario("oom"),
        schedule=lambda r: ((1.0, 0.5) if r < 8 else (0.25, 0.5)))
    metrics = {a.metric for a in tel.health.alerts}
    assert metrics & {"worker.completion_s", "phase.tail_p95_s"}, \
        f"oom fired no straggler alert (got {metrics})"


def test_pool_death_fires_hit_rate_alert():
    plan = FaultPlan(pool_death=PoolDeathSpec(
        t=_healthy_midpoint(pool=True), fraction=1.0))
    tel, _ = _monitored_drive(
        plan, pool=scheduler.WarmPool(ttl=300.0, prewarmed=48))
    metrics = {a.metric for a in tel.health.alerts}
    assert "pool.phase_hit_rate" in metrics, \
        f"pool death fired no hit-rate alert (got {metrics})"


def test_corruption_fires_block_error_rate_alert():
    """The coded engine publishes a per-phase block error rate whenever a
    CorruptionSpec is attached (0.0 on clean phases) — a mid-solve
    corruption window must drift the CUSUM off that exact baseline."""
    _, healthy_clock = _newton_solve(None)
    t_mid = 0.5 * healthy_clock.time
    tel = obs.Telemetry(monitors=True)
    _newton_solve(FaultPlan(corruption=CorruptionSpec(prob=0.5,
                                                      t_start=t_mid)),
                  telemetry=tel)
    metrics = {a.metric for a in tel.health.alerts}
    assert "coded.block_error_rate" in metrics, \
        f"corruption fired no block-error alert (got {metrics})"
    # And the healthy solve's stream holds the zero baseline silently.
    tel_h = obs.Telemetry(monitors=True)
    _newton_solve(None, telemetry=tel_h)
    assert not any(a.metric == "coded.block_error_rate"
                   for a in tel_h.health.alerts)


def test_alerts_render_in_trace_report(tmp_path):
    """The chaos alerts survive the export pipeline: JSONL dump ->
    ``make_report --trace`` tables (what CI renders per push)."""
    from benchmarks.make_report import trace_report
    plan = _fleet_alert_plan("az_burst", _healthy_midpoint())
    tel, _ = _monitored_drive(plan)
    assert tel.health.alerts
    path = tmp_path / "chaos_run.jsonl"
    obs.dump_jsonl(tel, path)
    rows = obs.load_jsonl(path)
    assert obs.alerts_from_rows(rows)
    report = trace_report(rows)
    assert "Health monitors" in report
    assert any(a.metric in report for a in tel.health.alerts)
