"""Telemetry subsystem tests: tracer, metrics, critical path, Perfetto.

The load-bearing contracts:

1. The no-op default (``obs.NULL``) and a live ``obs.Telemetry`` are
   interchangeable: attaching a recorder to any simulated run changes no
   ``(seconds, dollars)`` total and no iterate (telemetry draws no
   randomness and never moves the clock).
2. The critical-path analysis matches hand-computed CPM values and the
   binding chain of a real dispatched DAG.
3. The Perfetto export is byte-stable: a committed golden file built from
   a synthetic span set (no RNG, no jax sampling — deterministic under
   any jax version) must match ``dumps_stable`` forever.

Regenerate the golden export (only after an INTENTIONAL format change):

    PYTHONPATH=src python tests/test_obs.py --regen
"""
import math
import pathlib

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core.straggler import SimClock, StragglerModel
from repro.runtime import FleetConfig
from repro.scheduler import PhaseSpec, WarmPool, run_dag

PERFETTO_GOLDEN = pathlib.Path(__file__).parent / "fixtures" / \
    "perfetto_golden.json"


# ------------------------------------------------------------------ tracer
def test_tracer_hierarchy_and_rows():
    tr = obs.SpanTracer()
    run = tr.begin("newton", "run", 0.0, schedule="dag")
    it = tr.begin("iter0", "iteration", 0.0)
    ph = tr.emit("grad", "phase", 0.0, 0.5, policy="wait_all")
    att = tr.emit("run", "attempt", 0.0, 0.4, track="grad/w0")
    tr.end(it, 0.5)
    after = tr.emit("post", "charge", 0.5, 0.625)
    tr.end(run, 0.625)

    spans = {s.span_id: s for s in tr.spans}
    assert spans[ph].parent_id == it
    assert spans[att].parent_id == it
    assert spans[after].parent_id == run      # iteration already closed
    assert spans[run].parent_id == 0
    assert spans[it].end == 0.5 and spans[run].end == 0.625
    assert [s.name for s in tr.children(it)] == ["grad", "run"]
    assert [s.name for s in tr.by_kind("phase")] == ["grad"]
    row = spans[att].as_row()
    assert row["kind"] == "span" and row["track"] == "grad/w0"
    assert spans[ph].duration == 0.5


def test_tracer_out_of_order_end_unwinds():
    tr = obs.SpanTracer()
    a = tr.begin("a", "run", 0.0)
    b = tr.begin("b", "iteration", 0.0)
    tr.end(a, 1.0)                 # closes b too
    spans = {s.span_id: s for s in tr.spans}
    assert spans[b].end == 1.0 and spans[a].end == 1.0
    assert tr.current == 0
    with pytest.raises(KeyError):
        tr.end(999, 1.0)


def test_tracer_set_attrs_and_open_end_is_nan():
    tr = obs.SpanTracer()
    sid = tr.begin("r", "run", 0.0)
    assert math.isnan(tr.spans[0].end)
    tr.set_attrs(sid, makespan=2.0)
    assert tr.spans[0].attrs["makespan"] == 2.0


def test_null_telemetry_is_inert():
    tel = obs.NULL
    assert not tel.enabled
    assert tel.trace.begin("x", "run", 0.0) == 0
    assert tel.trace.emit("x", "phase", 0.0, 1.0) == 0
    tel.trace.end(0, 1.0)
    tel.trace.set_attrs(0, a=1)
    assert tel.trace.spans == [] and tel.trace.by_kind("phase") == []
    c = tel.metrics.counter("n")
    c.inc()
    g = tel.metrics.gauge("g")
    g.set(3.0)
    tel.metrics.histogram("h").observe(1.0)
    assert tel.metrics.snapshot() == \
        {"counters": {}, "gauges": {}, "histograms": {}}


# ----------------------------------------------------------------- metrics
def test_metrics_registry():
    reg = obs.MetricsRegistry()
    reg.counter("a").inc()
    reg.counter("a").inc(2.5)
    reg.gauge("b").set(1.0)
    reg.gauge("b").set(4.0)
    h = reg.histogram("c")
    for v in (5.0, 1.0, 3.0, 2.0, 4.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["counters"]["a"] == 3.5
    assert snap["gauges"]["b"] == {"n": 2, "value": 4.0}
    assert reg.gauge("b").series == [1.0, 4.0]
    assert h.count == 5 and h.total == 15.0
    assert h.percentile(50) == 3.0
    assert h.percentile(100) == 5.0
    assert snap["histograms"]["c"]["p50"] == 3.0
    assert snap["histograms"]["c"]["max"] == 5.0


# ----------------------------------------------------------- critical path
def test_critical_path_hand_computed():
    # A and B are roots; C joins both (B binds: finish 3 == C's start);
    # D hangs off A with room to slip.  Makespan 5.
    rep = obs.critical_path({
        "A": (0.0, 2.0, ()),
        "B": (0.0, 3.0, ()),
        "C": (3.0, 5.0, ("A", "B")),
        "D": (2.0, 4.0, ("A",)),
    })
    assert rep.makespan == 5.0
    assert rep.critical_path == ("B", "C")
    assert rep.critical_seconds == 5.0
    slacks = {n: p.slack for n, p in rep.phases.items()}
    assert slacks == {"A": 1.0, "B": 0.0, "C": 0.0, "D": 1.0}
    assert rep.phases["B"].on_critical_path
    assert not rep.phases["D"].on_critical_path
    rows = rep.rows()
    assert [r["phase"] for r in rows[:2]] == ["B", "C"]   # chain first


def test_critical_path_validates():
    with pytest.raises(ValueError):
        obs.critical_path({})
    with pytest.raises(ValueError):
        obs.critical_path({"a": (0.0, 1.0, ("ghost",))})
    with pytest.raises(ValueError):
        obs.critical_path({"a": (2.0, 1.0, ())})


def test_critical_path_from_real_dag():
    clock = SimClock(StragglerModel(p_tail=0.05, tail_hi=3.0))
    res = run_dag(clock, jax.random.PRNGKey(7), [
        PhaseSpec("gx", 8, policy="wait_all", flops_per_worker=2e5),
        PhaseSpec("gxt", 8, policy="wait_all", flops_per_worker=2e5,
                  deps=("gx",)),
        PhaseSpec("hess", 8, policy="wait_all", flops_per_worker=6e5),
        PhaseSpec("ls", 8, policy="wait_all", flops_per_worker=1e5,
                  deps=("gxt", "hess")),
    ])
    rep = res.critical_path()
    assert rep.critical_path[-1] == "ls"
    assert rep.makespan == res.makespan
    # Every phase is either on the chain (slack 0) or strictly off it.
    for name, p in rep.phases.items():
        assert (p.slack == 0.0) == p.on_critical_path or p.slack == 0.0
    # The chain is connected: each member's start is its predecessor's
    # finish, and the last member finishes at the makespan.
    for a, b in zip(rep.critical_path, rep.critical_path[1:]):
        assert rep.phases[b].start == rep.phases[a].finish
    assert rep.phases[rep.critical_path[-1]].finish - rep.start \
        == rep.makespan


# ---------------------------------------------------------------- perfetto
def _synthetic_spans():
    """A deterministic span tree (no RNG, exact binary floats) shaped like
    one DAG-scheduled Newton iteration — the golden export's source."""
    tr = obs.SpanTracer()
    run = tr.begin("newton", "run", 0.0, schedule="dag")
    it = tr.begin("iter0", "iteration", 0.0)
    tr.emit("grad/0:X", "phase", 0.0, 0.25, policy="k_of_n", workers=2,
            deps=[], dollars=0.000125, gb_seconds=1.5)
    tr.emit("hessian", "phase", 0.0, 0.1875, policy="k_of_n", workers=2,
            deps=[], dollars=0.00025, gb_seconds=3.0)
    tr.emit("grad/1:XT", "phase", 0.25, 0.5, policy="k_of_n", workers=2,
            deps=["grad/0:X"], dollars=0.000125, gb_seconds=1.5)
    tr.emit("linesearch", "phase", 0.5, 0.625, policy="wait_all", workers=2,
            deps=["grad/1:XT", "hessian"], dollars=0.0000625,
            gb_seconds=0.75)
    tr.emit("cold", "attempt", 0.0, 0.0625, track="grad/0:X/w0")
    tr.emit("run", "attempt", 0.0625, 0.25, track="grad/0:X/w0", attempt=0)
    tr.emit("run", "attempt", 0.0, 0.125, track="grad/0:X/w1", attempt=0)
    tr.emit("failed", "attempt", 0.0, 0.0625, track="hessian/w0", attempt=0)
    tr.emit("retry", "attempt", 0.0625, 0.1875, track="hessian/w0",
            attempt=1)
    tr.end(it, 0.625)
    tr.end(run, 0.625)
    return tr.spans


def test_perfetto_layout():
    trace = obs.to_perfetto(_synthetic_spans())
    evs = trace["traceEvents"]
    slices = [e for e in evs if e["ph"] == "X"]
    metas = [e for e in evs if e["ph"] == "M"]
    # run + iteration nest on the master tid.
    master = [e for e in slices if e["pid"] == obs.perfetto.MASTER_PID
              and e["tid"] == obs.perfetto.MASTER_TID]
    assert {e["name"] for e in master} == {"newton", "iter0"}
    # Overlapping phases land on distinct lanes; the serialized chain
    # member reuses lane 0.
    by_name = {e["name"]: e for e in slices if e["cat"] == "phase"}
    assert by_name["grad/0:X"]["tid"] != by_name["hessian"]["tid"]
    assert by_name["grad/1:XT"]["tid"] == by_name["grad/0:X"]["tid"]
    # One worker tid per track label, under the workers pid.
    wslices = [e for e in slices if e["pid"] == obs.perfetto.WORKERS_PID]
    tids = {}
    for e in wslices:
        tids.setdefault(e["tid"], []).append(e["name"])
    assert len(tids) == 3
    assert sorted(tids[1]) == ["cold", "run"]         # grad/0:X/w0
    track_names = {m["args"]["name"] for m in metas
                   if m["pid"] == obs.perfetto.WORKERS_PID
                   and m["name"] == "thread_name"}
    assert track_names == {"grad/0:X/w0", "grad/0:X/w1", "hessian/w0"}
    # Timestamps are simulated microseconds.
    assert by_name["linesearch"]["ts"] == 0.5e6
    assert by_name["linesearch"]["dur"] == 0.125e6
    obs.validate_trace(trace, require_phases=("hessian", "linesearch"))


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_blocks_skipped_counts_the_dropped_blocks_per_round(monkeypatch,
                                                            platform):
    """Where the data's platform selects the MXU kernel, each sketch round
    adds total_blocks - survivors to kernel.count_sketch.blocks_skipped;
    the segment sums skip nothing, so the CPU path has no such counter.
    The program itself runs on the CPU either way: the counter follows
    the path the platform selects."""
    from repro.core import newton
    monkeypatch.setattr(newton, "_platform", lambda x: platform)
    tel = obs.Telemetry()
    _tiny_newton(tel)
    snap = tel.metrics.snapshot()
    survivors = tel.metrics.histogram("sketch.survivors").values
    assert len(survivors) == 2          # one sketch round per iteration
    total = 5                           # N = 64 / 16 = 4, e = ceil(0.25 N)
    assert all(s < total for s in survivors)
    if platform == "tpu":
        assert snap["counters"]["kernel.path.mxu_count_sketch"] == 2.0
        assert snap["counters"]["kernel.count_sketch.blocks_skipped"] \
            == sum(total - s for s in survivors) == 2.0
    else:
        assert "kernel.count_sketch.blocks_skipped" not in snap["counters"]


def test_perfetto_golden_bytes():
    got = obs.dumps_stable(obs.to_perfetto(_synthetic_spans()))
    assert PERFETTO_GOLDEN.exists(), \
        "run: PYTHONPATH=src python tests/test_obs.py --regen"
    assert got == PERFETTO_GOLDEN.read_text()
    # And the committed bytes are themselves a valid trace.
    obs.validate_file(PERFETTO_GOLDEN,
                      require_phases=("grad/0:X", "hessian", "linesearch"))


def test_validate_trace_rejects():
    with pytest.raises(ValueError):
        obs.validate_trace({"traceEvents": []})
    ok = obs.to_perfetto(_synthetic_spans())
    with pytest.raises(ValueError, match="ghost"):
        obs.validate_trace(ok, require_phases=("ghost",))
    bad = {"traceEvents": [{"name": "x", "cat": "phase", "ph": "X",
                            "ts": 0.0, "dur": -1.0, "pid": 1, "tid": 1}]}
    with pytest.raises(ValueError, match="negative dur"):
        obs.validate_trace(bad, require_worker_tracks=False)
    with pytest.raises(ValueError, match="pid 2 is empty"):
        obs.validate_trace({"traceEvents": [
            {"name": "x", "cat": "phase", "ph": "X", "ts": 0.0, "dur": 1.0,
             "pid": 1, "tid": 1}]})


# ------------------------------------------------------------------ export
def test_jsonl_round_trip_and_tables(tmp_path):
    tel = obs.Telemetry()
    for s in _synthetic_spans():
        tel.trace.spans.append(s)
    tel.metrics.counter("fleet.phases").inc(4)
    path = tmp_path / "run.jsonl"
    obs.dump_jsonl(tel, path)
    rows = obs.load_jsonl(path)
    assert rows[-1]["kind"] == "metrics"
    assert rows[-1]["counters"]["fleet.phases"] == 4.0
    assert sum(r.get("span_kind") == "phase" for r in rows) == 4

    summary = obs.phase_summary_rows(rows)
    by_phase = {r["phase"]: r for r in summary}
    assert by_phase["grad/0:X"]["seconds"] == 0.25
    assert by_phase["hessian"]["dollars"] == 0.00025
    table = obs.phase_table(rows)
    assert "TOTAL" in table and "linesearch" in table

    reports = obs.dag_reports_from_rows(rows)
    assert len(reports) == 1
    assert reports[0].critical_path == ("grad/0:X", "grad/1:XT",
                                        "linesearch")
    assert reports[0].phases["hessian"].slack == 0.3125
    cp_table = obs.critical_path_table(reports[0])
    assert "critical path: grad/0:X -> grad/1:XT -> linesearch" in cp_table


def test_bench_rows_table_shared_formatter():
    from benchmarks.common import json_row
    rows = [json_row("a", 12.5, sim_s=1.25, usd=0.5),
            json_row("b", 7.5, sim_s=0.5, warm=3)]
    table = obs.bench_rows_table(rows)
    lines = table.splitlines()
    assert [c.strip() for c in lines[0].split("|")[1:6]] == \
        ["name", "us_per_call", "sim_s", "usd", "warm"]
    assert "12.5" in table and "0.5" in table


# ----------------------------------------------- attach points / inertness
def _fleet_drive(telemetry=None):
    clock = SimClock(StragglerModel(p_tail=0.1, tail_hi=3.0),
                     fleet=FleetConfig(failure_rate=0.2,
                                       cold_start_prob=0.3),
                     pool=WarmPool(ttl=5.0, prewarmed=2),
                     telemetry=telemetry)
    for r in range(3):
        clock.phase(jax.random.PRNGKey(r), 6, policy="k_of_n", k=4,
                    flops_per_worker=2e5, comm_units=1.0,
                    phase_name=f"p{r}")
    clock.charge(0.125, phase_name="decode")
    return clock


def test_fleet_telemetry_is_observation_only():
    plain = _fleet_drive()
    tel = obs.Telemetry()
    live = _fleet_drive(tel)
    assert live.time == plain.time
    assert live.dollars == plain.dollars

    phases = tel.trace.by_kind("phase")
    assert [s.name for s in phases] == ["p0", "p1", "p2"]
    assert all(s.end == pytest.approx(s.start + s.duration) for s in phases)
    assert tel.trace.by_kind("charge")[0].name == "decode"
    attempts = tel.trace.by_kind("attempt")
    assert attempts and all(a.track for a in attempts)
    # Worker slices sit inside their phase's interval.
    for a in attempts:
        ph = next(p for p in phases if a.track.startswith(p.name + "/"))
        assert ph.start <= a.start <= a.end

    snap = tel.metrics.snapshot()
    assert snap["counters"]["fleet.phases"] == 3.0
    assert snap["counters"]["fleet.attempts"] >= 18.0
    assert snap["counters"]["fleet.cold_starts"] \
        + snap["counters"]["fleet.warm_hits"] > 0
    assert snap["histograms"]["phase.elapsed_s"]["count"] == 3
    assert snap["gauges"]["pool.warm_hits_total"]["value"] \
        == snap["counters"]["fleet.warm_hits"]


def test_pool_snapshot():
    pool = WarmPool(ttl=10.0, prewarmed=3)
    assert pool.snapshot(0.0) == {"warm_hits": 0, "cold_starts": 0,
                                  "killed": 0, "free": 3, "containers": 3}
    pool.acquire(1.0)
    snap = pool.snapshot(1.0)
    assert snap["warm_hits"] == 1 and snap["free"] == 2


def _tiny_newton(telemetry=None, schedule="dag"):
    from repro.core import newton, sketch
    from repro.core.objectives import Dataset, LogisticRegression
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (128, 8))
    y = jnp.sign(x @ jax.random.normal(jax.random.PRNGKey(1), (8,)))
    cfg = newton.NewtonConfig(
        iters=2, schedule=schedule,
        sketch=sketch.OverSketchConfig(sketch_dim=64, block_size=16,
                                       straggler_tolerance=0.25))
    model = StragglerModel(p_tail=0.05, tail_hi=3.0)
    clock = SimClock(model, telemetry=telemetry) \
        if telemetry is not None else model
    return newton.oversketched_newton(
        LogisticRegression(), Dataset(x=x, y=y), jnp.zeros(8), cfg,
        model=clock)


def test_newton_telemetry_is_observation_only():
    plain = _tiny_newton()
    tel = obs.Telemetry()
    live = _tiny_newton(tel)
    assert live.history["time"] == plain.history["time"]
    assert live.history["cost"] == plain.history["cost"]
    assert live.history["fval"] == plain.history["fval"]

    runs = tel.trace.by_kind("run")
    assert len(runs) == 1 and runs[0].name == "newton"
    iters = tel.trace.by_kind("iteration")
    assert len(iters) == 2
    # Every iteration carries the DAG critical-path decomposition, and
    # the recorded chain reaches the joining line search.
    for s in iters:
        assert s.attrs["critical_path"][-1] == "linesearch"
        assert s.attrs["dag_makespan"] > 0
        assert set(s.attrs["slack"]) >= {"hessian", "linesearch"}
    snap = tel.metrics.snapshot()
    kernel_paths = [k for k in snap["counters"] if k.startswith("kernel.path.")]
    assert kernel_paths, "hessian phase must log the kernel path taken"
    # On the CPU the OverSketch apply lowers to the segment sums.
    assert kernel_paths == ["kernel.path.segment_sum"]
    assert sum(snap["counters"][k] for k in kernel_paths) == 2.0
    assert snap["gauges"]["sketch.m_eff"]["value"] > 0
    assert 0.0 <= snap["gauges"]["sketch.mp_debias"]["value"] < 1.0

    trace = obs.to_perfetto(tel.trace.spans)
    obs.validate_trace(trace, require_phases=("hessian", "linesearch"))


def test_giant_telemetry_is_observation_only():
    from repro.core.objectives import Dataset, LogisticRegression
    from repro.optim.giant import GiantConfig, giant
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (96, 6))
    y = jnp.sign(x @ jax.random.normal(jax.random.PRNGKey(4), (6,)))
    data = Dataset(x=x, y=y)
    cfg = GiantConfig(iters=2, num_workers=8)

    def go(telemetry=None):
        model = StragglerModel(p_tail=0.05, tail_hi=3.0)
        clock = SimClock(model, telemetry=telemetry) \
            if telemetry is not None else model
        return giant(LogisticRegression(), data, jnp.zeros(6), cfg,
                     model=clock)

    plain = go()
    tel = obs.Telemetry()
    live = go(tel)
    assert live["time"] == plain["time"]
    assert live["cost"] == plain["cost"]
    assert tel.trace.by_kind("run")[0].name == "giant"
    assert len(tel.trace.by_kind("iteration")) == 2
    names = {s.name for s in tel.trace.by_kind("phase")}
    assert {"grad", "local-newton"} <= names




def _regen():
    PERFETTO_GOLDEN.parent.mkdir(parents=True, exist_ok=True)
    PERFETTO_GOLDEN.write_text(
        obs.dumps_stable(obs.to_perfetto(_synthetic_spans())))
    print(f"wrote {PERFETTO_GOLDEN}")


if __name__ == "__main__":
    import sys
    if "--regen" in sys.argv:
        _regen()
    else:
        sys.exit("usage: python tests/test_obs.py --regen")
