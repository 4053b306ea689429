"""The program's spans and device scopes as the benchmark reads them
(``bench/span_reduce.py`` and the readers that use it): interval
arithmetic on made-up traces, the span metrics on a small trace recorded
on one TPU v5e by ``record_trace.py`` (``fixtures/trace_spans.xplane.pb``),
the host and device clocks that trace shares, and the five older readers,
pinned on the older fixture."""
import importlib.util
import json
import os

import jax
import pytest

from bench import span_reduce as sr
from bench import trace_reduce as tr
from bench import work

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
SPANS_FIXTURE = os.path.join(HERE, "fixtures", "trace_spans.xplane.pb")
OLD_FIXTURE = os.path.join(HERE, "fixtures", "trace.xplane.pb")
# record_trace.py's solve: 2 iterations at n = 2048, d = 64.
RECORDED = {"n": 2048, "d": 64,
            "newton": {"sketch": {"sketch_dim": 1024, "block_size": 128,
                                  "straggler_tolerance": 0.25},
                       "coded_block_rows": 128}}
TPU = "/device:TPU:0"


def reader(name):
    path = os.path.join(REPO, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("test_reader_" + name,
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Ctx:
    def __init__(self, trace, iterations=2, config=RECORDED):
        self.trace, self.iterations, self.config = trace, iterations, config
        self.peak = work.peaks("TPU v5 lite")


def made_up(spans=None, scopes=None):
    """A 100-ns window, busy over [10, 20) and [50, 60)."""
    t = tr.Trace(window=(0.0, 100.0), busy={TPU: [(10.0, 20.0),
                                                  (50.0, 60.0)]},
                 programs={}, gaps=[])
    if spans is not None:
        t.spans = spans
    if scopes is not None:
        t.scopes = scopes
    return t


SPANS = [(0, 100, "osn.solve"), (5, 45, "osn.iter"),
         (15, 30, "osn.sync.guard"), (45, 95, "osn.iter"),
         (48, 70, "osn.fleet"), (65, 70, "osn.sync.straggler")]


def test_idle_goes_to_the_innermost_span_and_sums_to_the_idle_time():
    idle = sr.idle_by_span(made_up(SPANS))
    # Idle: [0,10) [20,50) [60,100).  [0,5) solve, [5,10) iter,
    # [20,30) guard, [30,45) iter, [45,48) iter, [48,50) fleet,
    # [60,65) fleet, [65,70) straggler, [70,95) iter, [95,100) solve.
    assert {k: round(v * 1e9, 6) for k, v in idle.items()} == {
        "osn.solve": 10.0, "osn.iter": 48.0, "osn.sync.guard": 10.0,
        "osn.fleet": 7.0, "osn.sync.straggler": 5.0}
    assert sum(idle.values()) * 1e9 == pytest.approx(80.0)


def test_idle_outside_every_span_is_named_none():
    idle = sr.idle_by_span(made_up([(30, 40, "osn.iter")]))
    assert round(idle["osn.iter"] * 1e9, 6) == 10.0
    assert round(idle[None] * 1e9, 6) == 70.0


def test_span_readers_read_the_partition_and_the_sync_count():
    ctx = Ctx(made_up(SPANS), iterations=2)
    assert reader("fleet_idle_ms").read(ctx) == pytest.approx(3.5e-6)
    assert reader("sync_idle_ms").read(ctx) == pytest.approx(7.5e-6)
    assert reader("syncs_per_iter").read(ctx) == 1.0


def test_scope_readers_average_the_union_over_device_planes():
    scopes = {"osn_sketch": {TPU: [(10.0, 20.0)],
                             "/device:TPU:1": [(10.0, 30.0)]},
              "osn_gram": {TPU: [(50.0, 54.0)]}}
    ctx = Ctx(made_up(scopes=scopes), iterations=1)
    assert reader("sketch_ms").read(ctx) == pytest.approx(15e-6)
    assert reader("gram_ms").read(ctx) == pytest.approx(4e-6)


@pytest.mark.parametrize("name", sr.METRICS)
def test_span_readers_read_nothing_from_a_trace_without_spans(name):
    assert reader(name).read(Ctx(made_up())) is None
    assert reader(name).read(Ctx(made_up([], {}))) is None


# ------------------------------------------------------------ chip fixtures
@pytest.fixture(scope="module")
def recorded():
    trace = tr.reduce(SPANS_FIXTURE)
    return sr.attach(trace, SPANS_FIXTURE)


def test_recorded_spans_hold_one_solve_of_two_iterations(recorded):
    names = [n for _, _, n in recorded.spans]
    assert names.count("osn.solve") == 1
    assert names.count("osn.iter") == 2
    for stage in ("osn.gradient", "osn.hessian", "osn.direction",
                  "osn.linesearch", "osn.history"):
        assert names.count(stage) == 2, stage
    # 21 reads in the first iteration (the encodes'), 17 in the second.
    assert sum(n.startswith(sr.SYNC_PREFIX) for n in names) == 38


def test_recorded_scopes_split_the_hessian_program(recorded):
    assert set(recorded.scopes) == {"osn_hess_sqrt", "osn_sketch",
                                    "osn_gram"}
    parts = {s: sr.scope_s(recorded, s) for s in recorded.scopes}
    assert all(v > 0 for v in parts.values())
    # The scopes lie inside jit_fn's device time and do not overlap.
    assert sum(parts.values()) <= recorded.programs["jit_fn"] * (1 + 1e-9)
    ivs = [iv for s in recorded.scopes for iv in recorded.scopes[s][TPU]]
    covered = sum(b - a for a, b in tr.union(ivs)) * 1e-9
    assert covered == pytest.approx(sum(parts.values()), rel=1e-9)


def test_recorded_idle_is_partitioned_and_nearly_all_under_spans(recorded):
    idle = sr.idle_by_span(recorded)
    total = recorded.window_s - recorded.busy_s
    assert sum(idle.values()) == pytest.approx(total, rel=1e-9)
    assert set(idle) - {None} <= {n for _, _, n in recorded.spans}
    assert idle.get(None, 0.0) < 0.1 * total


def test_recorded_span_metrics_read_numbers(recorded):
    ctx = Ctx(recorded)
    values = {name: reader(name).read(ctx) for name in sr.METRICS}
    assert all(v is not None and v > 0 for v in values.values()), values
    assert values["syncs_per_iter"] == 19.0


def _modules(pd):
    for plane in pd.planes:
        if plane.name == TPU:
            for line in plane.lines:
                if line.name == tr.MODULES_LINE:
                    return sorted((e.start_ns, e.start_ns + e.duration_ns,
                                   tr.program_name(e.name))
                                  for e in line.events)
    return []


# Sync sites whose read waits for the work dispatched just before it
# (the straggler and mask reads may return a value made long before).
WAITING_SITES = ("osn.sync.decode", "osn.sync.survivors", "osn.sync.guard",
                 "osn.sync.history")


def test_host_spans_and_device_ops_share_one_clock(recorded):
    """Each iteration's first Hessian program starts on the device after
    that iteration's ``osn.hessian`` span opened on the host, and a read
    that waits for the work queued before it returns after that work has
    finished on the device."""
    pd = jax.profiler.ProfileData.from_file(SPANS_FIXTURE)
    modules = _modules(pd)
    spans = recorded.spans
    hess = sorted(s for s in spans if s[2] == "osn.hessian")
    iters = sorted(s for s in spans if s[2] == "osn.iter")
    for (h0, _, _), (i0, i1, _) in zip(hess, iters):
        first = min(a for a, _, n in modules
                    if n == "jit_fn" and i0 <= a < i1)
        assert first > h0
    syncs = [s for s in spans if s[2] in WAITING_SITES]
    assert len(syncs) == 2 * (2 + 1 + 1 + 3)
    for _, s1, name in syncs:
        assert max(b for a, b, _ in modules if a < s1) <= s1, name


def test_old_trace_without_program_spans_reads_nothing():
    trace = sr.attach(tr.reduce(OLD_FIXTURE), OLD_FIXTURE)
    assert trace.spans == []
    assert trace.scopes == {}
    for name in sr.METRICS:
        assert reader(name).read(Ctx(trace)) is None, name


# Values of the five readers the benchmark had before the program spans,
# on the older fixture, as computed before the spans were added.
PINNED = {"hessian_ms": 0.18604500000000002,
          "hessian_roofline": 0.354839513107674,
          "gradient_ms": 0.0195205,
          "iter_roofline": 0.0015614297335043526,
          "device_idle_share": 99.80613398407442}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_older_readers_read_the_older_fixture_as_before(name):
    ctx = Ctx(tr.reduce(OLD_FIXTURE))
    assert reader(name).read(ctx) == pytest.approx(PINNED[name], rel=1e-12)


# ---------------------------------------------------------------- the script
def test_script_prints_span_metrics_after_the_result_line(
        checkout, cpu_chip, monkeypatch, capsys):
    """On the CPU: no device plane, so the scope metrics read nothing and
    the whole window is idle, partitioned by the program's spans."""
    from conftest import add_cell
    from jax.experimental.compilation_cache import compilation_cache
    name = add_cell(checkout)
    monkeypatch.setattr(cpu_chip, "compile_cache_dir", lambda env, root: None)
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    main = cpu_chip.main
    monkeypatch.setattr(cpu_chip, "main",
                        lambda argv: main(argv, root=checkout))
    try:
        rc = sr.main(["--workload", name, "--seed", "7", "--seconds", "0.2"])
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          saved[1])
        compilation_cache.reset_cache()
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    result, spans = json.loads(out[-2]), json.loads(out[-1])
    assert result["correct"] is True
    m = spans["span_metrics"]
    assert m["sketch_ms"] is None and m["gram_ms"] is None
    assert m["fleet_idle_ms"] > 0 and m["sync_idle_ms"] > 0
    # tiny-logistic solves 3 iterations: 17 reads each, 4 for the encodes.
    assert m["syncs_per_iter"] == pytest.approx((17 * 3 + 4) / 3)
    assert spans["iterations"] == 3 * result["attempted"]
    assert sum(spans["idle_by_span_s"].values()) == pytest.approx(
        result["device"]["window_s"], rel=1e-6)
    assert spans["span_counts"]["osn.solve"] == result["attempted"]
