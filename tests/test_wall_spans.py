"""The program's spans and device scopes on the profiler's clock
(``repro.obs.wall``): a 2-iteration solve traced on the CPU, read back
from the ``.xplane.pb``, and the scopes in the lowered Hessian programs."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro import obs, sketching
from repro.core import (Dataset, LogisticRegression, NewtonConfig,
                        OverSketchConfig, SimClock, StragglerModel,
                        make_code, newton, oversketched_newton)
from repro.obs import wall

D = 16
CFG = NewtonConfig(iters=2, sketch=OverSketchConfig(512, 64, 0.25),
                   coded_block_rows=64, seed=1)
# Host reads per iteration on the default path: 8 in the gradient (per
# coded matvec: the fleet's phase key and sampled times, the arrival mask,
# the decode flag), 3 in the Hessian (its fleet phase's 2, the surviving
# rows), the descent guard, 2 in the line search's fleet phase and 3 in the
# log.  The first iteration also bills the two encodes (2 reads each).
SYNCS_PER_ITER = [21, 17]


def _data():
    x = jax.random.uniform(jax.random.PRNGKey(0), (1024, D),
                           minval=-1.0, maxval=1.0)
    y = jnp.sign(x @ jax.random.normal(jax.random.PRNGKey(1), (D,)))
    return Dataset(x, y)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(host spans of the solve's thread, the untraced solve's w, the
    traced solve's w)."""
    data, obj = _data(), LogisticRegression(lam=1e-5)
    plain = oversketched_newton(obj, data, jnp.zeros(D), CFG)
    out = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(out):
        res = oversketched_newton(obj, data, jnp.zeros(D), CFG)
        jax.block_until_ready(res.w)
    path, = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    lines = [[(e.start_ns, e.start_ns + e.duration_ns, e.name)
              for e in line.events if e.name.startswith("osn.")]
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines]
    spans, = [line for line in lines if line]
    return spans, plain.w, res.w


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_one_solve_span_holds_one_iteration_span_per_iteration(traced):
    spans, _, _ = traced
    solves = [s for s in spans if s[2] == wall.SOLVE]
    iters = [s for s in spans if s[2] == wall.ITER]
    assert len(solves) == 1
    assert len(iters) == CFG.iters
    assert all(_inside(s, solves[0]) for s in spans)


def test_every_stage_runs_once_per_iteration_inside_its_iteration(traced):
    spans, _, _ = traced
    iters = sorted(s for s in spans if s[2] == wall.ITER)
    for stage in wall.STAGES:
        found = sorted(s for s in spans if s[2] == stage)
        assert len(found) == len(iters), stage
        for s, it in zip(found, iters):
            assert _inside(s, it), stage


def test_every_sync_span_sits_inside_a_stage(traced):
    spans, _, _ = traced
    stages = [s for s in spans if s[2] in wall.STAGES]
    syncs = [s for s in spans if s[2].startswith(wall.SYNC_PREFIX)]
    assert syncs
    assert {s[2] for s in syncs} <= set(wall.SYNCS)
    for s in syncs:
        assert any(_inside(s, st) for st in stages), s
    # The fleet's own reads sit inside a fleet span, itself in a stage.
    fleet = [s for s in spans if s[2] == wall.FLEET]
    for s in syncs:
        if s[2] == wall.SYNC_STRAGGLER:
            assert any(_inside(s, f) for f in fleet), s
    for f in fleet:
        assert any(_inside(f, st) for st in stages), f


def test_sync_spans_per_iteration_are_the_default_paths_host_reads(traced):
    spans, _, _ = traced
    iters = sorted(s for s in spans if s[2] == wall.ITER)
    counts = [sum(1 for s in spans if s[2].startswith(wall.SYNC_PREFIX)
                  and _inside(s, it)) for it in iters]
    assert counts == SYNCS_PER_ITER
    per_site = {name: sum(1 for s in spans if s[2] == name and
                          _inside(s, iters[-1])) for name in wall.SYNCS}
    assert per_site == {wall.SYNC_STRAGGLER: 8, wall.SYNC_MASK: 2,
                        wall.SYNC_DECODE: 2, wall.SYNC_SURVIVORS: 1,
                        wall.SYNC_GUARD: 1, wall.SYNC_HISTORY: 3}


def test_one_solve_opens_the_encode_span_and_sets_the_held_bytes(traced):
    """The parity encodes are dispatched once, under ``osn.encode`` inside
    the solve and before its first iteration, and each code's bytes kept
    on the device are its 2g+1 parity blocks of b rows by the operand's
    width."""
    spans, _, _ = traced
    solve, = [s for s in spans if s[2] == wall.SOLVE]
    encode, = [s for s in spans if s[2] == wall.ENCODE]
    assert _inside(encode, solve)
    assert encode[1] <= min(s[0] for s in spans if s[2] == wall.ITER)
    tel = obs.Telemetry()
    data = _data()
    oversketched_newton(LogisticRegression(lam=1e-5), data, jnp.zeros(D),
                        CFG, model=SimClock(StragglerModel(), telemetry=tel))
    n, d = data.x.shape
    gauges = tel.metrics.snapshot()["gauges"]
    for tag, rows, width in (("X", n, d), ("XT", d, n)):
        code = make_code(rows, min(CFG.coded_block_rows, rows))
        want = 4 * (2 * code.grid + 1) * code.block_rows * width
        assert gauges[f"coded.held_bytes.{tag}"] == {"value": want, "n": 1}


def test_tracing_leaves_the_iterate_unchanged(traced):
    _, plain, traced_w = traced
    assert jnp.array_equal(plain, traced_w)


def _hessian_program(platforms=None):
    """The default Hessian program's StableHLO with its locations: lowered
    for this process's platform, or exported for ``platforms`` (no chip
    is needed to lower for one)."""
    data, obj = _data(), LogisticRegression(lam=1e-5)
    fam = sketching.get("oversketch", CFG.sketch)
    state = fam.sample(jax.random.PRNGKey(3), data.x.shape[0])
    fn = newton._jitted_sketched_hessian(obj, fam)
    args = (jnp.zeros(D), data, state,
            jnp.ones((CFG.sketch.total_blocks,), bool))
    if platforms is None:
        return fn.lower(*args).as_text(debug_info=True)
    return jax.export.export(fn, platforms=platforms)(*args).mlir_module()


def test_lowered_hessian_carries_the_three_device_scopes():
    text = _hessian_program()
    for scope in wall.SCOPES:
        assert f"jit(fn)/{scope}/" in text, scope
    # The count sketch's streamed blocks run inside the sketch scope, in
    # the branch apply_sketch's platform switch takes on the CPU.
    assert re.search(
        rf"jit\(fn\)/{wall.SKETCH}/cond/branch_\d+_fun/while/body/", text)


def test_mxu_kernel_falls_under_the_sketch_scope_alone():
    """Lowered for a TPU, the Hessian calls the MXU count-sketch kernel
    from inside ``osn_sketch`` alone, and its Gram's matmuls run under
    ``osn_gram``."""
    text = _hessian_program(("tpu",))
    locs = dict(re.findall(r"^(#loc\d+) = loc\(\"([^\"]*)\"", text, re.M))
    call, = re.findall(r"call @(_count_sketch_apply)\(.*loc\((#loc\d+)\)$",
                       text, re.M)
    scope = locs[call[1]]
    assert scope.startswith(f"jit(fn)/{wall.SKETCH}/"), scope
    assert f"/{wall.GRAM}/" not in scope and f"/{wall.HESS_SQRT}/" not in scope
    body = text[text.index(f"func.func private @{call[0]}("):]
    body = body[:body.index("\n  }")]
    assert "stablehlo.custom_call @tpu_custom_call" in body
    gram_ops = [name for name in locs.values()
                if name.startswith(f"jit(fn)/{wall.GRAM}/")]
    assert any(name.endswith("dot_general") for name in gram_ops)


def test_distributed_average_direction_carries_the_device_scopes():
    data, obj = _data(), LogisticRegression(lam=1e-5)
    cfg = OverSketchConfig(512, 64, 0.25)
    fam = sketching.get("oversketch", cfg)
    state = fam.sample(jax.random.PRNGKey(3), data.x.shape[0])
    fn = newton._jitted_distavg_direction(obj, fam, False)
    text = fn.lower(jnp.zeros(D), data, jnp.ones(D), state,
                    jnp.ones((cfg.total_blocks,), bool)).as_text(
                        debug_info=True)
    for scope in wall.SCOPES:
        assert f"/{scope}/" in text, scope
