#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on a machine whose JAX sees the chips the cell
asks for (``BENCHMARK.json``).  One process drives one chip.

Set-up makes the cell's data on the device from ``--seed`` and warms up
with one 1-iteration solve, which compiles every program a solve runs.
The window then runs the traffic's solves back to back through
``repro.core.oversketched_newton`` until the first solve boundary at or
after ``--seconds``.  Once it has closed and the peak device memory has
been read, ``bench/check.py`` judges every solve against the plain
reference.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result carries
the per-layer metrics read from the trace (``bench/metrics/``), the
device's busy and window seconds, and a breakdown.  The last lines of
standard error give each number compared beside its limit; the last line
of standard output is the result, one JSON object.

Exits 1 without a TPU or with fewer chips than the cell asks for, and 2
where the checkout holds no program (``src/repro``); neither prints a
result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# libtpu writes its logs under /tmp unless told otherwise.
os.environ.setdefault("TPU_LOG_DIR", "disabled")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TOP = 10            # entries in each list of the breakdown


class NoChip(RuntimeError):
    pass


def compile_cache_dir(environ, root: str) -> str:
    """JAX_COMPILATION_CACHE_DIR where it is set, else a fixed directory in
    the checkout (a path that never moves, so later runs hit it)."""
    return environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")


def require_chip(chips: int) -> dict:
    """The device record of the result; raises NoChip without enough TPUs."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips; JAX found {len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": chips}


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _top(pairs, n=TOP):
    return [[k, v] for k, v in sorted(pairs, key=lambda kv: -kv[1])[:n]]


class Context:
    """What a per-layer metric's ``read(ctx)`` may use."""

    def __init__(self, trace, cell, iterations, peak):
        self.trace = trace              # bench.trace_reduce.Trace
        self.config = cell.config
        self.iterations = iterations    # Newton iterations in the window
        self.peak = peak                # this device's row of peaks.json


def main(argv=None, root: str = ROOT, newton=None) -> int:
    args = parse(argv)
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        print("bench: no program in this checkout (src/repro)",
              file=sys.stderr)
        return 2
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)

    import jax
    jax.config.update("jax_compilation_cache_dir",
                      compile_cache_dir(os.environ, root))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import cell as cells, check, trace_reduce, work

    cell = cells.load(root, args.workload)
    try:
        device = require_chip(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    iters = int(cell.traffic["iters"])
    limits = cells.limits_of(cell)

    # ---- set-up: data, then one 1-iteration solve that compiles all ----
    x, y = cells.make_data(cell, args.seed)
    cells.solve(cell, x, y, cells.solve_seed(args.seed, -1), 1, newton)
    setup_s = time.perf_counter() - T0

    # ---- the window: whole solves, back to back ----
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace \
        else None
    if trace_dir:
        trace_reduce.start(trace_dir)
    # Compile-cache misses are programs compiled in the window; hits are
    # programs the program traced again and loaded from the cache.
    events = []

    def listen(event, **kw):
        if event.startswith("/jax/compilation_cache/cache_"):
            events.append(event)

    jax.monitoring.register_event_listener(listen)
    answers = []
    t_start = time.perf_counter()
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        while True:
            with jax.profiler.TraceAnnotation("solve"):
                answers.append(cells.solve(
                    cell, x, y, cells.solve_seed(args.seed, len(answers)),
                    iters, newton))
            if time.perf_counter() - t_start >= args.seconds:
                break
    window_s = time.perf_counter() - t_start
    jax.monitoring.unregister_event_listener(listen)
    trace_path = trace_reduce.stop(trace_dir) if trace_dir else None
    peak = cells.peak_bytes()
    device["memory_peak_bytes"] = peak

    # ---- the check, after the window and the memory reading ----
    verdict = check.judge(cell.objective, x, y, cell.config, answers, limits)
    print(f"bench: {cell.name} seed {args.seed}: {len(answers)} solves in "
          f"{window_s!r} s, seconds per solve "
          f"{[a.seconds for a in answers]!r}, f* {verdict['f_star']!r}, "
          f"programs compiled in the window "
          f"{events.count('/jax/compilation_cache/cache_misses')}, "
          f"loaded from the compile cache "
          f"{events.count('/jax/compilation_cache/cache_hits')}",
          file=sys.stderr)

    result = {"correct": verdict["failed"] == 0 and len(answers) > 0,
              "attempted": len(answers), "failed": verdict["failed"]}
    if not args.trace:
        # An end-to-end metric named <quantity>.<variant> reports the
        # quantity under the variant's own bound.
        values = {"solve_s": window_s / len(answers), "setup_s": setup_s,
                  "peak_hbm_gb": None if peak is None else peak / 1e9}
        result["metrics"] = {}
        for m in cell.end_to_end:
            value = values[m["name"].split(".")[0]]
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
    else:
        trace = trace_reduce.reduce(trace_path)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = Context(trace, cell, len(answers) * iters,
                      work.peaks(device["kind"]))
        result["metrics"] = {}
        for m in cell.per_layer:
            value = cell.reader(m["name"]).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        gaps = {}
        for name, secs in trace.gaps:
            gaps[name] = gaps.get(name, 0.0) + secs
        result["breakdown"] = {"device_ops": _top(trace.programs.items()),
                               "idle_gaps": _top(gaps.items())}
    result["device"] = device
    # A reading that is not finite is written as null (JSON has no inf).
    result["check"] = {k: {"value": v if math.isfinite(v) else None,
                           "limit": limits[k]}
                       for k, v in verdict["numbers"].items()}
    for line in check.lines(verdict["numbers"], limits):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
