#!/usr/bin/env python3
"""Record the small device trace that tests/bench/test_bench_trace_reduce.py
reads, on one TPU chip:

    python3 tests/bench/record_trace.py [OUT_DIR]

It traces one 2-iteration OverSketched Newton solve at a tiny size, inside
the benchmark's ``window`` and ``solve`` annotations, with the profiler
options the benchmark uses, and copies the ``.xplane.pb`` to
``OUT_DIR/trace.xplane.pb`` (default ``tests/bench/fixtures``).  It
prints, for each plane and line of the trace, the event count and the most
frequent event names, which is what a reader of the reduction needs to see.
"""
from __future__ import annotations

import collections
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import trace_reduce  # noqa: E402
from bench.objectives import logistic  # noqa: E402
from repro.core import (Dataset, LogisticRegression, NewtonConfig,  # noqa: E402
                        OverSketchConfig, oversketched_newton)


def summary(path: str) -> None:
    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            names = collections.Counter(e.name for e in events)
            print("  LINE", repr(line.name), len(events))
            for name, count in names.most_common(12):
                print("     ", count, repr(name[:120]))
            if events:
                print("      stats:", dict(events[0].stats))


def main() -> int:
    out = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        ROOT, "tests", "bench", "fixtures")
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    x, y = logistic.generate(jax.random.PRNGKey(0), 2048, 64, 10.0, True)
    ds = Dataset(x, y)
    cfg = NewtonConfig(iters=2, sketch=OverSketchConfig(1024, 128, 0.25),
                       coded_block_rows=128, seed=1)
    obj = LogisticRegression(lam=1e-5)
    jax.block_until_ready(oversketched_newton(obj, ds, jnp.zeros(64), cfg).w)
    tmp = tempfile.mkdtemp()
    try:
        trace_reduce.start(tmp)
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            with jax.profiler.TraceAnnotation("solve"):
                jax.block_until_ready(
                    oversketched_newton(obj, ds, jnp.zeros(64), cfg).w)
        path = trace_reduce.stop(tmp)
        os.makedirs(out, exist_ok=True)
        shutil.copy(path, os.path.join(out, "trace.xplane.pb"))
        summary(path)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
