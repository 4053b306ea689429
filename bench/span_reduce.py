#!/usr/bin/env python3
"""The program's own spans and device scopes in a profiler trace.

The program marks itself on the profiler's clock: host spans named
``osn.*`` (``jax.profiler.TraceAnnotation``: the solve, each iteration,
its stages, each call into the simulated fleet, each blocking
device-to-host read ``osn.sync.<site>``) and device scopes named ``osn_*``
(``jax.named_scope``: hess_sqrt, sketch and Gram inside the Hessian
program).  The names are the program's; they are matched here by prefix
only, so a program without them yields empty results, never an error.

``attach(trace, path)`` adds these fields to a ``bench.trace_reduce.Trace``
read from the same file; ``bench/run.py`` calls it on every traced run
before its readers run:

- ``spans``: the ``osn.*`` events of the host thread that holds the
  benchmark's ``window`` span, as (start, end, name) in nanoseconds;
- ``solves``: the (start, end) of the benchmark's ``solve`` annotations
  on that thread;
- ``scopes``: for each ``osn_*`` scope, per device plane, the union of the
  intervals of the operations whose framework op name has that scope as a
  path component, clipped to the window.  The op-to-scope map is xprof's
  ``hlo_stats`` table of the same file, joined to the ``XLA Ops`` events
  by program id (the ``(id)`` suffix of the enclosing ``XLA Modules``
  event) and HLO op name.  A union, not a sum: a ``while`` op's event
  encloses its body's ops.  Without xprof, ``scopes`` is empty.

``idle_by_span(trace)`` gives every idle nanosecond of the window (the
intervals ``gaps`` names) to the innermost ``osn.*`` span open over it
(None where none is): a partition of the idle time.

Run as a script, it runs one traced cell through ``bench/run.py`` and then
prints one more JSON line: the span metrics of ``bench/metrics/`` read on
the run's own trace, the idle partition, span counts, per-iteration wall
times and, for each solve, its longest idle stretch and the span over it:

    python3 bench/span_reduce.py --workload <cell> --seed <n> --seconds <s>
"""
from __future__ import annotations

import bisect
import importlib.util
import json
import os
import re
import statistics
import sys
import tempfile
from typing import Dict, List, Optional, Tuple

import jax

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace_reduce as tr  # noqa: E402

Span = Tuple[float, float, str]

SPAN_PREFIX = "osn."
SYNC_PREFIX = "osn.sync."
SCOPE_PREFIX = "osn_"
ITER = "osn.iter"
SOLVE = "solve"          # the benchmark's annotation around each solve
_HLO_NAME = re.compile(r"^%?([^\s=]+)")
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


def _window_thread(pd) -> List[Span]:
    """The events of the host thread that holds the ``window`` span."""
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = list(tr._events(line))
            if any(name == tr.WINDOW for _, _, name in events):
                return events
    return []


def _scope_table(path: str) -> Optional[Dict[Tuple[str, str], Tuple[str]]]:
    """{(program id, HLO op name): the ``osn_*`` scopes on its path}, or
    None where xprof is not installed."""
    try:
        from xprof.convert import raw_to_tool_data
    except ImportError:
        return None
    # xprof caches what it derives beside the file it reads: point it at a
    # link in a scratch directory so the trace's own directory stays as is.
    with tempfile.TemporaryDirectory() as tmp:
        link = os.path.join(tmp, os.path.basename(path))
        os.symlink(os.path.abspath(path), link)
        data, _ = raw_to_tool_data.xspace_to_tool_data([link], "hlo_stats",
                                                       {})
    table = json.loads(data)
    cols = [c["id"] for c in table["cols"]]
    ip, ih, it = (cols.index(k) for k in
                  ("program_id", "hlo_op_name", "tf_op_name"))
    out = {}
    for row in table["rows"]:
        cells = row["c"]
        parts = str(cells[it]["v"]).split("/")
        found = tuple(p for p in parts if p.startswith(SCOPE_PREFIX))
        if found:
            out[(str(cells[ip]["v"]), str(cells[ih]["v"]))] = found
    return out


def device_scopes(path: str, pd, window: tr.Interval
                  ) -> Dict[str, Dict[str, List[tr.Interval]]]:
    """{scope: {device plane: merged op intervals in the window}}."""
    table = _scope_table(path)
    if table is None:
        print("bench: xprof is not installed; device scopes not read",
              file=sys.stderr)
        return {}
    raw: Dict[str, Dict[str, List[tr.Interval]]] = {}
    for plane in pd.planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if tr.OPS_LINE not in lines or tr.MODULES_LINE not in lines:
            continue
        modules = sorted(tr._events(lines[tr.MODULES_LINE]))
        starts = [m[0] for m in modules]
        for a, b, name in tr._events(lines[tr.OPS_LINE]):
            k = bisect.bisect_right(starts, a) - 1
            if k < 0 or modules[k][1] < a:
                continue
            pid = _PROGRAM_ID.search(modules[k][2])
            op = _HLO_NAME.match(name)
            if pid is None or op is None:
                continue
            for scope in table.get((pid.group(1), op.group(1)), ()):
                raw.setdefault(scope, {}).setdefault(
                    plane.name, []).append((a, b))
    return {scope: {p: tr.union(tr.clip(iv, *window))
                    for p, iv in planes.items()}
            for scope, planes in raw.items()}


def attach(trace: "tr.Trace", path: str, pd=None) -> "tr.Trace":
    """Add ``spans``, ``solves`` and ``scopes`` (module docstring) to
    ``trace``."""
    pd = pd or jax.profiler.ProfileData.from_file(path)
    lo, hi = trace.window
    thread = _window_thread(pd)
    trace.spans = [e for e in thread
                   if e[2].startswith(SPAN_PREFIX) and lo <= e[0] < hi]
    trace.solves = sorted((a, b) for a, b, n in thread if n == SOLVE)
    trace.scopes = device_scopes(path, pd, trace.window)
    return trace


def scope_s(trace, scope: str) -> Optional[float]:
    """Device seconds under ``scope``, averaged over the device planes that
    ran it; None where the trace has no scopes or none ran under it."""
    planes = (getattr(trace, "scopes", None) or {}).get(scope)
    if not planes:
        return None
    total = sum(b - a for iv in planes.values() for a, b in iv)
    return total * 1e-9 / len(planes)


def idle_pieces(trace, spans: List[Span]
                ) -> List[Tuple[tr.Interval, Optional[str]]]:
    """The window's idle intervals, cut at every span boundary, each with
    the innermost span open over it."""
    busy = next(iter(trace.busy.values()), [])
    bounds = sorted({t for a, b, _ in spans for t in (a, b)})
    pieces = []
    for a, b in tr.complement(busy, *trace.window):
        i = bisect.bisect_right(bounds, a)
        j = bisect.bisect_left(bounds, b)
        edges = [a] + bounds[i:j] + [b]
        pieces.extend(zip(edges[:-1], edges[1:]))
    names = tr.innermost(spans, [(a + b) / 2 for a, b in pieces])
    return list(zip(pieces, names))


def idle_by_span(trace) -> Optional[Dict[Optional[str], float]]:
    """{innermost ``osn.*`` span (None: none open): idle seconds}; None
    where the trace holds no program spans."""
    spans = getattr(trace, "spans", None)
    if not spans:
        return None
    out: Dict[Optional[str], float] = {}
    for (a, b), name in idle_pieces(trace, spans):
        out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out


# ---------------------------------------------------------------- the script
def summary(trace) -> dict:
    """What the script prints after the run's own result line."""
    spans = trace.spans
    iters = sorted((a, b) for a, b, n in spans if n == ITER)
    counts: Dict[str, int] = {}
    for _, _, name in spans:
        counts[name] = counts.get(name, 0) + 1
    pieces = idle_pieces(trace, spans) if spans else []
    per_solve = []
    for lo, hi in trace.solves:
        inside = [(b - a, a, name) for (a, b), name in pieces
                  if lo <= a < hi]
        its = [a for a, _ in iters if lo <= a < hi]
        longest = max(inside, default=None)
        per_solve.append({
            "seconds": (hi - lo) * 1e-9,
            "idle_s": sum(x[0] for x in inside) * 1e-9,
            "longest_idle_s": longest and longest[0] * 1e-9,
            "longest_idle_span": longest and longest[2],
            "longest_idle_iteration": longest and (
                bisect.bisect_right(its, longest[1]) - 1)})
    iter_s = [(b - a) * 1e-9 for a, b in iters]
    idle = idle_by_span(trace) or {}
    return {
        "idle_by_span_s": {str(k): v for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])},
        "idle_s": sum(idle.values()),
        "scopes_s": {s: scope_s(trace, s) for s in sorted(
            getattr(trace, "scopes", {}))},
        "span_counts": counts,
        "iteration_s": {"n": len(iter_s),
                        "median": statistics.median(iter_s)
                        if iter_s else None,
                        "max": max(iter_s, default=None)},
        "solves": per_solve}


def main(argv=None) -> int:
    """Run ``bench/run.py`` traced, keep the context it hands its readers
    (the trace with its spans attached), then print the span metrics and
    the summary as one JSON line."""
    from bench import run
    argv = list(sys.argv[1:] if argv is None else argv)
    kept: dict = {}
    context = run.Context

    class Context(context):
        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            kept["ctx"] = self

    run.Context = Context
    try:
        rc = run.main(argv + ["--trace", "1"])
    finally:
        run.Context = context
    if rc != 0 or "ctx" not in kept:
        return rc
    ctx = kept["ctx"]
    metrics = {name: _reader(name).read(ctx) for name in METRICS}
    print(json.dumps({"span_metrics": metrics, "iterations": ctx.iterations,
                      **summary(ctx.trace)}), flush=True)
    return 0


# The readers of bench/metrics/ that read these spans and scopes.
METRICS = ("sketch_ms", "gram_ms", "fleet_idle_ms", "sync_idle_ms",
           "syncs_per_iter")


def _reader(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("span_metric_" + name,
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


if __name__ == "__main__":
    sys.exit(main())
