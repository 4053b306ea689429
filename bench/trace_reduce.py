"""From a profiler trace to the numbers the per-layer metrics read.

The benchmark traces its measured window with ``start``/``stop`` and hands
the ``.xplane.pb`` to ``reduce``, which reads it with nothing but
``jax.profiler.ProfileData`` and returns a ``Trace``:

- ``window``: the span of the benchmark's ``window`` annotation (the whole
  trace where there is none), in nanoseconds on the trace's clock;
- ``busy``: the union of the intervals in which an operation ran on a
  device, clipped to the window, per device plane;
- ``programs``: device seconds per program, from each device's module line,
  keyed by the program's name as the trace shows it without its ``(id)``
  suffix (``jit_coded_matvec``);
- ``gaps``: the idle intervals inside the window, each named by the host
  span that was open over it: the innermost of the benchmark's own
  annotations and the host events the runtime records on the thread that
  holds them.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

import jax

Interval = Tuple[float, float]

# The benchmark's annotation around its measured window.
WINDOW = "window"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_ID_SUFFIX = re.compile(r"\(\d+\)$")


def start(log_dir: str) -> None:
    """Start tracing into ``log_dir``: device activity and host spans, with
    the Python call tracer off (it would slow the host loop under test)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop(log_dir: str) -> str:
    """Stop tracing; return the path of the ``.xplane.pb`` written."""
    jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {paths}")
    return paths[0]


def program_name(event_name: str) -> str:
    return _ID_SUFFIX.sub("", event_name).strip()


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def complement(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    return gaps


@dataclasses.dataclass
class Trace:
    window: Interval
    busy: Dict[str, List[Interval]]         # device plane -> merged busy
    programs: Dict[str, float]              # program -> device seconds
    gaps: List[Tuple[str, float]]           # (host span, seconds)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Device-busy seconds, averaged over the devices that ran."""
        if not self.busy:
            return 0.0
        total = sum(b - a for iv in self.busy.values() for a, b in iv)
        return total * 1e-9 / len(self.busy)

    def device_s(self, names) -> Optional[float]:
        """Device seconds of the named programs, or None if none ran."""
        found = [self.programs[n] for n in names if n in self.programs]
        return sum(found) if found else None


def innermost(spans: List[Tuple[float, float, str]],
              instants: List[float]) -> List[Optional[str]]:
    """For each instant, the name of the innermost span open at it (None
    where none is).  ``spans`` are (start, end, name) of one thread, so
    they nest; one sweep in time order keeps the open ones on a stack."""
    spans = sorted(spans, key=lambda s: (s[0], -s[1]))
    order = sorted(range(len(instants)), key=lambda k: instants[k])
    names: List[Optional[str]] = [None] * len(instants)
    stack: List[Tuple[float, float, str]] = []
    i = 0
    for k in order:
        t = instants[k]
        while i < len(spans) and spans[i][0] <= t:
            while stack and stack[-1][1] <= spans[i][0]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] <= t:
            stack.pop()
        names[k] = stack[-1][2] if stack else None
    return names


def _events(line):
    for e in line.events:
        yield e.start_ns, e.start_ns + e.duration_ns, e.name


def reduce(path: str, pd=None) -> Trace:
    """Reduce one trace file (``pd``: the file already read, if it has
    been).  The host thread that holds the benchmark's ``window`` span
    names the gaps."""
    pd = pd or jax.profiler.ProfileData.from_file(path)
    window, host = None, []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = list(_events(line))
            for a, b, name in events:
                if name == WINDOW:
                    window, host = (a, b), events

    busy_raw: Dict[str, List[Interval]] = {}
    programs: Dict[str, float] = {}
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        op_line = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        if op_line is not None:
            busy_raw[plane.name] = [(a, b) for a, b, _ in _events(op_line)]
        if MODULES_LINE in lines:
            for a, b, name in _events(lines[MODULES_LINE]):
                if window and not (window[0] <= a < window[1]):
                    continue
                prog = program_name(name)
                programs[prog] = programs.get(prog, 0.0) + (b - a) * 1e-9

    if window is None:
        spans = [iv for ivs in busy_raw.values() for iv in ivs]
        window = (min(a for a, _ in spans), max(b for _, b in spans)) \
            if spans else (0.0, 0.0)
    busy = {k: union(clip(v, *window)) for k, v in busy_raw.items()}

    first = next(iter(busy.values()), [])
    idle = complement(first, *window)
    names = innermost(host, [(a + b) / 2 for a, b in idle])
    gaps = [(name or "untraced", (b - a) * 1e-9)
            for name, (a, b) in zip(names, idle)]
    return Trace(window=window, busy=busy, programs=programs, gaps=gaps)
