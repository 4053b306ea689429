"""fleet_idle_ms: milliseconds per iteration in which the device is idle
while the innermost program span open is ``osn.fleet``.

Layer: straggler clock (``core/straggler.py``, ``runtime/``,
``scheduler/``): the simulated fleet's phases, policies and peeling
checks, run on the host.  Each idle nanosecond of the window goes to the
innermost ``osn.*`` span over it (``bench/span_reduce.py``), so a
sampling read inside the fleet counts as a sync, not here.  None where
the trace holds no program spans.
"""
from bench import span_reduce

SPAN = "osn.fleet"


def read(ctx):
    idle = span_reduce.idle_by_span(ctx.trace)
    if idle is None or not ctx.iterations:
        return None
    return idle.get(SPAN, 0.0) / ctx.iterations * 1e3
