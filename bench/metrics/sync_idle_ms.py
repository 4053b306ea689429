"""sync_idle_ms: milliseconds per iteration in which the device is idle
while the innermost program span open is a blocking device-to-host read
(``osn.sync.<site>``).

Layer: solver step (``core/newton.py`` and the reads it makes through
the coded decode and the fleet).  The idle time of a read is the copy
back and the host's wake-up after the device finished.  None where the
trace holds no program spans.
"""
from bench import span_reduce


def read(ctx):
    idle = span_reduce.idle_by_span(ctx.trace)
    if idle is None or not ctx.iterations:
        return None
    secs = sum(v for k, v in idle.items()
               if k is not None and k.startswith(span_reduce.SYNC_PREFIX))
    return secs / ctx.iterations * 1e3
