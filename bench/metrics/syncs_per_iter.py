"""syncs_per_iter: blocking device-to-host reads per iteration, counted
as the ``osn.sync.*`` spans that start in the window.

Layer: solver step.  Each read the program makes on the solve's path
sits in one such span (``repro.obs.wall``), so this counts the reads
that one jitted iteration step would remove.  None where the trace holds
no program spans.
"""
from bench import span_reduce


def read(ctx):
    spans = getattr(ctx.trace, "spans", None)
    if not spans or not ctx.iterations:
        return None
    syncs = sum(1 for _, _, name in spans
                if name.startswith(span_reduce.SYNC_PREFIX))
    return syncs / ctx.iterations
