"""hessian_roofline: the least time of the Hessian's necessary work, as a
percentage of the device time its programs took.

Layer: Hessian.  The programs are those of ``hessian_ms``; the work is the
cell's objective's ``work(config)["hessian"]`` (for logistic,
``bench.work.hessian``: A read once, the count-sketch scatter into every
block, the survivors' Gram, hess_sqrt's own arithmetic, the d x d
result), and the least time the larger of ops over the chip's peak
FLOP/s and bytes over its peak bytes/s (``bench/peaks.json``).  None
where none of the programs ran.
"""
from bench import work

PROGRAMS = ("jit_fn", "jit__randint", "jit__rademacher")


def read(ctx):
    secs = ctx.trace.device_s(PROGRAMS)
    if not secs or not ctx.iterations:
        return None
    least, _ = work.least_time(ctx.objective.work(ctx.config)["hessian"],
                               ctx.peak)
    return least / (secs / ctx.iterations) * 100.0
