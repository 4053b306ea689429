"""gradient_roofline: the least time of the gradient's necessary work, as a
percentage of the device time its coded programs took.

Layer: coded gradient (``core/coded.py``).  The programs are those of
``gradient_ms``: the coded matvecs (``jit_coded_matvec``) and the parity
encodes each solve makes once (``jit_encode_2d``), per iteration.  The
work is the cell's objective's ``work(config)["gradient"]`` (for
logistic, ``bench.work.gradient``: X w and X^T r uncoded, two reads of
X), so a code that re-reads X, or writes more than its parity, reads a
lower share.  None where none of the programs ran.
"""
from bench import work

PROGRAMS = ("jit_coded_matvec", "jit_encode_2d")


def read(ctx):
    secs = ctx.trace.device_s(PROGRAMS)
    if not secs or not ctx.iterations:
        return None
    least, _ = work.least_time(ctx.objective.work(ctx.config)["gradient"],
                               ctx.peak)
    return least / (secs / ctx.iterations) * 100.0
