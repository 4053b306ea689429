"""gradient_ms: device milliseconds per iteration of the coded gradient.

Layer: coded gradient (``core/coded.py``).  The programs, as the trace
names them: the straggler-resilient matvecs (``jit_coded_matvec``) and the
product-code encodes of X and X^T that each solve makes once
(``jit_encode_2d``), spread over the iterations.  None where none ran.
"""

PROGRAMS = ("jit_coded_matvec", "jit_encode_2d")


def read(ctx):
    secs = ctx.trace.device_s(PROGRAMS)
    if secs is None or not ctx.iterations:
        return None
    return secs / ctx.iterations * 1e3
