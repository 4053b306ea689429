"""hessian_ms: device milliseconds per iteration of the Hessian's programs.

Layer: Hessian (``objectives.hess_sqrt``, the count sketch of
``core/sketch.py::apply_sketch``, on a TPU the MXU kernel of
``kernels/count_sketch.py``, and the survivors' Gram).  The programs, as
the trace names them: the jitted closure of ``newton._jitted_sketched_hessian``
(``jit_fn``) and the count-sketch draw (``jit__randint``,
``jit__rademacher``).  None where none of them ran.
"""

PROGRAMS = ("jit_fn", "jit__randint", "jit__rademacher")


def read(ctx):
    secs = ctx.trace.device_s(PROGRAMS)
    if secs is None or not ctx.iterations:
        return None
    return secs / ctx.iterations * 1e3
