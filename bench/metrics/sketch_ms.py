"""sketch_ms: device milliseconds per iteration under the device scope
``osn_sketch``.

Layer: Hessian.  The scope wraps the sketch apply inside the Hessian
program (``SketchFamily.gram``): ``core/sketch.py::apply_sketch``, which
on a TPU for blocks of up to 1024 rows (every cell's) runs the MXU
kernel of ``kernels/count_sketch.py`` (``mxu_count_sketch``) over the
blocks the straggler mask keeps.  Read from the union of the intervals
of the operations under the scope (``bench/span_reduce.py``).  None
where the trace carries no device scopes.
"""
from bench import span_reduce

SCOPE = "osn_sketch"


def read(ctx):
    secs = span_reduce.scope_s(ctx.trace, SCOPE)
    if secs is None or not ctx.iterations:
        return None
    return secs / ctx.iterations * 1e3
