"""sketch_ms: device milliseconds per iteration under the device scope
``osn_sketch``.

Layer: Hessian.  The scope wraps the sketch apply inside the Hessian
program (``SketchFamily.gram``): at the cells' default path the count
sketch's ``lax.map`` of segment sums over the blocks.  On the fused
kernel path the one sketch->Gram kernel falls under this scope, so
``gram_ms`` then reads nothing.  Read from the union of the intervals of
the operations under the scope (``bench/span_reduce.py``).  None where
the trace carries no device scopes.
"""
from bench import span_reduce

SCOPE = "osn_sketch"


def read(ctx):
    secs = span_reduce.scope_s(ctx.trace, SCOPE)
    if secs is None or not ctx.iterations:
        return None
    return secs / ctx.iterations * 1e3
