"""gram_ms: device milliseconds per iteration under the device scope
``osn_gram``.

Layer: Hessian.  The scope wraps the survivors' Gram of the sketched
blocks (``core.sketch.sketched_gram``) inside the Hessian program.  On the
fused kernel path the Gram runs inside the sketch kernel, under
``osn_sketch`` (``sketch_ms``), and this reads nothing.  None where the
trace carries no device scopes.
"""
from bench import span_reduce

SCOPE = "osn_gram"


def read(ctx):
    secs = span_reduce.scope_s(ctx.trace, SCOPE)
    if secs is None or not ctx.iterations:
        return None
    return secs / ctx.iterations * 1e3
