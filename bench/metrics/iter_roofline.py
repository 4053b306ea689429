"""iter_roofline: the least time of one iteration's necessary work, as a
percentage of the traced window's seconds per iteration.

Layer: solver step (``core/newton.py``).  The work is
``bench.work.iteration``: the Hessian's, two uncoded X matvecs for the
gradient, one X read for the line search and the Cholesky direction.  It
is read against wall time, so it survives a change that fuses or renames
the programs.  None where the window ran no iteration.
"""
from bench import work


def read(ctx):
    if not ctx.iterations or ctx.trace.window_s <= 0:
        return None
    cfg = ctx.config
    least, _ = work.least_time(
        work.iteration(cfg["n"], cfg["d"],
                       *work.sketch_blocks(cfg["newton"]["sketch"])),
        ctx.peak)
    return least / (ctx.trace.window_s / ctx.iterations) * 100.0
