"""Span and scope names on the profiler's clock: the wall/device-clock layer.

The rest of ``repro.obs`` stamps the *simulated* fleet clock.  The names
here mark the program on the clock the device runs on, through JAX's own
profiler: ``jax.profiler.TraceAnnotation`` for host spans and
``jax.named_scope`` for device scopes inside a jitted program.  There is
no tracer object: a span costs one TraceMe whether or not a trace is
being recorded, and the profiler's ``.xplane.pb`` is the export.  Call
sites pass these constants unchanged, so every span of one kind carries
the same name in every trace.

Host spans (``oversketched_newton`` and the fleet it calls):

==========================  ==================================================
``osn.solve``               one ``oversketched_newton`` call
``osn.encode``              the dispatch of the product codes' parity encodes
``osn.iter``                one iteration; its start is the iteration's stamp
``osn.gradient``            step 1, the coded gradient and its matvecs
``osn.hessian``             steps 2+3, the sketch draw and the Hessian dispatch
``osn.direction``           the Cholesky/CG (and debias) dispatch
``osn.linesearch``          step 4, the search and the update ``w + step * p``
``osn.history``             the per-iteration log and the adaptive sketch
``osn.fleet``               a call into the simulated fleet (``SimClock.phase``)
``osn.sync.<site>``         one blocking device-to-host read, at its site
==========================  ==================================================

Sync sites: ``guard`` (the descent guard's ``g.p``), ``history`` (logged f,
gradient norm, step), ``decode`` (the coded decode's success flag and the
corruption checks), ``mask`` (the fleet's arrival mask read back for the
decode), ``survivors`` (the sketch's surviving-row count, which waits for
the Hessian program queued before it) and ``straggler`` (the fleet's
sampled worker times and phase keys).  A sync span nests inside its
stage's span, so counting ``osn.sync.*`` spans counts the solve's host
reads where they happen.

Device scopes (``jax.named_scope``; they appear in each operation's
framework op name, ``jit(fn)/osn_sketch/while/...``):

==================  ==========================================================
``osn_hess_sqrt``   ``objective.hess_sqrt`` inside the Hessian programs
``osn_sketch``      the sketch apply
``osn_gram``        the survivors' Gram of the sketched blocks
==================  ==========================================================
"""

SOLVE = "osn.solve"
ENCODE = "osn.encode"
ITER = "osn.iter"
GRADIENT = "osn.gradient"
HESSIAN = "osn.hessian"
DIRECTION = "osn.direction"
LINESEARCH = "osn.linesearch"
HISTORY = "osn.history"
FLEET = "osn.fleet"

SYNC_PREFIX = "osn.sync."
SYNC_GUARD = "osn.sync.guard"
SYNC_HISTORY = "osn.sync.history"
SYNC_DECODE = "osn.sync.decode"
SYNC_MASK = "osn.sync.mask"
SYNC_SURVIVORS = "osn.sync.survivors"
SYNC_STRAGGLER = "osn.sync.straggler"

HESS_SQRT = "osn_hess_sqrt"
SKETCH = "osn_sketch"
GRAM = "osn_gram"

STAGES = (GRADIENT, HESSIAN, DIRECTION, LINESEARCH, HISTORY)
SYNCS = (SYNC_GUARD, SYNC_HISTORY, SYNC_DECODE, SYNC_MASK, SYNC_SURVIVORS,
         SYNC_STRAGGLER)
SCOPES = (HESS_SQRT, SKETCH, GRAM)
