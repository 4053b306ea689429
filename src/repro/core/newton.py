"""OverSketched Newton (paper Alg. 3 / Alg. 4): the master loop.

Master-side Python loop (the paper's T is in the tens) dispatching jitted
distributed phases:

  1. gradient  — exact, straggler-resilient via the 2-D product code (Alg. 1)
  2. Hessian   — approximate, straggler-resilient via a block-structured
     sketch (Alg. 2).  The family is pluggable (``NewtonConfig.sketch_family``
     resolves through ``repro.sketching``): the paper's OverSketch plus SRHT,
     SJLT, Gaussian and Nystrom row-sampling, all sharing the k-of-n
     survivor semantics because every family is per-block unbiased.
  3. direction — Cholesky/CG (strongly convex) or pinv/MINRES (weakly
     convex), optionally Marchenko-Pastur debiased (``debias=True``,
     Romanov-Zhang-Pilanci 2024); ``sketch_mode="distributed-avg"`` instead
     averages per-worker debiased directions (Bartan-Pilanci 2020).
  4. step size — distributed Armijo (Eq. 5) / grad-norm (Eq. 6) line search

Each distributed phase is scored by the straggler simulation clock
(`core.straggler`), which is how the paper's wall-clock comparisons are
reproduced on a single-device container.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

import numpy as np

from repro.core import coded, linesearch, sketch, solvers, straggler
from repro.core.objectives import Dataset
from repro import obs, scheduler, sketching
from repro.obs import wall
from repro.runtime.faults import PhaseExhaustedError


def _telemetry(clock) -> "obs.Telemetry":
    """The clock's attached telemetry, or the zero-overhead no-op."""
    return clock.telemetry if clock is not None else obs.NULL


def _decodable(erased_grid: "np.ndarray") -> bool:
    """Host-side peeling feasibility check on the (g+1)x(g+1) erasure grid.
    Mirrors coded.peel_decode: a line with exactly one missing cell can be
    recovered; iterate to fixpoint."""
    known = ~erased_grid.copy()
    g1 = known.shape[0]
    for _ in range(2 * g1):
        if known.all():
            return True
        progress = False
        for axis in (0, 1):
            missing = (~known).sum(axis=axis)
            for i in np.where(missing == 1)[0]:
                if axis == 0:
                    j = int(np.argmin(known[:, i]))
                    known[j, i] = True
                else:
                    j = int(np.argmin(known[i, :]))
                    known[i, j] = True
                progress = True
        if not progress:
            return False
    return bool(known.all())


@dataclasses.dataclass(frozen=True)
class NewtonConfig:
    iters: int = 20
    sketch: sketch.OverSketchConfig = dataclasses.field(
        default_factory=lambda: sketch.OverSketchConfig(
            sketch_dim=2048, block_size=256, straggler_tolerance=0.25))
    beta: float = 0.1
    candidates: tuple = linesearch.DEFAULT_CANDIDATES
    unit_step: bool = False
    solver: str = "auto"            # auto | chol | cg | pinv | minres
    cg_iters: int = 64
    gradient_policy: str = "coded"  # coded | wait_all | ignore | speculative
    hessian_policy: str = "oversketch"   # oversketch | exact | exact_speculative
    # Sketch family registry key: oversketch | srht | sjlt | gaussian | nystrom
    sketch_family: str = "oversketch"
    # Marchenko-Pastur inverse-bias correction of the sketched direction.
    debias: bool = False
    # blocks: one sketch, blocks pooled into a single Gram (paper Alg. 2).
    # distributed-avg: each surviving block-worker solves its own d x d
    # system and the master averages (debiased) directions — needs
    # block_size > d to be well-posed.
    sketch_mode: str = "blocks"
    # distributed-avg per-block d x d solver: chol (dense Cholesky) | cg
    # (matvec-only conjugate gradient, cg_iters steps — for d beyond
    # master-factorization scale).
    distavg_solver: str = "chol"
    coded_block_rows: int = 256
    # Master-side pipeline overlap (Sec. 4.1): the one-time product-code
    # encodes launch together and hide behind earlier compute phases.
    overlap_encode: bool = True
    # Phase dispatch: "dag" emits each iteration as a phase DAG through
    # repro.scheduler — the Hessian-sketch fan-out launches concurrently
    # with the gradient round (they are independent within an iteration;
    # Sec. 4.1 / Bartan-Pilanci's concurrent sketch dispatch) — while
    # "sequential" keeps the historical one-phase-at-a-time clock.  The
    # iterates are identical either way (same phase keys => same masks);
    # only the simulated timeline differs.
    schedule: str = "dag"
    # Per-phase Lambda sizing: declare each phase's working set so it bills
    # at its own memory_gb (scheduler.sizing) instead of the paper's
    # fleet-wide 3 GB.  Off by default to keep historical dollar totals.
    phase_memory: bool = False
    seed: int = 0
    track_test_error: bool = False
    # Paper Thm 3.2 remark: "the sketch dimension can be increased to reduce
    # eps ... and improve the convergence rate in practice" — when iteration
    # progress stalls (the eps-linear tail), double the sketch dimension.
    adaptive_sketch: bool = False
    adaptive_stall_ratio: float = 0.25   # f-decrease ratio that counts as a stall
    adaptive_max_growth: int = 4         # cap: sketch_dim <= 4x initial
    # What drives adaptive growth: "stall" = the f-decrease heuristic above;
    # "mp" = the measured Marchenko-Pastur debias factor 1 - d/m_eff of the
    # SURVIVING sketch rows — grow whenever it falls below
    # adaptive_mp_target, i.e. the sketch is too biased to trust, whether
    # or not f has stalled yet (ROADMAP: the MP factor says *when*).
    adaptive_metric: str = "stall"
    adaptive_mp_target: float = 0.75
    # Graceful degradation under a fault plan (repro.runtime.faults) whose
    # retry budget genuinely exhausts (FleetConfig.fail_open=False).
    # "degrade": accept the surviving sketch blocks when at least
    # survivor_floor of num_blocks landed; below the floor, re-dispatch
    # the sketch round once on fresh capacity; if that exhausts too, take
    # a plain gradient step for the iteration.  "raise": propagate
    # PhaseExhaustedError to the caller (strict mode).
    fault_fallback: str = "degrade"
    survivor_floor: float = 0.5
    # Parity-check detection of corrupted coded-matvec products (fault
    # plan CorruptionSpec): detected cells are demoted to erasures and
    # flow through the existing peeling decoder; off = trust arrived
    # bytes (the silent-corruption negative control).
    corruption_detection: bool = True


@dataclasses.dataclass
class NewtonResult:
    w: jax.Array
    history: Dict[str, List[float]]


def _phase_mem(enabled: bool, working_set_bytes: float) -> Optional[float]:
    """Declared Lambda size for a phase, or None for the fleet-wide 3 GB."""
    return scheduler.lambda_memory_gb(working_set_bytes) if enabled else None


def _ws_gb(working_set_bytes: float) -> float:
    """True per-worker working set in GB, always declared to the engine
    (``working_set_gb``) — unlike the billed ``memory_gb``, which stays
    opt-in via ``phase_memory``.  Inert unless a fault plan with an
    ``OomSpec`` is attached: an undersized Lambda then OOM-kills instead
    of merely billing cheap."""
    return float(working_set_bytes) / 2.0 ** 30


class CodedMatvecEngine:
    """Holds the one-time 2-D product-code encodings of X and X^T (the paper
    amortizes encoding across iterations, Sec. 4.1) and serves straggler-
    resilient matvecs.  A code's systematic blocks are X itself, so the
    engine keeps X by reference and only the two parity stacks
    (``coded.encode_2d``), both summed from X with no transpose of it.

    Each operand's encode is billed as a real fleet phase on first use.
    With ``overlap_encode`` (the default, the paper's pipeline) both
    encodes are kicked off when the engine comes up and run concurrently
    with any compute dispatched since — the X^T encode hides behind the
    X matvec via ``run_phase(not_before=...)``; ``overlap_encode=False``
    serializes them (the makespan upper bound)."""

    def __init__(self, data: Dataset, block_rows: int,
                 model: Optional[straggler.StragglerModel],
                 overlap_encode: bool = True, phase_memory: bool = False,
                 corruption_detection: bool = True):
        self.model = model
        self.overlap_encode = overlap_encode
        self.phase_memory = phase_memory
        self.corruption_detection = corruption_detection
        self._encode_pending = {"X", "XT"}
        self._encode_t0: Optional[float] = None
        n, d = data.x.shape
        br_n = max(1, min(block_rows, n))
        br_d = max(1, min(block_rows, d))
        self.code_x = coded.make_code(n, br_n)      # for X @ v    (n rows)
        self.code_xt = coded.make_code(d, br_d)     # for X^T @ v  (d rows)
        self.x = data.x
        with TraceAnnotation(wall.ENCODE):
            self.parity = {
                "X": coded.encode_2d(data.x, self.code_x),
                "XT": coded.encode_2d(data.x, self.code_xt, transpose=True)}
        self.out_rows = {"X": n, "XT": d}
        self.cols = {"X": d, "XT": n}    # the width of one coded block
        self.fallbacks = 0
        # Degraded-mode latch: flips on the first *observed* corruption
        # (a parity flag or a codeword-verification reject).  From then
        # on coded phases wait for FULL arrival instead of the first
        # peelable subset — with every cell present, row x column parity
        # intersection localizes corruption exactly and the verification
        # backstop catches sign-cancellation pathologies, so every later
        # matvec is either exact or a billed relaunch, never silently
        # wrong.  (Racing ahead of stragglers is what lets corruption be
        # absorbed into peel-recovered cells undetectably.)
        self.paranoid = False

    def _mv(self, tag: str, v: jax.Array, erased: Optional[jax.Array]):
        # X and the parity go to the jitted coded_matvec as arguments: a
        # jitted closure over self would bake them into the compiled
        # program as constants.
        return coded.coded_matvec(self.x, self.parity[tag], v,
                                  self.code_for(tag), erased, tag == "XT")

    def code_for(self, tag: str) -> coded.ProductCode:
        return self.code_x if tag == "X" else self.code_xt

    def matvec(self, tag: str, v: jax.Array, clock: straggler.SimClock,
               key: jax.Array, policy: str,
               dag: Optional[scheduler.DagRun] = None,
               name: Optional[str] = None,
               after: Tuple[str, ...] = ()) -> jax.Array:
        """One straggler-resilient coded matvec.

        With ``dag`` the compute phase (and, on decode failure, the retry
        phase) is dispatched as a named DAG node with deps ``after`` —
        the matvec chain inside one gradient stays serialized through
        those edges while independent phases (the Hessian sketch) overlap
        it.  The one-time encode phases keep their own clock-level
        ``not_before`` overlap machinery either way."""
        code = self.code_for(tag)
        w = code.num_workers
        cols = self.cols[tag]
        flops = 2.0 * code.block_rows * cols   # one block matvec
        mem_bytes = scheduler.matvec_worker_bytes(code.block_rows, cols)
        mem = _phase_mem(self.phase_memory, mem_bytes)
        ws = _ws_gb(mem_bytes)
        enc_floor = {"t": None}   # set if this call bills an encode phase

        def phase(k, policy, *, kk=None, decodable=None, comm_units=1.0):
            if dag is not None:
                # The compute phase consumes this operand's encode: when
                # the encode was billed in this call (on the direct clock,
                # outside the DAG), floor the launch at its finish so the
                # matvec cannot be simulated before its input exists.
                res = dag.dispatch(scheduler.PhaseSpec(
                    name=name or tag, workers=w, policy=policy,
                    k=kk, flops_per_worker=flops, comm_units=comm_units,
                    memory_gb=mem, working_set_gb=ws, decodable=decodable,
                    deps=after), key=k, min_start=enc_floor["t"])
                return res.elapsed, res.mask
            return clock.phase(k, w, policy=policy, k=kk,
                               flops_per_worker=flops,
                               comm_units=comm_units, decodable=decodable,
                               memory_gb=mem, working_set_gb=ws,
                               phase_name=name or tag)

        def phase_safe(k, policy, **kw):
            # A fault plan with a real retry budget (fail_open=False) can
            # exhaust mid-phase: the attempts are already billed and the
            # clock advanced; degrade to whatever arrived — the coded
            # path treats the dead workers as erasures.
            try:
                return phase(k, policy, **kw)
            except PhaseExhaustedError as e:
                _telemetry(clock).metrics.counter(
                    "coded.exhausted_phases").inc()
                return e.elapsed, jnp.asarray(e.mask)
        if self.model is not None and tag in self._encode_pending:
            # One-time product-code encode of this operand, billed on
            # first use.  Both encodes launch when the engine comes up
            # (first matvec's clock time); the overlapped variant lets
            # the later operand's encode hide behind earlier compute
            # (Sec. 4.1), the sequential one pays it in full.
            self._encode_pending.discard(tag)
            if self._encode_t0 is None:
                self._encode_t0 = clock.time
            enc_flops = float(code.block_rows * cols)  # parity adds
            nb = self._encode_t0 if self.overlap_encode else None
            if nb is not None and nb == clock.time:
                # Launching "now" overlaps nothing: take the sequential
                # path so the clock stays bit-identical to it (the
                # engine's advance=elapsed shortcut, no ULP re-rounding).
                nb = None
            try:
                clock.phase(jax.random.fold_in(key, 555), w,
                            policy="wait_all", flops_per_worker=enc_flops,
                            comm_units=1.0, not_before=nb, memory_gb=mem,
                            working_set_gb=ws, phase_name=f"encode:{tag}")
            except PhaseExhaustedError:
                # Encode attempts billed, budget gone: the master re-runs
                # the cheap parity sums locally; the operand is still
                # usable, so only the wasted round is lost.
                _telemetry(clock).metrics.counter(
                    "coded.exhausted_phases").inc()
            # After this call the clock sits at (at least) the encode's
            # finish — the earliest instant this operand can be consumed.
            enc_floor["t"] = clock.time
        erased = None
        corrupt = None
        arrived = None
        if self.model is not None and policy == "coded":
            # Faithful master: results stream in; decode starts as soon as
            # the arrived set is peelable (paper Alg. 1 step 8).  The
            # streaming wait runs through the fleet engine's coded_decode
            # policy with the peeling-feasibility predicate.
            g1 = code.grid + 1
            if self.paranoid and self.corruption_detection:
                _, mask = phase_safe(key, "wait_all")
            else:
                k_min = max(1, w - (2 * code.grid + 1))
                _, mask = phase_safe(key, "coded_decode", kk=k_min,
                                     decodable=lambda m: _decodable(
                                         ~m.reshape(g1, g1)))
            with TraceAnnotation(wall.SYNC_MASK):
                arrived = np.asarray(mask)
            erased = jnp.asarray(~arrived).reshape(g1, g1)
            lc = clock.last_corruption
            if lc is not None:
                # The fault plane flagged some arrived results as
                # corrupted (bit flips / stale S3 reads).  Report the
                # per-phase block error rate even when zero — the health
                # monitors need the clean baseline to detect the shift.
                corrupt = np.asarray(lc) & arrived
                tel = _telemetry(clock)
                if tel.enabled:
                    tel.metrics.gauge("coded.block_error_rate").set(
                        float(corrupt.sum()) / float(w))
        elif self.model is not None and policy == "wait_all":
            phase_safe(key, "wait_all")
        elif self.model is not None and policy == "speculative":
            phase_safe(key, "speculative")
        elif self.model is not None and policy == "ignore":
            # mini-batch style: drop stragglers' contributions entirely —
            # handled by the caller using an uncoded gradient; we still pay
            # the k-of-n time.
            phase_safe(key, "k_of_n", kk=max(1, int(0.95 * w)))
        if corrupt is not None and corrupt.any():
            # Reconstruct what the master actually received: clean block
            # products plus seeded garbage at the corrupted cells.
            g1 = code.grid + 1
            prods = coded.block_products(self.x, self.parity[tag], v, code,
                                         tag == "XT")
            noise = (jnp.sqrt(jnp.mean(prods ** 2)) + 1e-30) * \
                jax.random.normal(straggler.on_device_of(
                    jax.random.fold_in(key, 777), prods), prods.shape)
            cgrid = jnp.asarray(corrupt.reshape(g1, g1))
            prods = jnp.where(cgrid[..., None], prods + noise, prods)
            known = jnp.asarray(arrived.reshape(g1, g1))
            tel = _telemetry(clock)
            if tel.enabled:
                tel.metrics.counter("coded.corruption_injected").inc(
                    int(corrupt.sum()))
            if self.corruption_detection:
                # Parity checks demote localizable corruption to erasures;
                # the post-decode codeword verification rejects anything
                # that slipped through (ok=False -> billed full relaunch
                # below) instead of returning a silently wrong product.
                y, ok, n_flagged = coded.verified_decode(
                    prods, known, code, self.out_rows[tag])
                if tel.enabled and n_flagged:
                    tel.metrics.counter("coded.corruption_detected").inc(
                        n_flagged)
                if (n_flagged or not bool(ok)) and not self.paranoid:
                    self.paranoid = True
                    if tel.enabled:
                        tel.metrics.counter("coded.paranoid_mode").inc()
                if y is None:
                    y = jnp.zeros((self.out_rows[tag],), prods.dtype)
            else:
                y, ok = coded.decode_matvec(prods, known, code,
                                            self.out_rows[tag])
        else:
            y, ok = self._mv(tag, v, erased)
        if erased is not None:
            with TraceAnnotation(wall.SYNC_DECODE):
                ok = bool(ok)
        if erased is not None and not ok:
            # Decode failure (erasure pattern beyond the code): the paper's
            # master re-launches stragglers; charge a full re-execution round.
            self.fallbacks += 1
            y, _ = self._mv(tag, v, None)
            if self.model is not None:
                _telemetry(clock).metrics.counter(
                    "coded.decode_fallbacks").inc()
                kf = jax.random.fold_in(key, 1)
                try:
                    # An exhausted compute phase never registered with the
                    # DAG, so only declare the edge when the dep exists;
                    # otherwise the barrier at the current clock stands in.
                    if dag is not None and (name or tag) in dag.results:
                        dag.dispatch(scheduler.PhaseSpec(
                            name=(name or tag) + "/retry", workers=w,
                            policy="wait_all", comm_units=1.0,
                            memory_gb=mem, working_set_gb=ws,
                            deps=((name or tag),)), key=kf)
                    else:
                        clock.phase(kf, w, policy="wait_all",
                                    comm_units=1.0, memory_gb=mem,
                                    working_set_gb=ws,
                                    phase_name=(name or tag) + "/retry")
                except PhaseExhaustedError:
                    # The relaunch round itself exhausted: its attempts
                    # are billed, the master already recomputed y above.
                    _telemetry(clock).metrics.counter(
                        "coded.exhausted_phases").inc()
        return y


def _solve_direction(objective, h_hat: jax.Array, g: jax.Array,
                     cfg: NewtonConfig) -> jax.Array:
    solver = cfg.solver
    if solver == "auto":
        solver = "chol" if objective.strongly_convex else "pinv"
    if solver == "chol":
        return -solvers.psd_solve(h_hat, g)
    if solver == "cg":
        return -solvers.conjugate_gradient(lambda v: h_hat @ v, g,
                                           jnp.zeros_like(g), cfg.cg_iters)
    if solver == "pinv":
        return -solvers.psd_pinv_solve(h_hat, g)
    if solver == "minres":
        return -solvers.minres(lambda v: h_hat @ v, g, cfg.cg_iters)
    raise ValueError(solver)


@functools.lru_cache(maxsize=64)
def _jitted_sketched_hessian(objective, family: "sketching.SketchFamily"):
    """Hashable frozen-dataclass objectives AND families => cacheable
    jitted closures.  ``state`` is the family's sketch realization pytree.

    The Hessian is ``SketchFamily.gram`` of the objective's square root:
    the family's apply, then the masked Gram.  The implementation the
    apply lowers to is ``SketchFamily.apply_path`` of the data's platform
    (``mxu_count_sketch`` or ``segment_sum`` for the OverSketch family,
    ``unfused`` for the others); ``_hessian_phase`` counts it as the
    telemetry metric ``kernel.path.<path>`` at this function's call site,
    since inside the jitted closure there is no Python left to log from.
    On ``mxu_count_sketch`` the same site counts the dropped blocks the
    kernel skips (``kernel.count_sketch.blocks_skipped``)."""
    def fn(w, data, state, survivors):
        with jax.named_scope(wall.HESS_SQRT):
            a = objective.hess_sqrt(w, data)
        d = a.shape[1]
        reg = objective.hess_reg * jnp.eye(d, dtype=a.dtype)
        return family.gram(state, a, survivors) + reg
    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def _jitted_distavg_direction(objective, family: "sketching.SketchFamily",
                              debias: bool,
                              solver: str = "chol", cg_iters: int = 64):
    """distributed-avg mode (Bartan-Pilanci 2020): every surviving block-
    worker solves its own per-block sketched system, the master averages
    the (Marchenko-Pastur debiased) directions.  Per-worker sketch rows =
    block_size, so the debias factor is 1 - d/b.  Also returns the masked
    average of H_k g for the weakly-convex line search.  ``solver`` picks
    the per-block d x d solve: dense Cholesky, or matvec-only CG for d
    beyond master-factorization scale."""
    b = family.cfg.block_size

    if solver == "cg":
        def block_solve(hk, g):
            return solvers.conjugate_gradient(
                lambda v: hk @ v, g, jnp.zeros_like(g), cg_iters)
    elif solver == "chol":
        block_solve = solvers.psd_solve
    else:
        raise ValueError(f"unknown distavg_solver {solver!r}")

    def fn(w, data, g, state, survivors):
        with jax.named_scope(wall.HESS_SQRT):
            a = objective.hess_sqrt(w, data)
        d = a.shape[1]
        with jax.named_scope(wall.SKETCH):
            a_t = family.apply(state, a)  # (K,b,d)
        eye = jnp.eye(d, dtype=a_t.dtype)
        with jax.named_scope(wall.GRAM):
            grams = jnp.einsum("kbd,kbe->kde", a_t, a_t) \
                + objective.hess_reg * eye
        p_k = -jax.vmap(lambda hk: block_solve(hk, g))(grams)
        if debias:
            p_k = sketching.debias_direction(p_k, d, b)
        m = survivors.astype(a_t.dtype)
        n_avail = jnp.maximum(m.sum(), 1.0)
        p = jnp.einsum("k,kd->d", m, p_k) / n_avail
        hg = jnp.einsum("k,kde,e->d", m, grams, g) / n_avail
        return p, hg
    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def _jitted_exact_hessian(objective):
    def fn(w, data):
        a = objective.hess_sqrt(w, data)
        d = a.shape[1]
        return a.T @ a + objective.hess_reg * jnp.eye(d, dtype=a.dtype)
    return jax.jit(fn)


def _platform(x) -> str:
    """The platform a program on ``x`` runs on: its device's, or the
    default backend's for a host array."""
    if isinstance(x, jax.Array):
        return next(iter(x.devices())).platform
    return jax.default_backend()


def _hess_rows(objective, data: Dataset, w: jax.Array) -> Tuple[int, int]:
    shape = jax.eval_shape(objective.hess_sqrt, w, data).shape
    return shape[0], shape[1]


def _hessian_phase(objective, data: Dataset, w: jax.Array, cfg: NewtonConfig,
                   key: jax.Array, clock: Optional[straggler.SimClock],
                   dag: Optional[scheduler.DagRun] = None,
                   tag: str = "hessian"
                   ) -> Tuple[jax.Array, Optional[float]]:
    """Returns (H_hat, m_eff): the (approximate or exact) Hessian including
    the hess_reg * I term, and the surviving sketch-row count m_eff that the
    Marchenko-Pastur debias factor needs (None on the exact path).
    Under a fault plan with ``fail_open=False`` and
    ``cfg.fault_fallback="degrade"``, ``(None, None)`` means the sketch
    round (and its one re-dispatch) lost too many blocks to trust — the
    caller takes a plain gradient step for the iteration.

    Worker accounting follows the paper: a sketched Hessian invokes
    (N+e)*(d/b)^2 workers (Alg. 2 step 3) vs ceil(n/b)*(d/b)^2 for the exact
    product — same per-worker block work, vastly different worker counts and
    master I/O when n >> m.  Per-worker flops and I/O come from the family's
    cost hooks, so e.g. dense Gaussian pays its O(n*b*d) apply honestly.

    With ``dag`` the phase is dispatched as a dependency-free DAG node — it
    launches at the iteration start, concurrent with the gradient round
    (the sketch S^T A depends on w only, not on g).  The phase key is the
    same either way, so the survivor mask (hence the iterate) is identical
    under both schedules."""
    n_rows, d = _hess_rows(objective, data, w)
    b = max(cfg.sketch.block_size, 1)
    d_blocks = max(1, -(-d // b))

    def run(workers, policy, k=None, flops=0.0, comm=0.0, mem=None,
            ws=None, name=None, rkey=None, min_start=None):
        name = tag if name is None else name
        rkey = key if rkey is None else rkey
        if dag is not None:
            return dag.dispatch(scheduler.PhaseSpec(
                name=name, workers=workers, policy=policy, k=k,
                flops_per_worker=flops, comm_units=comm,
                memory_gb=mem, working_set_gb=ws), key=rkey,
                min_start=min_start).mask
        _, mask = clock.phase(rkey, workers, policy=policy, k=k,
                              flops_per_worker=flops, comm_units=comm,
                              memory_gb=mem, working_set_gb=ws,
                              phase_name=name)
        return mask

    if cfg.hessian_policy == "oversketch":
        scfg = cfg.sketch
        fam = sketching.get(cfg.sketch_family, scfg)
        survivors = jnp.ones((scfg.total_blocks,), bool)
        if clock is not None:
            # Alg. 2 termination is per OUTPUT TILE: each of the (d/b)^2
            # tiles waits for any N of its N+e sketch-block workers.  The
            # tile groups run in parallel (phase time ~ one k-of-n round);
            # the master I/O scales with the full worker count.
            total_workers = scfg.total_blocks * d_blocks * d_blocks
            mem_bytes = scheduler.sketch_worker_bytes(scfg.block_size,
                                                      min(d, b))
            kw = dict(k=scfg.num_blocks, flops=fam.block_flops(n_rows, d),
                      comm=fam.comm_units(d) * total_workers,
                      mem=_phase_mem(cfg.phase_memory, mem_bytes),
                      ws=_ws_gb(mem_bytes))
            try:
                survivors = run(scfg.total_blocks, "k_of_n", **kw)
            except PhaseExhaustedError as e:
                if cfg.fault_fallback == "raise":
                    raise
                # The sketch round exhausted its retry budget (attempts
                # billed, clock advanced).  Every sketch block is
                # per-block unbiased, so any survivor subset is still an
                # unbiased (thinner) sketch: accept the survivors when at
                # least survivor_floor of num_blocks landed — m_eff
                # shrinks and the MP debias absorbs the extra bias.
                # Below the floor, re-dispatch the round once on fresh
                # capacity; if that exhausts too, signal the caller to
                # take a plain gradient step this iteration.
                _telemetry(clock).metrics.counter(
                    "newton.fault_fallbacks").inc()
                floor = max(1, math.ceil(
                    cfg.survivor_floor * scfg.num_blocks))
                surv = np.asarray(e.mask)
                if int(surv.sum()) >= floor:
                    survivors = jnp.asarray(surv)
                else:
                    try:
                        survivors = run(
                            scfg.total_blocks, "k_of_n",
                            name=tag + "/retry",
                            rkey=jax.random.fold_in(key, 13),
                            min_start=float(clock.time), **kw)
                    except PhaseExhaustedError as e2:
                        surv2 = np.asarray(e2.mask)
                        if int(surv2.sum()) < floor:
                            return None, None
                        survivors = jnp.asarray(surv2)
        state = fam.sample(
            straggler.on_device_of(jax.random.fold_in(key, 7), data.x),
            n_rows)
        tel = _telemetry(clock)
        if tel.enabled:
            # Audit trail for the sketch's routing: the apply that the
            # platform of the data selects, not one assumed from the config.
            path = fam.apply_path(_platform(data.x))
            tel.metrics.counter(f"kernel.path.{path}").inc()
        fn = _jitted_sketched_hessian(objective, fam)
        h_hat = fn(w, data, state, survivors)
        # Queued behind the Hessian program, so this read waits for it.
        n_surv = jnp.sum(survivors)
        with TraceAnnotation(wall.SYNC_SURVIVORS):
            n_surv = float(n_surv)
        m_eff = n_surv * scfg.block_size
        if tel.enabled:
            if path == "mxu_count_sketch":
                # The MXU kernel does no matmul for a dropped block.
                tel.metrics.counter("kernel.count_sketch.blocks_skipped"
                                    ).inc(scfg.total_blocks - n_surv)
            tel.metrics.gauge("sketch.m_eff").set(m_eff)
            tel.metrics.gauge("sketch.mp_debias").set(
                max(0.0, 1.0 - d / m_eff) if m_eff > 0 else 0.0)
            # Survivor count per sketch round: the straggler-aware
            # provisioning statistic the launch planner reads back out of
            # the cross-run store (obs.store run records keep the full
            # per-round series).
            tel.metrics.histogram("sketch.survivors").observe(n_surv)
        return h_hat, m_eff
    # exact Hessian (paper's "exact Newton" baseline)
    block_flops = 2.0 * b * min(d, b) ** 2    # one (b x d_tile) gram block
    if clock is not None:
        workers = max(1, -(-n_rows // b)) * d_blocks * d_blocks
        policy = ("speculative" if cfg.hessian_policy == "exact_speculative"
                  else "wait_all")
        mem_bytes = scheduler.sketch_worker_bytes(b, min(d, b))
        try:
            run(workers, policy, flops=block_flops, comm=0.05 * workers,
                mem=_phase_mem(cfg.phase_memory, mem_bytes),
                ws=_ws_gb(mem_bytes))
        except PhaseExhaustedError:
            if cfg.fault_fallback == "raise":
                raise
            # Attempts billed; the exact product is deterministic, so the
            # master's local recompute stands in for the lost round.
            _telemetry(clock).metrics.counter(
                "newton.fault_fallbacks").inc()
    return _jitted_exact_hessian(objective)(w, data), None


def _distavg_direction_phase(objective, data: Dataset, w: jax.Array,
                             g: jax.Array, cfg: NewtonConfig, key: jax.Array,
                             clock: Optional[straggler.SimClock],
                             dag: Optional[scheduler.DagRun] = None,
                             grad_dep: Optional[str] = None,
                             tag: str = "distavg"
                             ) -> Tuple[jax.Array, jax.Array]:
    """sketch_mode="distributed-avg": one worker per sketch block, each
    paying its apply + d x d Gram + local Cholesky solve; the master only
    ships d-vectors back (comm ~ d per worker, not a d x d Gram tile).
    Returns (direction, averaged H_k g for the weakly-convex search).

    With ``dag`` the round splits at its true data dependency, the way
    Bartan-Pilanci's analysis assumes it is dispatched: the SKETCH phase
    (apply + per-block Gram, a function of w only) launches concurrently
    with the gradient round, and the SOLVE phase (needs g shipped to the
    survivors) runs after both.  The survivor mask comes from the sketch
    phase under the same key as the sequential combined phase; under the
    default all-off fleet lifecycle the duration ORDER is scale-invariant
    in the per-worker flop count, so the mask — hence the direction — is
    schedule-invariant.  With cold starts or failures enabled the split
    phase's smaller flop count can reorder arrivals (additive delays vs
    multiplicative work), so masks may differ between schedules there —
    honest modelling of the split round, not a bug."""
    n_rows, d = _hess_rows(objective, data, w)
    scfg = cfg.sketch
    fam = sketching.get(cfg.sketch_family, scfg)
    survivors = jnp.ones((scfg.total_blocks,), bool)
    if clock is not None:
        # No coded-matmul stage to amortize into here, so a family that
        # reports apply_flops=0 (oversketch) still pays one streaming pass
        # over A on each worker.
        apply_flops = fam.apply_flops(n_rows, d) or 2.0 * n_rows * d
        gram_flops = 2.0 * scfg.block_size * d * d
        solve_flops = (d ** 3 / 3.0 if cfg.distavg_solver == "chol"
                       else 2.0 * cfg.cg_iters * d * d)   # cg matvecs
        mem_bytes = scheduler.distavg_worker_bytes(scfg.block_size, d)
        mem = _phase_mem(cfg.phase_memory, mem_bytes)
        ws = _ws_gb(mem_bytes)
        try:
            if dag is not None:
                sk = dag.dispatch(scheduler.PhaseSpec(
                    name=f"{tag}-sketch", workers=scfg.total_blocks,
                    policy="k_of_n", k=scfg.num_blocks,
                    flops_per_worker=apply_flops + gram_flops,
                    comm_units=0.01 * scfg.total_blocks, memory_gb=mem,
                    working_set_gb=ws), key=key)
                survivors = sk.mask
                # An exhausted gradient phase never registers with the
                # DAG; keep only edges to phases that actually exist and
                # let the barrier at the current clock stand in for the
                # missing one (same convention as GIANT's chain).
                want = (f"{tag}-sketch",) + \
                    ((grad_dep,) if grad_dep is not None else ())
                deps = tuple(dd for dd in want if dd in dag.results)
                dag.dispatch(scheduler.PhaseSpec(
                    name=f"{tag}-solve", workers=scfg.num_blocks,
                    policy="wait_all", flops_per_worker=solve_flops,
                    comm_units=0.01 * scfg.num_blocks, memory_gb=mem,
                    working_set_gb=ws, deps=deps),
                    key=jax.random.fold_in(key, 11),
                    sequential=len(deps) < len(want))
            else:
                _, mask = clock.phase(key, scfg.total_blocks,
                                      policy="k_of_n",
                                      k=scfg.num_blocks,
                                      flops_per_worker=(apply_flops
                                                        + gram_flops
                                                        + solve_flops),
                                      comm_units=0.01 * scfg.total_blocks,
                                      memory_gb=mem, working_set_gb=ws,
                                      phase_name=tag)
                survivors = mask
        except PhaseExhaustedError as e:
            if cfg.fault_fallback == "raise":
                raise
            # Exhausted retry budget: every attempt is billed; the
            # finite-finisher mask stands in for the k-of-n survivors
            # (per-block directions are independently unbiased, so the
            # average over fewer blocks just carries more variance — the
            # caller's descent guard backstops a zero-survivor round).
            _telemetry(clock).metrics.counter(
                "newton.fault_fallbacks").inc()
            if e.mask.shape == (scfg.total_blocks,):
                survivors = jnp.asarray(e.mask)
    state = fam.sample(
        straggler.on_device_of(jax.random.fold_in(key, 7), data.x), n_rows)
    fn = _jitted_distavg_direction(objective, fam, cfg.debias,
                                   cfg.distavg_solver, cfg.cg_iters)
    return fn(w, data, g, state, survivors)


def oversketched_newton(objective, data: Dataset, w0: jax.Array,
                        cfg: NewtonConfig,
                        model: Optional[straggler.StragglerModel] = straggler.StragglerModel()
                        ) -> NewtonResult:
    """Run OverSketched Newton; returns the iterate and a per-iteration log.

    ``model`` is either a ``StragglerModel`` (a fresh default fleet clock is
    built) or a prebuilt ``straggler.SimClock`` — the way to score a run on
    a custom fleet (cold starts, failures, trace record/replay; see
    ``repro.runtime``).  ``history["cost"]`` logs cumulative simulated
    dollars alongside ``history["time"]``'s simulated seconds.

    The call runs inside the ``osn.solve`` profiler span and each iteration
    inside ``osn.iter``, with its stages, fleet calls and host reads under
    the spans ``repro.obs.wall`` names.
    """
    with TraceAnnotation(wall.SOLVE):
        return _oversketched_newton(objective, data, w0, cfg, model)


def _oversketched_newton(objective, data: Dataset, w0: jax.Array,
                         cfg: NewtonConfig, model) -> NewtonResult:
    if cfg.sketch_mode not in ("blocks", "distributed-avg"):
        raise ValueError(f"unknown sketch_mode {cfg.sketch_mode!r}")
    if cfg.distavg_solver not in ("chol", "cg"):
        raise ValueError(f"unknown distavg_solver {cfg.distavg_solver!r}")
    if cfg.schedule not in ("dag", "sequential"):
        raise ValueError(f"unknown schedule {cfg.schedule!r}")
    if cfg.adaptive_metric not in ("stall", "mp"):
        raise ValueError(f"unknown adaptive_metric {cfg.adaptive_metric!r}")
    if cfg.fault_fallback not in ("degrade", "raise"):
        raise ValueError(f"unknown fault_fallback {cfg.fault_fallback!r}")
    if not 0.0 < cfg.survivor_floor <= 1.0:
        raise ValueError(
            f"survivor_floor must be in (0, 1], got {cfg.survivor_floor}")
    if (cfg.adaptive_sketch and cfg.adaptive_metric == "mp"
            and (cfg.sketch_mode != "blocks"
                 or cfg.hessian_policy != "oversketch")):
        raise ValueError(
            "adaptive_metric='mp' needs the surviving sketch-row count, "
            "which only the sketch_mode='blocks' + "
            "hessian_policy='oversketch' path reports")
    if cfg.sketch_mode == "distributed-avg":
        if cfg.hessian_policy != "oversketch":
            raise ValueError(
                "sketch_mode='distributed-avg' requires "
                f"hessian_policy='oversketch', got {cfg.hessian_policy!r}")
        d_hess = int(np.asarray(w0).size)
        if cfg.sketch.block_size <= d_hess:
            raise ValueError(
                "distributed-avg needs block_size > Hessian dim for the "
                f"per-worker solves to be well-posed: block_size="
                f"{cfg.sketch.block_size} <= d={d_hess}")
    sketching.get(cfg.sketch_family, cfg.sketch)   # fail fast on bad family
    # The key chain lives on the host: the fleet's folds and draws run
    # there, and the sketch draw takes one copy of its key to the data.
    key = straggler.host_key(cfg.seed)
    if isinstance(model, straggler.SimClock):
        clock, model = model, model.model
    else:
        clock = straggler.SimClock(model) if model is not None else None
    engine = CodedMatvecEngine(data, cfg.coded_block_rows, model,
                               overlap_encode=cfg.overlap_encode,
                               phase_memory=cfg.phase_memory,
                               corruption_detection=cfg.corruption_detection)
    tel = _telemetry(clock)
    if tel.enabled:
        # The bytes each code keeps on the device beside X: its parity.
        for tag, parity in engine.parity.items():
            tel.metrics.gauge(f"coded.held_bytes.{tag}").set(parity.nbytes)

    w = jnp.asarray(w0, jnp.float32)
    hist: Dict[str, List[float]] = {k: [] for k in (
        "iter", "fval", "gnorm", "step", "time", "cost", "test_error",
        "sketch_dim")}

    grad_fn = jax.jit(objective.gradient)
    val_fn = jax.jit(objective.value)
    live_cfg = cfg
    init_sketch_dim = cfg.sketch.sketch_dim   # growth cap baseline; cfg is
    #                                           rebound to live_cfg below
    prev_f = None
    prev_decrease = None

    run_span = tel.trace.begin(
        "newton", "run", clock.time if clock is not None else 0.0,
        sketch_family=cfg.sketch_family, schedule=cfg.schedule,
        sketch_mode=cfg.sketch_mode)
    if tel.enabled and cfg.solver in ("cg", "minres"):
        tel.metrics.gauge("newton.cg_iters").set(cfg.cg_iters)

    for t in range(cfg.iters):
        with TraceAnnotation(wall.ITER):
            cfg = live_cfg
            key, kg, kh, kl = jax.random.split(key, 4)
            it_span = tel.trace.begin(
                f"iter{t}", "iteration",
                clock.time if clock is not None else float(t))
            # One iteration = one phase DAG: gradient matvecs chain through
            # dependency edges, the Hessian sketch is a root node launched
            # at the iteration start (concurrent with the gradient), the
            # line search joins both.  schedule="sequential" keeps the
            # historical one-phase-at-a-time dispatch; the phase keys —
            # hence masks and iterates — are the same either way.
            dag = (scheduler.DagRun(clock, key=key)
                   if cfg.schedule == "dag" and clock is not None
                   else None)

            # --- 1. gradient (straggler-resilient coded matvecs, Alg. 1) ---
            with TraceAnnotation(wall.GRADIENT):
                grad_tail = None
                if cfg.gradient_policy == "exact" or model is None:
                    g = grad_fn(w, data)
                else:
                    # Fixed per-tag fold constants: Python's str hash is
                    # salted per process, which would break cross-process
                    # seed reproducibility of the straggler samples.
                    mv_seq = {"n": 0}

                    def mv(tag, v):
                        kf = jax.random.fold_in(kg, {"X": 3, "XT": 5}[tag])
                        if dag is None:
                            return engine.matvec(tag, v, clock, kf,
                                                 cfg.gradient_policy)
                        after = (dag.last,) if dag.last is not None else ()
                        y = engine.matvec(
                            tag, v, clock, kf, cfg.gradient_policy, dag=dag,
                            name=f"grad/{mv_seq['n']}:{tag}", after=after)
                        mv_seq["n"] += 1
                        return y

                    g = objective.gradient_via(w, data, mv)
                    if dag is not None:
                        grad_tail = dag.last

            # --- 2+3. sketched Hessian (Alg. 2) and direction ---------------
            m_eff = None
            with TraceAnnotation(wall.HESSIAN):
                if cfg.sketch_mode == "distributed-avg":
                    # per-worker solves + master-side direction averaging
                    p, hg = _distavg_direction_phase(
                        objective, data, w, g, cfg, kh, clock, dag=dag,
                        grad_dep=grad_tail)
                else:
                    h_hat, m_eff = _hessian_phase(objective, data, w, cfg,
                                                  kh, clock, dag=dag)
            with TraceAnnotation(wall.DIRECTION):
                if cfg.sketch_mode == "distributed-avg":
                    pass            # p came with the Hessian phase
                elif h_hat is None:
                    # Fault degradation: the sketch round (and its
                    # re-dispatch) lost too many blocks — take a plain
                    # gradient step, with hg = g (H = I) keeping the
                    # weakly-convex search coherent.
                    p, hg = -g, g
                    tel.metrics.counter("newton.gradient_fallbacks").inc()
                else:
                    p = _solve_direction(objective, h_hat, g, cfg)
                    if cfg.debias and m_eff is not None:
                        p = sketching.debias_direction(p, p.shape[0], m_eff)
                    hg = None

                # Descent guard: whatever produced p (a starved sketch, a
                # debias factor driven past zero by casualties, a corrupted
                # Hessian estimate that slipped through), only a finite
                # descent direction may reach the line search — anything
                # else degrades to steepest descent instead of diverging.
                gp = jnp.vdot(g, p)
                with TraceAnnotation(wall.SYNC_GUARD):
                    gp = float(gp)
                if not math.isfinite(gp) or gp >= 0.0:
                    p, hg = -g, g
                    tel.metrics.counter("newton.safeguard_fallbacks").inc()

            # --- 4. distributed line search (Sec. 3.2) ---------------------
            with TraceAnnotation(wall.LINESEARCH):
                if cfg.unit_step:
                    step = jnp.asarray(1.0)
                elif objective.strongly_convex:
                    step = linesearch.linesearch_strongly_convex(
                        objective, data, w, p, g, cfg.beta, cfg.candidates)
                else:
                    if hg is None:
                        hg = h_hat @ g
                    step = linesearch.linesearch_weakly_convex(
                        objective, data, w, p, g, hg, cfg.beta,
                        cfg.candidates)
                if clock is not None and not cfg.unit_step:
                    nb = max(1, data.x.shape[0]
                             // max(cfg.coded_block_rows, 1))
                    ls_flops = 2.0 * cfg.coded_block_rows * \
                        data.x.shape[1] * len(cfg.candidates)
                    ls_bytes = scheduler.matvec_worker_bytes(
                        cfg.coded_block_rows, data.x.shape[1])
                    ls_mem = _phase_mem(cfg.phase_memory, ls_bytes)
                    try:
                        if dag is not None:
                            # The line search consumes p, i.e. every phase
                            # so far; by then the clock already sits at the
                            # DAG's frontier, so it dispatches on the
                            # engine's exact sequential path.  The edges
                            # are still declared (sequential dispatch
                            # ignores them for timing) so the recorded DAG
                            # joins here and the critical-path walk can
                            # cross the line search.
                            dag.dispatch(scheduler.PhaseSpec(
                                name="linesearch", workers=nb,
                                policy="wait_all", flops_per_worker=ls_flops,
                                comm_units=0.5, memory_gb=ls_mem,
                                working_set_gb=_ws_gb(ls_bytes),
                                deps=tuple(dag.results)),
                                key=kl, sequential=True)
                        else:
                            clock.phase(kl, nb, policy="wait_all",
                                        flops_per_worker=ls_flops,
                                        comm_units=0.5, memory_gb=ls_mem,
                                        working_set_gb=_ws_gb(ls_bytes),
                                        phase_name="linesearch")
                    except PhaseExhaustedError:
                        if cfg.fault_fallback == "raise":
                            raise
                        # Billed, lost: the search objective values are
                        # master-side math, so the chosen step survives
                        # the dead fan-out.
                        tel.metrics.counter("newton.fault_fallbacks").inc()

                w = w + step * p

            with TraceAnnotation(wall.HISTORY):
                hist["iter"].append(t)
                f_now = val_fn(w, data)
                with TraceAnnotation(wall.SYNC_HISTORY):
                    f_now = float(f_now)
                hist["fval"].append(f_now)
                gnorm = jnp.linalg.norm(grad_fn(w, data))
                with TraceAnnotation(wall.SYNC_HISTORY):
                    hist["gnorm"].append(float(gnorm))
                with TraceAnnotation(wall.SYNC_HISTORY):
                    hist["step"].append(float(step))
                hist["time"].append(clock.time if clock is not None
                                    else float(t + 1))
                hist["cost"].append(clock.dollars if clock is not None
                                    else 0.0)
                hist["sketch_dim"].append(live_cfg.sketch.sketch_dim)

                if tel.enabled:
                    tel.metrics.gauge("newton.sketch_dim").set(
                        live_cfg.sketch.sketch_dim)
                    # Per-iteration seconds/dollars deltas: the
                    # cost-per-iteration streams the online health monitors
                    # watch for blowups.
                    many = len(hist["time"]) > 1
                    tel.metrics.gauge("newton.iter_seconds").set(
                        hist["time"][-1] - (hist["time"][-2] if many else 0.0))
                    tel.metrics.gauge("newton.iter_dollars").set(
                        hist["cost"][-1] - (hist["cost"][-2] if many else 0.0))
                    if cfg.solver in ("cg", "minres"):
                        tel.metrics.gauge("newton.cg_iters").set(cfg.cg_iters)
                    if dag is not None and dag.results:
                        # Per-iteration critical-path + slack report
                        # (ROADMAP's DagResult analytics item), attached to
                        # the iteration span so exporters and make_report
                        # can render it.
                        rep = dag.critical_path()
                        tel.trace.set_attrs(
                            it_span,
                            critical_path=list(rep.critical_path),
                            dag_makespan=rep.makespan,
                            slack={n: p.slack for n, p in rep.phases.items()})
                tel.trace.end(it_span, clock.time if clock is not None
                              else float(t + 1))

                # --- adaptive sketch growth (paper Thm 3.2 remark) ----------
                if cfg.adaptive_sketch:
                    if cfg.adaptive_metric == "mp":
                        # Grow when the MEASURED Marchenko-Pastur factor of
                        # the surviving sketch rows says the sketch is too
                        # biased to trust — a leading indicator available
                        # from iteration 0, unlike the trailing f-decrease
                        # stall below.
                        stalled = m_eff is not None and sketching.mp_stalled(
                            int(p.shape[0]), m_eff, cfg.adaptive_mp_target)
                    elif prev_f is not None:
                        decrease = prev_f - f_now
                        # Stall = progress fell off vs the last iteration;
                        # an INCREASE in f (decrease < 0, the eps-too-coarse
                        # divergence regime) is always a stall, whatever
                        # the previous decrease was.
                        stalled = decrease < 0 or (
                            prev_decrease is not None and prev_decrease > 0
                            and decrease
                            < cfg.adaptive_stall_ratio * prev_decrease)
                    else:
                        stalled = False
                    grown = live_cfg.sketch.sketch_dim // init_sketch_dim
                    if stalled and grown < cfg.adaptive_max_growth:
                        new_sketch = dataclasses.replace(
                            live_cfg.sketch,
                            sketch_dim=live_cfg.sketch.sketch_dim * 2)
                        live_cfg = dataclasses.replace(live_cfg,
                                                       sketch=new_sketch)
                        tel.metrics.counter("newton.adaptive_growth").inc()
                if prev_f is not None:
                    prev_decrease = prev_f - f_now
                prev_f = f_now
                if cfg.track_test_error and data.x_test is not None:
                    err = objective.error(w, data.x_test, data.y_test)
                    with TraceAnnotation(wall.SYNC_HISTORY):
                        hist["test_error"].append(float(err))
                else:
                    hist["test_error"].append(float("nan"))

    tel.trace.end(run_span,
                  clock.time if clock is not None else float(cfg.iters))
    return NewtonResult(w=w, history=hist)
