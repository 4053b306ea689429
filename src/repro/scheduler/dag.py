"""Phase-DAG dispatch on top of ``FleetEngine.run_phase(not_before=...)``.

The scheduler sits between optimizers and the fleet engine: an optimizer
declares one iteration as ``PhaseSpec``s with dependency edges, and the
scheduler dispatches each phase at the absolute launch time

    launch(p) = max(dag_start, max over deps d of finish(d))

via the engine's ``not_before`` machinery — so two phases with no path
between them (the gradient round and the Hessian-sketch fan-out, paper
Sec. 4.1 / Bartan-Pilanci's concurrent sketch round) run concurrently on
the simulated timeline, while billing stays position-independent.

Two entry points:

  - ``DagRun`` — the imperative handle optimizers use: ``dispatch(spec)``
    one phase at a time, with data-dependent specs allowed (the coded
    matvec's decode-failure retry phase only exists when the decode
    failed).  Finish times are tracked per name; later dispatches name
    their deps.
  - ``run_dag(clock, key, specs)`` — the declarative form: validates the
    DAG, canonicalizes the dispatch order (see ``spec.canonical_order``),
    and dispatches everything.  ``sequential=True`` dispatches the same
    canonical order with every edge treated as a full barrier at the
    current clock — the makespan upper bound every DAG schedule is
    measured against.

Exactness contracts:

  - A phase whose launch time equals the current clock takes the engine's
    sequential path (``not_before=None``) — no ``(now + e) - now`` float
    re-rounding — so a DAG whose edges serialize every phase reproduces
    the sequential schedule's ``(seconds, dollars)`` bit-for-bit.
  - Phase keys fold the spec's stable ``key_fold`` into the run key (or
    the caller passes an explicit per-phase key), so a phase's duration
    draw depends only on its name, never on dispatch order.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
from jax.profiler import TraceAnnotation

from repro.obs import wall
from repro.scheduler.spec import PhaseSpec, canonical_order


@dataclasses.dataclass
class PhaseResult:
    """One dispatched phase on the absolute simulated timeline."""

    spec: PhaseSpec
    start: float          # absolute launch time
    elapsed: float        # master wait incl. comm (= run_phase's elapsed)
    finish: float         # start + elapsed
    mask: object          # finished-worker mask from the termination policy


@dataclasses.dataclass
class DagResult:
    """What ``run_dag`` hands back."""

    order: List[str]                      # canonical dispatch order
    results: Dict[str, PhaseResult]
    start: float
    makespan: float                       # max finish - start

    def finish(self, name: str) -> float:
        return self.results[name].finish

    def critical_path(self):
        """Makespan-binding chain + per-phase slack of this dispatched DAG
        (an ``obs.CriticalPathReport``; see ``repro.obs.critical_path``)."""
        from repro import obs
        return obs.from_dag(self)


class DagRun:
    """Imperative phase-DAG dispatch against one clock.

    ``clock`` is a ``core.straggler.SimClock`` (or anything with its
    ``phase()``/``time`` surface).  ``key`` seeds per-phase keys for specs
    dispatched without an explicit key.
    """

    def __init__(self, clock, key: Optional[jax.Array] = None,
                 start: Optional[float] = None):
        self.clock = clock
        self.key = key
        self.start = float(clock.time if start is None else start)
        self.results: Dict[str, PhaseResult] = {}
        self.last: Optional[str] = None   # most recently dispatched name

    def launch_time(self, spec: PhaseSpec) -> float:
        missing = [d for d in spec.deps if d not in self.results]
        if missing:
            raise ValueError(
                f"phase {spec.name!r} depends on undispatched {missing}")
        return max([self.start]
                   + [self.results[d].finish for d in spec.deps])

    def dispatch(self, spec: PhaseSpec, key: Optional[jax.Array] = None,
                 sequential: bool = False,
                 min_start: Optional[float] = None) -> PhaseResult:
        """Simulate one phase at its DAG launch time; returns its result.

        ``sequential=True`` ignores the edges and launches at the current
        clock — the barrier baseline.  ``min_start`` floors the launch
        time — how a caller expresses a dependency on work that ran on
        the direct clock outside the DAG (e.g. the coded matvec's
        one-time encode phases).  Phases launching exactly at the current
        clock take the engine's ``not_before=None`` path either way,
        keeping serialized DAGs bit-identical to sequential runs.  The
        call runs inside the profiler span ``osn.fleet``.
        """
        with TraceAnnotation(wall.FLEET):
            if spec.name in self.results:
                raise ValueError(f"phase {spec.name!r} already dispatched")
            if key is None:
                if self.key is None:
                    raise ValueError(
                        f"phase {spec.name!r}: DagRun has no base key; pass "
                        "one to DagRun(...) or dispatch(..., key=...)")
                key = jax.random.fold_in(self.key, spec.key_fold)
            now = float(self.clock.time)
            nb = now if sequential else self.launch_time(spec)
            if min_start is not None:
                nb = max(nb, float(min_start))
            elapsed, mask = self.clock.phase(
                key, spec.workers, policy=spec.policy, k=spec.k,
                work_per_worker=spec.work_per_worker,
                flops_per_worker=spec.flops_per_worker,
                comm_units=spec.comm_units, decodable=spec.decodable,
                not_before=None if nb == now else nb,
                memory_gb=spec.memory_gb,
                working_set_gb=spec.working_set_gb,
                phase_name=spec.name, phase_deps=spec.deps)
            finish = float(self.clock.time) if nb == now else nb + elapsed
            res = PhaseResult(spec=spec, start=nb, elapsed=float(elapsed),
                              finish=finish, mask=mask)
            self.results[spec.name] = res
            self.last = spec.name
            return res

    @property
    def makespan(self) -> float:
        if not self.results:
            return 0.0
        return max(r.finish for r in self.results.values()) - self.start

    def critical_path(self):
        """Critical-path + slack report over the phases dispatched so far
        (an ``obs.CriticalPathReport``; see ``repro.obs.critical_path``)."""
        from repro import obs
        return obs.from_dag(self)


def run_dag(clock, key: jax.Array, specs: Sequence[PhaseSpec], *,
            sequential: bool = False,
            start: Optional[float] = None) -> DagResult:
    """Validate, canonicalize, and dispatch a whole phase DAG.

    The dispatch order — hence every duration draw, pool interaction, and
    ledger addition — is the canonical topological order, a pure function
    of the DAG: declaring the same phases in any topological order gives
    bit-identical ``(seconds, dollars)``.
    """
    order = canonical_order(specs)
    run = DagRun(clock, key=key, start=start)
    for s in order:
        run.dispatch(s, sequential=sequential)
    return DagResult(order=[s.name for s in order], results=run.results,
                     start=run.start, makespan=run.makespan)
