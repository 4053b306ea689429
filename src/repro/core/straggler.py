"""Straggler model + simulation clock.

Calibrated to the paper's Fig. 1 (3600 AWS Lambda workers): median job time
~135 s with ~2% of workers straggling up to ~180 s (~1.33x median).  We model
per-worker job time as

    t_w = base * lognormal(0, body_sigma) * (1 + straggler * tail)

with P[straggler] = p_tail and tail ~ U[tail_lo, tail_hi].  The *clock*
(``SimClock``, a facade over the discrete-event ``repro.runtime`` fleet
engine) turns per-phase worker lifecycles into simulated wall time and
dollars under pluggable termination policies (wait_all / k_of_n /
speculative / hedged / coded_decode), which is how every optimizer in this
repo is scored — the container has one physical device, so comparisons the
paper makes in wall-clock and AWS dollars on Lambda are made here in
deterministic simulated seconds and simulated dollars.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.obs import wall


@functools.cache
def host_device() -> jax.Device:
    """The device the clock draws on: the CPU backend JAX starts beside
    any accelerator, or the default device where there is no CPU backend."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return jax.devices()[0]


def on_host(key: jax.Array) -> jax.Array:
    """``key`` committed to ``host_device()``: every split, fold and draw
    derived from it then runs there as well, with the same bits (the key
    derivation is integer arithmetic) and no read from an accelerator."""
    dev = host_device()
    if getattr(key, "committed", False) and key.devices() == {dev}:
        return key
    return jax.device_put(key, dev)


def host_key(seed: int) -> jax.Array:
    """``jax.random.PRNGKey(seed)``, made and committed on the host."""
    with jax.default_device(host_device()):
        return on_host(jax.random.PRNGKey(seed))


def on_device_of(key: jax.Array, x) -> jax.Array:
    """A host key copied, with no read, to the device that holds ``x``: a
    draw that feeds a device program (a sketch, noise, a row sample) runs
    beside its data.  Data on several devices, or on none, takes the key
    uncommitted on the default device, where its programs place it."""
    devices = x.devices() if isinstance(x, jax.Array) else ()
    if len(devices) == 1:
        return jax.device_put(key, next(iter(devices)))
    return jax.device_put(np.asarray(key))


@dataclasses.dataclass(frozen=True)
class StragglerModel:
    base_time: float = 1.0        # median per-worker job time (per work unit)
    body_sigma: float = 0.08      # lognormal body spread
    p_tail: float = 0.02          # Fig. 1: ~2% stragglers
    tail_lo: float = 0.3          # straggler slowdown factor lower bound
    tail_hi: float = 1.5          # up to 2.5x median
    invoke_overhead: float = 0.1  # per-phase worker invocation overhead
    comm_per_unit: float = 0.05   # storage/communication cost per data unit
    flops_per_second: float = 2e6  # simulated worker throughput (Lambda-ish
    #                               scale at the CPU bench problem sizes)

    def sample_times(self, key: jax.Array, num_workers: int,
                     work_per_worker: float = 1.0,
                     flops_per_worker: Optional[float] = None) -> jax.Array:
        """Per-worker job completion times for one distributed phase.

        Work is given either in abstract seconds (work_per_worker) or as a
        per-worker flop count (flops_per_worker), converted through the
        model's simulated throughput — phases with genuinely different
        per-worker compute (a matvec block vs a local Newton solve) then get
        proportionally different durations, which is what makes the
        scheme-vs-scheme comparisons honest.

        The draws run as eager ops on the key's device; the fleet engine
        passes host keys (``on_host``).  They stay eager: a jitted draw
        fuses the float ops differently and moves the last bits of the
        times, hence the simulated seconds and dollars."""
        if flops_per_worker is not None:
            work_per_worker = flops_per_worker / self.flops_per_second
        k1, k2, k3 = jax.random.split(key, 3)
        body = jnp.exp(self.body_sigma * jax.random.normal(k1, (num_workers,)))
        is_tail = jax.random.bernoulli(k2, self.p_tail, (num_workers,))
        tail = jax.random.uniform(k3, (num_workers,), minval=self.tail_lo,
                                  maxval=self.tail_hi)
        slow = 1.0 + is_tail * tail
        return self.invoke_overhead + self.base_time * work_per_worker * body * slow


# The production termination policies live in the ``repro.runtime.policies``
# registry (what SimClock.phase dispatches through); the helpers below are
# the jax-native order-statistic forms kept for direct use on sampled time
# arrays (tests, notebooks).  ``speculative_time`` — the only nontrivial one
# — delegates to the registry so there is a single implementation.

def wait_all_time(times: jax.Array) -> jax.Array:
    """Policy: wait for every worker (uncoded baseline)."""
    return jnp.max(times)


def k_of_n_time(times: jax.Array, k: int) -> jax.Array:
    """Policy: proceed when any k of n workers finish (coded / sketched)."""
    return jnp.sort(times)[k - 1]


def k_of_n_mask(times: jax.Array, k: int) -> jax.Array:
    """Which workers finished by the k-of-n deadline (ties kept, >=k true)."""
    return times <= k_of_n_time(times, k)


def speculative_time(times: jax.Array, key: jax.Array,
                     model: StragglerModel,
                     watch_fraction: float = 0.9,
                     work_per_worker: float = 1.0,
                     flops_per_worker: Optional[float] = None) -> jax.Array:
    """Policy: speculative execution (paper Sec. 5.3).

    Wait for ``watch_fraction`` of workers, then re-launch the stragglers and
    take min(original finish, deadline + relaunch finish) per straggler.
    Relaunches redo the phase's *actual* work (``work_per_worker`` /
    ``flops_per_worker`` must match what produced ``times``) — the historical
    default of unit work made relaunched stragglers finish unrealistically
    fast, flattering every speculative baseline.
    """
    from repro.runtime import policies as rt_policies   # lazy: imports us
    n = times.shape[0]

    def sample_relaunch():
        relaunch = model.sample_times(key, n, work_per_worker,
                                      flops_per_worker)
        with TraceAnnotation(wall.SYNC_STRAGGLER):
            return np.asarray(relaunch, dtype=np.float64)

    ctx = rt_policies.PhaseContext(watch_fraction=watch_fraction,
                                   sample_relaunch=sample_relaunch)
    with TraceAnnotation(wall.SYNC_STRAGGLER):
        times = np.asarray(times, dtype=np.float64)
    out = rt_policies.get_policy("speculative")(times, ctx)
    return jnp.asarray(out.elapsed)


class SimClock:
    """Simulated wall time (and dollars) across distributed phases.

    Thin facade over ``repro.runtime.FleetEngine`` — the discrete-event
    fleet simulator with per-worker lifecycle (cold start / failure-retry),
    the termination-policy registry, cost accounting, and trace
    record/replay.  The historical ``phase()``/``charge()``/``time`` API is
    preserved so optimizer call sites are unchanged; richer behaviour is
    opted into via the keyword-only constructor args (see
    ``runtime/README.md``).
    """

    def __init__(self, model: StragglerModel, time: float = 0.0, *,
                 fleet=None, cost=None, recorder=None, replay=None,
                 pool=None, telemetry=None, faults=None):
        from repro.runtime import FleetEngine   # lazy: runtime imports us
        self.engine = FleetEngine(model, fleet=fleet, cost=cost,
                                  recorder=recorder, replay=replay,
                                  pool=pool, telemetry=telemetry,
                                  faults=faults)
        if time:
            self.engine.seconds += float(time)

    @property
    def model(self) -> StragglerModel:
        return self.engine.model

    @property
    def time(self) -> float:
        return self.engine.seconds

    @property
    def dollars(self) -> float:
        return self.engine.dollars

    @property
    def ledger(self):
        return self.engine.ledger

    @property
    def telemetry(self):
        """The attached ``obs.Telemetry`` (or the zero-overhead no-op)."""
        return self.engine.telemetry

    @property
    def last_corruption(self):
        """Boolean per-worker corruption flags of the most recent phase
        (None unless a fault plan with a ``CorruptionSpec`` is attached) —
        the coded-matvec layer turns these into parity-detected erasures."""
        return self.engine.last_corruption

    def charge(self, elapsed: float, phase_name=None) -> None:
        """Directly add externally-computed phase time (e.g. the coded
        master's wait-until-decodable simulation)."""
        with TraceAnnotation(wall.FLEET):
            self.engine.charge(elapsed, phase_name=phase_name)

    def phase(self, key: jax.Array, num_workers: int, *,
              work_per_worker: float = 1.0,
              flops_per_worker: Optional[float] = None,
              policy: str = "wait_all", k: Optional[int] = None,
              comm_units: float = 0.0,
              decodable=None,
              not_before: Optional[float] = None,
              memory_gb: Optional[float] = None,
              working_set_gb: Optional[float] = None,
              phase_name: Optional[str] = None,
              phase_deps: Tuple[str, ...] = ()) -> Tuple[float, jax.Array]:
        """Simulate one phase; returns (elapsed, finished_mask).

        ``not_before`` (absolute simulated seconds) overlaps this phase
        with whatever advanced the clock since that time; ``memory_gb``
        bills it at its own Lambda size; ``working_set_gb`` declares the
        true per-worker working set (the fault plane's OOM threshold);
        ``phase_name``/``phase_deps`` label the phase's telemetry span —
        see ``FleetEngine.run_phase``.  The call runs inside the profiler
        span ``osn.fleet``."""
        with TraceAnnotation(wall.FLEET):
            elapsed, mask = self.engine.run_phase(
                key, num_workers, work_per_worker=work_per_worker,
                flops_per_worker=flops_per_worker, policy=policy, k=k,
                comm_units=comm_units, decodable=decodable,
                not_before=not_before, memory_gb=memory_gb,
                working_set_gb=working_set_gb,
                phase_name=phase_name, phase_deps=phase_deps)
            return elapsed, jnp.asarray(mask)
