"""Faults planted in the timed path, to show that ``correct`` catches them.

Each stands in for ``repro.core.oversketched_newton`` with its signature.
``tests/bench/test_bench_control.py`` drives whole runs with each on the
CPU, and ``bench/control.py --faults`` reads their numbers at a cell's own
size on the chip.  A single-chip cell exchanges nothing between chips, so
that fault does not apply here.
"""
from __future__ import annotations

import jax.numpy as jnp


def unchanged(objective, data, w0, cfg):
    """A solve that returns its state unchanged, its objective reported
    truly."""
    from repro.core import NewtonResult
    return NewtonResult(w=w0, history={
        "fval": [float(objective.value(w0, data))]})


def half_rows(objective, data, w0, cfg):
    """Half of the rows left out, the mean taken over the rest."""
    from repro.core import Dataset, oversketched_newton
    return oversketched_newton(
        objective, Dataset(x=data.x[::2], y=data.y[::2]), w0, cfg)


def iterate_altered(objective, data, w0, cfg):
    """The answer altered where it is produced: its largest weight's sign
    flipped."""
    from repro.core import oversketched_newton
    res = oversketched_newton(objective, data, w0, cfg)
    i = int(jnp.argmax(jnp.abs(res.w)))
    res.w = res.w.at[i].multiply(-1.0)
    return res


def report_altered(objective, data, w0, cfg):
    """The reported objective altered where it is produced, by 0.1%."""
    from repro.core import oversketched_newton
    res = oversketched_newton(objective, data, w0, cfg)
    res.history["fval"][-1] *= 1.001
    return res


ALL = (unchanged, half_rows, iterate_altered, report_altered)
