"""One cell of the benchmark, found by name, and the calls it makes into the
program.

``BENCHMARK.json`` names each cell's configuration and traffic mix.  Their
files, each found by its name:

- ``bench/configs/<config>.json`` (as the ``configs`` entry gives it): the
  problem's sizes, the objective by name, the precision, and under
  ``newton`` the ``NewtonConfig`` fields the solve takes;
- ``bench/objectives/<objective>.py``: the objective's data generator, the
  program class that solves it, the iterate a solve starts from
  (``start``), the work of one iteration that the roofline metrics divide
  by (``work``), and its plain reference;
- ``bench/traffic/<traffic>.json``: how solves arrive and how many
  iterations each runs;
- ``bench/workloads/<cell>.json``: the limits that decide ``correct``,
  with the readings they were set from;
- ``bench/metrics/<metric>.py`` for a per-layer metric, or, for a metric
  named ``<quantity>.<variant>``, the quantity's ``<quantity>.py``.

Nothing here is specific to one cell, so a new cell, configuration,
objective, mix or metric is a new file and an entry in ``BENCHMARK.json``.
What the harness cannot run it refuses when the cell is loaded.

The program is reached only through the public ``repro.core`` exports.
"""
from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import os
import time
from typing import List, Optional

import jax
import jax.numpy as jnp

# Traffic loops the harness runs: "closed", solves back to back, each
# caller waiting on its solve.
LOOPS = ("closed",)
# The precision a configuration states, and the one below it that the
# control computes in.
CONTROL_DTYPE = {"float32": jnp.bfloat16}
# NewtonConfig fields that a configuration may not set: the run sets the
# iterations and seeds, and a cell runs the program's default Hessian path.
NEWTON_OWNED = ("iters", "seed", "use_kernels")


def derive_seed(seed: int, *tag) -> int:
    """A 31-bit seed derived from the run's ``--seed`` (any size) and a tag,
    so that each consumer (data, solve i, ...) gets its own stream."""
    text = ":".join(str(t) for t in (seed,) + tag).encode()
    return int.from_bytes(hashlib.sha256(text).digest()[:4], "little") >> 1


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: str
    objective: object = None     # the module of bench/objectives/<name>.py

    def reader(self, metric: str):
        """The module of the metric's reader; it has ``read(ctx)``."""
        base = os.path.join(self.root, "bench", "metrics")
        path = os.path.join(base, metric + ".py")
        if not os.path.isfile(path):
            path = os.path.join(base, metric.split(".")[0] + ".py")
        return _module(path, "bench_metric_" + metric.replace(
            ".", "_").replace("-", "_"))


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def validate(config: dict, traffic: dict) -> None:
    """Raise ValueError for what the harness cannot run as stated."""
    if traffic.get("loop") not in LOOPS:
        raise ValueError(f"traffic loop {traffic.get('loop')!r} is not "
                         f"implemented; known: {LOOPS}")
    if int(traffic["iters"]) < 1:
        raise ValueError("traffic iters must be at least 1")
    if config.get("precision") not in CONTROL_DTYPE:
        raise ValueError(f"precision {config.get('precision')!r} has no "
                         f"control; known: {sorted(CONTROL_DTYPE)}")
    if int(config.get("n_test", 0)) != 0:
        raise ValueError("the harness makes no test set: n_test must be 0")
    owned = sorted(set(config.get("newton", {})) & set(NEWTON_OWNED))
    if owned:
        raise ValueError(f"a configuration may not set {owned} under "
                         f"'newton'")


def load(root: str, name: str) -> Cell:
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _json(os.path.join(root, "bench", "traffic",
                                 w["traffic"] + ".json"))
    validate(config, traffic)
    objective = _module(
        os.path.join(root, "bench", "objectives",
                     config["objective"] + ".py"),
        "bench_objective_" + config["objective"])
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        limits=_json(os.path.join(root, "bench", "workloads",
                                  name + ".json"))["limits"],
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=root, objective=objective)


def make_data(cell: Cell, seed: int):
    """(x, y) on the default device, from the run's seed."""
    key = jax.random.PRNGKey(derive_seed(seed, "data"))
    return jax.block_until_ready(cell.objective.make_data(key, cell.config))


def solve_seed(seed: int, index: int) -> int:
    return derive_seed(seed, "solve", index)


@dataclasses.dataclass
class Answer:
    """What one solve returned: its iterate and the objective it reported."""
    w: jax.Array
    f: float
    seconds: float


def newton_config(core, config: dict, iters: int, seed: int):
    kw = dict(config.get("newton", {}))
    if "sketch" in kw:
        kw["sketch"] = core.OverSketchConfig(**kw["sketch"])
    return core.NewtonConfig(iters=iters, seed=seed, **kw)


def solve(cell: Cell, x, y, seed: int, iters: int, newton=None) -> Answer:
    """One solve through the program's normal path, from the objective's
    ``start``.

    ``newton`` stands in for ``repro.core.oversketched_newton`` (the tests
    plant faults through it)."""
    import repro.core as core
    cls = getattr(core, cell.objective.PROGRAM)
    objective = cls(**cell.objective.program_args(cell.config))
    cfg = newton_config(core, cell.config, iters, seed)
    run = newton or core.oversketched_newton
    t0 = time.perf_counter()
    res = run(objective, core.Dataset(x=x, y=y),
              cell.objective.start(x, y, cell.config), cfg)
    jax.block_until_ready(res.w)
    return Answer(w=res.w, f=float(res.history["fval"][-1]),
                  seconds=time.perf_counter() - t0)


def limits_of(cell: Cell) -> dict:
    """{number: limit} from the cell file's ``limits``."""
    return {k: float(v["limit"]) for k, v in cell.limits.items()}


def peak_bytes() -> Optional[int]:
    """Peak bytes in use on the chip so far, where the backend reports it."""
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")
