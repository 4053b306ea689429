#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, at a cell's own size.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 \
        [--program N] [--faults]

For each seed it makes the cell's data as a run does, computes the float32
reference, and judges two kinds of answer with ``bench/check.py``:

- the control: the reference itself put in the program's place, run for
  the traffic's iterations in the precision below the one the
  configuration states (bfloat16 for float32), reporting its own
  objective.  Its numbers set the upper readings, and it has to come out
  not correct;
- with ``--program N``: N solves of the program as the window runs them
  (solve seeds 0..N-1 of that run seed), whose numbers set the lower
  readings;
- with ``--faults``: one solve with each fault of ``bench/faults.py``
  planted in the program's place.

One JSON line per seed on standard output.  The benchmark's own runs do
not run this.  It needs a TPU, as a run does.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def control_answer(cell, x, y):
    """The control's answer, in the precision below the configuration's."""
    from bench import cell as cells
    w, f = cell.objective.control(
        x, y, cell.config, int(cell.traffic["iters"]),
        cells.CONTROL_DTYPE[cell.config["precision"]])
    return cells.Answer(w=w, f=f, seconds=0.0)


def readings(cell, seed: int, program: int = 0, planted=()) -> dict:
    from bench import cell as cells, check
    limits = cells.limits_of(cell)
    x, y = cells.make_data(cell, seed)
    obj, cfg = cell.objective, cell.config
    out = {"seed": seed}
    ctrl = check.judge(obj, x, y, cfg, [control_answer(cell, x, y)], limits)
    f_star = ctrl["f_star"]
    out["control"] = ctrl["numbers"]
    out["control_correct"] = ctrl["failed"] == 0
    if program:
        iters = int(cell.traffic["iters"])
        answers = [cells.solve(cell, x, y, cells.solve_seed(seed, i), iters)
                   for i in range(program)]
        prog = check.judge(obj, x, y, cfg, answers, limits, f_star)
        out["program"] = prog["numbers"]
        out["program_correct"] = prog["failed"] == 0
        out["solve_seconds"] = [a.seconds for a in answers]
    for fault in planted:
        ans = cells.solve(cell, x, y, cells.solve_seed(seed, 0),
                          int(cell.traffic["iters"]), fault)
        out.setdefault("faults", {})[fault.__name__] = check.judge(
            obj, x, y, cfg, [ans], limits, f_star)["numbers"]
    out["f_star"] = f_star
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", type=int, default=0)
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args(argv)
    import jax
    from bench import cell as cells, faults, run
    jax.config.update("jax_compilation_cache_dir",
                      run.compile_cache_dir(os.environ, ROOT))
    cell = cells.load(ROOT, args.workload)
    try:
        run.require_chip(cell.chips)
    except run.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 1
    for seed in args.seeds:
        print(json.dumps(readings(cell, seed, args.program,
                                  faults.ALL if args.faults else ())),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
