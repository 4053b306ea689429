"""The comparison that decides ``correct``.

Each solve in the window is an answer: the iterate w it returned and the
objective f it reported for w.  Once the window has closed, the cell's
float32 reference (``optimum`` and ``value_at`` of its
``bench/objectives/<objective>.py``) gives the optimum f*, and every answer
is judged by two numbers:

- ``f_gap``: (F(w) - f*) / |f*|, with F the reference objective.  How far
  the solve's iterate is from the optimum after the traffic's iterations:
  it holds the sketched Hessian (a poor Hessian converges slower), the
  coded gradient and the line search to what the method reaches.
- ``f_report``: |f - F(w)| / |F(w)|.  Whether the objective the solve
  reports is the objective of the iterate it returns.

An answer fails when either number is above its limit or is not finite.
The limits, and the readings they were set from, are in the cell's file
under ``bench/workloads/``.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

NUMBERS = ("f_gap", "f_report")


def judge(objective, x, y, config: dict, answers: List,
          limits: Dict[str, float], f_star: Optional[float] = None) -> dict:
    """{"numbers": worst reading of each number, "failed": answers failed,
    "f_star": the reference optimum}.  ``f_star`` may be given where the
    reference has already run on this data."""
    if f_star is None:
        _, f_star = objective.optimum(x, y, config)
    worst = {k: -math.inf for k in NUMBERS}
    failed = 0
    for ans in answers:
        f_true = objective.value_at(x, y, ans.w, config)
        got = {"f_gap": (f_true - f_star) / abs(f_star),
               "f_report": abs(ans.f - f_true) / abs(f_true)}
        bad = False
        for k, v in got.items():
            if not math.isfinite(v):
                v, bad = math.inf, True
            worst[k] = max(worst[k], v)
            bad = bad or v > limits[k]
        failed += bad
    return {"numbers": worst, "failed": failed, "f_star": f_star}


def lines(numbers: Dict[str, float], limits: Dict[str, float]) -> List[str]:
    """One plain line per number compared, its reading beside its limit."""
    return [f"{k} {numbers[k]!r} limit {limits[k]!r}" for k in NUMBERS]
