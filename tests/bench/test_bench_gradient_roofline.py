"""``gradient_roofline`` (``bench/metrics/gradient_roofline.py``) on the
two committed traces recorded on one TPU v5e by ``record_trace.py``: the
coded programs' share of the gradient's roofline, and None where no coded
program ran."""
import importlib.util
import os

import pytest

from bench import trace_reduce as tr
from bench import work

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
SPANS_FIXTURE = os.path.join(HERE, "fixtures", "trace_spans.xplane.pb")
OLD_FIXTURE = os.path.join(HERE, "fixtures", "trace.xplane.pb")
# record_trace.py's solve: 2 iterations at n = 2048, d = 64.
RECORDED = {"n": 2048, "d": 64}
ITERATIONS = 2
PROGRAMS = ("jit_coded_matvec", "jit_encode_2d")


def reader():
    path = os.path.join(REPO, "bench", "metrics", "gradient_roofline.py")
    spec = importlib.util.spec_from_file_location(
        "test_reader_gradient_roofline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Ctx:
    def __init__(self, trace):
        self.trace, self.iterations, self.config = (trace, ITERATIONS,
                                                    RECORDED)
        self.peak = work.peaks("TPU v5 lite")


@pytest.mark.parametrize("fixture", [OLD_FIXTURE, SPANS_FIXTURE],
                         ids=["older", "spans"])
def test_gradient_roofline_reads_the_coded_programs_share(fixture):
    """Two uncoded reads of X over the coded programs' device time per
    iteration: a share under 100% where they ran, None where none did."""
    trace = tr.reduce(fixture)
    share = reader().read(Ctx(trace))
    secs = trace.device_s(PROGRAMS)
    least, bound = work.least_time(
        work.gradient(RECORDED["n"], RECORDED["d"]), work.peaks(
            "TPU v5 lite"))
    assert bound == "memory"
    assert share == pytest.approx(least / (secs / ITERATIONS) * 100,
                                  rel=1e-12)
    assert 0 < share < 100
    trace.programs = {k: v for k, v in trace.programs.items()
                      if k not in PROGRAMS}
    assert reader().read(Ctx(trace)) is None
