"""The reduction from a profiler trace to busy time, program time and
named idle gaps: interval arithmetic, and a small trace recorded on one
TPU v5e (``fixtures/trace.xplane.pb``, made by ``record_trace.py``)."""
import os

import pytest

from bench import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "trace.xplane.pb")


def test_union_merges_overlaps_and_keeps_disjoint_intervals():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (8, 9)]) == \
        [(0, 4), (5, 7), (8, 9)]
    assert tr.union([]) == []


def test_clip_and_complement_cover_the_window_exactly():
    busy = tr.union(tr.clip([(-5, 1), (2, 3), (9, 20)], 0, 10))
    assert busy == [(0, 1), (2, 3), (9, 10)]
    gaps = tr.complement(busy, 0, 10)
    assert gaps == [(1, 2), (3, 9)]
    assert sum(b - a for a, b in busy + gaps) == 10


def test_program_names_drop_the_run_id():
    assert tr.program_name("jit_coded_matvec(12345)") == "jit_coded_matvec"
    assert tr.program_name("jit_fn") == "jit_fn"


def test_gaps_are_named_by_the_innermost_open_host_span():
    spans = [(0, 100, "window"), (10, 50, "solve"),
             (20, 30, "PjitFunction(fn)"), (60, 70, "solve")]
    assert tr.innermost(spans, [150, 25, 40, 65, 55, 5]) == \
        [None, "PjitFunction(fn)", "solve", "solve", "window", "window"]


@pytest.fixture(scope="module")
def recorded():
    return tr.reduce(FIXTURE)


def test_recorded_trace_has_device_time_inside_the_window(recorded):
    assert recorded.window_s > 0
    assert 0 < recorded.busy_s < recorded.window_s
    gap_s = sum(s for _, s in recorded.gaps)
    assert recorded.busy_s + gap_s == pytest.approx(recorded.window_s,
                                                    rel=1e-6)


def test_recorded_trace_names_the_solver_programs(recorded):
    for prog in ("jit_fn", "jit_coded_matvec", "jit_encode_2d",
                 "jit__randint", "jit__rademacher"):
        assert recorded.programs.get(prog, 0) > 0, prog
    assert recorded.device_s(("jit_fn",)) == recorded.programs["jit_fn"]
    assert recorded.device_s(("no_such_program",)) is None
    # Module spans may hold idle time between their ops; busy counts ops.
    assert sum(recorded.programs.values()) <= recorded.window_s


def test_recorded_gaps_are_named_by_host_spans(recorded):
    names = {n for n, _ in recorded.gaps}
    assert "untraced" not in names
    assert names
