"""The comparison that decides ``correct`` has been shown to fail: the
control (the reference in bfloat16, in the program's place) and each fault
a single-chip solve cell can have come out not correct, on the CPU at a
tiny size, while the program itself comes out correct."""
import pytest

from bench import cell as cells, control, faults
from conftest import add_cell


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_the_control_is_not_correct_and_the_program_is(checkout, seed):
    name = add_cell(checkout)
    out = control.readings(cells.load(checkout, name), seed, program=1)
    assert out["control_correct"] is False
    assert out["program_correct"] is True
    # The control fails by its reported objective, rounded to bfloat16.
    assert out["control"]["f_report"] > 10 * out["program"]["f_report"]


@pytest.mark.parametrize("fault", faults.ALL, ids=lambda f: f.__name__)
def test_each_fault_comes_out_not_correct(checkout, harness, fault):
    name = add_cell(checkout)
    rc, res, err = harness(checkout, name, seed=21, newton=fault)
    assert rc == 0, err
    assert res["correct"] is False
    assert res["failed"] == res["attempted"] >= 1


def test_the_sound_program_is_correct(checkout, harness):
    name = add_cell(checkout)
    rc, res, err = harness(checkout, name, seed=21)
    assert rc == 0 and res["correct"] is True, err
