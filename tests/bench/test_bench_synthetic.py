"""The paper's synthetic cell: its configuration as the harness loads it,
and the program against the plain reference on a problem of the same
shape scaled down, on the CPU."""
import dataclasses
import os

import pytest

from bench import cell as cells, check, work
from repro.core import make_code

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# Scaled down from 300,000 x 3000 with the cell's sketch rule (10 d // b
# + 1) b: both product codes have g >= 3 and a ragged last grid row.
SCALED = {"n": 6000, "d": 300, "coded_block_rows": 16,
          "sketch": {"sketch_dim": 3008, "block_size": 64,
                     "straggler_tolerance": 0.25}}


@pytest.fixture(scope="module")
def cell():
    return cells.load(REPO, "synthetic-logistic")


def test_synthetic_cell_loads_at_its_published_size(cell):
    cfg = cell.config
    assert (cfg["n"], cfg["d"], cfg["n_test"]) == (300_000, 3000, 0)
    assert cell.chips == 1 and cell.traffic["iters"] == 8
    assert set(cfg["reduced"]) == {"n_test"}
    # The paper's sketch for its synthetic problem: sketch_dim_mult 10.
    assert work.sketch_blocks(cfg["newton"]["sketch"]) == (148, 118, 256)
    assert cfg["newton"]["sketch"]["sketch_dim"] == \
        (10 * cfg["d"] // 256 + 1) * 256


def test_program_matches_the_reference_at_a_scaled_synthetic_shape(cell):
    n, d, br = SCALED["n"], SCALED["d"], SCALED["coded_block_rows"]
    for rows in (n, d):
        code = make_code(rows, br)
        assert code.grid >= 3
        assert rows < code.padded_blocks * br
    newton = dict(cell.config["newton"], coded_block_rows=br,
                  sketch=SCALED["sketch"])
    small = dataclasses.replace(
        cell, config=dict(cell.config, n=n, d=d, newton=newton))
    x, y = cells.make_data(small, seed=15)
    iters = int(small.traffic["iters"])
    answers = [cells.solve(small, x, y, cells.solve_seed(15, i), iters)
               for i in range(2)]
    verdict = check.judge(small.objective, x, y, small.config, answers,
                          cells.limits_of(small))
    assert verdict["failed"] == 0, verdict
