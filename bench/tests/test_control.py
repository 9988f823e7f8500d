"""The control: the reference computed from bfloat16 inputs, put in the
program's place, must fail a limit that the program itself passes."""

import pytest

from conftest import DOMAIN_CAP, ROWS, SEED, run_cell


@pytest.mark.parametrize("name", ["favorita.ridge", "retailer.ridge"])
def test_control_fails_where_the_program_passes(name):
    from bench import run as R
    from bench.tools.readings import control

    r = run_cell(name, seconds=0.5)
    limits = {k: v["limit"] for k, v in r["checks"].items()}
    assert r["correct"], r["checks"]
    cell = R.Cell(name)
    low = control(cell, SEED, cell.cfg.shrunk(ROWS, DOMAIN_CAP))
    assert any(low[k] > limits[k] for k in limits), (low, limits)
