"""The control of ``correct`` at the cells' own sizes.

The configurations state 16-bit words (the paper's Table 1 fabric; the
simulator holds them in int32).  The control is the plain reference
computed in the next narrower words, int8, put in the program's place:
the exact comparison must call it wrong on every seed on which 8 bits
change an answer, and int16 must hold every answer."""
import os

import numpy as np
import pytest

from chipbench import control, harness, lanes

SEEDS = tuple(range(1, 13)) + (2 ** 31 + 5,)


def _grid(workload, seed):
    cell = harness.load_cell(workload)
    return lanes.Grid(cell.config, seed,
                      os.path.join(harness.BENCH_DIR, "kinds"))


def test_int8_control_fails_the_eval_grid_on_every_seed():
    got = control.readings("eval4x4.grid", SEEDS)
    for seed in SEEDS:
        assert got[seed]["int8"] > 0
        assert got[seed]["int16"] == 0


def test_int8_control_fails_exactly_where_8_bits_change_an_answer():
    """A seed whose Fig. 17 answers all fit 8 bits would leave int8 exact
    and no fault, and the control would pass for that reason alone."""
    got = control.readings("scaling.packed", SEEDS)
    failing = 0
    for seed in SEEDS:
        g = _grid("scaling.packed", seed)
        info = np.iinfo(np.int8)
        wide = sum(bool(((g.reference(p) > info.max)
                         | (g.reference(p) < info.min)).any())
                   for p in g.points)
        assert got[seed]["int8"] == wide
        assert got[seed]["int16"] == 0
        failing += wide > 0
    assert failing >= len(SEEDS) - 3


@pytest.mark.parametrize("workload", ["eval4x4.grid", "scaling.packed"])
def test_reference_matches_itself_in_wide_words(workload):
    g = _grid(workload, 7)
    assert control.wrong_answers(g, np.int64) == 0
