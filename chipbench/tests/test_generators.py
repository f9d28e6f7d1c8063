"""Every generator reproduces its data from the same seed, with the same
shapes, and so the same simulated work, on every seed."""
import json
import os

import numpy as np
import pytest

from chipbench import lanes, witness
from chipbench.tests.conftest import BENCH, TINY_CONFIG

CONFIGS = ("paper_eval_4x4", "paper_scaling_fig17")
SEEDS = (0, 7, 2 ** 31 + 12345)


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def _same(a, b):
    return all(np.array_equal(a[k], b[k]) for k in a) and a.keys() == b.keys()


@pytest.mark.parametrize("name", CONFIGS)
def test_grid_data_repeats_per_seed(name):
    kinds = os.path.join(BENCH, "kinds")
    for seed in SEEDS:
        g1 = lanes.Grid(_config(name), seed, kinds)
        g2 = lanes.Grid(_config(name), seed, kinds)
        assert all(_same(a, b) for a, b in zip(g1.data, g2.data))
    g3 = lanes.Grid(_config(name), SEEDS[0] + 1, kinds)
    assert not all(_same(a, b) for a, b in zip(g1.data, g3.data))


@pytest.mark.parametrize("name", CONFIGS)
def test_every_seed_gives_the_same_shapes(name):
    """Sparsity patterns, graphs and weights are the configuration's own;
    the seed changes nonzero values alone."""
    kinds = os.path.join(BENCH, "kinds")
    grids = [lanes.Grid(_config(name), s, kinds) for s in SEEDS]
    for datas in zip(*(g.data for g in grids)):
        for key in datas[0]:
            for d in datas[1:]:
                assert np.array_equal(d[key] != 0, datas[0][key] != 0)
                if key in ("rowptr", "col", "wgt", "rank", "mask"):
                    assert np.array_equal(d[key], datas[0][key])


def test_every_seed_simulates_the_same_work():
    """Two seeds' grids, run on the CPU, take the same cycles, hops and
    per-PE busy counts lane by lane; only the memory image differs."""
    recs = [witness.cpu_records(
        lanes.Grid(TINY_CONFIG, s, os.path.join(BENCH, "kinds")), False)
        for s in (1, 2 ** 31 + 99)]
    assert recs[0].keys() == recs[1].keys()
    for label in recs[0]:
        assert recs[0][label][:-1] == recs[1][label][:-1]
    assert any(recs[0][k][-1] != recs[1][k][-1] for k in recs[0])


def test_grid_points_in_sweep_order():
    g = lanes.Grid(_config("paper_eval_4x4"), 1, os.path.join(BENCH, "kinds"))
    assert len(g.points) == 39
    assert [p.mode for p in g.points[:13]] == ["nexus"] * 13
    g = lanes.Grid(_config("paper_scaling_fig17"), 1,
                   os.path.join(BENCH, "kinds"))
    assert [p.size for p in g.points] == [(2, 2)] * 3 + [(4, 4)] * 3 + \
        [(8, 8)] * 3


def test_negative_seed_is_refused():
    with pytest.raises(ValueError):
        lanes.Grid(_config("paper_eval_4x4"), -1, os.path.join(BENCH, "kinds"))
