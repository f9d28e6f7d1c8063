"""The arithmetic of the end-to-end and per-layer metrics."""
import types

import pytest

from chipbench import harness, measures
from chipbench.lanes import Point
from chipbench.tests.conftest import BENCH


def _lane(size, cycles=None):
    p = Point("x", 0, "nexus", "rows", size)
    r = None if cycles is None else types.SimpleNamespace(cycles=cycles)
    return harness.Lane(p, result=r)


def _ctx(lanes, t_open=10.0, t_close=14.0, requests=(), **kw):
    return types.SimpleNamespace(lanes=lanes, t_open=t_open, t_close=t_close,
                                 window_s=t_close - t_open,
                                 requests=list(requests), **kw)


def test_pe_cycles_use_each_lanes_own_mesh():
    # a 2x2 lane packed into an 8x8 super-lane counts 4 PEs, not 64
    lanes = [_lane((2, 2), 100), _lane((8, 8), 10), _lane((4, 4))]
    assert measures.pe_cycles(lanes) == 100 * 4 + 10 * 64


def test_sim_rate_over_window_to_last_request_end():
    read = harness.load_metric(BENCH, "sim_pe_cycles_per_s").read
    lanes = [_lane((4, 4), 1000), _lane((4, 4), 3000)]
    ctx = _ctx(lanes, t_open=1.0, t_close=5.0, requests=[object()])
    assert read(ctx) == pytest.approx(4000 * 16 / 4.0)
    assert read(_ctx(lanes)) is None          # no blocking requests


def test_setup_and_counters_pass_through():
    ctx = types.SimpleNamespace(setup_s=12.5, requests=[])
    assert harness.load_metric(BENCH, "setup_s").read(ctx) == 12.5
    assert harness.load_metric(BENCH, "pack_efficiency").read(ctx) is None
