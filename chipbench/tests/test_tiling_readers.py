"""The readers of the tiled cell: the tile planner's and the tile fold's
host seconds, and the tiles' AM fill, on fabricated windows."""
import types

import pytest

from chipbench import program_trace as P
from chipbench.tests.test_program_trace import OPS, MODULES, _ctx, _read, \
    _window

HOST = [("chipbench.window", 0, 1000), ("chipbench.request", 0, 1000),
        ("chipbench.compile", 0, 100), ("repro.compile.tile_plan", 10, 40),
        ("chipbench.sweep", 100, 900), ("repro.sweep.validate", 100, 200),
        ("repro.sweep.place", 300, 100), ("repro.engine.dispatch", 400, 10),
        ("repro.engine.wait", 410, 390), ("repro.sweep.unpack", 800, 150),
        ("repro.sweep.tile_fold", 880, 100)]


def test_tile_span_readers_subtract_device_time():
    planes = _window(HOST, OPS, MODULES)
    _, prog = P.spans_of(planes)
    ctx = _ctx(planes, program_spans=prog)
    assert _read("tile_plan_s.sweep", ctx) == pytest.approx(40e-9)
    # the fold's 100 ns overlap the op at 900-920
    assert _read("sweep_tile_fold_s.sweep", ctx) == pytest.approx(80e-9)


def test_tile_span_readers_give_none_without_the_spans():
    planes = _window(HOST[:3] + HOST[4:10], OPS, MODULES)
    _, prog = P.spans_of(planes)
    ctx = _ctx(planes, program_spans=prog)
    for name in ("tile_plan_s.sweep", "sweep_tile_fold_s.sweep"):
        assert _read(name, ctx) is None


def test_tile_am_fill_is_the_mean_over_requests():
    req = lambda fill: (0, 1, types.SimpleNamespace(
        tiles=None if fill is None else types.SimpleNamespace(am_fill=fill)))
    ctx = types.SimpleNamespace(requests=[req(0.6), req(0.7), req(None)])
    assert _read("tile_am_fill", ctx) == pytest.approx(0.65)
    plain = types.SimpleNamespace(requests=[req(None)])
    assert _read("tile_am_fill", plain) is None
    # a report from a program without tiled lanes has no such field
    old = types.SimpleNamespace(requests=[(0, 1, types.SimpleNamespace())])
    assert _read("tile_am_fill", old) is None
