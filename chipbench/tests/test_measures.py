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


def _svc_lane(t_due, t_done, result=True):
    p = Point("x", 0, "nexus", "rows", (4, 4))
    return harness.Lane(p, result=object() if result else None,
                        t_due=t_due, t_done=t_done)


def _svc_ctx(lanes, **kw):
    stats_open = dict(n_slices=10, occupancy_sum=4.0, stepped_pe_ticks=1000,
                      live_pe_ticks=300)
    stats_close = dict(n_slices=30, occupancy_sum=19.0,
                       stepped_pe_ticks=5000, live_pe_ticks=1500)
    return harness.Window(
        cell=None, setup_s=1.0, t_open=100.0, t_close=110.0, lanes=lanes,
        requests=[], stepped_pe_ticks=4000, n_devices=1,
        stats_open=stats_open, stats_close=stats_close, **kw)


def _read(name, ctx):
    return harness.load_metric(BENCH, name).read(ctx)


def test_lane_p90_is_the_nearest_rank_of_due_to_result():
    lanes = [_svc_lane(100.0 + i, 100.0 + i + (i + 1) / 10)
             for i in range(20)]                  # latencies 0.1 .. 2.0
    ctx = _svc_ctx(lanes)
    assert _read("lane_p90_s", ctx) == pytest.approx(1.8)   # rank 18 of 20
    assert measures.lane_percentile_s(ctx, 0.5) == pytest.approx(1.0)
    # two lanes that never came lie beyond every finished one
    lanes[0] = _svc_lane(100.0, None, result=False)
    lanes[1] = _svc_lane(101.0, 101.5, result=False)
    assert _read("lane_p90_s", _svc_ctx(lanes)) == pytest.approx(2.0)
    for i in range(2, 4):
        lanes[i] = _svc_lane(100.0 + i, None, result=False)
    assert _read("lane_p90_s", _svc_ctx(lanes)) is None


def test_service_counters_read_the_change_over_the_window():
    ctx = _svc_ctx([])
    assert _read("service_occupancy", ctx) == pytest.approx(15.0 / 20)
    assert _read("engine_live_share.service", ctx) == pytest.approx(
        1200 / 4000)
    none = _ctx([], stats_open=None, stats_close=None)
    for name in ("service_occupancy", "engine_live_share.service"):
        assert _read(name, none) is None


class _Trace:
    window = (0, 1000)

    def busy_ns(self, chip, lo, hi):
        return 250 if chip == 0 else 500


def test_service_device_readers_use_the_trace_and_the_stats_change():
    ctx = _svc_ctx([], trace=_Trace())
    assert _read("engine_ns_per_pe_tick.service", ctx) == pytest.approx(
        250 / 4000)
    assert _read("device_idle_share.service", ctx) == pytest.approx(0.75)
    ctx.n_devices = 2
    assert _read("device_idle_share.service", ctx) == pytest.approx(0.625)
    assert measures.engine_ns_per_pe_tick(ctx) == pytest.approx(750 / 4000)


@pytest.mark.parametrize("runs,whole", [(20, True), (19, False), (0, False)])
def test_a_trace_short_of_engine_runs_reports_no_device_trace_metric(
        runs, whole):
    """The window made 20 engine calls; a trace holding fewer runs of the
    engine was cut short, and its device times are not reported."""
    cell = harness.load_cell("eval4x4.service")
    tr = _Trace()
    tr.runs = {0: {"jit_engine_fn": runs}}
    metrics, seen = harness.read_traced(cell, _svc_ctx([], trace=tr))
    assert seen["whole"] is whole
    assert seen["engine_calls"] == 20 and seen["engine_runs"] == [runs]
    assert seen["busy_s"] == [250 / 1e9] and seen["window_s"] == 1000 / 1e9
    device = {m["name"] for m in cell.per_layer
              if m["source"] == "device_trace"}
    counters = {m["name"] for m in cell.per_layer} - device
    assert device and counters
    assert set(metrics) == (device | counters if whole else counters)
