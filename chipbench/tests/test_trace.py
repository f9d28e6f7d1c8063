"""The trace reduction, on hand-made intervals and on a small trace
recorded on a TPU v5e (``data/v5e_sweep_slice.json``)."""
import json
import os
import types

import numpy as np
import pytest

from chipbench import measures
from chipbench import trace as T

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "v5e_sweep_slice.json")


def _planes(doc):
    ev = lambda e: types.SimpleNamespace(name=e[0], start_ns=e[1],
                                         duration_ns=e[2])
    return [types.SimpleNamespace(
        name=p["name"],
        lines=[types.SimpleNamespace(name=ln["name"],
                                     events=[ev(e) for e in ln["events"]])
               for ln in p["lines"]]) for p in doc["planes"]]


def test_merge_cover_and_gaps():
    m = T.merge([(5, 9), (0, 2), (1, 3), (9, 10), (20, 25)])
    assert m == [(0, 3), (5, 10), (20, 25)]
    assert T.covered(m, 2, 22) == 1 + 5 + 2
    assert T.gaps(m, 0, 30) == [(3, 5), (10, 20), (25, 30)]


def test_self_time_subtracts_enclosed_ops():
    events = [(0, 100, "%while"), (10, 30, "%fusion.1"), (40, 50, "%add"),
              (42, 45, "%inner"), (200, 210, "%after")]
    got = T.self_times(events, 0, 150)
    assert got["%while"] == pytest.approx(70e-9)
    assert got["%add"] == pytest.approx(7e-9)
    assert got["%inner"] == pytest.approx(3e-9)
    assert "%after" not in got
    # clipped to a window that cuts the loop and its first child
    got = T.self_times(events, 20, 60)
    assert got["%while"] == pytest.approx(20e-9)
    assert got["%fusion.1"] == pytest.approx(10e-9)
    assert sum(got.values()) == pytest.approx(40e-9)


def test_op_name_is_the_hlo_instruction():
    assert T.op_name("%fusion.12 = s32[16]{0} fusion(%a)") == "%fusion.12"


def _brute(doc, chip_plane):
    """Busy nanoseconds of the window by marking a boolean timeline."""
    host = [p for p in doc["planes"] if p["name"] == T.HOST_PLANE][0]
    (lo, hi), = [(e[1], e[1] + e[2]) for ln in host["lines"]
                 for e in ln["events"] if e[0] == "chipbench.window"]
    mark = np.zeros(hi - lo, bool)
    for ln in chip_plane["lines"]:
        if ln["name"] == T.OPS_LINE:
            for _, s, d in ln["events"]:
                mark[max(s, lo) - lo:max(min(s + d, hi) - lo, 0)] = True
    return int(mark.sum()), hi - lo


def test_recorded_trace_reduces_like_a_brute_force_timeline():
    with open(FIXTURE) as f:
        doc = json.load(f)
    tr = T.from_planes(_planes(doc), chips=1)
    chip = [p for p in doc["planes"] if p["name"] == "/device:TPU:0"][0]
    busy, span = _brute(doc, chip)
    lo, hi = tr.window
    assert hi - lo == span
    assert tr.busy_ns(0, lo, hi) == busy
    ctx = types.SimpleNamespace(trace=tr, n_devices=1, stepped_pe_ticks=1000)
    assert measures.device_idle_share(ctx) == pytest.approx(1 - busy / span)
    assert measures.engine_ns_per_pe_tick(ctx) == pytest.approx(busy / 1000)
    bd = tr.breakdown()
    assert 0 < len(bd["device_ops"]) <= T.TOP
    assert all(s > 0 for _, s in bd["device_ops"])
    # nested ops' self times partition the busy time
    assert sum(tr.op_s[0].values()) == pytest.approx(busy / 1e9)
    gap_total = sum(s for _, s in bd["idle_gaps"])
    assert gap_total <= (span - busy) / 1e9 + 1e-12
    assert {n for n, _ in bd["idle_gaps"]} <= {
        "compile", "sweep", "request", "client_wait", "outside_spans"}


def test_per_request_spans_and_host_time():
    with open(FIXTURE) as f:
        doc = json.load(f)
    tr = T.from_planes(_planes(doc), chips=1)
    ctx = types.SimpleNamespace(trace=tr)
    (s, e), = tr.spans_named("sweep")
    assert measures.per_request_span_s(ctx, "sweep") == pytest.approx(
        (e - s) / 1e9)
    assert measures.host_s_in(ctx, "sweep") == pytest.approx(
        ((e - s) - tr.any_busy_ns(s, e)) / 1e9)


def test_a_trace_without_a_window_is_refused():
    with pytest.raises(ValueError):
        T.from_planes([], chips=1)


def test_program_runs_are_counted_per_chip_inside_the_window():
    planes = _planes({"planes": [
        {"name": T.HOST_PLANE, "lines": [{"name": "python", "events": [
            ["chipbench.window", 100, 900]]}]},
        {"name": "/device:TPU:0", "lines": [{"name": T.MODULES_LINE,
                                             "events": [
            ["jit_engine_fn(12)", 50, 40], ["jit_engine_fn(12)", 150, 300],
            ["jit__install_fn(7)", 500, 10], ["jit_engine_fn(12)", 600, 200],
            ["jit_engine_fn(12)", 1000, 50]]}]},
        {"name": "/device:TPU:1", "lines": [{"name": T.MODULES_LINE,
                                             "events": [
            ["jit_engine_fn(12)", 150, 300]]}]}]})
    tr = T.from_planes(planes, chips=2)
    assert tr.runs[0] == {"jit_engine_fn": 2, "jit__install_fn": 1}
    assert tr.runs[1] == {"jit_engine_fn": 1}
