"""The open-loop service client, its schedule, and the choice of client
by the mix's ``loop``."""
import concurrent.futures
import importlib
import threading
import time

import numpy as np
import pytest

from chipbench import harness, lanes, service
from chipbench.tests.conftest import BENCH, TINY_CONFIG, make_bench

SERVICE_MIX = {"loop": "service", "rate": 4.0, "n_supers": 4, "chunk": 512,
               "slice_chunks": 2, "trace_seconds": 1}


@pytest.fixture
def service_bench(tmp_path):
    return make_bench(tmp_path, mixes={"service": SERVICE_MIX})


def _gaps(due, seconds):
    return np.sort(np.diff(np.append(due, seconds)))


def _rounds(pts):
    """Each lane's round: how many lanes of its point fell due before."""
    seen = {}
    out = []
    for p in pts:
        out.append(seen.get(p, 0))
        seen[p] = out[-1] + 1
    return np.array(out)


@pytest.mark.parametrize("rate,seconds", [(5.0, 36.0), (2.8, 36.0),
                                          (4.0, 3.0)])
def test_every_seed_gets_the_same_lanes_and_gaps_in_its_own_order(
        rate, seconds):
    a_due, a_pts = service.schedule(rate, seconds, 39, 7)
    b_due, b_pts = service.schedule(rate, seconds, 39, 2 ** 31 + 12345)
    n = round(rate * seconds)
    for due, pts in ((a_due, a_pts), (b_due, b_pts)):
        assert len(due) == len(pts) == n
        assert due[0] == 0 and np.all(np.diff(due) > 0)
        assert due[-1] < seconds
        # a lane never falls due ahead of a lane two rounds before it
        r = _rounds(pts)
        assert np.all(np.maximum.accumulate(r) - r <= 1)
    # the arrivals are the same for every seed; only the points move
    np.testing.assert_array_equal(a_due, b_due)
    assert np.array_equal(np.sort(a_pts), np.sort(b_pts))
    counts = np.bincount(a_pts, minlength=39)
    assert counts.max() - counts.min() <= 1
    if n > 2:
        assert not np.array_equal(a_pts, b_pts)
    again = service.schedule(rate, seconds, 39, 7)
    assert np.array_equal(again[0], a_due) and np.array_equal(again[1], a_pts)


def test_gaps_are_exponential_quantiles_at_the_rate():
    due, _ = service.schedule(5.0, 36.0, 39, 1)
    gaps = _gaps(due, 36.0)
    q = (np.arange(180) + 0.5) / 180
    want = -np.log1p(-q) / 5.0
    np.testing.assert_allclose(gaps, want * 36.0 / want.sum(), rtol=1e-12)
    assert abs(36.0 / want.sum() - 1) < 0.003


def test_a_mix_without_loop_builds_the_same_blocking_request(monkeypatch):
    """Without ``loop`` the harness keeps its blocking client, and its
    request is the one it always sent, sharding off."""
    sweep_mod = importlib.import_module("repro.core.sweep")
    seen = []

    def fake(cfg, req):
        seen.append(req)
        raise RuntimeError("stop")
    monkeypatch.setattr(sweep_mod, "sweep", fake)
    grid = lanes.Grid(TINY_CONFIG, 3, BENCH + "/kinds")
    for mix, shard in (({"pack": True, "trace_seconds": 1}, False),
                       ({"pack": True, "shard": True,
                         "trace_seconds": 1}, True)):
        client = harness.make_client(grid, mix, 3)
        assert type(client) is harness.ClosedSweep
        with pytest.raises(RuntimeError):
            client.request()
        req = seen[-1]
        assert req.shard is shard and req.pack is True
        assert req.modes == tuple(p.mode for p in grid.points)
        assert req.deadlines is None and req.chunk == 512
        assert req.validate == "static" and req.geoms is None
        assert len(req.workloads) == len(grid.points)


def test_service_client_judges_every_lane_correct(service_bench):
    root, bench = service_bench
    seconds = 3.0
    res = harness.run("tiny.service", 2 ** 31 + 99, seconds, False,
                      t_start=time.perf_counter(), root=root,
                      bench_dir=bench, require_chip=False)
    assert res["correct"], res["checks"]
    assert res["attempted"] == round(SERVICE_MIX["rate"] * seconds)
    assert res["failed"] == 0
    assert set(res["metrics"]) == {"lane_p90_s", "setup_s"}
    assert res["metrics"]["lane_p90_s"]["value"] > 0


class _StubGrid:
    """Three points; the first compile takes a second."""

    def __init__(self):
        self.points = [lanes.Point("x", 0, "nexus", "rows", (2, 2))] * 3
        self.calls = 0
        self.lock = threading.Lock()

    def build(self, point):
        with self.lock:
            self.calls += 1
            first = self.calls == 1
        if first:
            time.sleep(1.0)
        return object()


class _StubService:
    stats = {"n_slices": 0, "stepped_pe_ticks": 0}

    def submit(self, wl, mode):
        fut = concurrent.futures.Future()
        fut.set_result(object())
        return fut


def test_a_slow_compile_does_not_hold_up_the_lanes_behind_it():
    """The lanes are taken up by several sender threads: while the first
    lane compiles for a second, the next ones fall due and go out on
    time."""
    client = object.__new__(service.OpenService)
    client.grid, client.svc = _StubGrid(), _StubService()
    client.rate, client.seed = 6.0, 5
    got = client.window(1.0, time.perf_counter())
    lanes_ = got["lanes"]
    assert len(lanes_) == 6 and all(ln.t_done is not None for ln in lanes_)
    assert got["generator_late_s"] < 0.3
    # one thread taking the lanes in turn would start the second after
    # the first lane's second of compiling
    assert lanes_[1].t_due - lanes_[0].t_due < 0.7
