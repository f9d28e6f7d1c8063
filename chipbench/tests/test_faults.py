"""The harness's comparison catches a broken timed path.

Each test drives a whole run of a tiny cell on the CPU (the look for a
chip skipped) with the engine broken underneath the window, the way a
wrong optimisation would break it, and sees ``correct`` come out false.
The sound run beside them comes out true.  The blocking client and the
open-loop service client are each broken in their own window; the
four-chip cell's fault, the lanes of three chips never coming back, is
in ``test_sharded.py``.

The witnesses of the statistics run after the window, on the engine as
the program has it; a fault planted in the window alone is one the chip
path makes, and one planted for the whole run is a change to the
program's timing model, which only the committed golden record sees.
"""
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness, service
from chipbench.tests.conftest import make_bench
from chipbench.tests.test_service import SERVICE_MIX


def _copy(st):
    return jax.tree.map(jnp.copy, st)


def _unchanged(st_in, st_out):
    """A step that returns the state it was given."""
    return st_in


def _half_batch(st_in, st_out):
    """The second half of the batch never stepped."""
    b = st_out.cycle.shape[0]
    if b < 2:
        return st_in
    return jax.tree.map(lambda new, old: new.at[b // 2:].set(old[b // 2:]),
                        st_out, st_in)


def _altered(st_in, st_out):
    """One answer altered where it is produced: every memory word of the
    first lane one higher."""
    return st_out._replace(mem_val=st_out.mem_val.at[0].add(1))


def _hops(st_in, st_out):
    """Statistics altered, answers kept: every link traversal counted
    twice on the first lane."""
    return st_out._replace(st_hops=st_out.st_hops.at[0].multiply(2))


def _broken(engine, fault):
    def broken(*args):
        st_in = _copy(args[5])
        st, over, idle, ticks = engine(*args)
        return fault(st_in, st), over, idle, ticks
    return broken


def _break_engine(monkeypatch, fault):
    """Break every engine the program hands out from now on."""
    from repro.core import machine
    real_get = machine._get_engine
    monkeypatch.setattr(machine, "_get_engine", lambda *x, **k:
                        _broken(real_get(*x, **k), fault))


def _break_window(monkeypatch, fault):
    """Break the engine that the window drives, for the window alone: the
    blocking sweep asks ``machine._get_engine`` on every request."""
    closed = harness.ClosedSweep.window

    def closed_window(self, *a):
        with pytest.MonkeyPatch.context() as mp:
            _break_engine(mp, fault)
            return closed(self, *a)

    monkeypatch.setattr(harness.ClosedSweep, "window", closed_window)


def _break_service_window(monkeypatch, fault):
    """Break the resident service's engine for the window alone: the
    service fetched its engine once, at set-up.  A lane that never comes
    is given up sooner than on the chip."""
    real = service.OpenService.window

    def window(self, *a):
        engine = self.svc._engine
        self.svc._engine = _broken(engine, fault)
        try:
            return real(self, *a)
        finally:
            self.svc._engine = engine

    monkeypatch.setattr(service.OpenService, "window", window)
    monkeypatch.setattr(service.OpenService, "DRAIN_S", 5.0)


@pytest.fixture
def service_bench(tmp_path):
    # lanes due faster than a slice retires them, so every super-lane of
    # the service holds one
    return make_bench(tmp_path, mixes={"service": dict(SERVICE_MIX,
                                                       rate=30.0)})


def _run_service(service_bench):
    root, bench = service_bench
    return harness.run("tiny.service", 2 ** 31 + 98, 1.0, False,
                       t_start=time.perf_counter(), root=root,
                       bench_dir=bench, require_chip=False)


def _run(tiny_bench):
    root, bench = tiny_bench
    return harness.run("tiny.closed", 2 ** 31 + 99, 0.01, False,
                       t_start=time.perf_counter(), root=root,
                       bench_dir=bench, require_chip=False)


def test_sound_run_is_correct(tiny_bench):
    res = _run(tiny_bench)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["checks"]) == {"wrong_answers", "unfinished_lanes",
                                  "stat_drift", "golden_drift"}


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _altered])
def test_broken_engine_is_not_correct(tiny_bench, monkeypatch, fault):
    _break_window(monkeypatch, fault)
    res = _run(tiny_bench)
    assert not res["correct"], res["checks"]
    assert res["failed"] > 0


def test_statistics_drift_is_caught_by_the_cpu_witness(tiny_bench,
                                                       monkeypatch):
    """Answers right, statistics off on the timed path alone."""
    _break_window(monkeypatch, _hops)
    res = _run(tiny_bench)
    checks = {k: c["value"] for k, c in res["checks"].items()}
    assert checks["wrong_answers"] == 0 and checks["stat_drift"] > 0
    assert not res["correct"]


def test_timing_model_change_is_caught_by_the_golden(tiny_bench,
                                                     monkeypatch):
    """The statistics changed for the whole program, witnesses included:
    the chip and the CPU agree, and only the committed record differs."""
    _break_engine(monkeypatch, _hops)
    res = _run(tiny_bench)
    checks = {k: c["value"] for k, c in res["checks"].items()}
    assert checks["wrong_answers"] == 0 and checks["stat_drift"] == 0
    assert checks["golden_drift"] > 0 and not res["correct"]


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _altered])
def test_broken_service_engine_is_not_correct(service_bench, monkeypatch,
                                              fault):
    _break_service_window(monkeypatch, fault)
    res = _run_service(service_bench)
    assert not res["correct"], res["checks"]
    assert res["failed"] > 0


def test_service_statistics_drift_is_caught_by_the_cpu_witness(
        service_bench, monkeypatch):
    """Answers right, statistics off in the service's window alone."""
    _break_service_window(monkeypatch, _hops)
    res = _run_service(service_bench)
    checks = {k: c["value"] for k, c in res["checks"].items()}
    assert checks["wrong_answers"] == 0 and checks["stat_drift"] > 0
    assert not res["correct"]
