"""Every PE-tick an engine call steps is accounted for.

``EngineTelemetry`` splits ``stepped_pe_ticks`` into ticks that simulated
a cycle (``live``), ticks of lanes that had already finished while
others stepped (``finished``), the chunk tail past a shard's last cycle
(``tail``) and rows that carry no lane (``pad``).  The four must sum to
the stepped ticks exactly on every path that calls the engine, and the
live ticks must equal the simulated PE-cycles wherever nothing is
fast-forwarded.
"""
import numpy as np
import pytest

from repro.core import compiler
from repro.core.machine import MachineConfig, engine_call_ticks
from repro.core.sweep import SweepRequest, sweep
from repro.serve import DeadlineError, SweepService

SPLIT = ("live_pe_ticks", "finished_pe_ticks", "tail_pe_ticks",
         "pad_pe_ticks")


def _cfg(w=4, h=4, **kw):
    kw.setdefault("mem_words", 1024)
    kw.setdefault("max_cycles", 100_000)
    return MachineConfig(width=w, height=h, **kw)


@pytest.fixture(scope="module")
def mixed():
    """Four spmv lanes of different meshes and lengths."""
    rng = np.random.default_rng(23)
    wls = []
    for n, m in ((2, 6), (3, 9), (4, 6), (2, 12)):
        a = compiler.random_sparse(m, m, 0.4, rng)
        x = rng.integers(-3, 4, size=(m,))
        wls.append(compiler.build_spmv(a, x, _cfg(n, n)))
    return wls


def _pe_cycles(wls, results):
    return sum(r.cycles * wl.geom[0] * wl.geom[1]
               for wl, r in zip(wls, results))


def _assert_split(tel):
    parts = {k: getattr(tel, k) for k in SPLIT}
    assert all(v >= 0 for v in parts.values()), parts
    assert sum(parts.values()) == tel.stepped_pe_ticks, tel.to_json()


@pytest.mark.parametrize("pack,shard", [(False, False), (True, False),
                                        (False, True), (True, True)],
                         ids=["unpacked", "packed", "sharded",
                              "packed-sharded"])
def test_sweep_accounts_every_stepped_tick(mixed, pack, shard):
    """``shard`` splits the lanes over every device the host shows (one
    device: the plain engine)."""
    rep = sweep(_cfg(), SweepRequest(workloads=mixed, pack=pack,
                                     shard=shard))
    tel = rep.telemetry
    _assert_split(tel)
    assert tel.live_pe_ticks > 0 and tel.tail_pe_ticks > 0
    # lanes of unequal length share a device shard (unless each has a
    # device of its own), and the 2x2 and 3x3 lanes leave rows of the
    # 4x4 PE axis empty when unpacked
    if rep.shard is None or rep.shard.lanes_per_device > 1:
        assert tel.finished_pe_ticks > 0
    if not pack:
        assert tel.pad_pe_ticks > 0
    assert tel.live_pe_ticks == _pe_cycles(mixed, rep)
    assert tel.to_json()["live_pe_ticks"] == tel.live_pe_ticks


def test_sweep_with_deadlines_accounts_every_stepped_tick(mixed):
    rep = sweep(_cfg(), SweepRequest(workloads=mixed,
                                     deadlines=[3, None, 7, None]))
    assert [r.cycles for r in rep][0::2] == [3, 7]
    _assert_split(rep.telemetry)


def test_service_drain_accounts_every_stepped_tick(mixed):
    with SweepService(_cfg(), template=mixed, n_supers=2,
                      slice_chunks=1) as svc:
        futs = [svc.submit(wl, mode="nexus") for wl in mixed]
        futs.append(svc.submit(mixed[1], mode="tia", deadline_cycles=5))
        svc.drain(timeout=600)
        tel = svc.telemetry
    assert all(f.done() for f in futs)
    with pytest.raises(DeadlineError):
        futs[-1].result()
    assert tel.engine_calls > 1
    _assert_split(tel)


@pytest.mark.parametrize("path", ["unpacked", "packed", "service"])
def test_live_ticks_are_the_simulated_pe_cycles(mixed, path):
    """Without fast-forward every live tick simulates one PE-cycle."""
    cfg = _cfg(fast_forward=False)
    if path == "service":
        with SweepService(cfg, template=mixed, n_supers=2,
                          slice_chunks=1) as svc:
            futs = [svc.submit(wl, mode="nexus") for wl in mixed]
            svc.drain(timeout=600)
            tel = svc.telemetry
        results = [f.result() for f in futs]
    else:
        rep = sweep(cfg, SweepRequest(workloads=mixed,
                                      pack=path == "packed"))
        tel, results = rep.telemetry, list(rep)
    _assert_split(tel)
    assert tel.live_pe_ticks == _pe_cycles(mixed, results)


def test_engine_call_ticks_by_hand():
    """Two device shards of two lanes, four PE rows each."""
    ticks = np.array([8, 8, 4, 4])
    cycle0 = np.zeros((4, 4), np.int32)
    cycle0[2] = 10                   # a resumed lane
    cycle1 = np.array([[5, 5, 5, 0],     # 3 lane rows, one empty row
                       [8, 8, 8, 8],
                       [13, 13, 13, 13],
                       [0, 0, 0, 0]])    # an inert shard-pad lane
    rows = np.array([[1, 1, 1, 0], [1, 1, 1, 1],
                     [1, 1, 1, 1], [0, 0, 0, 0]], bool)
    got = engine_call_ticks(ticks, cycle0, cycle1, rows, 2, 4)
    assert got == dict(
        stepped_pe_ticks=8 * 8 + 4 * 8,
        plain_pe_ticks=8 * 8 + 4 * 8,
        live_pe_ticks=3 * 5 + 4 * 8 + 4 * 3,
        finished_pe_ticks=3 * 3,
        tail_pe_ticks=4 * 1,
        pad_pe_ticks=8 * 1 + 4 * 4)
    assert sum(got[k] for k in SPLIT) == got["stepped_pe_ticks"]
