"""Batched on-device execution (`machine.run_many`): per-lane metrics must
be bit-identical to sequential `machine.run`, early-idle lanes must freeze
at their own cycle count, padding must be semantically inert, and the
pending-FIFO overflow guard must still fire."""
import numpy as np
import pytest

from repro.core import batch, compiler, machine
from repro.core.machine import MachineConfig

RNG = np.random.default_rng(23)


def _cfg(**kw):
    kw.setdefault("mem_words", 1024)
    kw.setdefault("max_cycles", 100_000)
    return MachineConfig(**kw)


def _graph(nv=24, k=4, seed=3):
    import networkx as nx
    g = nx.connected_watts_strogatz_graph(nv, k, 0.3, seed=seed)
    rp = np.zeros((nv + 1,), dtype=np.int64)
    cols = []
    for v in range(nv):
        nbrs = sorted(g.neighbors(v))
        rp[v + 1] = rp[v] + len(nbrs)
        cols.extend(nbrs)
    return rp, np.array(cols, dtype=np.int64)


def _solo(wl, cfg):
    return machine.run(cfg, wl.prog, wl.static_ams, wl.amq_len, wl.mem_val,
                       wl.mem_meta)


def _metrics(r):
    return (r.cycles, r.executed, r.enroute, r.hops, r.injected,
            r.completed)


@pytest.fixture(scope="module")
def mixed():
    """Three mixed workloads on one fabric config: SpMV, SpM+SpM, BFS."""
    cfg = _cfg()
    a = compiler.random_sparse(16, 16, 0.3, RNG)
    b = compiler.random_sparse(16, 16, 0.3, RNG)
    x = RNG.integers(-4, 5, size=(16,))
    rp, col = _graph()
    wls = [
        compiler.build_spmv(a, x, cfg),
        compiler.build_spmadd(a, b, cfg),
        compiler.build_bfs(rp, col, 0, cfg),
    ]
    return cfg, wls


def test_run_many_matches_sequential(mixed):
    cfg, wls = mixed
    solo = [_solo(wl, cfg) for wl in wls]
    batched = machine.run_many(cfg, wls)
    assert len(batched) == len(wls)
    for wl, s, m in zip(wls, solo, batched):
        assert m.completed, wl.name
        assert wl.check(m.mem_val), wl.name
        assert _metrics(m) == _metrics(s), wl.name
        np.testing.assert_array_equal(m.per_pe_busy, s.per_pe_busy)
        np.testing.assert_array_equal(m.stall_per_port, s.stall_per_port)
        assert m.utilization == s.utilization
        assert m.enroute_frac == s.enroute_frac


@pytest.mark.slow
def test_early_idle_lane_freezes(mixed):
    """A tiny lane batched next to a long one reports its OWN cycle count
    (frozen at its individual idle), not the batch maximum."""
    cfg, wls = mixed
    tiny_a = compiler.random_sparse(4, 4, 0.5, RNG)
    tiny_x = RNG.integers(-4, 5, size=(4,))
    tiny = compiler.build_spmv(tiny_a, tiny_x, cfg)
    s_tiny = _solo(tiny, cfg)
    s_big = _solo(wls[2], cfg)
    assert s_tiny.cycles < s_big.cycles  # precondition: lanes finish apart
    m_tiny, m_big = machine.run_many(cfg, [tiny, wls[2]])
    assert _metrics(m_tiny) == _metrics(s_tiny)
    assert _metrics(m_big) == _metrics(s_big)


@pytest.mark.slow
def test_mixed_mem_words_padding_is_inert(mixed):
    """Lanes compiled at different mem_words pad to the common maximum
    without perturbing any metric."""
    cfg, wls = mixed
    big_cfg = _cfg(mem_words=2048)
    a = compiler.random_sparse(12, 12, 0.4, RNG)
    x = RNG.integers(-4, 5, size=(12,))
    wide = compiler.build_spmv(a, x, big_cfg)
    s_small = _solo(wls[0], cfg)
    s_wide = _solo(wide, big_cfg)
    m_small, m_wide = machine.run_many(cfg, [wls[0], wide])
    assert _metrics(m_small) == _metrics(s_small)
    assert _metrics(m_wide) == _metrics(s_wide)
    assert wls[0].check(m_small.mem_val) and wide.check(m_wide.mem_val)


def test_engine_cache_reuse(mixed):
    """Same MachineConfig => one cached engine, and (because the program is
    a traced argument) one XLA executable across different workloads."""
    cfg, wls = mixed
    machine.run_many(cfg, [wls[0]])
    before = machine.engine_cache_size()
    engine = machine._ENGINE_CACHE[machine._engine_key(cfg, cfg.n_pes, 512)]
    traces = engine._cache_size()
    machine.run_many(cfg, [wls[1]])   # different program, same shapes
    assert machine.engine_cache_size() == before
    assert engine._cache_size() == traces


def test_fabric_size_mismatch_rejected_on_static_path(mixed):
    """Without per-lane geometry (bare tuples / traced_geometry=False)
    fabric sizes must still match — the pre-geometry contract."""
    cfg, wls = mixed
    other = MachineConfig(width=2, height=2, mem_words=1024)
    a = compiler.random_sparse(8, 8, 0.4, RNG)
    x = RNG.integers(-4, 5, size=(8,))
    small_fab = compiler.build_spmv(a, x, other)
    # bare tuples carry no geometry: mixed sizes cannot be stacked
    as_tuple = (small_fab.prog, small_fab.static_ams, small_fab.amq_len,
                small_fab.mem_val, small_fab.mem_meta)
    with pytest.raises(ValueError, match="fabric sizes must match"):
        machine.run_many(cfg, [wls[0], as_tuple])
    with pytest.raises(ValueError, match="PEs"):
        machine.run_many(other, [(wls[0].prog, wls[0].static_ams,
                                  wls[0].amq_len, wls[0].mem_val,
                                  wls[0].mem_meta)])
    # a static-geometry engine rejects lanes off the baked-in mesh
    import dataclasses
    static_cfg = dataclasses.replace(cfg, traced_geometry=False)
    with pytest.raises(ValueError, match="traced_geometry"):
        machine.run_many(static_cfg, [wls[0], small_fab])


@pytest.mark.slow
def test_pending_fifo_overflow_guard(monkeypatch):
    """The consumption-guarantee invariant (machine.run_many's RuntimeError)
    still fires: with a tiny pending FIFO, the stream throttle disabled
    and network messages let in on one free slot (no NET_RESERVE, so
    nothing parks), a streaming workload must trip the high-water
    check."""
    monkeypatch.setattr(machine, "PEND_CAP", 4)
    monkeypatch.setattr(machine, "STREAM_THROTTLE", 10**9)
    monkeypatch.setattr(machine, "NET_RESERVE", 1)
    cfg = _cfg()
    a = compiler.random_sparse(16, 16, 0.5, np.random.default_rng(1))
    x = np.random.default_rng(2).integers(-4, 5, size=(16,))
    wl = compiler.build_spmv(a, x, cfg)
    # chunk=1 checks the high-water mark every cycle — the run is far
    # shorter than the default 512-cycle chunk, which would only sample
    # the (already drained) FIFO after global idle.
    with pytest.raises(RuntimeError, match="pending-FIFO overflow"):
        machine.run_many(cfg, [wl], chunk=1)


def test_stack_workloads_padding_shapes(mixed):
    cfg, wls = mixed
    stacked = batch.stack_workloads(wls)
    assert stacked.batch == len(wls)
    assert stacked.n_pes == cfg.n_pes
    assert stacked.prog.shape[1] % batch.PROG_BUCKET == 0
    assert stacked.prog.shape[1] >= max(w.prog.shape[0] for w in wls)
    assert stacked.mem_words == max(w.mem_val.shape[1] for w in wls)
    # padded rows are NOP config entries / zero memory
    for i, wl in enumerate(wls):
        assert (stacked.prog[i, wl.prog.shape[0]:] == 0).all()
        np.testing.assert_array_equal(
            stacked.mem_val[i, :, :wl.mem_val.shape[1]], wl.mem_val)
