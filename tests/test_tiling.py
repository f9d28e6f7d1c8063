"""SpMV operands larger than the fabric: the column-block tile planner and
tiled lanes in ``sweep()``.

A 24x200 operand with a dense row on a 2x2 array whose SRAM and AM queue
are cut down, so it needs several tiles and the dense row is split across
all of them.  Each tile runs as a lane of the sweep's engine call (or
through the packer) and the lane folds back into one answer and one set
of statistics.
"""
import numpy as np
import pytest

from repro.core import am, compiler, partition
from repro.core.compiler import CompiledWorkload, TiledWorkload
from repro.core.machine import MachineConfig
from repro.core.sweep import SweepRequest, fold_tiles, sweep

CFG = MachineConfig(width=2, height=2, mem_words=96, queue_cap=64,
                    max_cycles=20_000)


def _operand(seed=3, m=24, n=200, density=0.2):
    rng = np.random.default_rng(seed)
    a = compiler.random_sparse(m, n, density, rng)
    a[5] = rng.integers(1, 4, size=n)           # one dense row
    x = rng.integers(-3, 4, size=n)
    return a, x


@pytest.fixture(scope="module")
def tiled():
    a, x = _operand()
    return {s: compiler.build_spmv(a, x, CFG, strategy=s)
            for s in ("dissimilarity", "rows")}


def test_planner_splits_into_tiles_that_fit(tiled):
    """Every tile fits the per-PE rule (x block + y + one word per AM
    within the SRAM, AMs within the queue); the blocks cover each column
    once, and the dense row is split across every tile."""
    a, x = _operand()
    for wl in tiled.values():
        assert isinstance(wl, TiledWorkload)
        assert len(wl.tiles) >= 3
        cols = [j for lo, hi in wl.blocks for j in range(lo, hi)]
        assert cols == list(range(a.shape[1]))
        for tile, (lo, hi) in zip(wl.tiles, wl.blocks):
            assert isinstance(tile, CompiledWorkload)
            assert (tile.alloc_top + tile.amq_len <= CFG.mem_words).all()
            assert (tile.amq_len <= CFG.queue_cap).all()
            row5 = tile.static_ams[:, :, am.F_TAG][
                np.arange(CFG.queue_cap)[None] < tile.amq_len[:, None]]
            assert (row5 == 5).sum() == hi - lo      # its slice of row 5
        assert np.array_equal(wl.expected, a @ x)


def test_planner_grows_blocks_as_far_as_they_fit():
    """Each block stops only where one more column would break the rule
    on some PE (the planner's incremental counts agree with the layout
    build_spmv makes)."""
    a, x = _operand()
    rowptr, col, _ = compiler.csr_from_dense(a)
    place = partition.partition_csr(rowptr, col, CFG.n_pes,
                                    strategy="rows").row_to_pe
    blocks = compiler.plan_spmv_tiles(rowptr, col, place, a.shape[1], CFG)
    big = MachineConfig(width=2, height=2, mem_words=4096)
    for lo, hi in blocks[:-1]:
        wider = compiler.build_spmv(a[:, lo:hi + 1], x[lo:hi + 1], big,
                                    row_to_pe=place)
        assert (wider.alloc_top + wider.amq_len > CFG.mem_words).any() \
            or (wider.amq_len > CFG.queue_cap).any()


@pytest.mark.parametrize("pack", [False, True], ids=["unpacked", "packed"])
def test_tiled_sweep_equals_numpy(tiled, pack):
    """Nexus and TIA lanes of a tiled operand, beside a plain lane, give
    numpy's answer; the report counts the tiled lanes and their fill."""
    a, x = _operand()
    small = compiler.build_spmv(a[:8, :12], x[:12], CFG)
    assert isinstance(small, CompiledWorkload)
    wls = [tiled["dissimilarity"], small, tiled["rows"]]
    rep = sweep(CFG, SweepRequest(workloads=wls,
                                  modes=["nexus", "nexus", "tia"],
                                  pack=pack))
    assert len(rep) == 3
    for wl, r in zip(wls, rep):
        assert r.completed
        assert wl.check(r.mem_val)
    assert rep[0].mem_val.shape[0] == len(wls[0].tiles)
    n_tiles = len(wls[0].tiles) + len(wls[2].tiles)
    assert rep.tiles.n_tiled_lanes == 2 and rep.tiles.n_tiles == n_tiles
    ams = wls[0].n_static_ams + wls[2].n_static_ams
    assert rep.tiles.am_fill == pytest.approx(
        ams / (n_tiles * CFG.n_pes * CFG.mem_words))
    assert rep.to_json()["tiles"]["n_tiles"] == n_tiles


def test_fold_is_the_sum_over_tiles_run_as_plain_lanes(tiled):
    wl = tiled["rows"]
    whole = sweep(CFG, SweepRequest(workloads=[wl], modes=["tia"]))[0]
    parts = sweep(CFG, SweepRequest(workloads=list(wl.tiles),
                                    modes=["tia"] * len(wl.tiles)))
    assert whole.cycles == sum(r.cycles for r in parts)
    for f in ("executed", "enroute", "hops", "injected"):
        assert getattr(whole, f) == sum(getattr(r, f) for r in parts)
    assert np.array_equal(whole.per_pe_busy,
                          sum(r.per_pe_busy for r in parts))
    assert np.array_equal(whole.stall_per_port,
                          sum(r.stall_per_port for r in parts))
    assert np.array_equal(whole.mem_val,
                          np.stack([r.mem_val for r in parts]))
    assert fold_tiles(list(parts)).to_json() == whole.to_json()


def test_one_cycle_deadline_stops_every_tile(tiled):
    wls = list(tiled.values())
    rep = sweep(CFG, SweepRequest(workloads=wls, modes=["nexus", "tia"],
                                  deadlines=[1, 1]))
    for wl, r in zip(wls, rep):
        assert not r.completed
        assert r.cycles == len(wl.tiles)        # one cycle per tile


def test_operand_that_fits_stays_plain():
    a, x = _operand()
    wl = compiler.build_spmv(a, x, MachineConfig(width=2, height=2,
                                                 mem_words=96))
    assert isinstance(wl, CompiledWorkload)


def test_planner_refuses_a_column_no_tiling_fits():
    a = np.ones((40, 8), np.int64)          # 10 rows per PE: 2x10 + x > 16
    with pytest.raises(MemoryError, match="no column tiling fits"):
        compiler.build_spmv(a, np.ones(8, np.int64),
                            MachineConfig(width=2, height=2, mem_words=16,
                                          queue_cap=4), strategy="rows")


def test_service_refuses_tiled_workload(tiled):
    from repro.serve import SweepService
    with SweepService(CFG, n_supers=1) as svc:
        with pytest.raises(ValueError, match="tiled"):
            svc.submit(tiled["rows"], mode="tia")
