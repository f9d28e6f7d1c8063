"""Paper Fig. 17: performance scaling with PE-array size (2x2 -> 8x8).

Runs the same workloads on growing fabrics; near-linear scaling is the
claim (slope flattens when the problem no longer covers the fabric).

The mesh geometry is per-lane *runtime data* to the compiled engine
(``MachineConfig.traced_geometry``), so the ENTIRE sizes x workloads grid
stacks into the lanes of ONE ``machine.run_many`` call — and with
``pack=True`` (the default here) small meshes are co-scheduled as
disjoint sub-meshes of shared 8x8 super-lanes
(``repro.core.batch.pack_schedule``), so the padded PE axis carries
useful work instead of dead rows: the whole sweep costs one engine
compile (``machine.engine_cache_size() == 1`` afterwards) and a handful
of wave dispatches.  ``--bench`` times the packed grid against BOTH the
per-size-compile baseline (one batched run per mesh size, each paying
its own trace — the PR-2 state of this script) and the unpacked
one-engine grid (the PR-3 state, which padded every lane to 8x8), plus
a packed+sharded leg (``run_many(shard=True)``: the lane axis split
over ``jax.devices()``).  ``--shard`` runs the main grid sharded —
bit-identical results, a no-op on one device.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from benchmarks.workloads import powerlaw_sparse, small_world_graph
from repro.core import compiler, machine
from repro.core.machine import MachineConfig
from repro.core.sweep import SweepReport, SweepRequest, sweep

OUT = os.path.join(os.path.dirname(__file__), "..", "experiments", "bench",
                   "fig17.json")
SIZES = [(2, 2), (4, 4), (8, 8)]


def _builders():
    rng = np.random.default_rng(5)
    m = 128
    a = powerlaw_sparse(m, m, rng, 0.25)
    x = rng.integers(-3, 4, size=(m,))
    aa = powerlaw_sparse(40, 40, rng, 0.4)
    bb = powerlaw_sparse(40, 40, rng, 0.4)
    rp, col = small_world_graph(96, 4, 3)
    return {
        "spmv": lambda c: compiler.build_spmv(a, x, c),
        "spmspm": lambda c: compiler.build_spmspm(aa, bb, c),
        "bfs": lambda c: compiler.build_bfs(rp, col, 0, c),
    }


def _size_cfg(w: int, h: int) -> MachineConfig:
    return MachineConfig(width=w, height=h, mem_words=8192,
                         max_cycles=400_000)


def build_grid(builders, sizes=SIZES):
    """Compile every workload at every mesh size (placement is
    size-dependent, so each (size, workload) point is its own lane)."""
    lanes = []   # [(size, name, wl)]
    for (w, h) in sizes:
        cfg = _size_cfg(w, h)
        for name, b in builders.items():
            lanes.append(((w, h), name, b(cfg)))
    return lanes


def run_grid(builders, sizes=SIZES, *, pack: bool = True,
             shard: bool = False) -> dict:
    """The Fig. 17 table alone; see :func:`run_grid_report` for the
    table plus the sweep's packing / sharding schedules."""
    table, _ = run_grid_report(builders, sizes, pack=pack, shard=shard)
    return table


def run_grid_report(builders, sizes=SIZES, *, pack: bool = True,
                    shard: bool = False) -> tuple[dict, SweepReport]:
    """The entire sizes x workloads grid in ONE packed sweep call.

    Returns ``(table, report)``: {workload: {"WxH": {cycles,
    utilization}}} — the Fig. 17 table — after asserting every lane
    completed bit-exact, plus the :class:`SweepReport` whose ``pack`` /
    ``shard`` fields carry the packing-efficiency numbers and device
    plan.  With ``pack`` (default) small meshes are co-scheduled inside
    shared padded super-lanes; ``shard=True`` additionally splits each
    wave's lane axis over ``jax.devices()`` (bit-identical; a no-op on
    one device).
    """
    lanes = build_grid(builders, sizes)
    report = sweep(_size_cfg(*sizes[0]), SweepRequest(
        workloads=[wl for _, _, wl in lanes], pack=pack, shard=shard))
    out: dict = {name: {} for name in builders}
    for ((w, h), name, wl), r in zip(lanes, report):
        assert r.completed and wl.check(r.mem_val), f"{name} @ {w}x{h}"
        out[name][f"{w}x{h}"] = dict(cycles=r.cycles,
                                     utilization=r.utilization)
    return out, report


def bench_smoke(sizes=SIZES) -> dict:
    """The compile-bound regime: the same sizes x workloads sweep
    structure on tiny (CI-smoke-sized) problems, one-engine grid vs
    per-size-compile baseline.

    Here each lane finishes in a few hundred cycles, so the sweep's cost
    IS the engine compiles — and sharing one traced-geometry engine
    across every mesh size is a direct cold-time win (one compile instead
    of one per size).  This is the regime CI's bench job and the
    fabric-size autotuner live in."""
    import dataclasses

    import jax

    rng = np.random.default_rng(7)
    a = compiler.random_sparse(16, 16, 0.3, rng)
    x = rng.integers(-3, 4, size=(16,))
    rp, col = small_world_graph(24, 4, 3)
    builders = {
        "spmv": lambda c: compiler.build_spmv(a, x, c),
        "bfs": lambda c: compiler.build_bfs(rp, col, 0, c),
    }

    def cfg_for(w, h):
        return dataclasses.replace(_size_cfg(w, h), mem_words=1024)

    lanes = []
    for (w, h) in sizes:
        for b in builders.values():
            lanes.append(((w, h), b(cfg_for(w, h))))

    jax.config.update("jax_enable_compilation_cache", False)
    machine.clear_engine_cache()
    t0 = time.time()
    for (w, h) in sizes:
        machine.run_many(cfg_for(w, h),
                         [wl for sz, wl in lanes if sz == (w, h)])
    t_per_size = time.time() - t0
    n_per_size = machine.engine_cache_size()

    machine.clear_engine_cache()
    t0 = time.time()
    machine.run_many(cfg_for(*sizes[0]), [wl for _, wl in lanes])
    t_grid = time.time() - t0
    n_grid = machine.engine_cache_size()

    print(f"smoke sweep ({len(sizes)} sizes x {len(builders)} tiny "
          "workloads), cold process each way:")
    print(f"  per-size batches: {n_per_size} compiles, {t_per_size:.1f}s")
    print(f"  one-engine grid:  {n_grid} compile,  {t_grid:.1f}s  "
          f"-> {t_per_size / t_grid:.1f}x")
    return dict(per_size_cold_s=t_per_size, per_size_engines=n_per_size,
                grid_cold_s=t_grid, grid_engines=n_grid,
                speedup_cold=t_per_size / t_grid)


def bench() -> dict:
    """Time the full sizes x workloads sweep three ways: the PACKED
    one-call grid (sub-mesh lane packing, the default ``run_grid`` path)
    vs the per-size-compile baseline (one batched run per mesh size —
    each distinct geometry paying its own engine trace, the PR-2 state)
    vs the unpacked one-engine grid (every lane padded to 8x8, the PR-3
    state whose run-time regression packing reverses).

    Prints cold numbers (including compiles) and steady-state numbers
    (engines cached in-process).  Paper scale is run-bound on CPU: the
    unpacked grid steps 9 x 64 padded PE rows for as long as the slowest
    2x2 lane runs, while the packed schedule steps one 64-PE super-lane
    per wave — so packing recovers the per-size run cost AND keeps the
    single-compile cold win.  Smoke scale (:func:`bench_smoke`) is
    compile-bound — there the one-engine grid's single compile IS the
    win."""
    import jax

    builders = _builders()
    lanes = build_grid(builders)

    # Baseline emulation: no persistent compile cache, fresh in-process
    # engines, one batched run per mesh size (the PR-2 capability).
    jax.config.update("jax_enable_compilation_cache", False)
    machine.clear_engine_cache()
    t0 = time.time()
    per_size = {}
    for (w, h) in SIZES:
        cfg = _size_cfg(w, h)
        wls = [wl for (sz, _, wl) in lanes if sz == (w, h)]
        # homogeneous batch: no padding, engine specialized to this size
        per_size[w, h] = machine.run_many(cfg, wls)
    t_seq_cold = time.time() - t0
    n_seq_engines = machine.engine_cache_size()
    t0 = time.time()
    for (w, h) in SIZES:
        cfg = _size_cfg(w, h)
        wls = [wl for (sz, _, wl) in lanes if sz == (w, h)]
        machine.run_many(cfg, wls)
    t_seq_warm = time.time() - t0

    machine.clear_engine_cache()
    t0 = time.time()
    grid = machine.run_many(_size_cfg(2, 2), [wl for _, _, wl in lanes])
    t_cold = time.time() - t0
    n_grid_engines = machine.engine_cache_size()
    t0 = time.time()
    grid = machine.run_many(_size_cfg(2, 2), [wl for _, _, wl in lanes])
    t_warm = time.time() - t0

    pack_req = SweepRequest(workloads=[wl for _, _, wl in lanes], pack=True)
    machine.clear_engine_cache()
    t0 = time.time()
    packed_rep = sweep(_size_cfg(2, 2), pack_req)
    t_pack_cold = time.time() - t0
    n_pack_engines = machine.engine_cache_size()
    t0 = time.time()
    packed_rep = sweep(_size_cfg(2, 2), pack_req)
    t_pack_warm = time.time() - t0
    packed, pack_stats = packed_rep.lanes, packed_rep.pack

    shard_req = SweepRequest(workloads=[wl for _, _, wl in lanes],
                             pack=True, shard=True)
    machine.clear_engine_cache()
    t0 = time.time()
    sharded_rep = sweep(_size_cfg(2, 2), shard_req)
    t_shard_cold = time.time() - t0
    n_shard_engines = machine.engine_cache_size()
    t0 = time.time()
    sharded_rep = sweep(_size_cfg(2, 2), shard_req)
    t_shard_warm = time.time() - t0
    sharded, shard_stats = sharded_rep.lanes, sharded_rep.shard

    # per-lane metrics identical between all four paths
    it = iter(zip(grid, packed, sharded))
    for (w, h) in SIZES:
        for s in per_size[w, h]:
            g, p, d = next(it)
            assert (s.cycles, s.executed, s.hops) == (g.cycles, g.executed,
                                                      g.hops)
            assert (s.cycles, s.executed, s.hops) == (p.cycles, p.executed,
                                                      p.hops)
            assert (s.cycles, s.executed, s.hops) == (d.cycles, d.executed,
                                                      d.hops)
    print(f"fig17 grid ({len(SIZES)} sizes x {len(builders)} workloads = "
          f"{len(lanes)} lanes), metrics identical:")
    print(f"  per-size batches, {n_seq_engines} engine compiles, cold: "
          f"{t_seq_cold:.1f}s   (steady: {t_seq_warm:.1f}s)")
    print(f"  unpacked grid,    {n_grid_engines} engine compile,  cold: "
          f"{t_cold:.1f}s  -> {t_seq_cold / t_cold:.1f}x   "
          f"(steady: {t_warm:.1f}s)")
    print(f"  packed grid,      {n_pack_engines} engine compile,  cold: "
          f"{t_pack_cold:.1f}s  -> {t_seq_cold / t_pack_cold:.1f}x   "
          f"(steady: {t_pack_warm:.1f}s -> "
          f"{t_seq_warm / t_pack_warm:.1f}x)")
    print(f"  packed+sharded,   {n_shard_engines} engine compile,  cold: "
          f"{t_shard_cold:.1f}s   (steady: {t_shard_warm:.1f}s) on "
          f"{shard_stats.n_devices} device(s), "
          f"{shard_stats.lanes_per_device} lanes/device")
    print(f"  packing: {pack_stats.n_waves} waves, efficiency "
          f"{pack_stats.packing_efficiency:.2f} (unpacked "
          f"{pack_stats.unpacked_efficiency:.2f})")
    smoke = bench_smoke()
    return dict(per_size_cold_s=t_seq_cold, per_size_warm_s=t_seq_warm,
                per_size_engines=n_seq_engines,
                grid_cold_s=t_cold, grid_warm_s=t_warm,
                grid_engines=n_grid_engines,
                packed_cold_s=t_pack_cold, packed_warm_s=t_pack_warm,
                packed_engines=n_pack_engines,
                sharded_cold_s=t_shard_cold, sharded_warm_s=t_shard_warm,
                sharded_engines=n_shard_engines,
                n_devices=shard_stats.n_devices,
                lanes_per_device=shard_stats.lanes_per_device,
                speedup_cold=t_seq_cold / t_cold,
                speedup_warm=t_seq_warm / t_warm,
                packed_speedup_cold=t_seq_cold / t_pack_cold,
                packed_speedup_warm=t_seq_warm / t_pack_warm,
                sharded_speedup_warm=t_pack_warm / t_shard_warm,
                pack_stats=pack_stats.to_json(),
                smoke=smoke)


def main(force: bool = False, shard: bool = False):
    if os.path.exists(OUT) and not force and not shard:
        with open(OUT) as f:
            data = json.load(f)
    else:
        data, report = run_grid_report(_builders(), shard=shard)
        if shard and report.shard is not None:
            print(f"sharded over {report.shard.n_devices} device(s), "
                  f"{report.shard.lanes_per_device} lanes/device")
        os.makedirs(os.path.dirname(OUT), exist_ok=True)
        with open(OUT, "w") as f:
            json.dump(data, f, indent=1)

    print("=" * 78)
    print("Fig. 17 — scaling with array size (speedup over 2x2; "
          "ideal 4x4 = 4, 8x8 = 16)")
    print("=" * 78)
    print(f"{'workload':<10}" + "".join(f"{f'{w}x{h}':>6}" for (w, h) in SIZES)
          + "    utilization @8x8")
    for name, sizes in data.items():
        base = sizes["2x2"]["cycles"]
        row = f"{name:<10}"
        for (w, h) in SIZES:
            row += f"{base / sizes[f'{w}x{h}']['cycles']:>6.1f}"
        row += f"{100 * sizes['8x8']['utilization']:>18.0f}%"
        print(row)
    print("-" * 78)
    print("scaling tracks fabric size while the problem covers it; "
          "utilization (not problem size) is the limiter — paper §5.4")
    return data


if __name__ == "__main__":
    machine.enable_persistent_compile_cache()
    if "--bench" in sys.argv:
        bench()
    else:
        main(force="--force" in sys.argv, shard="--shard" in sys.argv)
