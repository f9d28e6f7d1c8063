"""Sweep-service throughput benchmark + soak driver.

Measures the resident :class:`repro.serve.SweepService` (continuous
batching on the one cached engine: submit -> future, mid-wave refill of
retired rectangles) against *sequential blocking* ``machine.run_many``
calls on the SAME traffic — one call per lane, warm engines, which is
what a client without the service would do between grid points.

Two canned traffic shapes:

  * ``fig17`` — the Fig. 17 sizes x workloads grid (2x2 ... 8x8 meshes,
    dissimilar runtimes: lanes of every size retire at different times,
    which is exactly the regime mid-wave refill pays for itself in).
    Defaults to the CI-smoke problem scale; ``--paper`` swaps in the
    paper-scale problems (reported, never gated — see
    :func:`fig17_traffic`);
  * ``smoke`` — the CI smoke grid's three tiny 2x2 workloads (uniform
    runtimes; records the service's overhead floor).

Every service result is checked bit-identical to the one-shot
``run_many`` reference before a number is reported, and the service must
have compiled exactly ONE engine.  ``bench_ci`` runs both legs and gates
on the fig17 speedup (service throughput must not drop below the
sequential baseline); this module's ``main`` doubles as a soak driver —
seeded random interleaved submission rounds against the same reference.

    PYTHONPATH=src python -m benchmarks.serve_bench --traffic fig17
    PYTHONPATH=src python -m benchmarks.serve_bench --soak --rounds 3
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

from repro.core import machine
from repro.core.machine import MachineConfig


def fig17_traffic(copies: int = 1, *, paper: bool = False):
    """Dissimilar-runtime traffic: the Fig. 17 sizes x workloads grid
    (2x2 ... 8x8 meshes), duplicated ``copies`` times.  Returns
    ``(base_cfg, lanes)``.

    The default problem scale is the CI-smoke one (same shapes as
    ``fig17_scaling.bench_smoke``): every lane retires within a few
    engine chunks, so the sequential baseline pays one blocking
    dispatch per lane while the service amortizes dispatches across
    co-tenants — the regime CI's bench job lives in, and the one the
    gated service leg measures.  ``paper=True`` swaps in the
    paper-scale problems, where a 2x2 mesh runs ~16x longer than the
    8x8 on the same input; the arena then steps its full padded row
    count for the whole small-mesh tail, so on a CPU backend the
    service trades throughput for latency overlap there (reported,
    never gated)."""
    import dataclasses

    from benchmarks.fig17_scaling import SIZES, _builders, _size_cfg
    from benchmarks.workloads import small_world_graph
    from repro.core import compiler
    if paper:
        builders, cfg_for = _builders(), _size_cfg
    else:
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
    for _ in range(copies):
        for (w, h) in SIZES:
            cfg = cfg_for(w, h)
            for name in sorted(builders):
                lanes.append(builders[name](cfg))
    return cfg_for(*SIZES[-1]), lanes


def smoke_traffic(copies: int = 2):
    """Uniform traffic: the CI smoke grid's 2x2 workloads, duplicated
    ``copies`` times.  Returns ``(base_cfg, lanes)``."""
    from benchmarks import harness
    from benchmarks.bench_ci import smoke_workloads
    cfg = MachineConfig(width=2, height=2, mem_words=1024,
                        max_cycles=100_000)
    placement = harness._placement_for(machine.mode_code(cfg))
    wls = smoke_workloads()
    lanes = []
    for _ in range(copies):
        for wl in wls:
            lanes.append(wl.build(cfg, placement))
    return cfg, lanes


def _same(a, b) -> bool:
    """Bit-identity of two RunResults: every scalar/stat field plus the
    final memory image."""
    return (a.to_json() == b.to_json()
            and np.array_equal(np.asarray(a.mem_val),
                               np.asarray(b.mem_val)))


def service_throughput(cfg, lanes, *, n_supers: int = 2,
                       slice_chunks: int = 2, chunk: int = 512,
                       label: str = "fig17") -> dict:
    """Steady-state lanes/s: sequential blocking run_many vs the service.

    Both sides run the traffic twice — the first pass pays every compile
    (per-mesh-size engines for the sequential side, the one arena engine
    for the service), the second pass is timed.  Service results are
    checked bit-identical to the sequential ones lane by lane; any drift
    lands in the returned record's ``drift`` list (and fails the CI
    gate).  The engine cache is cleared before the service is built, so
    ``engine_cache_size`` in the record counts the service's engines
    alone (must be 1)."""
    from repro.serve import SweepService

    def seq_pass():
        return [machine.run_many(cfg, [wl])[0] for wl in lanes]

    seq_pass()                                 # warm: pays the compiles
    t0 = time.time()
    seq_results = seq_pass()
    t_seq = time.time() - t0

    machine.clear_engine_cache()
    with SweepService(cfg, template=lanes, n_supers=n_supers,
                      chunk=chunk, slice_chunks=slice_chunks) as svc:
        for f in svc.map(lanes):               # warm: arena engine trace
            f.result()
        t0 = time.time()
        futs = svc.map(lanes)
        svc.drain()
        t_svc = time.time() - t0
        svc_results = [f.result() for f in futs]
        occupancy = svc.refill_occupancy
        stats = dict(svc.stats)
    engines = machine.engine_cache_size()

    drift = [f"lane {i}: service result != sequential run_many"
             for i, (a, b) in enumerate(zip(svc_results, seq_results))
             if not _same(a, b)]
    n = len(lanes)
    return dict(traffic=label, n_lanes=n,
                seq_wall_s=round(t_seq, 3),
                service_wall_s=round(t_svc, 3),
                seq_lanes_per_s=round(n / t_seq, 3),
                service_lanes_per_s=round(n / t_svc, 3),
                speedup=round(t_seq / t_svc, 3),
                refill_occupancy=round(occupancy, 4),
                n_refills=int(stats["n_refills"]),
                n_slices=int(stats["n_slices"]),
                engine_cache_size=engines,
                drift=drift)


def soak(cfg, lanes, *, rounds: int = 3, seed: int = 0, n_supers: int = 2,
         slice_chunks: int = 2) -> dict:
    """Seeded random interleaved submission rounds on one resident
    service; every future must come back bit-identical to the one-shot
    ``run_many`` reference, with exactly one compiled engine."""
    from repro.serve import SweepService
    ref = machine.run_many(cfg, list(lanes))
    rng = np.random.default_rng(seed)
    drift: list[str] = []
    machine.clear_engine_cache()
    with SweepService(cfg, template=lanes, n_supers=n_supers,
                      slice_chunks=slice_chunks) as svc:
        for rd in range(rounds):
            order = [int(i) for i in rng.permutation(len(lanes))]
            futs = {i: svc.submit(lanes[i]) for i in order}
            svc.drain()
            for i, f in futs.items():
                if not _same(f.result(), ref[i]):
                    drift.append(f"round {rd} lane {i}: service result "
                                 "!= one-shot run_many")
        occupancy = svc.refill_occupancy
        stats = dict(svc.stats)
    return dict(rounds=rounds, n_lanes=len(lanes), drift=drift,
                engine_cache_size=machine.engine_cache_size(),
                refill_occupancy=round(occupancy, 4),
                n_refills=int(stats["n_refills"]),
                n_retired=int(stats["n_retired"]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--traffic", choices=["fig17", "smoke"],
                    default="fig17")
    ap.add_argument("--copies", type=int, default=None,
                    help="traffic duplication factor (default: 2)")
    ap.add_argument("--paper", action="store_true",
                    help="paper-scale fig17 problems (small meshes run "
                         "16x longer than the 8x8; throughput is "
                         "reported, never gated)")
    ap.add_argument("--n-supers", type=int, default=2)
    ap.add_argument("--slice-chunks", type=int, default=None,
                    help="engine chunks per scheduler slice (default: "
                         "1 for fig17, 2 for smoke)")
    ap.add_argument("--chunk", type=int, default=None,
                    help="service engine chunk in cycles (default: 128 "
                         "for fig17, 512 for smoke); the sequential "
                         "baseline always runs the run_many default")
    ap.add_argument("--soak", action="store_true",
                    help="run interleaved-submission soak rounds instead "
                         "of the throughput comparison")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="also write the record as JSON here")
    args = ap.parse_args()

    machine.enable_persistent_compile_cache()

    fig17 = args.traffic == "fig17"
    copies = args.copies or 2
    slice_chunks = args.slice_chunks or (1 if fig17 else 2)
    chunk = args.chunk or (128 if fig17 else 512)
    if fig17:
        cfg, lanes = fig17_traffic(copies=copies, paper=args.paper)
    else:
        cfg, lanes = smoke_traffic(copies=copies)

    if args.soak:
        rec = soak(cfg, lanes, rounds=args.rounds, seed=args.seed,
                   n_supers=args.n_supers, slice_chunks=slice_chunks)
        print(f"soak [{args.traffic}]: {rec['rounds']} rounds x "
              f"{rec['n_lanes']} lanes, {rec['n_retired']} retirements, "
              f"{rec['n_refills']} mid-wave refills, occupancy "
              f"{rec['refill_occupancy']:.2f}, engines "
              f"{rec['engine_cache_size']}")
    else:
        label = args.traffic + ("-paper" if args.paper else "")
        rec = service_throughput(cfg, lanes, n_supers=args.n_supers,
                                 slice_chunks=slice_chunks,
                                 chunk=chunk, label=label)
        print(f"service [{args.traffic}]: {rec['n_lanes']} lanes — "
              f"sequential {rec['seq_lanes_per_s']} lanes/s, service "
              f"{rec['service_lanes_per_s']} lanes/s "
              f"({rec['speedup']:.2f}x), refill occupancy "
              f"{rec['refill_occupancy']:.2f}, {rec['n_refills']} "
              f"refills, engines {rec['engine_cache_size']}")
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rec, f, indent=1)
    if rec["drift"]:
        print("\nSERVICE DRIFT (results not bit-identical):",
              file=sys.stderr)
        for msg in rec["drift"]:
            print(f"  - {msg}", file=sys.stderr)
        return 1
    if rec["engine_cache_size"] != 1:
        print(f"service compiled {rec['engine_cache_size']} engines "
              "(want 1)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
