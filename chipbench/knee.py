"""Find the highest arrival rate a service cell sustains, on the chip.

    python chipbench/knee.py --workload eval4x4.service --seed <n> \
        --seconds 36 --rates 3 4 5 6 8 10

One process, one set-up: for each rate, a fresh ``SweepService`` of the
cell's mix is warmed up and sent the cell's open-loop schedule at that
rate for ``--seconds``.  Each rate prints one JSON line to standard
output: lanes sent, lanes answered right (answers only: the statistics'
witnesses are the benchmark run's), ``lanes_per_s``, ``lane_p90_s``, how
late the generator ran, and the backlog (lanes due and not yet answered)
at the middle and at the end of the schedule.  A rate is sustained when
``lanes_per_s`` is at least 0.97 of the rate offered and the backlog at
the end of the schedule is no larger than at its middle.  The cell's mix
then holds three quarters of the highest rate sustained (``PERF.md``
keeps the sweep).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

if __package__ in (None, ""):
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_root, os.path.join(_root, "src")]

from chipbench import harness, lanes, measures, service  # noqa: E402


def sweep_rates(workload: str, seed: int, seconds: float, rates,
                require_chip: bool = True) -> list[dict]:
    cell = harness.load_cell(workload)
    harness._devices(cell.chips, require_chip)
    from repro.core import machine
    machine.enable_persistent_compile_cache()
    grid = lanes.Grid(cell.config, seed,
                      os.path.join(harness.BENCH_DIR, "kinds"))
    rows = []
    for rate in rates:
        client = service.OpenService(grid, dict(cell.mix, rate=rate), seed)
        try:
            client.warm_up()
            t_open = time.perf_counter()
            got = client.window(seconds, t_open)
        finally:
            client.close()
        ok = sum(ln.result is not None and ln.error is None
                 and ln.result.completed and harness.answer_matches(
                     grid, ln.point, ln.wl.read_result(ln.result.mem_val))
                 for ln in got["lanes"])
        ctx = harness.Window(
            cell=cell, setup_s=0.0, t_open=t_open, t_close=got["t_close"],
            lanes=got["lanes"], requests=[], stepped_pe_ticks=0,
            n_devices=cell.chips, stats_open=got["stats_open"],
            stats_close=got["stats_close"])
        per_s = ok / ctx.window_s
        mid = service.backlog(ctx.lanes, t_open + seconds / 2)
        end = service.backlog(ctx.lanes, t_open + seconds)
        row = dict(rate=rate, lanes=len(ctx.lanes), ok=ok,
                   lanes_per_s=per_s,
                   lane_p90_s=measures.lane_percentile_s(ctx, 0.90),
                   lane_p50_s=measures.lane_percentile_s(ctx, 0.50),
                   window_s=ctx.window_s,
                   generator_late_max_s=got["generator_late_s"],
                   backlog_mid=mid, backlog_end=end,
                   sustained=per_s >= 0.97 * rate and end <= mid)
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    try:
        sweep_rates(args.workload, args.seed, args.seconds, args.rates)
    except harness.NoChip as e:
        print(f"knee: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
