"""The control of the comparison that decides ``correct``.

The configurations state 16-bit words (the paper's Table 1 word, which
the simulator holds in int32).  The control puts the plain reference,
computed in narrower integer words, in the program's place, and counts
the lanes that the harness's exact comparison then calls wrong: over one
request of the grid, or over the lanes of one window of ``run_seconds``
for an open-loop cell.  It needs no chip and no program: it reads the
configuration and the seed only.

    python chipbench/control.py --workload eval4x4.grid --seeds 1 2 3
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from chipbench import harness, lanes, service  # noqa: E402

WORDS = (np.int16, np.int8)


def wrong_answers(grid: lanes.Grid, dtype, points=None) -> int:
    """Lanes (``points``, default one request of the grid) whose answer,
    taken from the reference in ``dtype`` words, the exact comparison
    calls wrong."""
    return sum(not harness.answer_matches(grid, p, grid.reference(p, dtype))
               for p in (grid.points if points is None else points))


def window_points(cell: harness.Cell, grid: lanes.Grid, seed: int,
                  seconds: float) -> list:
    """The grid points one window of the cell sends."""
    if cell.mix.get("loop", "closed") != "service":
        return grid.points
    _, idx = service.schedule(float(cell.mix["rate"]), seconds,
                              len(grid.points), seed)
    return [grid.points[i] for i in idx]


def readings(workload: str, seeds, root: str = harness.ROOT,
             bench_dir: str = harness.BENCH_DIR) -> dict:
    cell = harness.load_cell(workload, root, bench_dir)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        seconds = float(json.load(f)["run_seconds"])
    out = {}
    for seed in seeds:
        g = lanes.Grid(cell.config, seed, os.path.join(bench_dir, "kinds"))
        pts = window_points(cell, g, seed, seconds)
        out[seed] = {np.dtype(d).name: wrong_answers(g, d, pts)
                     for d in WORDS}
        out[seed]["lanes"] = len(pts)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    print(json.dumps(dict(workload=args.workload,
                          wrong_answers=readings(args.workload, args.seeds))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
