"""Two witnesses for a lane's simulated statistics, outside the window.

The answer of a lane has a plain reference (``kinds/<kind>.py``); its
cycle-level statistics have none outside the program.  So they are held
to two records, both made by the program on the CPU backend after the
window has closed:

* the window's own grid, replayed through the same ``sweep()`` request on
  the CPU: a lane that the chip ran differently from the CPU, or
  differently on one repeat, reads as drift;
* the configuration's grid at ``GOLDEN_SEED``, replayed on the CPU and
  compared with the record committed in ``golden/<config>.json``: a
  change to the program's timing model, which the first witness cannot
  see because both backends run it alike, reads as drift there.

Write a configuration's record (CPU, once, when the configuration is
added):

    JAX_PLATFORMS=cpu python chipbench/witness.py --workload eval4x4.grid
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys

import numpy as np

if __package__ in (None, ""):
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_root, os.path.join(_root, "src")]

from chipbench import lanes  # noqa: E402

GOLDEN_SEED = 0


def _digest(a) -> str:
    a = np.ascontiguousarray(np.asarray(a), np.int32)
    return hashlib.sha256(repr(a.shape).encode() + a.tobytes()).hexdigest()


def lane_record(r) -> list:
    """A lane's simulated statistics and memory image, which every run of
    the lane on any backend must reproduce exactly."""
    return [int(r.cycles), int(r.executed), int(r.enroute), int(r.hops),
            bool(r.completed), _digest(r.per_pe_busy),
            _digest(r.stall_per_port), _digest(r.mem_val)]


def cpu_records(grid: lanes.Grid, pack: bool) -> dict:
    """``{label: lane_record}`` of every point of ``grid``, run as one
    blocking ``sweep()`` on the CPU backend."""
    import jax
    from repro.core.sweep import SweepRequest, sweep
    with jax.default_device(jax.devices("cpu")[0]):
        wls = grid.build_all()
        report = sweep(grid.run_cfg, SweepRequest(
            workloads=wls, modes=[p.mode for p in grid.points], pack=pack))
    return {p.label: lane_record(r)
            for p, r in zip(grid.points, report, strict=True)}


def golden_path(bench_dir: str, config_name: str) -> str:
    return os.path.join(bench_dir, "golden", f"{config_name}.json")


def golden_grid(cell, bench_dir: str) -> lanes.Grid:
    return lanes.Grid(cell.config, GOLDEN_SEED,
                      os.path.join(bench_dir, "kinds"))


def load_golden(bench_dir: str, config_name: str) -> dict | None:
    path = golden_path(bench_dir, config_name)
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        return json.load(f)


def write_golden(cell, bench_dir: str) -> str:
    """Record the configuration's grid at ``GOLDEN_SEED`` as the CPU runs
    it now."""
    recs = cpu_records(golden_grid(cell, bench_dir),
                       bool(cell.mix.get("pack", False)))
    path = golden_path(bench_dir, cell.config_name)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        rows = (f"{json.dumps(k)}: {json.dumps(v)}"
                for k, v in sorted(recs.items()))
        f.write("{\n" + ",\n".join(rows) + "\n}\n")
    return path


def main(argv=None) -> int:
    from chipbench import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True,
                    help="a cell of the configuration to record")
    args = ap.parse_args(argv)
    print(write_golden(harness.load_cell(args.workload), harness.BENCH_DIR))
    return 0


if __name__ == "__main__":
    sys.exit(main())
