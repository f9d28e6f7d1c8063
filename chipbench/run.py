"""The benchmark's one command.

    python chipbench/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell asks
for (``BENCHMARK.json``).  Without a TPU, or with fewer chips than the cell
needs, it exits non-zero and prints no result.  See
:mod:`chipbench.harness` for what a run does.
"""
import time

T_START = time.perf_counter()   # set-up is timed from here

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src"))
                if p not in sys.path]
# the witnesses of the statistics replay the grid on the CPU backend, so
# a platform list that names the chip alone gets the CPU beside it
_platforms = os.environ.get("JAX_PLATFORMS", "")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

if __name__ == "__main__":
    from chipbench import harness
    sys.exit(harness.main(t_start=T_START))
