"""Helpers for the benchmark's CPU tests.

Run them by path, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests
"""
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src"))
                if p not in sys.path]

TINY_CONFIG = {
    "source": "test fabric",
    "fabric": {"max_cycles": 20000},
    "word": "int32",
    "modes": {"nexus": "dissimilarity", "tia": "rows"},
    "shape_seed": 0,
    "sizes": [[2, 2]],
    "lanes": [
        {"name": "spmv", "kind": "spmv", "m": 8, "n": 8, "density": 0.4,
         "mem_words": 256},
        {"name": "bfs", "kind": "bfs", "nv": 12, "k": 4, "mem_words": 256},
    ],
}
MIXES = {
    "closed": {"pack": False, "trace_seconds": 1},
}


def make_bench(tmp, config=TINY_CONFIG, mixes=MIXES):
    """A checkout-like directory: the real kinds and metric readers, a
    tiny configuration, and one cell per mix (``tiny.<mix>``).  Returns
    ``(root, bench_dir)``."""
    root = str(tmp)
    bench = os.path.join(root, "chipbench")
    for sub in ("kinds", "metrics"):
        shutil.copytree(os.path.join(BENCH, sub), os.path.join(bench, sub))
    os.makedirs(os.path.join(bench, "configs"))
    os.makedirs(os.path.join(bench, "traffic"))
    with open(os.path.join(bench, "configs", "tiny.json"), "w") as f:
        json.dump(config, f)
    for name, mix in mixes.items():
        with open(os.path.join(bench, "traffic", f"{name}.json"), "w") as f:
            json.dump(mix, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = [f"tiny.{m}" for m in mixes]
    spec["configs"] = [dict(name="tiny", source="test",
                            file="chipbench/configs/tiny.json", reduced=[],
                            why="test")]
    spec["workloads"] = [dict(name=f"tiny.{m}", config="tiny", traffic=m,
                              chips=1, why="test") for m in mixes]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = list(cells)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    write_golden(root, bench, cells[0])
    return root, bench


def write_golden(root, bench, cell):
    """Record the golden statistics of ``cell``'s configuration, as a
    configuration added to the benchmark brings them."""
    from chipbench import harness, witness
    witness.write_golden(harness.load_cell(cell, root, bench), bench)


@pytest.fixture
def tiny_bench(tmp_path):
    return make_bench(tmp_path)
