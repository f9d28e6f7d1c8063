"""The harness finds configurations, mixes, kinds and metrics by name, so
a cell is added with new files and entries only."""
import json
import os
import subprocess
import sys
import textwrap
import time

from chipbench import harness
from chipbench.tests.conftest import BENCH, ROOT, make_bench, write_golden


def test_every_cell_of_the_benchmark_loads():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.load_metric(BENCH, m["name"]).read)


def test_throwaway_cell_runs_from_new_files_only(tmp_path):
    """A new kind, configuration, mix and metric, written only as files
    and entries, run through the unchanged harness."""
    root, bench = make_bench(tmp_path)
    with open(os.path.join(bench, "kinds", "spmv_twice.py"), "w") as f:
        f.write(textwrap.dedent('''
            import numpy as np
            from chipbench.gen.sparse import dense_ints, powerlaw_sparse

            def generate(p, shape, value):
                return dict(a=2 * powerlaw_sparse(p["m"], p["m"], shape, 0.5),
                            x=dense_ints((p["m"],), value))

            def build(d, cfg, strategy):
                from repro.core import compiler
                return compiler.build_spmv(d["a"], d["x"], cfg,
                                           strategy=strategy)

            def reference(d, dtype=np.int64):
                return d["a"].astype(dtype) @ d["x"].astype(dtype)
        '''))
    with open(os.path.join(bench, "metrics", "lanes_total.py"), "w") as f:
        f.write("def read(ctx):\n    return len(ctx.lanes)\n")
    with open(os.path.join(bench, "configs", "new.json"), "w") as f:
        json.dump({"fabric": {"max_cycles": 20000}, "modes":
                   {"nexus": "dissimilarity"}, "shape_seed": 3,
                   "sizes": [[2, 2]],
                   "lanes": [{"name": "a", "kind": "spmv_twice", "m": 6,
                              "mem_words": 256}]}, f)
    with open(os.path.join(bench, "traffic", "twice.json"), "w") as f:
        json.dump({"pack": True, "trace_seconds": 1}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append(dict(name="new", source="test", reduced=[],
                                file="chipbench/configs/new.json", why="t"))
    spec["workloads"].append(dict(name="new.twice", config="new",
                                  traffic="twice", chips=1, why="t"))
    spec["per_layer"].append(dict(
        name="lanes_total", unit="lanes", better="higher",
        source="host_clock", layer="sweep host", moves="setup_s",
        workloads=["new.twice"]))
    spec["end_to_end"][0]["workloads"].append("new.twice")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    write_golden(root, bench, "new.twice")
    cell = harness.load_cell("new.twice", root, bench)
    assert [m["name"] for m in cell.per_layer] == ["lanes_total"]
    res = harness.run("new.twice", 2 ** 31 + 7, 0.01, False,
                      t_start=time.perf_counter(), root=root,
                      bench_dir=bench, require_chip=False)
    assert res["correct"] and res["attempted"] == 1
    assert set(res["metrics"]) == {"sim_pe_cycles_per_s", "setup_s"}
    assert list(res)[-1] == "checks"


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "eval4x4.grid", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
