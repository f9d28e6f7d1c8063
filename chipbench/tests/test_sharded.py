"""The four-chip path, rehearsed on four virtual CPU devices.

The blocking client splits each wave's super-lanes over every chip
(``shard``).  A sound run is correct and ran on four devices; a run in
which the lanes of every chip but the first never come back stepped (the
exchange between chips left out) is not.  Each runs in a process of its
own, as the number of devices is fixed when JAX starts."""
import json
import os
import subprocess
import sys

from chipbench.tests.conftest import ROOT

SCRIPT = r"""
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import pathlib
import jax
import pytest
from chipbench import harness
from chipbench.tests import test_faults
from chipbench.tests.conftest import make_bench

root, bench = make_bench(pathlib.Path(sys.argv[2]), mixes={
    "sharded": {"pack": True, "shard": True, "trace_seconds": 1}})
with open(root + "/BENCHMARK.json") as f:
    spec = json.load(f)
spec["workloads"][0]["chips"] = 4
with open(root + "/BENCHMARK.json", "w") as f:
    json.dump(spec, f)

devices = []
closed = harness.ClosedSweep.window


def window(self, *a):
    got = closed(self, *a)
    devices.extend(r.shard.n_devices for _, _, r in got["requests"])
    return got


harness.ClosedSweep.window = window


def other_chips_lost(st_in, st_out):
    b = st_out.cycle.shape[0]
    return jax.tree.map(
        lambda new, old: new.at[b // 4:].set(old[b // 4:]), st_out, st_in)


if sys.argv[3] == "fault":
    with pytest.MonkeyPatch.context() as mp:
        test_faults._break_window(mp, other_chips_lost)
        res = harness.run("tiny.sharded", 2 ** 31 + 77, 0.01, False,
                          t_start=time.perf_counter(), root=root,
                          bench_dir=bench, require_chip=False)
else:
    res = harness.run("tiny.sharded", 2 ** 31 + 77, 0.01, False,
                      t_start=time.perf_counter(), root=root,
                      bench_dir=bench, require_chip=False)
print(json.dumps(dict(res, shard_devices=devices)))
"""


def _run(tmp_path, kind):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", SCRIPT, ROOT, str(tmp_path),
                        kind], env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sharded_sweep_is_correct_on_four_devices(tmp_path):
    res = _run(tmp_path, "sound")
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4
    assert res["shard_devices"] and set(res["shard_devices"]) == {4}


def test_lanes_of_the_other_chips_lost_are_not_correct(tmp_path):
    res = _run(tmp_path, "fault")
    assert not res["correct"], res["checks"]
    assert res["failed"] > 0
