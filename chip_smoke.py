"""Run the simulator's main path on a TPU and check every lane bit for bit.

    python chip_smoke.py              # one chip: paper grids, fast-forward, service
    python chip_smoke.py --chips 4    # a four-chip host: the sharded path only

One chip runs four phases through the entry points users call:

* ``fig11``   — the Figs. 11-14 grid (13 workloads x 3 fabric modes,
  39 lanes) in one ``sweep()`` via ``benchmarks.harness``;
* ``fig17``   — the paper-scale Fig. 17 grid (3 workloads at 2x2 / 4x4 /
  8x8, ``mem_words=8192``), packed, via ``benchmarks.fig17_scaling``;
* ``chase``   — the 512-node pointer chase at 8x8, which must take the
  fast-forward branch (``dead_step_fraction > 0``);
* ``service`` — the Fig. 17 lanes submitted to a resident ``SweepService``
  and drained.

``--chips 4`` runs only the sharded path and what it is compared with:
the packed Fig. 17 grid with ``shard=True`` over all four chips, one
sharded ``SweepService`` drain, and the same grid unsharded on one chip.

Every lane must complete, pass its numpy oracle (``wl.check``) and match
``benchmarks/golden/chip_smoke.json`` — the CPU's answer — bit for bit:
the simulator is a deterministic int32 machine, so a chip that disagrees
with the CPU is a bug.  Per-phase seconds and memory printed on the way
are bring-up observations, not benchmark metrics.  The last line of
standard output is the JSON verdict.  There is no CPU fallback: without
a TPU the script exits non-zero before any phase.  The golden is written
on the CPU with ``JAX_PLATFORMS=cpu python chip_smoke.py --write-golden``.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [p for p in (os.path.join(ROOT, "src"), ROOT)
                if p not in sys.path]
GOLDEN = os.path.join(ROOT, "benchmarks", "golden", "chip_smoke.json")

SERVICE_SUPERS = 4      # resident super-lanes: divisible by four chips


# ----------------------------------------------------------------------
# phases: each returns ({lane label: RunResult}, facts) after checking
# that every lane completed and passed its oracle
# ----------------------------------------------------------------------
def _require(ok: bool, msg: str) -> None:
    if not ok:
        raise AssertionError(msg)


def _checked(label, wl, r):
    _require(r.completed, f"{label}: did not reach idle")
    _require(wl.check(r.mem_val), f"{label}: wrong result")
    return r


def phase_fig11():
    """The Figs. 11-14 grid: every workload on every fabric mode, one
    ``sweep()`` (the harness asserts completion and ``wl.check``)."""
    from benchmarks import harness
    from benchmarks.workloads import make_all
    from repro.core.machine import FABRIC_MODES
    wls = make_all()
    _, report = harness.run_grid_report(wls)
    labels = [f"{mode}/{wl.name}" for mode in FABRIC_MODES for wl in wls]
    return dict(zip(labels, report.lanes, strict=True)), {}


def _fig17_labels(builders):
    from benchmarks.fig17_scaling import SIZES
    return [f"{name}@{w}x{h}" for (w, h) in SIZES for name in builders]


def phase_fig17(shard: bool = False):
    """The paper-scale Fig. 17 grid in one packed ``sweep()`` (the
    script asserts completion and ``wl.check``)."""
    from benchmarks import fig17_scaling
    builders = fig17_scaling._builders()
    _, report = fig17_scaling.run_grid_report(builders, pack=True,
                                              shard=shard)
    facts = dict(waves=report.pack.n_waves)
    if shard:
        facts["n_devices"] = report.shard.n_devices
    return dict(zip(_fig17_labels(builders), report.lanes, strict=True)), facts


def phase_chase():
    """The scrambled 512-node pointer chase on 8x8: serial lone flights,
    the workload the fast-forward branch exists for."""
    from benchmarks.workloads import pointer_chase_graph
    from repro.core import compiler
    from repro.core.machine import MachineConfig
    from repro.core.sweep import SweepRequest, sweep
    cfg = MachineConfig(width=8, height=8, mem_words=8192,
                        max_cycles=400_000)
    rowptr, col, src = pointer_chase_graph(512)
    wl = compiler.build_bfs(rowptr, col, src, cfg)
    report = sweep(cfg, SweepRequest(workloads=[wl]))
    dead = report.telemetry.dead_step_fraction
    _require(dead > 0, "fast-forward branch never ran (dead_step_fraction 0)")
    return ({"bfs_chain512@8x8": _checked("chase", wl, report[0])},
            dict(dead_step_fraction=dead))


def phase_service(shard: bool = False):
    """The Fig. 17 lanes through a resident ``SweepService``: submit all,
    drain, and take every future's result (a failed future raises)."""
    from benchmarks import fig17_scaling
    from repro.serve import SweepService
    builders = fig17_scaling._builders()
    grid = fig17_scaling.build_grid(builders)
    wls = [wl for _, _, wl in grid]
    svc = SweepService(fig17_scaling._size_cfg(8, 8), template=wls,
                       n_supers=SERVICE_SUPERS, shard=shard)
    try:
        futures = svc.map(wls)
        svc.drain()
        results = [f.result() for f in futures]
    finally:
        svc.shutdown()
    labels = _fig17_labels(builders)
    rows = {lb: _checked(f"service {lb}", wl, r)
            for lb, wl, r in zip(labels, wls, results, strict=True)}
    facts = dict(n_devices=svc.n_devices) if shard else {}
    return rows, facts


# ----------------------------------------------------------------------
# golden comparison
# ----------------------------------------------------------------------
def _sha256(a) -> str:
    a = np.ascontiguousarray(np.asarray(a), np.int32)
    return hashlib.sha256(repr(a.shape).encode() + a.tobytes()).hexdigest()


def lane_record(r) -> dict:
    """What the golden pins per lane: the counters, plus digests of the
    per-PE busy map, the per-port stall map and the memory image."""
    return dict(cycles=int(r.cycles), executed=int(r.executed),
                enroute=int(r.enroute), hops=int(r.hops),
                per_pe_busy=_sha256(r.per_pe_busy),
                stall_per_port=_sha256(r.stall_per_port),
                mem_val=_sha256(r.mem_val))


def phase_records(rows: dict) -> dict:
    return {label: lane_record(r) for label, r in rows.items()}


def load_golden() -> dict:
    with open(GOLDEN) as f:
        return json.load(f)


def mismatches(records: dict, want: dict) -> list[str]:
    """Every lane whose record differs from the golden (or is missing)."""
    bad = [f"{lb}: missing from run" for lb in want if lb not in records]
    for lb, rec in records.items():
        if lb not in want:
            bad.append(f"{lb}: not in golden")
        elif rec != want[lb]:
            diff = {k: (rec[k], want[lb][k]) for k in rec
                    if rec[k] != want[lb][k]}
            bad.append(f"{lb}: (run, golden) {diff}")
    return bad


# ----------------------------------------------------------------------
# entry point
# ----------------------------------------------------------------------
# (printed name, phase, golden section)
ONE_CHIP = (("fig11", phase_fig11, "fig11"),
            ("fig17", phase_fig17, "fig17"),
            ("chase", phase_chase, "chase"),
            ("service", phase_service, "service"))
# the sharded path, then the unsharded grid it is compared with: both
# answer to the same golden section, hence to each other
FOUR_CHIP = (("fig17_sharded", lambda: phase_fig17(shard=True), "fig17"),
             ("service_sharded", lambda: phase_service(shard=True),
              "service"),
             ("fig17", phase_fig17, "fig17"))


class _CompileClock:
    """Sums JAX's own trace, lowering and backend-compile durations."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration


def run_phase(name, fn, golden_rows, clock, device, n_devices) -> None:
    """Run one phase, hold it to the golden, print what it took.  A
    sharded phase must have split its lanes over all ``n_devices``."""
    from repro.core import machine
    c0, t0 = clock.seconds, time.perf_counter()
    rows, facts = fn()
    wall = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    _require(facts.get("n_devices", n_devices) == n_devices,
             f"{name}: sharded over {facts.get('n_devices')} device(s), "
             f"want {n_devices}")
    bad = mismatches(phase_records(rows), golden_rows)
    _require(not bad, f"{name}: {len(bad)} lane(s) differ from the CPU "
                      "golden:\n  " + "\n  ".join(bad))
    stats = device.memory_stats() or {}
    print(f"phase {name}: " + json.dumps(dict(
        lanes_checked=len(rows), wall_s=wall, compile_s=compile_s,
        rest_s=wall - compile_s, engines=machine.engine_cache_size(),
        peak_bytes_in_use=stats.get("peak_bytes_in_use"), **facts)),
        flush=True)


def write_golden() -> int:
    import jax
    if jax.devices()[0].platform != "cpu":
        print("the golden is the CPU's answer: run with JAX_PLATFORMS=cpu",
              file=sys.stderr)
        return 1
    out = {name: phase_records(fn()[0]) for name, fn, _ in ONE_CHIP}
    os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
    with open(GOLDEN, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {GOLDEN}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every phase on one chip (default); 4: the "
                         "sharded path and its one-chip comparison")
    ap.add_argument("--write-golden", action="store_true",
                    help="regenerate the golden on the CPU and exit")
    args = ap.parse_args(argv)
    if args.write_golden:
        return write_golden()

    import jax
    devices = jax.devices()
    dev = devices[0]
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(devices))
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)
    if device["platform"] != "tpu":
        print("chip_smoke: no TPU found; this script has no CPU fallback",
              file=sys.stderr)
        return 1
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{device['count']} device(s)", file=sys.stderr)
        return 1

    from repro.core import machine
    print(f"compile cache: {machine.enable_persistent_compile_cache()}",
          flush=True)
    golden = load_golden()
    clock = _CompileClock()
    for name, fn, section in ONE_CHIP if args.chips == 1 else FOUR_CHIP:
        run_phase(name, fn, golden[section], clock, dev, device["count"])
    print(json.dumps(dict(ok=True, device=device)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
