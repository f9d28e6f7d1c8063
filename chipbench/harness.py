"""Run one benchmark cell: set up, measure a window, check every answer.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json`` (the path in the configuration's ``file``),
  whose lanes name workload kinds in ``kinds/<kind>.py``;
* ``traffic/<mix>.json``, read by :mod:`chipbench.traffic`, whose
  ``loop`` chooses the client that sends it;
* ``metrics/<metric>.py``, each a ``read(ctx)`` that returns a number, or
  None when the run holds nothing for it to read.

The mix keys, and the client that reads each:

* ``loop``: ``"closed"`` (the default) gives :class:`ClosedSweep`, one
  client sending the whole grid as a blocking ``sweep()`` back to back;
  ``"service"`` gives :class:`chipbench.service.OpenService`, single
  lanes due on an open-loop schedule, submitted to one resident
  ``SweepService``;
* ``pack`` (closed): the request's sub-mesh packing switch;
* ``shard`` (closed): split the lanes of each engine call over every
  chip of the host;
* ``rate``, ``n_supers``, ``chunk``, ``slice_chunks`` (service): lanes
  due per second, and the service's resident super-lanes, cycles per
  engine chunk and chunks per scheduler slice;
* ``trace_seconds`` (both): the window of a ``--trace 1`` run.

A run (``python chipbench/run.py --workload <cell> --seed <n> --seconds
<s> --trace <0|1>``) generates the data from the seed, warms up every
program of the cell's own shapes (counted as set-up), measures the window
with the profiler off (``--trace 0``: the cell's end-to-end metrics) or on
(``--trace 1``: its per-layer metrics), and then compares every lane the
window issued with the plain reference, and its simulated statistics with
the two witnesses of :mod:`chipbench.witness`.  The last line of standard
output is the result; the numbers compared are also the last lines of
standard error.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import threading
import time
import traceback

import numpy as np

from chipbench import lanes as lanes_mod
from chipbench import traffic
from chipbench import witness

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
SPAN_PREFIX = "chipbench."


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ----------------------------------------------------------------------
# the benchmark's files
# ----------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""
    name: str
    chips: int
    config_name: str
    config: dict
    mix: dict
    end_to_end: list
    per_layer: list
    bench_dir: str


def _reports(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in reported


def load_cell(name: str, root: str = ROOT,
              bench_dir: str = BENCH_DIR) -> Cell:
    """Find cell ``name`` in ``root/BENCHMARK.json`` and load its
    configuration and traffic mix, and the metrics it reports."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    with open(os.path.join(root, configs[w["config"]]["file"])) as f:
        config = json.load(f)
    mix = traffic.load(os.path.join(bench_dir, "traffic",
                                    f"{w['traffic']}.json"))
    e2e = [m for m in spec["end_to_end"] if _reports(m, name, set())]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if _reports(m, name, reported)]
    return Cell(name, int(w["chips"]), w["config"], config, mix, e2e,
                per_layer, bench_dir)


def load_metric(bench_dir: str, name: str):
    """The reader module ``metrics/<name>.py``."""
    return lanes_mod.load_file(os.path.join(bench_dir, "metrics",
                                            f"{name}.py"),
                               f"reader for metric {name!r}")


# ----------------------------------------------------------------------
# host spans and compile events
# ----------------------------------------------------------------------
def span(name: str):
    """The benchmark's own host span around a call into a layer: a
    ``TraceAnnotation``, so a traced run has it on the device trace's
    clock."""
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


class CompileClock:
    """Counts JAX's backend compiles and sums trace, lowering and
    compile seconds, from ``jax.monitoring``'s own events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            with self._lock:
                self.seconds += duration
                self.compiles += event == self.EVENTS[-1]

    def read(self) -> tuple[int, float]:
        with self._lock:
            return self.compiles, self.seconds


# ----------------------------------------------------------------------
# what the window produced
# ----------------------------------------------------------------------
@dataclasses.dataclass
class Lane:
    """One lane the window issued and what became of it."""
    point: lanes_mod.Point
    wl: object = None          # the compiled workload
    result: object = None      # its RunResult, once it came
    error: str | None = None
    t_due: float | None = None   # open loop: when it was due (host clock)
    t_done: float | None = None  # and when its future resolved


@dataclasses.dataclass
class Window:
    """What the metric readers get (``ctx``)."""
    cell: Cell
    setup_s: float
    t_open: float
    t_close: float
    lanes: list                # [Lane]
    requests: list             # [(t0, t1, SweepReport)]
    stepped_pe_ticks: int      # engine PE-steps taken inside the window
    n_devices: int
    trace: object = None       # chipbench.trace.Trace when traced
    stats_open: dict | None = None    # SweepService.stats at the open
    stats_close: dict | None = None   # and once the last lane resolved

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open


class ClosedSweep:
    """One client sending the whole grid as a blocking ``sweep()``,
    back to back.  Each request starts from the seed's data: the system's
    compiler runs inside it, as users pay for it."""

    def __init__(self, grid: lanes_mod.Grid, mix: dict):
        self.grid, self.mix = grid, mix

    def request(self, deadline: int | None = None):
        from repro.core.sweep import SweepRequest, sweep
        g = self.grid
        t0 = time.perf_counter()
        with span("request"):
            with span("compile"):
                wls = g.build_all()
            with span("sweep"):
                report = sweep(g.run_cfg, SweepRequest(
                    workloads=wls, modes=[p.mode for p in g.points],
                    pack=bool(self.mix.get("pack", False)),
                    shard=bool(self.mix.get("shard", False)),
                    deadlines=(None if deadline is None
                               else [deadline] * len(wls))))
        t1 = time.perf_counter()
        lanes = [Lane(p, wl, r)
                 for p, wl, r in zip(g.points, wls, report, strict=True)]
        return (t0, t1, report), lanes

    def warm_up(self) -> None:
        """One request of the window's own shapes.  Unpacked, each lane
        stops after one simulated cycle: the cycle budget is runtime data
        to the engine, so that compiles (or loads) every program the
        window runs without simulating the grid.  A packed request runs
        whole: with deadlines, its waves do not stop at the budget (see
        PERF.md, Open questions)."""
        packed = bool(self.mix.get("pack", False))
        self.request(deadline=None if packed else 1)

    def window(self, seconds: float, t_open: float):
        requests, lanes = [], []
        while not requests or time.perf_counter() - t_open < seconds:
            try:
                req, got = self.request()
            except Exception:
                traceback.print_exc()
                lanes += [Lane(p, error="request raised")
                          for p in self.grid.points]
                break
            requests.append(req)
            lanes += got
        t_close = requests[-1][1] if requests else time.perf_counter()
        ticks = sum(r.telemetry.stepped_pe_ticks for _, _, r in requests)
        return dict(t_close=t_close, lanes=lanes, requests=requests,
                    stepped_pe_ticks=ticks)

    def close(self) -> None:
        pass


def make_client(grid: lanes_mod.Grid, mix: dict, seed: int):
    """The client the mix's ``loop`` names."""
    if mix.get("loop", "closed") == "service":
        from chipbench.service import OpenService
        return OpenService(grid, mix, seed)
    return ClosedSweep(grid, mix)


# ----------------------------------------------------------------------
# correctness
# ----------------------------------------------------------------------
def answer_matches(grid: lanes_mod.Grid, point, answer,
                   dtype=np.int64) -> bool:
    """Whether ``answer`` equals the plain reference of ``point``'s lane
    computed in ``dtype`` words, exactly."""
    got, want = np.asarray(answer), grid.reference(point, dtype)
    return got.shape == want.shape and np.array_equal(got, want)


def judge(grid: lanes_mod.Grid, lanes: list, seen: dict | None,
          golden_now: dict | None, golden: dict | None) -> tuple[dict, int]:
    """Compare every lane with the plain reference and its statistics with
    the witnesses.  ``seen`` is :func:`witness.cpu_records` of the window's
    grid, ``golden_now`` that of the golden grid and ``golden`` its
    committed record (None where a witness could not be made).  Returns
    ``({check: (value, limit)}, lanes that failed)``.

    * ``wrong_answers``: lanes whose answer, read back from the final
      memory image, differs from the reference;
    * ``unfinished_lanes``: lanes that never came, raised, or did not
      reach idle within ``max_cycles``;
    * ``stat_drift``: lanes whose statistics or memory image differ from
      the CPU's run of the same grid point;
    * ``golden_drift``: points of the golden grid whose statistics, as the
      CPU runs them now, differ from the committed record.
    """
    wrong = unfinished = drift = failed = 0
    for ln in lanes:
        bad = False
        r = ln.result
        if r is None or ln.error is not None or not r.completed:
            unfinished += 1
            bad = True
        if r is not None:
            got = ln.wl.read_result(np.asarray(r.mem_val))
            if not answer_matches(grid, ln.point, got):
                wrong += 1
                bad = True
            if seen is None or seen.get(ln.point.label) != \
                    witness.lane_record(r):
                drift += 1
                bad = True
        failed += bad
    if golden is None or golden_now is None:
        golden_off = len(grid.points)
    else:
        golden_off = sum(golden_now.get(k) != v for k, v in golden.items())
        golden_off += len(set(golden_now) - set(golden))
    checks = dict(wrong_answers=(wrong, 0), unfinished_lanes=(unfinished, 0),
                  stat_drift=(drift, 0), golden_drift=(golden_off, 0))
    return checks, failed


def _witnesses(cell: Cell, grid: lanes_mod.Grid) -> tuple:
    """The CPU's records of the window's grid and of the golden grid, and
    the committed golden record; a witness that cannot be made is None."""
    pack = bool(cell.mix.get("pack", False))
    t0 = time.perf_counter()
    out = []
    for g in (grid, witness.golden_grid(cell, cell.bench_dir)):
        try:
            out.append(witness.cpu_records(g, pack))
        except Exception:
            traceback.print_exc()
            out.append(None)
    print(f"witness: {time.perf_counter() - t0:.3f} s on the CPU",
          file=sys.stderr, flush=True)
    golden = witness.load_golden(cell.bench_dir, cell.config_name)
    if golden is None:
        print("no golden record at " + witness.golden_path(
            cell.bench_dir, cell.config_name), file=sys.stderr)
    return out[0], out[1], golden


# ----------------------------------------------------------------------
# one run
# ----------------------------------------------------------------------
def _devices(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform}); "
                     "this benchmark has no CPU fallback")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devs)}")
    return devs


def _memory_peak(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _read(cell: Cell, metrics: list, ctx: Window) -> dict:
    out = {}
    for m in metrics:
        v = load_metric(cell.bench_dir, m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = dict(value=float(v), unit=m["unit"])
    return out


def trace_whole(ctx: Window) -> dict:
    """Whether the trace holds the whole window: every chip ran the engine
    as often as the window called it (a profile cut short has fewer
    runs), with each chip's busy seconds inside the window."""
    lo, hi = ctx.trace.window
    if ctx.stats_open is not None:
        calls = ctx.stats_close["n_slices"] - ctx.stats_open["n_slices"]
    else:
        calls = sum(r.telemetry.engine_calls for _, _, r in ctx.requests)
    runs = [ctx.trace.runs.get(d, {}).get("jit_engine_fn", 0)
            for d in range(ctx.n_devices)]
    return dict(whole=all(r == calls for r in runs),
                busy_s=[ctx.trace.busy_ns(d, lo, hi) / 1e9
                        for d in range(ctx.n_devices)],
                window_s=(hi - lo) / 1e9, engine_calls=calls,
                engine_runs=runs)


def read_traced(cell: Cell, ctx: Window) -> tuple[dict, dict]:
    """The per-layer metrics of a traced window, and :func:`trace_whole`.
    A trace that is not whole would give wrong device times, so no
    metric read from it (``source`` ``device_trace``) is reported."""
    seen = trace_whole(ctx)
    metrics = [m for m in cell.per_layer
               if seen["whole"] or m["source"] != "device_trace"]
    return _read(cell, metrics, ctx), seen


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, root: str = ROOT, bench_dir: str = BENCH_DIR,
        require_chip: bool = True, trace_dir: str | None = None) -> dict:
    """One run of one cell; returns the result object.  Raises
    :class:`NoChip` before any work when the chips are missing."""
    cell = load_cell(workload, root, bench_dir)
    devs = _devices(cell.chips, require_chip)
    import jax
    from repro.core import machine
    cache = machine.enable_persistent_compile_cache()
    clock = CompileClock()
    grid = lanes_mod.Grid(cell.config, seed, os.path.join(bench_dir, "kinds"))
    if trace:
        seconds = min(seconds, float(cell.mix["trace_seconds"]))
    client = make_client(grid, cell.mix, seed)
    try:
        client.warm_up()
        n0, s0 = clock.read()
        if trace:
            trace_dir = trace_dir or os.path.join(
                root, "experiments", "chipbench", "trace", workload)
            shutil.rmtree(trace_dir, ignore_errors=True)
            # the Python tracer would time every interpreter call and slow
            # the host several times over: spans and device only
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t_open = time.perf_counter()
        setup_s = t_open - t_start
        print("setup: " + json.dumps(dict(
            setup_s=setup_s, compiles=n0, compile_s=s0, cache=cache)),
            file=sys.stderr, flush=True)
        with span("window"):
            got = client.window(seconds, t_open)
        t_stop = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
        stop_s = time.perf_counter() - t_stop
        n1, s1 = clock.read()
        print("window: " + json.dumps(dict(
            window_s=got["t_close"] - t_open, compiles=n1 - n0,
            compile_s=s1 - s0)), file=sys.stderr, flush=True)
        peak = _memory_peak(devs)
    finally:
        client.close()
    ctx = Window(cell=cell, setup_s=setup_s, t_open=t_open,
                 t_close=got["t_close"], lanes=got["lanes"],
                 requests=got["requests"],
                 stepped_pe_ticks=got["stepped_pe_ticks"],
                 n_devices=cell.chips, stats_open=got.get("stats_open"),
                 stats_close=got.get("stats_close"))
    dev = devs[0]
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(devs), memory_peak_bytes=peak)
    out = {}
    if trace:
        from chipbench import trace as trace_mod
        t_load = time.perf_counter()
        ctx.trace = trace_mod.load(trace_dir, cell.chips)
        load_s = time.perf_counter() - t_load
        metrics, seen = read_traced(cell, ctx)
        device["busy_s"] = float(np.mean(seen["busy_s"]))
        device["window_s"] = seen["window_s"]
        print("trace: " + json.dumps(dict(seen, stop_s=stop_s,
                                          load_s=load_s)),
              file=sys.stderr, flush=True)
        if not seen["whole"]:
            print("trace: not whole, the device_trace metrics are left "
                  "out", file=sys.stderr, flush=True)
        out["breakdown"] = ctx.trace.breakdown()
    else:
        metrics = _read(cell, cell.end_to_end, ctx)
    checks, failed = judge(grid, ctx.lanes, *_witnesses(cell, grid))
    correct = bool(ctx.lanes) and all(v <= lim for v, lim in checks.values())
    result = dict(correct=correct, attempted=len(ctx.lanes), failed=failed,
                  metrics=metrics, device=device, **out)
    result["checks"] = {k: dict(value=v, limit=lim)
                        for k, (v, lim) in checks.items()}
    return result


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace), t_start=t_start)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
