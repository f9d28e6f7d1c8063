"""Reading the program's own marks in a trace: its ``repro.*`` host spans,
the scope of each device op, and the metrics that read them.

The trace reader of the benchmark (:mod:`chipbench.trace`) is left as it
was; these tests hold the new reader to it on the parent's fixture, and
to hand-made and recorded windows that carry the program's spans.
"""
import glob
import json
import os
import time
import types

import pytest

from chipbench import harness
from chipbench import program_trace as P
from chipbench import trace as T
from chipbench.tests.conftest import BENCH
from chipbench.tests.test_trace import FIXTURE, _planes

NEW_METRICS = ("sweep_validate_s.sweep", "sweep_place_s.sweep",
               "sweep_unpack_s.sweep", "pack_plan_s.sweep",
               "engine_live_share.sweep")


def _ev(name, s, d):
    return types.SimpleNamespace(name=name, start_ns=s, duration_ns=d)


def _window(host, ops, modules=()):
    """Planes of a hand-made window: host events ``(name, start, dur)``
    on one thread, device ops and module runs on chip 0."""
    line = lambda n, evs: types.SimpleNamespace(
        name=n, events=[_ev(*e) for e in evs])
    return [types.SimpleNamespace(name=T.HOST_PLANE,
                                  lines=[line("python", host)]),
            types.SimpleNamespace(name="/device:TPU:0", lines=[
                line(P.MODULES_LINE, modules), line(T.OPS_LINE, ops)])]


HOST = [("chipbench.window", 0, 1000), ("chipbench.request", 0, 1000),
        ("chipbench.sweep", 100, 900), ("repro.sweep.validate", 100, 200),
        ("repro.sweep.place", 300, 100), ("repro.engine.dispatch", 400, 10),
        ("repro.engine.wait", 410, 390), ("repro.sweep.unpack", 800, 150)]
OPS = [("%while.1 = s32[] while(%a)", 420, 300),
       ("%fusion.7 = s32[4] fusion(%b)", 430, 100),
       ("%copy.3 = s32[4] copy(%c)", 600, 50),
       ("%add.2 = s32[] add(%d, %e)", 900, 20)]
MODULES = [("jit_engine_fn(1)", 415, 310)]
SCOPES = {"jit_engine_fn(1)": {"while.1": P.UNSCOPED,
                               "fusion.7": "cycle.route",
                               "copy.3": P.UNSCOPED}}


def _ctx(planes, program_spans=None, requests=()):
    tr = T.from_planes(planes, chips=1)
    ctx = types.SimpleNamespace(trace=tr, requests=list(requests))
    if program_spans is not None:
        ctx.program_spans = program_spans
    return ctx


# ----------------------------------------------------------------------
# the parent's fixture reads as before
# ----------------------------------------------------------------------
def test_parent_fixture_reads_as_the_benchmark_reader_does():
    with open(FIXTURE) as f:
        planes = _planes(json.load(f))
    tr = T.from_planes(planes, chips=1)
    bench, prog = P.spans_of(planes)
    assert bench == tr.spans and prog == []
    got = P.analyse(planes, 1, {})
    lo, hi = tr.window
    assert got["device_busy_s"] == tr.busy_ns(0, lo, hi) / 1e9
    assert got["span_count"] == {} and got["host_s_per_request"] == {}
    (s, e), = tr.spans_named("sweep")
    assert got["sweep_host_s"] == pytest.approx(
        ((e - s) - tr.any_busy_ns(s, e)) / 1e9)
    # every op is unscoped without the program's modules, and the
    # scopes' self times add up to the busy time as the ops' do
    assert set(got["device_scopes"]) == {P.UNSCOPED}
    assert got["device_scopes"][P.UNSCOPED] == pytest.approx(
        sum(tr.op_s[0].values()))
    # gaps: the same gaps, named by the same (benchmark) spans
    want = tr.breakdown()["idle_gaps"]
    assert [[n[len(T.SPAN_PREFIX):], s] for n, s in got["idle_gaps"]] \
        == want


# ----------------------------------------------------------------------
# hand-made windows
# ----------------------------------------------------------------------
def test_spans_of_splits_the_two_prefixes():
    bench, prog = P.spans_of(_window(HOST, OPS))
    assert [n for n, *_ in bench] == ["window", "request", "sweep"]
    assert [n for n, *_ in prog] == ["sweep.validate", "sweep.place",
                                     "engine.dispatch", "engine.wait",
                                     "sweep.unpack"]


def test_gaps_are_named_by_the_innermost_span_of_either_prefix():
    # busy 420..720 and 900..920: gaps 0..420, 720..900 and 920..1000
    got = P.analyse(_window(HOST, OPS, MODULES), 1, SCOPES)
    assert [n for n, _ in got["idle_gaps"]] == [
        "repro.sweep.validate", "repro.sweep.unpack", "chipbench.sweep"]
    assert [s for _, s in got["idle_gaps"]] == pytest.approx(
        [420e-9, 180e-9, 80e-9])


def test_device_time_by_scope_accounts_for_all_busy_time():
    got = P.analyse(_window(HOST, OPS, MODULES), 1, SCOPES)
    scopes = got["device_scopes"]
    # the while's own time, the copy, and the add outside every module
    assert scopes[P.UNSCOPED] == pytest.approx((150 + 50 + 20) * 1e-9)
    assert scopes["cycle.route"] == pytest.approx(100e-9)
    assert sum(scopes.values()) == pytest.approx(got["device_busy_s"])
    assert got["device_busy_s"] == pytest.approx(320e-9)
    assert got["copy_scopes"] == {P.UNSCOPED: pytest.approx(50e-9)}


def test_host_time_of_the_leaf_spans_covers_the_sweep():
    got = P.analyse(_window(HOST, OPS, MODULES), 1, SCOPES)
    host = got["host_s_per_request"]
    assert host == pytest.approx({
        "sweep.validate": 200e-9, "sweep.place": 100e-9,
        "engine.dispatch": 10e-9, "engine.wait": 90e-9,
        "sweep.unpack": 130e-9})
    assert got["sweep_host_s"] == pytest.approx(580e-9)
    assert got["leaf_spans_host_s"] == pytest.approx(530e-9)
    assert got["leaf_share_of_sweep_host"] == pytest.approx(530 / 580)


PROGRAM_FIXTURE = os.path.join(os.path.dirname(FIXTURE),
                               "v5e_program_slice.json")


def test_recorded_window_with_the_programs_marks():
    """1.5 ms of a v5e trace at the end of an engine call: the device
    time splits by scope, and the gap after the call is the program's
    wait where the benchmark's reader can only say ``sweep``."""
    with open(PROGRAM_FIXTURE) as f:
        doc = json.load(f)
    planes = _planes(doc)
    tr = T.from_planes(planes, chips=1)
    got = P.analyse(planes, 1, doc["scopes"])
    lo, hi = tr.window
    busy = tr.busy_ns(0, lo, hi) / 1e9
    assert got["device_busy_s"] == busy
    scopes = got["device_scopes"]
    assert sum(scopes.values()) == pytest.approx(busy)
    assert {P.UNSCOPED, "cycle.transfer", "cycle.inject"} <= set(scopes)
    assert set(got["copy_scopes"]) <= set(scopes)
    assert tr.breakdown()["idle_gaps"][0][0] == "sweep"
    assert got["idle_gaps"][0][0] == "repro.engine.wait"
    _, prog = P.spans_of(planes)
    ctx = _ctx(planes, program_spans=prog)
    assert P.host_s_in(ctx, "engine.wait") == pytest.approx(
        got["host_s_per_request"]["engine.wait"])
    assert _read("sweep_validate_s.sweep", ctx) is None


def test_scope_of_takes_the_innermost_scope():
    assert P.scope_of("jit(engine_fn)/while/body/engine.freeze/"
                      "vmap(cycle.credit)/gather") == "cycle.credit"
    assert P.scope_of("jit(engine_fn)/while/body/engine.guard/ge") \
        == "engine.guard"
    assert P.scope_of("jit(engine_fn)/while/cond/and") == P.UNSCOPED
    assert P.scope_of("") == P.UNSCOPED


# ----------------------------------------------------------------------
# the metric readers
# ----------------------------------------------------------------------
def _read(name, ctx):
    return harness.load_metric(BENCH, name).read(ctx)


def test_span_readers_on_a_synthetic_window():
    planes = _window(HOST, OPS, MODULES)
    _, prog = P.spans_of(planes)
    ctx = _ctx(planes, program_spans=prog)
    assert _read("sweep_validate_s.sweep", ctx) == pytest.approx(200e-9)
    assert _read("sweep_place_s.sweep", ctx) == pytest.approx(100e-9)
    assert _read("sweep_unpack_s.sweep", ctx) == pytest.approx(130e-9)
    assert _read("pack_plan_s.sweep", ctx) is None     # an unpacked sweep


def test_span_readers_give_none_without_the_programs_spans():
    ctx = _ctx(_window(HOST[:3], OPS), program_spans=[])
    for name in NEW_METRICS[:4]:
        assert _read(name, ctx) is None


def test_live_share_reads_the_engine_counters():
    tel = lambda **k: types.SimpleNamespace(**k)
    reqs = [(0, 1, types.SimpleNamespace(telemetry=tel(
        stepped_pe_ticks=1000, live_pe_ticks=250))),
        (1, 2, types.SimpleNamespace(telemetry=tel(
            stepped_pe_ticks=3000, live_pe_ticks=750)))]
    ctx = types.SimpleNamespace(requests=reqs)
    assert _read("engine_live_share.sweep", ctx) == pytest.approx(0.25)
    old = [(0, 1, types.SimpleNamespace(telemetry=tel(
        stepped_pe_ticks=1000)))]
    assert _read("engine_live_share.sweep",
                 types.SimpleNamespace(requests=old)) is None
    assert _read("engine_live_share.sweep",
                 types.SimpleNamespace(requests=[])) is None


def test_a_trace_file_of_another_window_is_not_read(tmp_path):
    """The readers find the profile where the harness writes it, and
    read it only when its window is the one the run measured."""
    cell = types.SimpleNamespace(name="x.y",
                                 bench_dir=str(tmp_path / "chipbench"))
    ctx = types.SimpleNamespace(cell=cell, trace=types.SimpleNamespace(
        window=(0, 1)))
    assert P.program_spans(ctx) == []       # no file at all
    assert ctx.program_spans == []


# ----------------------------------------------------------------------
# compiled modules and whole runs, on the CPU
# ----------------------------------------------------------------------
def _profile(tmp_path, fn):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    return path


def test_op_scopes_reads_the_compiled_modules_of_a_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        with jax.named_scope("cycle.credit"):
            y = jnp.sin(x) * 2
        with jax.named_scope("engine.guard"):
            return jnp.cumsum(y) > 1

    x = jnp.ones(256)
    step(x).block_until_ready()
    path = _profile(tmp_path, lambda: step(x).block_until_ready())
    with open(path, "rb") as f:
        scopes = P.op_scopes(f.read())
    mine = [v for k, v in scopes.items() if k.startswith("jit_step(")]
    assert mine, sorted(scopes)
    seen = set(mine[0].values())
    assert {"cycle.credit", "engine.guard"} <= seen, mine[0]


def _traced_run(tiny_bench, monkeypatch, **kw):
    """A traced run on the CPU.  Its trace holds no chip plane, so no
    engine run to count against the window's calls: the device_trace
    readers are let through as a whole trace would let them."""
    real = harness.trace_whole
    monkeypatch.setattr(harness, "trace_whole",
                        lambda ctx: dict(real(ctx), whole=True))
    root, bench = tiny_bench
    return harness.run("tiny.closed", 2 ** 31 + 7, 0.01, True,
                       t_start=time.perf_counter(), root=root,
                       bench_dir=bench, require_chip=False, **kw)


def test_traced_run_reports_the_programs_metrics(tiny_bench, monkeypatch):
    res = _traced_run(tiny_bench, monkeypatch)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    for name in ("sweep_validate_s.sweep", "sweep_place_s.sweep",
                 "sweep_unpack_s.sweep"):
        assert m[name]["value"] > 0, name
    assert "pack_plan_s.sweep" not in m
    assert 0 < m["engine_live_share.sweep"]["value"] < 1
    assert m["sweep_host_s.sweep"]["value"] >= sum(
        m[n]["value"] for n in ("sweep_validate_s.sweep",
                                "sweep_place_s.sweep",
                                "sweep_unpack_s.sweep"))


def test_traced_run_without_the_programs_spans(tiny_bench, monkeypatch):
    """A program that opens no spans (an older one): the run completes
    and the span metrics are left out."""
    import contextlib
    import importlib

    for mod in (importlib.import_module("repro.core.machine"),
                importlib.import_module("repro.core.sweep")):
        monkeypatch.setattr(mod, "span",
                            lambda name: contextlib.nullcontext())
    res = _traced_run(tiny_bench, monkeypatch)
    assert res["correct"], res["checks"]
    for name in NEW_METRICS[:4]:
        assert name not in res["metrics"]
    assert "sweep_host_s.sweep" in res["metrics"]
