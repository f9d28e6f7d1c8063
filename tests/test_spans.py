"""The program's own trace: host spans around each phase of a sweep, and
named scopes on the engine's device ops.

A sweep run under ``jax.profiler`` leaves one ``repro.*`` span per phase
on the thread that called it, nested inside the caller's span, and the
engine's lowered program names every scope of the simulated cycle.
"""
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.core import compiler, machine
from repro.core.batch import stack_workloads
from repro.core.machine import MachineConfig
from repro.core.sweep import SweepRequest, sweep

PER_CALL = ("sweep.place", "engine.dispatch", "engine.wait", "sweep.unpack")
CYCLE_SCOPES = ("cycle.credit", "cycle.route", "cycle.select",
                "cycle.decode", "cycle.compute", "cycle.transfer",
                "cycle.inject", "cycle.stats")
ENGINE_SCOPES = ("engine.freeze", "engine.ff_probe", "engine.ff_step",
                 "engine.guard")


def _cfg(w=4, h=4, **kw):
    kw.setdefault("mem_words", 1024)
    kw.setdefault("max_cycles", 100_000)
    return MachineConfig(width=w, height=h, **kw)


@pytest.fixture(scope="module")
def lanes():
    """Five spmv lanes over three meshes: the packer needs more than one
    wave for them."""
    rng = np.random.default_rng(5)
    wls = []
    for n, m in ((2, 6), (2, 12), (3, 9), (4, 6), (4, 12)):
        a = compiler.random_sparse(m, m, 0.4, rng)
        x = rng.integers(-3, 4, size=(m,))
        wls.append(compiler.build_spmv(a, x, _cfg(n, n)))
    return wls


def _traced_spans(tmp_path, run):
    """Run ``run()`` under the profiler inside a ``caller`` span; return
    ``(caller, spans)``: the caller's ``(line, start, end)`` and every
    ``repro.*`` span as ``(name, line, start, end)``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("caller"):
            run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    callers, spans = [], []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                s, d = int(e.start_ns), int(e.duration_ns)
                if e.name == "caller":
                    callers.append((i, s, s + d))
                elif e.name.startswith("repro."):
                    spans.append((e.name[len("repro."):], i, s, s + d))
    caller, = callers
    return caller, spans


def _check_nested(caller, spans):
    line, lo, hi = caller
    for name, ln, s, e in spans:
        assert ln == line, f"{name} on another thread"
        assert lo <= s <= e <= hi, f"{name} outside the caller's span"


def _count(spans):
    out: dict = {}
    for name, *_ in spans:
        out[name] = out.get(name, 0) + 1
    return out


def test_unpacked_sweep_has_one_span_per_phase(lanes, tmp_path):
    cfg, req = _cfg(), SweepRequest(workloads=lanes)
    sweep(cfg, req)                        # compile outside the trace
    caller, spans = _traced_spans(tmp_path, lambda: sweep(cfg, req))
    _check_nested(caller, spans)
    assert _count(spans) == {"sweep.validate": 1,
                             **{p: 1 for p in PER_CALL}}


def test_packed_sweep_has_one_span_per_wave(lanes, tmp_path):
    cfg, req = _cfg(), SweepRequest(workloads=lanes, pack=True)
    n_waves = sweep(cfg, req).pack.n_waves
    assert n_waves > 1
    caller, spans = _traced_spans(tmp_path, lambda: sweep(cfg, req))
    _check_nested(caller, spans)
    assert _count(spans) == {"sweep.validate": 1, "pack.plan": 1,
                             "sweep.wave": n_waves,
                             **{p: n_waves for p in PER_CALL}}
    waves = [(s, e) for n, _, s, e in spans if n == "sweep.wave"]
    for name, _, s, e in spans:
        if name in PER_CALL:
            assert any(ws <= s <= e <= we for ws, we in waves), \
                f"{name} outside every wave"


@pytest.mark.parametrize("fast_forward", [True, False],
                         ids=["ff", "plain"])
def test_engine_names_every_scope(lanes, fast_forward):
    cfg = _cfg(fast_forward=fast_forward)
    wb = stack_workloads(lanes[:2])
    b, n = wb.batch, wb.n_pes
    st = machine.init_lanes(cfg, wb.static_ams, wb.amq_len, wb.mem_val,
                            wb.mem_meta)
    engine = machine._get_engine(cfg, 512, n)
    text = engine.lower(
        wb.prog, np.zeros((b,), np.int32), np.asarray(wb.geoms, np.int32),
        np.zeros((b, n), np.int32),
        np.tile(np.arange(n, dtype=np.int32), (b, 1)), st,
        machine.unbounded_budget(b, n)).as_text(debug_info=True)
    want = CYCLE_SCOPES + (ENGINE_SCOPES if fast_forward
                           else ("engine.freeze", "engine.guard"))
    # a scope opened under vmap reads ``vmap(cycle.credit)``
    missing = [s for s in want
               if not re.search(r"[(/]" + re.escape(s) + r"[)/]", text)]
    assert not missing, missing
    if not fast_forward:
        assert "engine.ff_" not in text
