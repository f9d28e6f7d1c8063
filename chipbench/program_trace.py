"""What the program itself marks in a traced window: its host spans and the
scope of each device operation.

The program opens a host span ``repro.<phase>`` around each phase of a
sweep (``repro.core.spans``: validate, pack plan, wave, place, engine
dispatch and wait, unpack) and names the sections of its engine with
``jax.named_scope``: ``cycle.<phase>`` for the phases of one simulated
cycle, ``engine.<part>`` for the engine's masking, fast-forward and
guard.  A program without them (an older one) leaves nothing here to
read, and every reader then gives None.

Where each lies in a TPU v5e trace (read by hand from chip traces):

* the spans are ``TraceAnnotation`` events on the host plane
  ``/host:CPU``, on the thread that called ``sweep()``, like the
  benchmark's own ``chipbench.*`` spans (:mod:`chipbench.trace`);
* an ``XLA Ops`` event carries no framework op name among its own stats
  (only ``device_offset_ps``, ``device_duration_ps`` and ``Time Scale
  Multiplier``).  The plane ``/host:metadata`` holds one event metadata
  per program, named as the program's ``XLA Modules`` event
  (``jit_engine_fn(<fingerprint>)``), with a stat ``Hlo Proto``: the
  compiled module, whose instructions keep ``metadata.op_name``, the
  name stack (``jit(engine_fn)/while/body/.../vmap(cycle.credit)/gather``).
  An op's scope is the innermost ``cycle.*`` or ``engine.*`` in the op
  name of its instruction; a fusion with no op name takes the scope most
  of its fused instructions carry.  Ops with neither are ``(unscoped)``:
  copies XLA adds for the loop carry among them.

``jax.profiler.ProfileData`` does not expose event metadata, so the
modules are read from the file's protobuf wire format here, field by
field (XSpace, XPlane, XEventMetadata, XStat; HloProto, HloModuleProto,
HloComputationProto, HloInstructionProto, OpMetadata).

Run on a traced window's directory to print the breakdown by scope and
by span (a run of the benchmark with ``--trace 1`` leaves it under
``experiments/chipbench/trace/<cell>``)::

    python chipbench/program_trace.py experiments/chipbench/trace/<cell>
"""
from __future__ import annotations

import bisect
import collections
import json
import os
import re
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from chipbench import trace as T  # noqa: E402

PREFIX = "repro."
UNSCOPED = "(unscoped)"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
SCOPE = re.compile(r"(?:cycle|engine)\.[a-z_]+")
# the spans that hold no other program span: their host time adds up
LEAVES = ("sweep.validate", "pack.plan", "sweep.place", "engine.dispatch",
          "engine.wait", "sweep.unpack")


# ----------------------------------------------------------------------
# spans
# ----------------------------------------------------------------------
def spans_of(planes) -> tuple[list, list]:
    """``(benchmark, program)`` spans of the host plane, each a list of
    ``(name, start, end)`` with its prefix removed."""
    bench, prog = [], []
    for plane in planes:
        if plane.name != T.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                for pre, out in ((T.SPAN_PREFIX, bench), (PREFIX, prog)):
                    if e.name.startswith(pre):
                        s = int(e.start_ns)
                        out.append((e.name[len(pre):], s,
                                    s + int(e.duration_ns)))
    return bench, prog


def in_window(spans, window) -> list:
    lo, hi = window
    return [(n, s, e) for n, s, e in spans if s >= lo and e <= hi]


def _trace_dir(ctx) -> str:
    """Where the harness writes a traced run's profile by default: under
    the checkout that holds the cell's benchmark directory."""
    root = os.path.dirname(os.path.abspath(ctx.cell.bench_dir))
    return os.path.join(root, "experiments", "chipbench", "trace",
                        ctx.cell.name)


def program_spans(ctx) -> list:
    """The program's spans in ``ctx``'s traced window, read once from the
    trace file the run wrote and kept on ``ctx``.  Empty where the file
    is not the one ``ctx.trace`` was read from, or holds none."""
    got = getattr(ctx, "program_spans", None)
    if got is None:
        got = []
        try:
            path = T.find(_trace_dir(ctx))
        except FileNotFoundError:
            path = None
        if path is not None:
            from jax.profiler import ProfileData
            bench, prog = spans_of(ProfileData.from_file(path).planes)
            windows = [(s, e) for n, s, e in bench if n == "window"]
            if windows and windows[-1] == tuple(ctx.trace.window):
                got = in_window(prog, ctx.trace.window)
        ctx.program_spans = got
    return got


def host_s_in(ctx, name: str) -> float | None:
    """Per request: seconds of the program's span ``name`` in which no
    chip ran an operation; None where the window holds no such span."""
    tr = ctx.trace
    n = len(tr.spans_named("request"))
    spans = [(s, e) for m, s, e in program_spans(ctx) if m == name]
    if not n or not spans:
        return None
    return sum((e - s) - tr.any_busy_ns(s, e) for s, e in spans) / 1e9 / n


def span_at(spans, t: int) -> str:
    """The innermost span open at ``t`` of ``(name, start, end)`` spans
    whose names carry their prefix, other than the benchmark's window."""
    best = None
    for n, s, e in spans:
        if n != T.SPAN_PREFIX + "window" and s <= t < e and (
                best is None or s >= best[1]):
            best = (n, s)
    return best[0] if best else "outside_spans"


# ----------------------------------------------------------------------
# the compiled modules in the trace, read from the protobuf wire format
# ----------------------------------------------------------------------
def _varint(b: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b: bytes, lo: int = 0, hi: int | None = None):
    """``(field, value)`` of the message in ``b[lo:hi]``: an int for a
    varint, a ``(start, end)`` slice for a length-delimited field."""
    i, hi = lo, len(b) if hi is None else hi
    while i < hi:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = b[i:i + n], i + n
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield field, v


def _all(b, msg, field) -> list:
    return [v for f, v in _fields(b, *msg) if f == field]


def _one(b, msg, field, default=None):
    got = _all(b, msg, field)
    return got[-1] if got else default


def _str(b, msg, field) -> str:
    v = _one(b, msg, field)
    return "" if v is None else b[v[0]:v[1]].decode("utf-8", "replace")


def _ints(b, msg, field) -> list[int]:
    """A repeated integer field, packed or not."""
    out = []
    for v in _all(b, msg, field):
        if isinstance(v, tuple):
            i = v[0]
            while i < v[1]:
                x, i = _varint(b, i)
                out.append(x)
        else:
            out.append(v)
    return out


def scope_of(op_name: str) -> str:
    """The innermost ``cycle.*`` / ``engine.*`` scope in a name stack."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else UNSCOPED


def _module_scopes(b: bytes, hlo) -> dict:
    """``{instruction: scope}`` of one ``HloProto``."""
    module = _one(b, hlo, 1)                 # HloProto.hlo_module
    comps = {}                               # id -> [(name, op_name, calls)]
    for comp in _all(b, module, 3):          # HloModuleProto.computations
        rows = []
        for ins in _all(b, comp, 2):         # .instructions
            meta = _one(b, ins, 7)           # .metadata (OpMetadata)
            rows.append((_str(b, ins, 1),
                         "" if meta is None else _str(b, meta, 2),
                         _ints(b, ins, 38)))  # .called_computation_ids
        comps[_one(b, comp, 5)] = rows       # HloComputationProto.id

    def fused(cid, seen) -> collections.Counter:
        out: collections.Counter = collections.Counter()
        if cid in seen:
            return out
        seen.add(cid)
        for _, op_name, calls in comps.get(cid, ()):
            if op_name:
                out[scope_of(op_name)] += 1
            for c in calls:
                out.update(fused(c, seen))
        return out

    out = {}
    for rows in comps.values():
        for name, op_name, calls in rows:
            if op_name:
                out[name] = scope_of(op_name)
                continue
            votes: collections.Counter = collections.Counter()
            for c in calls:
                votes.update(fused(c, set()))
            votes.pop(UNSCOPED, None)
            out[name] = (min(votes, key=lambda s: (-votes[s], s))
                         if votes else UNSCOPED)
    return out


def op_scopes(raw: bytes) -> dict:
    """``{program: {instruction: scope}}`` from the ``Hlo Proto`` stats of
    an ``.xplane.pb``'s metadata plane; programs are named as their
    ``XLA Modules`` events."""
    out = {}
    for f, plane in _fields(raw):
        if f != 1 or _str(raw, plane, 2) != METADATA_PLANE:   # XSpace.planes
            continue
        stat_names = {}
        for entry in _all(raw, plane, 5):         # XPlane.stat_metadata
            md = _one(raw, entry, 2)
            stat_names[_one(raw, md, 1)] = _str(raw, md, 2)
        for entry in _all(raw, plane, 4):         # XPlane.event_metadata
            md = _one(raw, entry, 2)
            for stat in _all(raw, md, 5):         # XEventMetadata.stats
                if stat_names.get(_one(raw, stat, 1)) == HLO_STAT:
                    hlo = _one(raw, stat, 6)      # XStat.bytes_value
                    if hlo is not None:
                        out[_str(raw, md, 2)] = _module_scopes(raw, hlo)
    return out


# ----------------------------------------------------------------------
# the whole window
# ----------------------------------------------------------------------
def _is_copy(op: str) -> bool:
    return op == "%copy" or op.startswith(("%copy.", "%copy-"))


def analyse(planes, chips: int, scopes: dict) -> dict:
    """One pass over a traced window.  Returns the host seconds of each
    program span per request (those in which no chip ran an op) and
    their sum over the leaf spans beside the benchmark's ``sweep`` span;
    device self-seconds per scope summed over chips (``device_scopes``,
    ``(unscoped)`` included, each op looked up in the module whose ``XLA
    Modules`` event encloses it) and those of ``copy`` ops
    (``copy_scopes``); and the longest idle gaps, named by the innermost
    span of either prefix."""
    planes = list(planes)
    bench, prog = spans_of(planes)
    windows = [(s, e) for n, s, e in bench if n == "window"]
    if not windows:
        raise ValueError("the trace holds no chipbench.window span")
    lo, hi = window = windows[-1]
    prog = in_window(prog, window)
    busy: dict = {}
    scope_s: collections.Counter = collections.Counter()
    copy_s: collections.Counter = collections.Counter()
    for plane in planes:
        if not plane.name.startswith(T.DEVICE_PLANE):
            continue
        try:
            chip = int(plane.name[len(T.DEVICE_PLANE):])
        except ValueError:
            continue
        if chip >= chips:
            continue
        mods, ops = [], []
        for line in plane.lines:
            if line.name not in (MODULES_LINE, T.OPS_LINE):
                continue
            out = mods if line.name == MODULES_LINE else ops
            for e in line.events:
                s = int(e.start_ns)
                out.append((s, s + int(e.duration_ns), e.name))
        mods.sort()
        starts = [m[0] for m in mods]
        tagged = []
        for s, e, name in ops:
            op = T.op_name(name)
            k = bisect.bisect_right(starts, s) - 1
            table = (scopes.get(mods[k][2], {})
                     if k >= 0 and s < mods[k][1] else {})
            tagged.append((s, e, (table.get(op.lstrip("%"), UNSCOPED),
                                  _is_copy(op))))
        busy[chip] = T.merge((s, e) for s, e, _ in tagged)
        for (scope, copy), sec in T.self_times(tagged, lo, hi).items():
            scope_s[scope] += sec
            if copy:
                copy_s[scope] += sec
    tr = T.Trace(busy, {}, bench, window)
    n_req = len(tr.spans_named("request")) or 1

    def host(spans):
        return sum((e - s) - tr.any_busy_ns(s, e) for s, e in spans) / 1e9

    per_span = collections.defaultdict(list)
    for n, s, e in prog:
        per_span[n].append((s, e))
    host_s = {n: host(v) / n_req for n, v in sorted(per_span.items())}
    sweep_host = host(tr.spans_named("sweep")) / n_req
    leaf = sum(host_s.get(n, 0.0) for n in LEAVES)
    named = ([(T.SPAN_PREFIX + n, s, e) for n, s, e in bench]
             + [(PREFIX + n, s, e) for n, s, e in prog])
    idle = sorted(T.gaps(tr.any_busy, lo, hi),
                  key=lambda g: g[0] - g[1])[:T.TOP]
    return dict(
        requests=n_req,
        window_s=(hi - lo) / 1e9,
        device_busy_s=sum(tr.busy_ns(d, lo, hi) for d in busy) / 1e9,
        span_count={n: len(v) / n_req for n, v in sorted(per_span.items())},
        host_s_per_request=host_s,
        sweep_host_s=sweep_host,
        leaf_spans_host_s=leaf,
        leaf_share_of_sweep_host=(leaf / sweep_host if sweep_host > 0
                                  else None),
        device_scopes=dict(scope_s.most_common()),
        copy_scopes=dict(copy_s.most_common()),
        idle_gaps=[[span_at(named, (s + e) // 2), (e - s) / 1e9]
                   for s, e in idle])


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args(argv)
    path = T.find(args.trace_dir)
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        scopes = op_scopes(f.read())
    planes = list(ProfileData.from_file(path).planes)
    print(json.dumps(analyse(planes, args.chips, scopes)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
