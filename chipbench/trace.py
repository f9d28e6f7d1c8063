"""Reduce a profiler trace to device busy time, idle gaps and host spans.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
On a TPU v5e it holds (read by hand from a chip trace of the Figs. 11-14
sweep):

* one plane ``/device:TPU:<n>`` per chip.  Its ``XLA Ops`` line has one
  event per operation the TensorCore ran, named by the HLO instruction
  (``%fusion.12 = s32[...] fusion(...)``); a ``while`` or ``conditional``
  event encloses the events of its body.  ``XLA Modules`` has one event
  per program run (``jit_engine_fn(<fingerprint>)``), ``Async XLA Ops``
  the copy-start/copy-done windows of DMAs that overlap compute.  Busy
  time is read from ``XLA Ops`` alone; the program runs are counted, to
  show that the trace holds every engine call the window made.
* the host plane ``/host:CPU``: one line per host thread (``python`` is
  the interpreter's), holding the runtime's own events and the
  benchmark's spans (``TraceAnnotation`` events named ``chipbench.<span>``).
* planes of no use here (``#Chip0 Host Interface``, ``#Chip0 Misc``,
  ``/host:metadata``, ``Task Environment``, ``/device:CUSTOM:...``).

Device and host events are on one clock, in nanoseconds.
"""
from __future__ import annotations

import collections
import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "chipbench."
TOP = 10


def merge(intervals) -> list[tuple[int, int]]:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged, lo: int, hi: int) -> int:
    """Nanoseconds of ``[lo, hi)`` that the merged intervals cover."""
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in merged)


def gaps(merged, lo: int, hi: int) -> list[tuple[int, int]]:
    """The parts of ``[lo, hi)`` that the merged intervals leave idle."""
    out, t = [], lo
    for s, e in merged:
        if e <= lo or s >= hi:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def op_name(event_name: str) -> str:
    """``%fusion.12 = s32[...] fusion(...)`` -> ``%fusion.12``."""
    return event_name.split(" = ", 1)[0]


def self_times(events, lo: int, hi: int) -> collections.Counter:
    """Seconds each operation ran itself inside ``[lo, hi)``: the part of
    its duration in the window, less the parts of the events it encloses.
    ``events`` are ``(start, end, name)`` of one line, nested as a stack."""
    out: collections.Counter = collections.Counter()
    stack: list[list] = []           # [end, name, self ns in the window]

    def clip(s, e):
        return max(0, min(e, hi) - max(s, lo))

    def pop():
        _, name, own = stack.pop()
        if own > 0:
            out[name] += own / 1e9

    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            pop()
        if stack:
            stack[-1][2] -= clip(s, e)
        stack.append([e, name, clip(s, e)])
    while stack:
        pop()
    return out


class Trace:
    """What a traced window reduces to: per chip, the merged intervals in
    which it ran an operation and each operation's self time; the
    benchmark's spans; and the window (the ``chipbench.window`` span)."""

    def __init__(self, busy: dict, op_s: dict, spans: list,
                 window: tuple[int, int], runs: dict | None = None):
        self.busy = busy              # {chip: merged [(start, end)]}
        self.op_s = op_s              # {chip: Counter(op -> seconds)}
        self.spans = spans            # [(name, start, end)], prefix removed
        self.window = window
        self.runs = runs or {}        # {chip: Counter(program -> runs)}
        self.any_busy = merge(iv for v in busy.values() for iv in v)

    def busy_ns(self, chip: int, lo: int, hi: int) -> int:
        return covered(self.busy.get(chip, ()), lo, hi)

    def any_busy_ns(self, lo: int, hi: int) -> int:
        """Nanoseconds of ``[lo, hi)`` in which some chip ran an op."""
        return covered(self.any_busy, lo, hi)

    def spans_named(self, name: str) -> list[tuple[int, int]]:
        """``name`` spans inside the window."""
        lo, hi = self.window
        return [(s, e) for n, s, e in self.spans
                if n == name and s >= lo and e <= hi]

    def span_at(self, t: int) -> str:
        """The innermost benchmark span open at ``t``, other than the
        window itself."""
        best = None
        for n, s, e in self.spans:
            if n != "window" and s <= t < e and (
                    best is None or s >= best[1]):
                best = (n, s)
        return best[0] if best else "outside_spans"

    def breakdown(self) -> dict:
        """The operations that ran longest by self time inside the window,
        summed over chips, and the longest gaps in which no chip ran
        anything, each named by the benchmark span open at its middle."""
        total: collections.Counter = collections.Counter()
        for c in self.op_s.values():
            total.update(c)
        lo, hi = self.window
        idle = sorted(gaps(self.any_busy, lo, hi),
                      key=lambda g: g[0] - g[1])[:TOP]
        return dict(
            device_ops=[[n, s] for n, s in total.most_common(TOP)],
            idle_gaps=[[self.span_at((s + e) // 2), (e - s) / 1e9]
                       for s, e in idle])


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def from_planes(planes, chips: int) -> Trace:
    """Reduce planes that have ``name`` and ``lines`` (each with ``name``
    and ``events`` of ``name``, ``start_ns`` and ``duration_ns``) —
    ``jax.profiler.ProfileData``'s, or a recorded fixture's."""
    planes = list(planes)
    spans = []
    for plane in planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = int(e.start_ns)
                        spans.append((e.name[len(SPAN_PREFIX):], s,
                                      s + int(e.duration_ns)))
    windows = [(s, e) for n, s, e in spans if n == "window"]
    if not windows:
        raise ValueError("the trace holds no chipbench.window span")
    lo, hi = windows[-1]
    busy, op_s, runs = {}, {}, {}
    for plane in planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        try:
            chip = int(plane.name[len(DEVICE_PLANE):])
        except ValueError:
            continue
        if chip >= chips:
            continue
        events = []
        runs[chip] = collections.Counter()
        for line in plane.lines:
            if line.name == OPS_LINE:
                for e in line.events:
                    s = int(e.start_ns)
                    events.append((s, s + int(e.duration_ns),
                                   op_name(e.name)))
            elif line.name == MODULES_LINE:
                runs[chip].update(e.name.split("(", 1)[0]
                                  for e in line.events
                                  if lo <= int(e.start_ns) < hi)
        busy[chip] = merge((s, e) for s, e, _ in events)
        op_s[chip] = self_times(events, lo, hi)
    return Trace(busy, op_s, spans, (lo, hi), runs)


def load(trace_dir: str, chips: int) -> Trace:
    from jax.profiler import ProfileData
    return from_planes(ProfileData.from_file(find(trace_dir)).planes, chips)
