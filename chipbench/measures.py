"""The arithmetic the metric readers share: each reader in ``metrics/``
picks what it reads from the window (``ctx``, a
:class:`chipbench.harness.Window`) and reduces it here."""
from __future__ import annotations

import math


def pe_cycles(lanes) -> int:
    """Simulated PE-cycles of the lanes that came back: each lane's cycles
    times the PE count of its own mesh, so padding rows never count."""
    return sum(int(ln.result.cycles) * ln.point.n_pes for ln in lanes
               if ln.result is not None)


def per_request_span_s(ctx, name: str) -> float | None:
    """Seconds of benchmark span ``name`` per request in the traced
    window."""
    tr = ctx.trace
    n = len(tr.spans_named("request"))
    if not n:
        return None
    return sum(e - s for s, e in tr.spans_named(name)) / 1e9 / n


def host_s_in(ctx, name: str) -> float | None:
    """Per request: seconds of span ``name`` in which no chip ran an
    operation."""
    tr = ctx.trace
    n = len(tr.spans_named("request"))
    if not n:
        return None
    return sum((e - s) - tr.any_busy_ns(s, e)
               for s, e in tr.spans_named(name)) / 1e9 / n


def engine_ns_per_pe_tick(ctx) -> float | None:
    """Device busy nanoseconds, summed over the chips, per PE-step the
    engine took in the traced window."""
    tr = ctx.trace
    lo, hi = tr.window
    busy = sum(tr.busy_ns(d, lo, hi) for d in range(ctx.n_devices))
    if ctx.stepped_pe_ticks <= 0 or busy <= 0:
        return None
    return busy / ctx.stepped_pe_ticks


def device_idle_share(ctx) -> float | None:
    """1 - busy / window, averaged over the chips the cell uses."""
    tr = ctx.trace
    lo, hi = tr.window
    if hi <= lo:
        return None
    return sum(1.0 - tr.busy_ns(d, lo, hi) / (hi - lo)
               for d in range(ctx.n_devices)) / ctx.n_devices


def stats_change(ctx, key: str) -> float | None:
    """The change of ``SweepService.stats[key]`` over the window; None
    where no service ran it."""
    if ctx.stats_open is None or ctx.stats_close is None:
        return None
    return ctx.stats_close[key] - ctx.stats_open[key]


def latency_s(lane) -> float:
    """Seconds from a lane's due time to its result; a lane that failed
    or never came misses any limit."""
    if lane.result is None or lane.error is not None or lane.t_done is None:
        return math.inf
    return lane.t_done - lane.t_due


def lane_percentile_s(ctx, q: float) -> float | None:
    """The ``q`` quantile, by nearest rank, of :func:`latency_s` over the
    lanes due in the window; None where it lies among lanes that never
    came."""
    lat = sorted(latency_s(ln) for ln in ctx.lanes if ln.t_due is not None)
    if not lat:
        return None
    v = lat[math.ceil(q * len(lat)) - 1]
    return v if math.isfinite(v) else None
