"""The open-loop client of the resident ``SweepService``.

Independent architects share one resident simulator: each submits a
single design point of the configuration's grid when it comes to mind and
waits on its answer.  So lanes arrive on a schedule, whether or not the
service keeps up (an open loop), and each lane is timed from the moment
it was due, which counts the wait a stall imposes on the lanes behind it.

The schedule gives every seed the same work: the same arrival times
(the gaps between them are stratified quantiles of the exponential
distribution at the mix's ``rate``, in one order fixed for the mix) and
the same number of lanes of each grid point, each point's lanes spread
over the window in rounds; the seed decides only which point falls due
when.  Each lane is compiled at its due time, as a user pays for it, and
submitted with its fabric mode, by a few sender threads, so that a burst
of arrivals is not held up behind one compile at a time.

The mix keys read here (``traffic/<mix>.json`` with ``"loop":
"service"``): ``rate`` (lanes due per second), ``n_supers`` (the
service's resident super-lanes), ``chunk`` (cycles per engine chunk) and
``slice_chunks`` (chunks per scheduler slice, the grain of retirement and
refill).
"""
from __future__ import annotations

import concurrent.futures
import json
import sys
import time

import numpy as np

from chipbench import lanes as lanes_mod

# the seed's stream for the order of the points, apart from the data's
ORDER_STREAM = 0x5E4
# the one order of the gaps, the same for every seed
GAP_ORDER = 0x6A9
# threads that compile and submit the lanes as they fall due
SENDERS = 4


def schedule(rate: float, seconds: float, n_points: int,
             seed: int) -> tuple[np.ndarray, np.ndarray]:
    """``(due, point)``: each lane's due time in seconds from the window's
    open, ascending and inside ``[0, seconds)``, and the index of its grid
    point.

    The ``round(rate * seconds)`` gaps are the midpoint quantiles of an
    exponential distribution at ``rate``, scaled by a hair (0.2% at 180
    lanes) so that they sum to ``seconds``, and put in one order that
    does not depend on the seed: the first lane is due at the open and
    the last one gap before ``seconds``.  Every point gets ``n //
    n_points`` lanes, and the ``n % n_points`` left over go to points
    spread evenly over the grid.  A point's k-th lane belongs to round k;
    the lanes fall due round by round, and the seed orders the points
    within each round (a lane may swap places with a neighbouring
    round's)."""
    if not (rate > 0 and seconds > 0 and n_points > 0):
        raise ValueError(f"rate {rate}, seconds {seconds} and n_points "
                         f"{n_points} must be > 0")
    n = max(1, round(rate * seconds))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps *= seconds / gaps.sum()
    gaps = np.random.default_rng(GAP_ORDER).permutation(gaps)
    counts = np.full(n_points, n // n_points)
    extra = n % n_points
    if extra:
        counts[np.arange(extra) * n_points // extra] += 1
    points = np.repeat(np.arange(n_points), counts)
    rounds = np.concatenate([np.arange(c) for c in counts])
    rng = np.random.default_rng(np.random.SeedSequence([ORDER_STREAM, seed]))
    points = points[np.argsort(rounds + rng.random(n), kind="stable")]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due, points


def backlog(lanes: list, t: float) -> int:
    """Lanes due by ``t`` (on the host clock) and not yet resolved then."""
    return sum(ln.t_due <= t and (ln.t_done is None or ln.t_done > t)
               for ln in lanes)


class OpenService:
    """One resident ``SweepService`` for the whole run, sized by the grid's
    compiled points, and the lanes the schedule sends it."""

    # a lane still unresolved this long after the last one fell due has
    # never come
    DRAIN_S = 60.0

    def __init__(self, grid: lanes_mod.Grid, mix: dict, seed: int):
        from repro.serve import SweepService
        self.grid, self.seed = grid, seed
        self.rate = float(mix["rate"])
        self.svc = SweepService(
            grid.run_cfg, template=grid.build_all(),
            n_supers=int(mix["n_supers"]), chunk=int(mix["chunk"]),
            slice_chunks=int(mix["slice_chunks"]))

    def warm_up(self) -> None:
        """One lane of each fabric mode, stopped after one simulated cycle:
        the budget is runtime data to the engine, so that loads (or
        compiles) the service's engine, its install and its retirement
        without simulating the grid."""
        from repro.serve import DeadlineError
        firsts = {}
        for p in self.grid.points:
            firsts.setdefault(p.mode, p)
        futs = [self.svc.submit(self.grid.build(p), mode=p.mode,
                                deadline_cycles=1) for p in firsts.values()]
        for f in futs:
            try:
                f.result()
            except DeadlineError:
                pass
        self.svc.drain()

    def _send(self, ln) -> tuple[float, object]:
        """Compile lane ``ln`` and submit it; returns how late it was taken
        up and its future (None where compile or submit raised: the lane
        counts as unfinished)."""
        from chipbench.harness import span
        late = time.perf_counter() - ln.t_due
        try:
            with span("compile"):
                ln.wl = self.grid.build(ln.point)
            fut = self.svc.submit(ln.wl, mode=ln.point.mode)
        except Exception as e:
            ln.error = f"submit raised {type(e).__name__}: {e}"
            return late, None
        fut.add_done_callback(lambda f: _resolved(ln, f))
        return late, fut

    def window(self, seconds: float, t_open: float) -> dict:
        from chipbench.harness import Lane
        due, points = schedule(self.rate, seconds, len(self.grid.points),
                               self.seed)
        stats_open = dict(self.svc.stats)
        lanes = [Lane(self.grid.points[pi], t_due=t_open + float(d))
                 for d, pi in zip(due, points)]
        with concurrent.futures.ThreadPoolExecutor(SENDERS) as pool:
            sent = []
            for ln in lanes:
                wait = ln.t_due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                sent.append(pool.submit(self._send, ln))
            sent = [s.result() for s in sent]
        late = max(lt for lt, _ in sent)
        futs = [f for _, f in sent if f is not None]
        concurrent.futures.wait(
            futs, timeout=max(0.0, lanes[-1].t_due + self.DRAIN_S
                              - time.perf_counter()))
        stats_close = dict(self.svc.stats)
        done = [ln.t_done for ln in lanes if ln.t_done is not None]
        t_close = max(done) if done else time.perf_counter()
        print("service: " + json.dumps(dict(
            lanes=len(lanes), offered_per_s=self.rate,
            generator_late_max_s=late,
            backlog_mid=backlog(lanes, t_open + seconds / 2),
            backlog_end=backlog(lanes, t_open + seconds),
            unresolved=sum(not f.done() for f in futs),
            n_slices=stats_close["n_slices"] - stats_open["n_slices"])),
            file=sys.stderr, flush=True)
        return dict(t_close=t_close, lanes=lanes, requests=[],
                    stepped_pe_ticks=(stats_close["stepped_pe_ticks"]
                                      - stats_open["stepped_pe_ticks"]),
                    stats_open=stats_open, stats_close=stats_close,
                    generator_late_s=late)

    def close(self) -> None:
        """Stop the scheduler; a lane still unresolved fails."""
        self.svc.shutdown(wait=False)


def _resolved(ln, fut) -> None:
    # runs on the service's scheduler thread, as the future resolves
    ln.t_done = time.perf_counter()
    err = fut.exception()
    if err is None:
        ln.result = fut.result()
    else:
        ln.error = f"{type(err).__name__}: {err}"

