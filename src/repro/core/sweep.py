"""Structured sweep surface: :class:`SweepRequest` in, :class:`SweepReport`
out.

``machine.run_many`` grew one keyword per PR — nine kwargs, two of them
mutable out-param *dicts* (``pack_stats`` / ``shard_stats``) that callers
had to pre-allocate and rummage through by string key.  That shape cannot
be a service contract (the sweep service queues requests and returns
futures — there is nowhere to hand an out-param back), so this module
replaces it:

* :class:`SweepRequest` — a frozen dataclass naming everything a sweep
  needs (workloads, per-lane modes / geoms / cycle hints, packing and
  sharding switches).  Hashable-by-identity config you can stash, log,
  or resubmit.
* :class:`SweepReport` — the lane :class:`~repro.core.machine.RunResult`
  list plus the packing (:class:`PackStats`) and sharding
  (:class:`ShardStats`) schedules as real typed fields.  Iterates and
  indexes like the old result list, so ``for r in report`` just works.
* :func:`sweep` — the entry point.  It calls the same implementation as
  ``run_many`` (:func:`repro.core.machine._run_many_impl`), so results
  are bit-identical to the legacy surface by construction.

The legacy kwargs stay available on ``run_many`` as a shim; passing the
out-param dicts emits a ``DeprecationWarning``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core import machine
from repro.core.compiler import TiledWorkload
from repro.core.machine import MachineConfig, RunResult
from repro.core.spans import span


@dataclasses.dataclass(frozen=True)
class SweepRequest:
    """One batched design-space sweep, declaratively.

    Attributes mirror :func:`repro.core.machine._run_many_impl`'s
    contract (see its docstring for full semantics):

    * ``workloads`` — compiled workloads (anything with ``prog`` /
      ``static_ams`` / ``amq_len`` / ``mem_val`` / ``mem_meta``), or an
      already-stacked :class:`repro.core.batch.BatchedWorkloads`.  A
      :class:`repro.core.compiler.TiledWorkload` runs its tiles as lanes
      and comes back as one result (:func:`fold_tiles`); the per-lane
      fields below name it once.
    * ``modes`` / ``geoms`` / ``cycle_hints`` — optional per-lane mode
      names or bitmasks, ``(width, height)`` meshes, and measured-cycle
      runtime hints.
    * ``deadlines`` — optional per-lane cycle deadlines (None entries =
      unbounded).  A deadlined lane makes no state transition past its
      bound: it reports ``completed=False`` frozen exactly at the
      deadline while every other lane (co-tenant sub-lanes included)
      runs to completion — the runaway-lane watchdog of the batched
      surface.
    * ``pack`` / ``super_geom`` — sub-mesh lane packing into shared
      super-lanes (``geoms`` must then be None: the packer places lanes).
    * ``shard`` — lane-axis device sharding over ``jax.devices()``.
    * ``chunk`` — cycles per jitted engine chunk.
    * ``validate`` — pre-dispatch static verification tier
      (:mod:`repro.analysis`): ``"static"`` (default) rejects lanes with
      error-severity findings (malformed AMs, co-tenancy escapes,
      provable capacity violations) with a
      :class:`~repro.analysis.WorkloadValidationError`; ``"strict"``
      also fails on warnings; ``"off"`` dispatches unchecked.

    Sequences are frozen to tuples on construction so a request is an
    immutable value: submitting it twice (or to the sweep service and
    the blocking path) runs the same sweep.
    """
    workloads: tuple
    modes: tuple | None = None
    geoms: tuple | None = None
    cycle_hints: tuple | None = None
    pack: bool = False
    super_geom: tuple | None = None
    shard: bool = False
    chunk: int = 512
    validate: str = "static"
    deadlines: tuple | None = None

    def __post_init__(self):
        from repro.core.batch import BatchedWorkloads
        if not isinstance(self.workloads, BatchedWorkloads):
            wls = tuple(self.workloads)
            if not wls:
                raise ValueError("SweepRequest needs at least one workload")
            object.__setattr__(self, "workloads", wls)
        for f in ("modes", "geoms", "cycle_hints", "deadlines"):
            v = getattr(self, f)
            if v is not None:
                object.__setattr__(self, f, tuple(v))
        if self.deadlines is not None:
            # fail the request at construction, not deep inside the
            # engine-call plumbing with an opaque shape error
            object.__setattr__(
                self, "deadlines",
                tuple(machine._validate_deadlines(self.deadlines,
                                                  self.n_lanes)))
        if self.super_geom is not None:
            w, h = self.super_geom
            object.__setattr__(self, "super_geom", (int(w), int(h)))
        if self.validate not in ("off", "static", "strict"):
            raise ValueError(
                f"validate={self.validate!r}: expected 'off', 'static' or "
                "'strict'")
        if self.cycle_hints is not None:
            # Fail the request at construction, not deep inside planning
            # with an opaque shape error.
            from repro.core.batch import validate_hints
            object.__setattr__(
                self, "cycle_hints",
                tuple(validate_hints(self.cycle_hints, self.n_lanes)))

    @property
    def n_lanes(self) -> int:
        from repro.core.batch import BatchedWorkloads
        if isinstance(self.workloads, BatchedWorkloads):
            return self.workloads.batch
        return len(self.workloads)


@dataclasses.dataclass(frozen=True)
class PackStats:
    """The packing schedule a ``pack=True`` sweep actually ran.

    ``plan`` is the wave list from ``pack_schedule`` (one dict per wave
    naming its super-lane geometries and sub-lane placements), kept as
    reported for artifact round-tripping.
    """
    n_waves: int
    n_super_lanes: int
    packing_efficiency: float
    unpacked_efficiency: float
    plan: tuple = ()

    def to_json(self) -> dict:
        return dict(n_waves=int(self.n_waves),
                    n_super_lanes=int(self.n_super_lanes),
                    packing_efficiency=float(self.packing_efficiency),
                    unpacked_efficiency=float(self.unpacked_efficiency),
                    plan=list(self.plan))


@dataclasses.dataclass(frozen=True)
class ShardStats:
    """The device-sharding plan a ``shard=True`` sweep actually ran.

    ``plan`` lists lanes per device (per wave, when packed).  On a
    single-device host ``n_devices`` is 1 and the plan is the trivial
    one — recorded, not omitted, so artifacts stay shape-stable across
    hosts.
    """
    n_devices: int
    lanes_per_device: int
    n_pad_lanes: int
    plan: tuple = ()

    def to_json(self) -> dict:
        return dict(n_devices=int(self.n_devices),
                    lanes_per_device=int(self.lanes_per_device),
                    n_pad_lanes=int(self.n_pad_lanes),
                    plan=list(self.plan))


@dataclasses.dataclass(frozen=True)
class EngineTelemetry:
    """Engine-efficiency counters for the sweep's engine calls.

    ``stepped_pe_ticks`` counts wall PE-steps the engine actually
    executed; ``plain_pe_ticks`` what the plain tick-per-cycle engine
    would have executed to reach the same final cycle counters (chunk
    granularity — exactly what ``fast_forward=False`` runs).  Their gap
    is the event-compression win: :attr:`dead_step_fraction` is the
    fraction of plain PE-steps the fast-forward engine skipped (0.0 by
    construction on plain engines, and on workloads with no compressible
    lone-flight stretches).

    The other four split ``stepped_pe_ticks`` exactly
    (:func:`repro.core.machine.engine_call_ticks`): ``live_pe_ticks``
    simulated a cycle of a (sub-)lane; ``finished_pe_ticks`` stepped a
    lane that had finished, or was capped or halted, while others in its
    device shard still ran; ``tail_pe_ticks`` ran past the shard's last
    simulated cycle to the end of the chunk; ``pad_pe_ticks`` stepped
    rows of no lane.
    """
    stepped_pe_ticks: int
    plain_pe_ticks: int
    engine_calls: int
    live_pe_ticks: int = 0
    finished_pe_ticks: int = 0
    tail_pe_ticks: int = 0
    pad_pe_ticks: int = 0

    @property
    def dead_step_fraction(self) -> float:
        if self.plain_pe_ticks <= 0:
            return 0.0
        return max(0.0, 1.0 - self.stepped_pe_ticks / self.plain_pe_ticks)

    def to_json(self) -> dict:
        return dict(stepped_pe_ticks=int(self.stepped_pe_ticks),
                    plain_pe_ticks=int(self.plain_pe_ticks),
                    engine_calls=int(self.engine_calls),
                    dead_step_fraction=float(self.dead_step_fraction),
                    live_pe_ticks=int(self.live_pe_ticks),
                    finished_pe_ticks=int(self.finished_pe_ticks),
                    tail_pe_ticks=int(self.tail_pe_ticks),
                    pad_pe_ticks=int(self.pad_pe_ticks))


@dataclasses.dataclass(frozen=True)
class TileStats:
    """The tiled lanes of a sweep (:class:`repro.core.compiler.TiledWorkload`).

    ``am_fill`` is the tiles' static AMs over tiles x PEs x the per-PE
    word budget each tile was fit into: the share of the tiles' SRAM
    that holds nonzeros.
    """
    n_tiled_lanes: int
    n_tiles: int
    am_fill: float

    def to_json(self) -> dict:
        return dict(n_tiled_lanes=int(self.n_tiled_lanes),
                    n_tiles=int(self.n_tiles), am_fill=float(self.am_fill))


@dataclasses.dataclass(frozen=True)
class SweepReport:
    """Everything a sweep produced: per-lane results + the schedules.

    Behaves like the legacy result list (``len`` / index / iterate all
    hit ``lanes``), so migrating a call site is usually just swapping
    the call.  ``pack`` / ``shard`` are None when the corresponding
    switch was off.  ``telemetry`` carries the engine's dead-step
    accounting (always present on the ``sweep()`` path); ``tiles`` is
    None unless the request held tiled lanes.
    """
    lanes: tuple                      # tuple[RunResult, ...] in input order
    pack: PackStats | None = None
    shard: ShardStats | None = None
    telemetry: EngineTelemetry | None = None
    tiles: TileStats | None = None

    def __post_init__(self):
        object.__setattr__(self, "lanes", tuple(self.lanes))

    def __len__(self) -> int:
        return len(self.lanes)

    def __iter__(self):
        return iter(self.lanes)

    def __getitem__(self, i):
        return self.lanes[i]

    @property
    def cycles(self) -> list[int]:
        """Per-lane cycle counts — feed back as ``cycle_hints`` to replan
        a follow-up sweep with measured runtimes."""
        return [r.cycles for r in self.lanes]

    def to_json(self) -> dict:
        """One JSON document for the whole sweep (lane rows via
        :meth:`RunResult.to_json`, schedules via their own ``to_json``)."""
        return dict(
            lanes=[r.to_json() for r in self.lanes],
            pack=None if self.pack is None else self.pack.to_json(),
            shard=None if self.shard is None else self.shard.to_json(),
            telemetry=(None if self.telemetry is None
                       else self.telemetry.to_json()),
            tiles=None if self.tiles is None else self.tiles.to_json(),
        )


def _expand_tiles(request: SweepRequest) -> tuple[list, dict, list]:
    """Each tiled lane of ``request`` as one lane per tile.  Returns the
    flat workloads, the per-lane request fields repeated per tile (a
    lane's deadline bounds each of its tiles; its cycle hint is shared
    out among them), and the number of flat lanes of each requested
    lane."""
    counts = [len(w.tiles) if isinstance(w, TiledWorkload) else 1
              for w in request.workloads]
    flat = [t for w in request.workloads
            for t in (w.tiles if isinstance(w, TiledWorkload) else (w,))]

    def repeat(v, share=False):
        if v is None:
            return None
        return [x / c if share else x
                for x, c in zip(v, counts) for _ in range(c)]

    fields = dict(modes=repeat(request.modes), geoms=repeat(request.geoms),
                  cycle_hints=repeat(request.cycle_hints, share=True),
                  deadlines=repeat(request.deadlines))
    return flat, fields, counts


def fold_tiles(results) -> RunResult:
    """One lane's result from its tiles' results, run one after another
    with a global synchronisation between them (§3.1.4): cycles and
    every statistic are the sums over the tiles, the lane completed if
    every tile did, and ``mem_val`` stacks the tiles' final images
    (``(T, N, MEM)``, zero-padded to the widest)."""
    n = results[0].per_pe_busy.shape[0]
    cycles = sum(r.cycles for r in results)
    executed = sum(r.executed for r in results)
    enroute = sum(r.enroute for r in results)
    busy = sum(np.asarray(r.per_pe_busy) for r in results)
    words = max(r.mem_val.shape[-1] for r in results)
    mem = np.zeros((len(results), n, words), results[0].mem_val.dtype)
    for i, r in enumerate(results):
        mem[i, :, :r.mem_val.shape[-1]] = r.mem_val
    return RunResult(
        cycles=cycles, mem_val=mem,
        utilization=executed / max(1, cycles * n),
        busy_frac=float(busy.sum()) / max(1, cycles * n),
        per_pe_busy=busy, executed=executed, enroute=enroute,
        enroute_frac=enroute / max(1, executed),
        hops=sum(r.hops for r in results),
        injected=sum(r.injected for r in results),
        stall_per_port=sum(np.asarray(r.stall_per_port) for r in results),
        completed=all(r.completed for r in results))


def sweep(cfg: MachineConfig, request: SweepRequest) -> SweepReport:
    """Run one :class:`SweepRequest` to completion and report it.

    Blocking, same engine cache and bit-identical results as the legacy
    ``run_many`` surface (both call the same implementation).  For
    overlapped / interleaved traffic on one warm engine, use
    :class:`repro.serve.SweepService` instead.
    """
    if not isinstance(request, SweepRequest):
        raise TypeError(f"sweep() takes a SweepRequest, got "
                        f"{type(request).__name__} (legacy kwargs live on "
                        f"machine.run_many)")
    ps: dict | None = {} if request.pack else None
    ss: dict | None = {} if request.shard else None
    from repro.core.batch import BatchedWorkloads
    wls = (request.workloads if isinstance(request.workloads,
                                           BatchedWorkloads)
           else list(request.workloads))
    fields = dict(modes=request.modes, geoms=request.geoms,
                  cycle_hints=request.cycle_hints,
                  deadlines=request.deadlines)
    tiled = [w for w in wls if isinstance(w, TiledWorkload)] \
        if not isinstance(wls, BatchedWorkloads) else []
    if tiled:
        wls, fields, counts = _expand_tiles(request)
    if request.validate != "off" and not isinstance(wls, BatchedWorkloads):
        # Static pre-dispatch verification (repro.analysis): reject
        # malformed lanes here, with per-lane diagnostics, instead of
        # letting them poison a shared fabric at runtime.
        from repro.analysis import validate_request
        with span("sweep.validate"):
            validate_request(wls, modes=fields["modes"],
                             strict=(request.validate == "strict"),
                             stream_wait_cap=cfg.stream_wait_cap)
    tm: dict = {}
    results = machine._run_many_impl(
        cfg, wls, chunk=request.chunk, pack=request.pack,
        super_geom=request.super_geom, pack_stats=ps,
        shard=request.shard, shard_stats=ss, telemetry=tm,
        **{k: None if v is None else list(v) for k, v in fields.items()})
    tiles = None
    if tiled:
        with span("sweep.tile_fold"):
            it = iter(results)
            results = [fold_tiles([next(it) for _ in range(c)])
                       if isinstance(w, TiledWorkload) else next(it)
                       for w, c in zip(request.workloads, counts)]
            budget = sum(len(w.tiles) * w.tiles[0].amq_len.shape[0]
                         * w.mem_budget for w in tiled)
            tiles = TileStats(
                n_tiled_lanes=len(tiled),
                n_tiles=sum(len(w.tiles) for w in tiled),
                am_fill=sum(w.n_static_ams for w in tiled) / budget)
    pack = None if ps is None else PackStats(
        n_waves=ps["n_waves"], n_super_lanes=ps["n_super_lanes"],
        packing_efficiency=ps["packing_efficiency"],
        unpacked_efficiency=ps["unpacked_efficiency"],
        plan=tuple(ps.get("plan", ())))
    shard = None if ss is None else ShardStats(
        n_devices=ss["n_devices"], lanes_per_device=ss["lanes_per_device"],
        n_pad_lanes=ss["n_pad_lanes"], plan=tuple(ss.get("plan", ())))
    telemetry = EngineTelemetry(
        engine_calls=tm.get("engine_calls", 0),
        **{k: tm.get(k, 0) for k in machine.TICK_COUNTERS})
    return SweepReport(lanes=tuple(results), pack=pack, shard=shard,
                       telemetry=telemetry, tiles=tiles)
