"""Nexus Machine cycle-level simulator (paper §3, Fig. 8) — in JAX.

The fabric is modeled as a *vectorized synchronous state machine*: the whole
PE array advances one clock per call of :func:`cycle`, and a run is a jitted
``lax.scan`` over cycles.  All state lives in fixed-shape ``int32`` arrays
(struct-of-arrays messages, see :mod:`repro.core.am`), so the simulator is a
pure JAX program — jit-able and vmap-able across configurations (used by the
design-space sweeps in benchmarks/fig16/fig17).

Modeled hardware (Fig. 8):
  * W×H mesh, 5-port routers (N/E/S/W + injection), 3-deep input buffers.
  * Turn-model (west-first) routing with *congestion-aware* adaptive choice
    between the two permitted minimal directions (§3.3.2).
  * ON/OFF flow control: a hop is granted only while the downstream buffer
    has ≥ 2 free slots (T_OFF = 1, T_ON = 2).
  * Separable allocation: one grant per output port, round-robin priority.
  * Per-PE: decode unit (dereference + streaming modes) and a compute
    unit (ALU) as SEPARATE single-issue units (Fig. 8b) — one memory-class
    and one ALU-class instruction may retire per cycle; an AM queue of
    compile-time static AMs; a pending-output FIFO into the injection
    port; dynamic AMs have injection priority over static AMs.
  * Opportunistic **in-network execution** (§3.1.3): an ALU-class message
    whose operands are complete may be intercepted and executed by any idle
    PE it traverses (``opportunistic=True``; disable to get the TIA
    baseline, add ``valiant=True`` for TIA-Valiant).

Simplifications (documented per DESIGN.md §2): single-cycle router / ALU /
SRAM; arithmetic in int32 without 16-bit wraparound (test data is kept in
range); off-chip refill of AM queues is modeled by the queue itself (loading
is overlapped with execution per §3.3.3, so steady-state behaviour matches).

Fabric modes as runtime data (the per-lane mode axis)
-----------------------------------------------------
The paper's cross-architecture comparisons (Figs. 11-14) run the *same*
workloads on Nexus, TIA and TIA-Valiant.  Those three execution models
differ only in the ``opportunistic`` / ``dual_issue`` / ``valiant``
behaviours, so the simulator encodes them as a per-lane **mode bitmask**
(:data:`MODE_OPPORTUNISTIC` | :data:`MODE_DUAL_ISSUE` |
:data:`MODE_VALIANT`) that is a *traced* argument of the compiled engine —
mode-dependent behaviour is masked dataflow (``jnp.where``), not Python
branching.  :data:`FABRIC_MODES` names the three paper architectures
(``nexus``/``tia``/``tia_valiant``) and maps them to mode codes; arbitrary
bitmask combinations (e.g. opportunistic-off but dual-issue-on ablations)
are equally valid lanes.  One compiled engine therefore serves the whole
(workload x mode) grid: :func:`run_many` accepts per-lane ``modes`` and
the engine-cache key ignores the mode flags entirely.

Fabric geometry as runtime data (the per-lane size axis)
---------------------------------------------------------
The paper's scaling result (Fig. 17: 2x2 -> 8x8 PE arrays) sweeps the mesh
*geometry*, so — like the mode — the per-lane ``(width, height)`` pair is a
*traced* ``(2,)`` int32 vector of the compiled engine (default
``traced_geometry=True``).  Every ``MachineState`` PE axis is padded to a
batch-wide ``N_max``; routing, neighbor indices and the PE coordinate maps
are computed from the traced geometry instead of the static
``cfg.neighbor_maps()`` table, and PEs at index >= width*height are
*inactive*: they hold all-zero state, are masked out of injection,
execution selection and the idle test, and are sliced out of per-lane
results — so a padded lane is bit-identical to its solo run on the native
mesh.  One compiled engine (keyed on ``N_max``, not on width/height)
therefore serves every (workload x mode x size) sweep point.

Sub-mesh lane packing (co-scheduling small meshes)
---------------------------------------------------
Padding every lane to the batch-wide ``N_max`` makes small lanes step
dead PE rows.  ``run_many(..., pack=True)`` co-schedules several small
lanes as *disjoint rectangular sub-meshes of one padded super-lane*
(:mod:`repro.core.batch`): west-first minimal routing never leaves the
src->dst bounding box, so rectangles are isolated by construction and
the engine only needs per-sub-lane *accounting* — the per-PE ``sub_ids``
vector groups PEs into sub-lanes whose cycle counters and statistics
freeze independently at each sub-lane's own idle point
(:func:`group_idle`), and ``local_ids`` keys the Valiant waypoint hash
on sub-mesh-local PE ids so a relocated lane draws its solo waypoint
sequence.  Dissimilar-runtime lanes are serialized into waves
(:func:`repro.core.batch.plan_waves`) that reuse the ONE compiled
engine; packed per-lane metrics are bit-identical to solo runs
(tests/test_lane_packing.py).

Multi-device lane sharding (scaling the lane axis)
---------------------------------------------------
Lanes are embarrassingly parallel — the vmapped cycle function never
reads across the batch axis — so ``run_many(..., shard=True)`` splits
the lane axis over ``jax.devices()`` with ``shard_map``: each device
runs the chunked while-loop over its own B/D lanes (no cross-device
sync per chunk) and per-lane metrics stay bit-identical to the
unsharded and solo runs.  :func:`repro.core.batch.plan_shards` balances
lanes across devices by the same runtime estimate the wave planner
uses and pads B to a multiple of the device count with inert empty
lanes.  The sharded engine is still ONE executable — per-lane
``prog``/mode/geometry stay runtime data; only a real multi-device
mesh keys a separate cache entry (``shard=True`` on one device reuses
the plain engine).  Composes with ``pack=True``: each wave's
super-lanes shard.

What stays *static* (compile-time) in :class:`MachineConfig`: the padded
PE-axis length, memory and queue capacities
(``mem_words``/``queue_cap``/``stream_wait_cap``), and ``max_cycles`` —
anything that changes array shapes or trip counts.  The three mode flags
and ``width``/``height`` remain on :class:`MachineConfig` as the *default*
mode / geometry for lanes that do not specify one, and — with
``traced_modes=False`` / ``traced_geometry=False`` — as fallbacks that
bake them into the trace exactly like the pre-traced engines (kept for
golden equivalence testing; one compile per mode / mesh size).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import am
from repro.core.spans import span
from repro.core.am import (
    C_DSTSEL, C_NEXT_PC, C_OP, C_OP1SEL, C_OP2SEL, C_RESSEL, C_ROTATE, CFG_F,
    F_DST0, F_DST1, F_DST2, F_HOPS, F_OP, F_OP1, F_OP1C, F_OP2, F_OP2C, F_PC,
    F_RES, F_RESC, F_TAG, F_VALID, F_VIA, MSG_F, OP_ADD, OP_CHECKSET, OP_DIV,
    OP_LOAD1, OP_LOAD2, OP_MAC, OP_MAX, OP_MIN, OP_MUL, OP_NOP, OP_STORE_ADD,
    OP_STORE_MIN, OP_STORE_SET, OP_STREAM, OP_SUB, UNSET, is_alu_op,
    is_mem_op,
)

DEPTH = 3          # input-buffer registers per port (§3.3.2)
PORTS = 5          # N, E, S, W, INJECT
P_N, P_E, P_S, P_W, P_INJ = range(5)
OUT_LOCAL = 4      # "output port" id meaning ejection to the Input NI
# AM NIC staging queue.  Consumption at the endpoint must be unconditional to
# preclude protocol (request–reply) deadlock — the paper relies on bubble
# flow control + compiler placement + runtime timeouts (§3.4); we provide the
# equivalent guarantee with a deep pending FIFO (overflow is asserted never
# to happen) and *backpressure-throttled* stream emission (§3.3.1: "the
# generation rate ... is determined by the backpressure signal").
PEND_CAP = 512
STREAM_THROTTLE = 8   # stream unit pauses while pending queue is this deep
# The three producers into the pending FIFO are gated so that its occupancy
# provably never exceeds PEND_CAP (see the reservation comments in
# _make_cycle): the stream gate checks the *post-execution-push* count, so
# it needs STREAM_THROTTLE < PEND_CAP; the execution units need 2 slots on
# top of the guard's high-water margin.  Checked here once because the
# constants are module-level (tests monkeypatch them to force violations).
assert STREAM_THROTTLE <= PEND_CAP - 3, "stream throttle must sit below cap"
# Free pending slots a message arriving from the network needs before an
# execution unit takes it, when executing it would push into the FIFO.
# Short of them the decode unit parks it in the wait queue, as it does a
# STREAM task, and it re-enters through the FIFO once that has as many
# free.  The PE's own messages in its inject port (TIA's anchored ALU ops,
# local static AMs) keep the 1/2-slot gates.  A hub PE needs both halves:
# one that keeps executing requests fills its FIFO with anchored ops that
# can then never execute (a deadlock inside one PE), and one that merely
# refuses them leaves them in the network, where they block its own
# replies (a deadlock through the network).  DEPTH + 5: with network and
# parked messages held back above PEND_CAP - NET_RESERVE, occupancy plus
# the inject port's DEPTH slots grows only by the last cycle's two pushes,
# so it stays below the guard's PEND_CAP - 2.
NET_RESERVE = DEPTH + 5

# --- fabric execution modes (per-lane runtime data) -------------------------
# Bitmask encoding of the three mode behaviours.  The mode travels with the
# lane through the compiled engine as a traced (B,) int32 vector, so every
# (workload x mode) sweep point shares ONE XLA executable.
MODE_OPPORTUNISTIC = 1   # in-network execution on idle PEs en route (§3.1.3)
MODE_DUAL_ISSUE = 2      # decode + compute units retire in the same cycle
MODE_VALIANT = 4         # randomized minimal-path (ROMM) injection routing

MODE_NEXUS = MODE_OPPORTUNISTIC | MODE_DUAL_ISSUE
MODE_TIA = 0
MODE_TIA_VALIANT = MODE_VALIANT

#: The paper's three fabric architectures, by name, in Fig. 11-14 order.
FABRIC_MODES = {
    "nexus": MODE_NEXUS,
    "tia": MODE_TIA,
    "tia_valiant": MODE_TIA_VALIANT,
}


def resolve_mode(mode) -> int:
    """Mode name (``FABRIC_MODES`` key) or raw bitmask -> int code."""
    if isinstance(mode, str):
        try:
            return FABRIC_MODES[mode]
        except KeyError:
            raise ValueError(f"unknown fabric mode {mode!r}; known: "
                             f"{sorted(FABRIC_MODES)}") from None
    code = int(mode)
    if not 0 <= code < 8:
        raise ValueError(f"mode bitmask out of range: {code}")
    return code


def mode_code(cfg: "MachineConfig") -> int:
    """The mode bitmask a config's flags describe (its default lane mode)."""
    return ((MODE_OPPORTUNISTIC if cfg.opportunistic else 0)
            | (MODE_DUAL_ISSUE if cfg.dual_issue else 0)
            | (MODE_VALIANT if cfg.valiant else 0))


def mode_flags(mode) -> dict:
    """Inverse of :func:`mode_code`: bitmask/name -> MachineConfig kwargs."""
    code = resolve_mode(mode)
    return dict(opportunistic=bool(code & MODE_OPPORTUNISTIC),
                dual_issue=bool(code & MODE_DUAL_ISSUE),
                valiant=bool(code & MODE_VALIANT))


@dataclasses.dataclass(frozen=True)
class MachineConfig:
    """Static (compile-time) machine parameters."""

    width: int = 4
    height: int = 4
    mem_words: int = 512          # 1 KB of 16-bit words per PE (Table 1)
    queue_cap: int = 2048         # AM-queue entries held per PE (see module doc)
    stream_wait_cap: int = 2048   # stream-task scheduler queue (see cycle())
    opportunistic: bool = True    # False => TIA baseline
    valiant: bool = False         # True  => TIA-Valiant baseline
    # Nexus dispatches the instruction carried in the message straight to
    # the decode OR compute unit — one of each may retire per cycle.  TIA's
    # scheduler tag-matches and its priority encoder *triggers one
    # instruction per cycle* (§2.2: the overhead the AM design removes), so
    # the TIA baselines run with dual_issue=False.
    dual_issue: bool = True
    max_cycles: int = 200_000
    # The mode flags above are *runtime data* to the compiled engine (see
    # module docstring): with traced_modes=True (default) they only pick the
    # default lane mode and the engine-cache key ignores them.  Setting
    # traced_modes=False bakes them into the trace as Python branches — the
    # pre-traced static engines, kept as the golden reference path.
    traced_modes: bool = True
    # Likewise width/height: with traced_geometry=True (default) they only
    # name the default lane geometry — the engine computes routing from a
    # traced per-lane (width, height) vector over a padded PE axis, and the
    # cache key keeps the padded length but not the mesh shape.  Setting
    # traced_geometry=False bakes the mesh into the trace (one compile per
    # fabric size — the pre-traced golden path).
    traced_geometry: bool = True
    # Event-compressed stepping (idle-cycle fast-forward): when a sub-lane's
    # whole remaining activity is ONE in-flight message in uncontended
    # flight, the engine advances that sub-lane by the message's remaining
    # west-first hop distance in a single masked step instead of ticking
    # every hop (:mod:`repro.core.fastforward`).  Cycle counters and every
    # per-PE statistic are bit-identical to the plain tick loop by
    # construction (the compressed advance replays exactly what the ticks
    # would have done); whenever the bound is 1 the engine degrades to the
    # plain behaviour.  fast_forward=False keeps the plain tick loop as the
    # reference implementation (the static==traced golden pattern) — it is
    # a *static* engine axis, so ff and plain key separate cache entries.
    fast_forward: bool = True

    @property
    def n_pes(self) -> int:
        return self.width * self.height

    def neighbor_maps(self) -> tuple[np.ndarray, np.ndarray]:
        """(N,4) neighbor PE id per direction (or -1) and opposite-port map."""
        n = self.n_pes
        nbr = np.full((n, 4), -1, dtype=np.int32)
        for p in range(n):
            x, y = p % self.width, p // self.width
            if y > 0:
                nbr[p, P_N] = p - self.width
            if x < self.width - 1:
                nbr[p, P_E] = p + 1
            if y < self.height - 1:
                nbr[p, P_S] = p + self.width
            if x > 0:
                nbr[p, P_W] = p - 1
        # A message leaving through N arrives on the neighbor's S port, etc.
        opp = np.array([P_S, P_W, P_N, P_E], dtype=np.int32)
        return nbr, opp


class MachineState(NamedTuple):
    """Complete fabric state (all fixed-shape int32/bool arrays)."""

    buf: jnp.ndarray        # (N, 5, DEPTH, MSG_F) input-port FIFOs
    buf_n: jnp.ndarray      # (N, 5) occupancy
    amq: jnp.ndarray        # (N, QCAP, MSG_F) static AM queues (read-only)
    amq_head: jnp.ndarray   # (N,)
    amq_len: jnp.ndarray    # (N,)
    pend: jnp.ndarray       # (N, PEND_CAP, MSG_F) output FIFO to inject port
    pend_h: jnp.ndarray     # (N,) circular-buffer head (oldest entry)
    pend_n: jnp.ndarray     # (N,)
    mem_val: jnp.ndarray    # (N, MEM) local data memory (values)
    mem_meta: jnp.ndarray   # (N, MEM, 2) per-word metadata (compiler-placed)
    stream_on: jnp.ndarray  # (N,) bool: streaming decode active
    stream_msg: jnp.ndarray  # (N, MSG_F) template message being streamed
    stream_base: jnp.ndarray  # (N,) current element address
    stream_left: jnp.ndarray  # (N,) elements remaining
    swq: jnp.ndarray        # (N, SWQ, MSG_F) stream-task wait queue
    swq_h: jnp.ndarray      # (N,) circular-buffer head (oldest entry)
    swq_n: jnp.ndarray      # (N,)
    rr: jnp.ndarray         # (N,) round-robin priority pointer
    cycle: jnp.ndarray      # (N,) per-PE cycle counter.  All PEs of one
    #   sub-lane advance in lockstep until their sub-lane idles, then
    #   freeze — so under sub-mesh packing each co-tenant keeps its own
    #   cycle count (solo lanes: one sub-lane = a uniform vector).
    # --- statistics (per-PE so packed sub-lanes account separately) -------
    st_busy: jnp.ndarray       # (N,) cycles each PE executed/streamed
    st_exec: jnp.ndarray       # (N,) instructions executed per PE
    st_enroute: jnp.ndarray    # (N,) executed opportunistically en route
    st_stall: jnp.ndarray      # (N, 5) head-of-line stall cycles per port
    st_hops: jnp.ndarray       # (N,) link traversals (sender-attributed)
    st_inj: jnp.ndarray        # (N,) messages injected


def init_state(cfg: MachineConfig,
               static_ams: np.ndarray,
               amq_len: np.ndarray,
               mem_val: np.ndarray,
               mem_meta: np.ndarray) -> MachineState:
    """Build the initial state from compiler outputs.

    Args:
      static_ams: (N, QCAP, MSG_F) per-PE compiled static AMs.
      amq_len:    (N,) number of valid entries per queue.
      mem_val/mem_meta: initial data-memory images.

    The PE-axis length is taken from ``static_ams`` (not ``cfg``): under
    traced geometry the arrays arrive padded to the batch-wide ``N_max``
    and the padded tail PEs start (and stay) all-zero.
    """
    n = int(static_ams.shape[0])
    z = jnp.zeros
    return MachineState(
        buf=z((n, PORTS, DEPTH, MSG_F), jnp.int32),
        buf_n=z((n, PORTS), jnp.int32),
        amq=jnp.asarray(static_ams, jnp.int32),
        amq_head=z((n,), jnp.int32),
        amq_len=jnp.asarray(amq_len, jnp.int32),
        pend=z((n, PEND_CAP, MSG_F), jnp.int32),
        pend_h=z((n,), jnp.int32),
        pend_n=z((n,), jnp.int32),
        mem_val=jnp.asarray(mem_val, jnp.int32),
        mem_meta=jnp.asarray(mem_meta, jnp.int32),
        stream_on=z((n,), jnp.bool_),
        stream_msg=z((n, MSG_F), jnp.int32),
        stream_base=z((n,), jnp.int32),
        stream_left=z((n,), jnp.int32),
        swq=z((n, cfg.stream_wait_cap, MSG_F), jnp.int32),
        swq_h=z((n,), jnp.int32),
        swq_n=z((n,), jnp.int32),
        rr=z((n,), jnp.int32),
        cycle=z((n,), jnp.int32),
        st_busy=z((n,), jnp.int32),
        st_exec=z((n,), jnp.int32),
        st_enroute=z((n,), jnp.int32),
        st_stall=z((n, PORTS), jnp.int32),
        st_hops=z((n,), jnp.int32),
        st_inj=z((n,), jnp.int32),
    )


# ----------------------------------------------------------------------------
# ALU
# ----------------------------------------------------------------------------
def _alu(op, a, b, res):
    """Vectorized ALU (op may be any opcode; result valid for ALU-class)."""
    div = jnp.where(b == 0, jnp.int32(0), a // jnp.where(b == 0, 1, b))
    return jnp.select(
        [op == OP_MUL, op == OP_ADD, op == OP_SUB, op == OP_MIN,
         op == OP_MAX, op == OP_DIV, op == OP_MAC],
        [a * b, a + b, a - b, jnp.minimum(a, b), jnp.maximum(a, b), div,
         res + a * b],
        default=jnp.int32(0),
    )


def _pick_one(cand: jnp.ndarray, rr: jnp.ndarray) -> jnp.ndarray:
    """Round-robin selection of one True lane per row.

    cand: (N, P) bool; rr: (N,) starting priority. Returns one-hot (N, P).
    """
    p = cand.shape[1]
    prio = (jnp.arange(p)[None, :] - rr[:, None]) % p
    score = jnp.where(cand, prio, p + 1)
    sel = jnp.argmin(score, axis=1)
    onehot = jax.nn.one_hot(sel, p, dtype=jnp.bool_)
    return onehot & cand.any(axis=1)[:, None] & cand


def _rotate_dsts(msg: jnp.ndarray) -> jnp.ndarray:
    """R1 <- R2 <- R3 <- -1 on a (..., MSG_F) message tensor."""
    msg = msg.at[..., F_DST0].set(msg[..., F_DST1])
    msg = msg.at[..., F_DST1].set(msg[..., F_DST2])
    msg = msg.at[..., F_DST2].set(-1)
    return msg


def _fifo_compact(buf: jnp.ndarray, keep: jnp.ndarray) -> jnp.ndarray:
    """Stable compaction of input-port FIFOs.

    buf: (..., D, F) slots, keep: (..., D) bool.  The kept slots move to
    the front in their order and the freed tail reads 0.  Output slot j
    takes the source slot k >= j whose rank among the kept slots is j: a
    select network over the static depth, with no sort, gather or scatter
    (those walk the array element by element on the chip).
    """
    depth = keep.shape[-1]
    rank = jnp.cumsum(keep, axis=-1) - 1
    slots = []
    for j in range(depth):
        out = jnp.zeros_like(buf[..., j, :])
        for k in range(j, depth):
            hit = keep[..., k] & (rank[..., k] == j)
            out = jnp.where(hit[..., None], buf[..., k, :], out)
        slots.append(out)
    return jnp.stack(slots, axis=-2)


def _fifo_set(buf: jnp.ndarray, port, slot, msg: jnp.ndarray,
              on: jnp.ndarray) -> jnp.ndarray:
    """``buf.at[pe, port, slot].set(msg)`` on every PE where ``on``.

    buf: (N, P, D, F) FIFOs; port, slot: (N,) int arrays or Python ints;
    msg: (N, F); on: (N,) bool.  A select against the static port and
    slot indices, not a scatter.
    """
    _, ports, depth, _ = buf.shape
    at = (on[:, None, None]
          & (jnp.asarray(port)[..., None, None] == jnp.arange(ports)[:, None])
          & (jnp.asarray(slot)[..., None, None] == jnp.arange(depth)))
    return jnp.where(at[..., None], msg[:, None, None, :], buf)


def _anchor_tia(nxt: jnp.ndarray, pe_ids: jnp.ndarray) -> jnp.ndarray:
    """TIA semantics (§2.2): compute is *anchored* with the data.

    An emitted ALU-class instruction executes on the emitting PE before the
    message moves on: retarget it to self (it re-enters through the inject
    port, paying the trigger/scheduler latency the paper attributes to TIA),
    push the true destination down the list, and mark it with F_VIA = -2 so
    execution knows to rotate the list back afterwards.
    """
    anchor = is_alu_op(nxt[..., F_OP]) & (nxt[..., F_DST0] != pe_ids) & \
        (nxt[..., F_VALID] == 1)
    nxt = nxt.at[..., F_DST2].set(
        jnp.where(anchor, nxt[..., F_DST1], nxt[..., F_DST2]))
    nxt = nxt.at[..., F_DST1].set(
        jnp.where(anchor, nxt[..., F_DST0], nxt[..., F_DST1]))
    nxt = nxt.at[..., F_DST0].set(jnp.where(anchor, pe_ids, nxt[..., F_DST0]))
    nxt = nxt.at[..., F_VIA].set(jnp.where(anchor, -2, nxt[..., F_VIA]))
    return nxt


# ----------------------------------------------------------------------------
# One clock cycle
# ----------------------------------------------------------------------------
def _make_cycle(cfg: MachineConfig, n_pes: int | None = None):
    """Build the program-, mode- and geometry-parametric cycle transition.

    Returns ``cycle(prog_j, mode, geom, st, local_ids=None) -> st`` where
    ``prog_j`` is the replicated configuration memory as a *traced*
    ``(P, CFG_F)`` array, ``mode`` a *traced* int32 mode bitmask (see
    :data:`FABRIC_MODES`) and ``geom`` a *traced* ``(2,)`` int32
    ``(width, height)`` vector.  Keeping the program, the execution mode
    and the mesh geometry out of the trace constants means one compiled
    engine serves every (workload x mode x size) point with the same
    shapes — the sweep compile cache in :func:`run_many` relies on this.
    With ``cfg.traced_modes=False`` / ``cfg.traced_geometry=False`` the
    corresponding argument is ignored and the config's flags / mesh are
    baked in as Python constants (the golden static paths).

    ``local_ids`` is the per-PE id *within its own sub-mesh* (defaults to
    the global PE index).  It only feeds the Valiant waypoint hash: under
    sub-mesh lane packing a relocated lane must draw the same waypoint
    sequence it would solo, so the hash keys on the sub-mesh-local id.

    ``halt`` is an optional (N,) bool mask of *budget-halted* PEs: rows
    where it is True make NO state transition this tick — no execution,
    no transit request, no stall/cycle/rr advance — so a budget-sliced
    engine call can freeze a sub-lane mid-chunk (its co-tenants keep
    stepping) and resume it later bit-identically.  ``halt=None`` (the
    default) is byte-for-byte the historical unconditional tick.  Halting
    is sound only per whole sub-lane (like idle freezing): west-first
    rectangle isolation guarantees a halted sub-lane neither sends nor
    receives across its boundary, so its transition is an exact no-op.

    ``n_pes`` is the PE-axis *array length* (>= the largest lane's
    width*height under traced geometry; must equal ``cfg.n_pes`` on the
    static path).

    Each section of the tick runs under a ``jax.named_scope``
    (``cycle.credit``, ``cycle.route``, ``cycle.select``,
    ``cycle.decode``, ``cycle.compute``, ``cycle.transfer``,
    ``cycle.inject``, ``cycle.stats``): op metadata only, so a device
    trace can put each operation down to its phase.
    """
    n = cfg.n_pes if n_pes is None else int(n_pes)
    if not cfg.traced_geometry:
        assert n == cfg.n_pes, \
            "static-geometry engines cannot pad the PE axis"
    # A message leaving through N arrives on the neighbor's S port, etc.
    opp_np = np.array([P_S, P_W, P_N, P_E], dtype=np.int32)
    opp = jnp.asarray(opp_np)          # (4,)
    pe_ids = jnp.arange(n, dtype=jnp.int32)

    def route(dest: jnp.ndarray, credit_ok: jnp.ndarray, w, xs,
              ys) -> jnp.ndarray:
        """West-first turn-model output port for (N,P) dest PE ids.

        credit_ok: (N,4) whether each directional output currently has
        downstream space — used for the *adaptive* choice between the two
        permitted minimal directions (congestion-aware, §3.3.2).
        ``w`` / ``xs`` / ``ys`` are the mesh width and per-PE coordinates
        (ints/arrays on the static path, traced values under traced
        geometry).
        Returns (N,P) int32 in {0..3, OUT_LOCAL}; undefined where dest<0.
        """
        dx = dest % w - xs[:, None]
        dy = dest // w - ys[:, None]
        # permitted minimal directions under west-first:
        #   dx<0  -> must go W first;  otherwise E (if dx>0) or N/S (if dy!=0)
        ns = jnp.where(dy < 0, P_N, P_S)
        east_ok = credit_ok[:, P_E][:, None]
        ns_ok = jnp.take_along_axis(
            credit_ok, jnp.broadcast_to(ns, dest.shape), axis=1)
        both = (dx > 0) & (dy != 0)
        # adaptive: among {E, N/S} prefer the one with credit; tie -> larger
        # remaining displacement (keeps paths spread).
        prefer_e = jnp.where(
            east_ok & ~ns_ok, True,
            jnp.where(~east_ok & ns_ok, False, jnp.abs(dx) >= jnp.abs(dy)))
        port = jnp.where(
            dx < 0, P_W,
            jnp.where(both, jnp.where(prefer_e, P_E, ns),
                      jnp.where(dx > 0, P_E,
                                jnp.where(dy != 0, ns, OUT_LOCAL))))
        return port.astype(jnp.int32)

    def cycle(prog_j: jnp.ndarray, mode: jnp.ndarray, geom: jnp.ndarray,
              st: MachineState,
              local_ids: jnp.ndarray | None = None,
              halt: jnp.ndarray | None = None) -> MachineState:
        sub_local = pe_ids if local_ids is None else local_ids
        # act masks every state-changing site below; with halt=None the
        # generated program is exactly the historical tick.
        act = None if halt is None else ~halt
        if cfg.traced_geometry:
            # Traced mesh: coordinates, neighbor indices and the active-PE
            # mask are recomputed from the (width, height) vector each
            # cycle — cheap (N,)-shaped integer work.  PEs at index >=
            # width*height are inactive: all their neighbor entries are -1
            # (no credit in, no transfers out) and they are masked out of
            # injection and execution selection below.  They also hold
            # all-zero state, so active PEs step bit-identically to a solo
            # run on the native mesh.
            w, gh = geom[0], geom[1]
            xs = pe_ids % w
            ys = pe_ids // w
            active = pe_ids < w * gh
            nbr = jnp.stack([
                jnp.where(active & (ys > 0), pe_ids - w, -1),
                jnp.where(active & (xs < w - 1), pe_ids + 1, -1),
                jnp.where(active & (ys < gh - 1), pe_ids + w, -1),
                jnp.where(active & (xs > 0), pe_ids - 1, -1),
            ], axis=1)                                  # (N,4) in N/E/S/W
        else:
            w = cfg.width
            xs = pe_ids % w
            ys = pe_ids // w
            active = None                               # every PE is real
            nbr = jnp.asarray(cfg.neighbor_maps()[0])   # (N,4)

        if cfg.traced_modes:
            # Traced scalars: mode-dependent behaviour below is masked
            # dataflow, identical bit-for-bit to the static branches.
            opp_on = (mode & MODE_OPPORTUNISTIC) != 0
            dual_on = (mode & MODE_DUAL_ISSUE) != 0
            val_on = (mode & MODE_VALIANT) != 0
        else:
            opp_on, dual_on, val_on = (cfg.opportunistic, cfg.dual_issue,
                                       cfg.valiant)

        def pick_mode(pred, on, off):
            """Static short-circuit for Python-bool preds, masked select
            (pytree-mapped) for traced ones."""
            if isinstance(pred, bool):
                return on() if pred else off()
            return jax.tree_util.tree_map(
                lambda a, b: jnp.where(pred, a, b), on(), off())

        def maybe_anchor(msgs):
            # TIA anchoring (compute stays with the data) applies exactly
            # when the lane is NOT opportunistic.
            return pick_mode(opp_on, lambda: msgs,
                             lambda: _anchor_tia(msgs, pe_ids))

        heads = st.buf[:, :, 0, :]                     # (N,5,F)
        head_v = st.buf_n > 0                          # (N,5)

        # --- downstream credit (ON/OFF flow control, T_OFF=1) -------------
        with jax.named_scope("cycle.credit"):
            # free slots at the input buffer each directional output feeds.
            down_n = jnp.where(
                nbr >= 0,
                st.buf_n[jnp.clip(nbr, 0), opp[None, :].repeat(n, 0)],
                DEPTH)                                     # (N,4)
            credit_ok = (nbr >= 0) & (DEPTH - down_n >= 2)

        # --- route computation --------------------------------------------
        with jax.named_scope("cycle.route"):
            via = heads[:, :, F_VIA]
            dest_eff = jnp.where(via >= 0, via, heads[:, :, F_DST0])
            out_port = route(dest_eff, credit_ok, w, xs, ys)   # (N,5)
            at_dest = dest_eff == pe_ids[:, None]
            # clear a reached Valiant waypoint: routing then targets DST0.
            clear_via = head_v & (via >= 0) & at_dest
            if act is not None:
                clear_via = clear_via & act[:, None]
            real_dest = heads[:, :, F_DST0] == pe_ids[:, None]
            is_local = head_v & real_dest & (via < 0)

        # --- execution selection (dual-issue, Fig. 8b) ----------------------
        with jax.named_scope("cycle.select"):
            # Each PE has TWO functional units the Input NI can feed per cycle:
            # the *decode unit* (memory-class ops: loads, stores, stream accept)
            # and the *compute unit* (ALU-class ops) — §3.3.1 lists them as
            # separate blocks, and the Fig. 5 cycle trace relies on a MUL and
            # the subsequent local memory update overlapping.  The Input NI may
            # eject *any* buffered message destined here, not only the FIFO
            # head — this removes head-of-line blocking behind a message whose
            # stream unit is busy, which together with the deep pending FIFO
            # gives the forward-progress guarantee the paper gets from bubble
            # flow control + placement/timeouts (§3.4).
            pend_free = (PEND_CAP - st.pend_n)[:, None, None]  # (N,1,1)
            slot_v = jnp.arange(DEPTH)[None, None, :] < st.buf_n[:, :, None]
            all_m = st.buf                                  # (N,5,D,F)
            opn_a = all_m[..., F_OP]                        # (N,5,D)
            local_a = slot_v & (all_m[..., F_DST0] == pe_ids[:, None, None]) & \
                (all_m[..., F_VIA] < 0)
            if active is not None:
                # inactive (padded) PEs never execute; their buffers are empty
                # anyway, so this mask is a defensive invariant, not a bit
                # change on active PEs.
                local_a = local_a & active[:, None, None]
            if act is not None:
                # budget-halted PEs execute nothing this tick
                local_a = local_a & act[:, None, None]
            # STREAM tasks are *always* consumable: they park in the stream-task
            # wait queue (the TIA-style scheduler queue) until the decode unit is
            # free, so they never clog the network (deadlock avoidance, §3.4).
            swq_ok = st.swq_n < cfg.stream_wait_cap - 1
            stream_a = opn_a == OP_STREAM
            # Terminal stores emit nothing — always executable (drains the
            # network regardless of pending back-pressure).
            no_emit_a = (opn_a == OP_STORE_ADD) | (opn_a == OP_STORE_SET) | \
                (stream_a & swq_ok[:, None, None])
            # A message from the network executes with NET_RESERVE free
            # slots, the PE's own (inject port) with the reservations below.
            # Short of them, a network message that would push parks in the
            # wait queue as a STREAM task does (the decode unit takes it
            # there), and re-enters through the pending FIFO once it has
            # room: the PE keeps consuming what the network brings it.
            net_a = (jnp.arange(PORTS) != P_INJ)[None, :, None]
            net_room = pend_free >= NET_RESERVE
            mem_room = jnp.where(net_a, net_room, pend_free >= 1)
            park_a = local_a & net_a & ~net_room & swq_ok[:, None, None] & (
                is_alu_op(opn_a) | (is_mem_op(opn_a) & ~no_emit_a))
            mem_cand = (local_a & is_mem_op(opn_a) &
                        (mem_room | no_emit_a) &
                        (~stream_a | swq_ok[:, None, None])) | park_a
            # Pending-FIFO reservation discipline (the consumption guarantee,
            # §3.4).  Three producers may push in one cycle — decode output,
            # compute output, stream spawn — and each is gated so occupancy
            # provably never exceeds PEND_CAP:
            #   * decode emits only with >= 1 free slot;
            #   * compute emits only with >= 2 free slots (its own push PLUS a
            #     same-cycle decode push: after both, pend_n <= PEND_CAP);
            #   * either unit takes a message that arrived from the network
            #     only with >= NET_RESERVE free slots, so the PE's own
            #     messages in its inject port can always retire and the FIFO
            #     drains (see NET_RESERVE); short of them it parks, and a
            #     parked message re-enters the FIFO only with as many;
            #   * the stream gate checks the *post-execution-push* count
            #     against STREAM_THROTTLE (<= PEND_CAP - 3, asserted at module
            #     scope), far below the cap.
            # The run_many overflow guard trips at pend_n >= PEND_CAP - 2: the
            # shallowest depth from which one more uncompensated cycle could
            # gate an execution unit — i.e. consumption would no longer be
            # unconditional (tests/test_pend_guard.py holds the invariant).
            alu_cand = local_a & is_alu_op(opn_a) & \
                jnp.where(net_a, net_room, pend_free >= 2)

            def sel_dual():
                # separate decode + compute units (Fig. 8b): one of each may
                # retire per cycle.
                return (_pick_one(mem_cand.reshape(n, PORTS * DEPTH),
                                  st.rr).reshape(n, PORTS, DEPTH),
                        _pick_one(alu_cand.reshape(n, PORTS * DEPTH),
                                  st.rr + 2).reshape(n, PORTS, DEPTH))

            def sel_single():
                # TIA triggered dispatch: the priority encoder fires ONE ready
                # instruction per PE per cycle (either unit).
                sel_one = _pick_one((mem_cand | alu_cand)
                                    .reshape(n, PORTS * DEPTH),
                                    st.rr).reshape(n, PORTS, DEPTH)
                return (sel_one & (is_mem_op(opn_a) | park_a),
                        sel_one & is_alu_op(opn_a) & ~park_a)

            sel_mem3, sel_alu3 = pick_mode(dual_on, sel_dual, sel_single)
            parks = (sel_mem3 & park_a).any(axis=(1, 2))   # decode parks
            any_alu_local = sel_alu3.any(axis=(1, 2))
            opn = heads[:, :, F_OP]

            def sel_opportunistic():
                # in-network computing: an idle compute unit intercepts a
                # passing ALU-class message whose operands are complete (head
                # only).  Interception happens *in the router pipeline*: the
                # message is transformed in place and continues from its input
                # buffer next cycle — it never takes the pend/inject detour, so
                # the cost is exactly one stalled-hop cycle (§3.1.3, Fig. 8a).
                head_next_op = prog_j[jnp.clip(heads[:, :, F_PC], 0,
                                               prog_j.shape[0] - 1), C_OP]
                icand = (head_v & ~real_dest & (via < 0) & is_alu_op(opn)
                         & (heads[:, :, F_OP1C] == 1) & (heads[:, :, F_OP2C] == 1)
                         & (head_next_op != OP_NOP))
                icand &= (~any_alu_local)[:, None]
                if active is not None:
                    icand &= active[:, None]
                if act is not None:
                    icand &= act[:, None]
                return _pick_one(icand, st.rr + 1)

            sel_icept = pick_mode(opp_on, sel_opportunistic,
                                  lambda: jnp.zeros((n, PORTS), dtype=jnp.bool_))
            icept3 = sel_icept[:, :, None] & (jnp.arange(DEPTH) == 0)[None, None, :]
            sel_alu3 = sel_alu3 | icept3
            # removal mask: locally-executed messages leave their FIFO;
            # intercepted heads stay (transformed in place below).
            sel_exec3 = (sel_mem3 | sel_alu3) & ~icept3
            flat = all_m.reshape(n, PORTS * DEPTH, MSG_F)
            msg = jnp.einsum("nkf,nk->nf", flat,
                             sel_mem3.reshape(n, PORTS * DEPTH).astype(jnp.int32))
            msg_alu = jnp.einsum(
                "nkf,nk->nf", flat,
                sel_alu3.reshape(n, PORTS * DEPTH).astype(jnp.int32))
            was_icept = sel_icept.any(axis=1)               # (N,)
            # heads busy this cycle (executed, or being transformed) do not
            # request a network transit.
            head_taken = (sel_mem3 | sel_alu3)[:, :, 0]
            mv = sel_mem3.any(axis=(1, 2))                  # decode-unit fires
            mv_alu = sel_alu3.any(axis=(1, 2))              # compute-unit fires

        # ============== EXECUTE: DECODE UNIT (memory-class) ================
        with jax.named_scope("cycle.decode"):
            op = jnp.where(mv & ~parks, msg[:, F_OP], OP_NOP)
            pc = msg[:, F_PC]
            cfg_row = prog_j[jnp.clip(pc, 0, prog_j.shape[0] - 1)]  # (N,CFG_F)
            addr_res = jnp.clip(msg[:, F_RES], 0, cfg.mem_words - 1)
            addr_op1 = jnp.clip(msg[:, F_OP1], 0, cfg.mem_words - 1)
            addr_op2 = jnp.clip(msg[:, F_OP2], 0, cfg.mem_words - 1)
            mem_r1 = jnp.take_along_axis(st.mem_val, addr_op1[:, None], 1)[:, 0]
            mem_r2 = jnp.take_along_axis(st.mem_val, addr_op2[:, None], 1)[:, 0]
            mem_rr = jnp.take_along_axis(st.mem_val, addr_res[:, None], 1)[:, 0]
            meta_r = jnp.take_along_axis(
                st.mem_meta, addr_res[:, None, None].repeat(2, 2), 1)[:, 0, :]

            # -- memory writes (stores execute at the owner PE: ≤1 per PE) ------
            do_add = mv & (op == OP_STORE_ADD)
            do_set = mv & (op == OP_STORE_SET)
            improved = msg[:, F_OP1] < mem_rr
            do_min = mv & (op == OP_STORE_MIN) & improved
            was_unset = mem_rr == UNSET
            do_chk = mv & (op == OP_CHECKSET) & was_unset
            new_word = jnp.where(do_add, mem_rr + msg[:, F_OP1],
                        jnp.where(do_set | do_min | do_chk, msg[:, F_OP1], mem_rr))
            write_mask = do_add | do_set | do_min | do_chk
            mem_val = st.mem_val
            mem_val = jax.vmap(
                lambda row, a, v, m: row.at[a].set(jnp.where(m, v, row[a]))
            )(mem_val, addr_res, new_word, write_mask)

            # -- outgoing dynamic AM construction --------------------------------
            nxt = msg
            nxt = nxt.at[:, F_OP].set(cfg_row[:, C_OP])
            nxt = nxt.at[:, F_PC].set(cfg_row[:, C_NEXT_PC])
            # LOADs fill an operand slot with the fetched word.
            is_l1, is_l2 = op == OP_LOAD1, op == OP_LOAD2
            nxt = nxt.at[:, F_OP1].set(jnp.where(is_l1, mem_r1, nxt[:, F_OP1]))
            nxt = nxt.at[:, F_OP1C].set(jnp.where(is_l1, 1, nxt[:, F_OP1C]))
            nxt = nxt.at[:, F_OP2].set(jnp.where(is_l2, mem_r2, nxt[:, F_OP2]))
            nxt = nxt.at[:, F_OP2C].set(jnp.where(is_l2, 1, nxt[:, F_OP2C]))
            rot = cfg_row[:, C_ROTATE] == 1
            nxt = jnp.where(rot[:, None], _rotate_dsts(nxt), nxt)
            nxt = nxt.at[:, F_VIA].set(-1)  # execution starts a fresh leg
            nxt = maybe_anchor(nxt)
            # Conditional continuations read the stored word's metadata:
            #   BFS: next level = Op1+1, stream the discovered vertex's adjacency
            #   SSSP: propagate the improved distance.
            cont = do_min | do_chk
            nxt = nxt.at[:, F_OP1].set(jnp.where(
                do_chk, msg[:, F_OP1] + 1,
                jnp.where(do_min, msg[:, F_OP1], nxt[:, F_OP1])))
            nxt = nxt.at[:, F_OP2].set(jnp.where(cont, meta_r[:, 0], nxt[:, F_OP2]))
            nxt = nxt.at[:, F_OP2C].set(jnp.where(cont, 0, nxt[:, F_OP2C]))
            nxt = nxt.at[:, F_DST0].set(jnp.where(cont, meta_r[:, 1], nxt[:, F_DST0]))
            nxt = nxt.at[:, F_DST1].set(jnp.where(cont, -1, nxt[:, F_DST1]))
            nxt = nxt.at[:, F_DST2].set(jnp.where(cont, -1, nxt[:, F_DST2]))

            # Does the executed instruction emit a message?
            terminal = (op == OP_STORE_ADD) | (op == OP_STORE_SET)
            cond_no = ((op == OP_STORE_MIN) & ~improved) | \
                      ((op == OP_CHECKSET) & ~was_unset)
            starts_stream = mv & (op == OP_STREAM)
            emits = mv & ~parks & ~terminal & ~cond_no & ~starts_stream & \
                (cfg_row[:, C_OP] != OP_NOP)
            nxt = nxt.at[:, F_VALID].set(jnp.where(emits, 1, 0))

        # ============== EXECUTE: COMPUTE UNIT (ALU-class) ==================
        with jax.named_scope("cycle.compute"):
            op_a = jnp.where(mv_alu, msg_alu[:, F_OP], OP_NOP)
            cfg_row_a = prog_j[jnp.clip(msg_alu[:, F_PC], 0,
                                        prog_j.shape[0] - 1)]
            alu_res = _alu(op_a, msg_alu[:, F_OP1], msg_alu[:, F_OP2],
                           msg_alu[:, F_RES])
            nxt_a = msg_alu
            nxt_a = nxt_a.at[:, F_OP].set(cfg_row_a[:, C_OP])
            nxt_a = nxt_a.at[:, F_PC].set(cfg_row_a[:, C_NEXT_PC])
            nxt_a = nxt_a.at[:, F_OP1].set(
                jnp.where(mv_alu, alu_res, nxt_a[:, F_OP1]))
            nxt_a = nxt_a.at[:, F_OP1C].set(
                jnp.where(mv_alu, 1, nxt_a[:, F_OP1C]))
            # An anchored message (F_VIA == -2, TIA mode) has executed its local
            # ALU op: resume the pushed-down destination list by rotating.
            anchored_exec = mv_alu & (msg_alu[:, F_VIA] == -2)
            rot_a = (cfg_row_a[:, C_ROTATE] == 1) | anchored_exec
            nxt_a = jnp.where(rot_a[:, None], _rotate_dsts(nxt_a), nxt_a)
            nxt_a = nxt_a.at[:, F_VIA].set(-1)
            nxt_a = maybe_anchor(nxt_a)
            emits_a = mv_alu & (cfg_row_a[:, C_OP] != OP_NOP)
            nxt_a = nxt_a.at[:, F_VALID].set(jnp.where(emits_a, 1, 0))

            # -- STREAM accept: push the stream task into the wait queue ---------
            # The wait queue (like the pending FIFO below) is a circular buffer:
            # push/pop are O(1) scatters/gathers instead of whole-array shifts,
            # which keeps the per-cycle cost independent of queue capacity.
            swq, swq_h, swq_n = st.swq, st.swq_h, st.swq_n
            wpos = (swq_h + swq_n) % cfg.stream_wait_cap
            swq = jax.vmap(
                lambda q, i, v, m: q.at[i].set(jnp.where(m, v, q[i]))
            )(swq, wpos, msg, starts_stream | parks)
            swq_n = swq_n + (starts_stream | parks).astype(jnp.int32)

            # -- STREAM issue: an idle decode unit pops the next waiting task.
            # Descriptor word (mem_val=base, meta0=count) at Op2 (address) — or
            # at Res when Op2 holds a value (PageRank: Op2 carries the degree).
            issue = (~st.stream_on) & (swq_n > 0)
            if act is not None:
                issue = issue & act
            task = jnp.take_along_axis(
                swq, swq_h[:, None, None].repeat(MSG_F, 2), 1)[:, 0, :]
            # a parked message at the head is no stream task: it leaves
            # through the pending FIFO below, once that has room
            parked = issue & (task[:, F_OP] != OP_STREAM)
            issue = issue & ~parked
            t_res = jnp.clip(task[:, F_RES], 0, cfg.mem_words - 1)
            t_op2 = jnp.clip(task[:, F_OP2], 0, cfg.mem_words - 1)
            desc_a = jnp.where(task[:, F_OP2C] == 1, t_res, t_op2)
            meta_d = jnp.take_along_axis(
                st.mem_meta, desc_a[:, None, None].repeat(2, 2), 1)[:, 0, :]
            s_base = jnp.take_along_axis(st.mem_val, desc_a[:, None], 1)[:, 0]
            s_cnt = meta_d[:, 0]
            stream_on = st.stream_on | (issue & (s_cnt > 0))
            stream_msg = jnp.where(issue[:, None], task, st.stream_msg)
            stream_base = jnp.where(issue, s_base, st.stream_base)
            stream_left = jnp.where(issue, s_cnt, st.stream_left)
            swq_h = (swq_h + issue.astype(jnp.int32)) % cfg.stream_wait_cap
            swq_n = swq_n - issue.astype(jnp.int32)

            # -- push executed-output AMs into the pending FIFO ------------------
            # (decode-unit output, then compute-unit output: ≤2 pushes/cycle;
            # circular buffer — see the stream wait queue above)
            pend, pend_h, pend_n = st.pend, st.pend_h, st.pend_n
            pos = (pend_h + pend_n) % PEND_CAP
            pend = jax.vmap(
                lambda q, i, v, m: q.at[i].set(jnp.where(m, v, q[i]))
            )(pend, pos, nxt, emits)
            pend_n = pend_n + emits.astype(jnp.int32)
            emits_a_pend = emits_a & ~was_icept      # intercepted: in-place
            pos_a = (pend_h + pend_n) % PEND_CAP
            pend = jax.vmap(
                lambda q, i, v, m: q.at[i].set(jnp.where(m, v, q[i]))
            )(pend, pos_a, nxt_a, emits_a_pend)
            pend_n = pend_n + emits_a_pend.astype(jnp.int32)

            # -- streaming decode: emit one spawned AM per cycle (backpressure-
            # throttled, see STREAM_THROTTLE above) -------------------------------
            can_emit = stream_on & (pend_n < STREAM_THROTTLE)
            if act is not None:
                can_emit = can_emit & act
            unpark = parked & (pend_n <= PEND_CAP - NET_RESERVE)
            swq_h = (swq_h + unpark.astype(jnp.int32)) % cfg.stream_wait_cap
            swq_n = swq_n - unpark.astype(jnp.int32)
            e_addr = jnp.clip(stream_base, 0, cfg.mem_words - 1)
            e_val = jnp.take_along_axis(mem_val, e_addr[:, None], 1)[:, 0]
            e_meta = jnp.take_along_axis(
                st.mem_meta, e_addr[:, None, None].repeat(2, 2), 1)[:, 0, :]
            t = stream_msg
            t_cfg = prog_j[jnp.clip(t[:, F_PC], 0, prog_j.shape[0] - 1)]
            sp = t
            sp = sp.at[:, F_VALID].set(1)
            sp = sp.at[:, F_OP].set(t_cfg[:, C_OP])
            sp = sp.at[:, F_PC].set(t_cfg[:, C_NEXT_PC])
            o1 = jnp.select(
                [t_cfg[:, C_OP1SEL] == 1, t_cfg[:, C_OP1SEL] == 2],
                [e_val, t[:, F_OP1] + e_val], t[:, F_OP1])
            o2 = jnp.select(
                [t_cfg[:, C_OP2SEL] == 1, t_cfg[:, C_OP2SEL] == 2,
                 t_cfg[:, C_OP2SEL] == 3],
                [e_val, e_meta[:, 0] + t[:, F_OP2], e_meta[:, 0] + t[:, F_OP1]],
                t[:, F_OP2])
            rs = jnp.select(
                [t_cfg[:, C_RESSEL] == 1, t_cfg[:, C_RESSEL] == 2],
                [t[:, F_RES] + e_meta[:, 0], e_meta[:, 0]], t[:, F_RES])
            sp = sp.at[:, F_OP1].set(o1).at[:, F_OP1C].set(1)
            sp = sp.at[:, F_OP2].set(o2)
            sp = sp.at[:, F_OP2C].set(jnp.where(t_cfg[:, C_OP2SEL] > 0,
                                                (t_cfg[:, C_OP2SEL] == 1)
                                                .astype(jnp.int32),
                                                t[:, F_OP2C]))
            sp = sp.at[:, F_RES].set(rs)
            use_meta_dst = t_cfg[:, C_DSTSEL] == 1
            rot_t = _rotate_dsts(t)
            sp = sp.at[:, F_DST0].set(
                jnp.where(use_meta_dst, e_meta[:, 1], rot_t[:, F_DST0]))
            sp = sp.at[:, F_DST1].set(
                jnp.where(use_meta_dst, t[:, F_DST1], rot_t[:, F_DST1]))
            sp = sp.at[:, F_DST2].set(
                jnp.where(use_meta_dst, t[:, F_DST2], rot_t[:, F_DST2]))
            sp = sp.at[:, F_VIA].set(-1)
            sp = maybe_anchor(sp)
            # the spawn's slot, or an unparked message's (never both: a
            # parked head means the stream unit is idle)
            push2 = can_emit | unpark
            pos2 = (pend_h + pend_n) % PEND_CAP
            pend = jax.vmap(
                lambda q, i, v, m: q.at[i].set(jnp.where(m, v, q[i]))
            )(pend, pos2, jnp.where(unpark[:, None], task, sp), push2)
            pend_n = pend_n + push2.astype(jnp.int32)
            stream_base = jnp.where(can_emit, stream_base + 1, stream_base)
            stream_left = jnp.where(can_emit, stream_left - 1, stream_left)
            stream_on = stream_on & (stream_left > 0)

        # ==================== ALLOCATE & TRANSFER ==========================
        with jax.named_scope("cycle.transfer"):
            req = head_v & ~head_taken & (out_port < 4)
            # stalled LOCAL heads that could not execute this cycle:
            stall_local = head_v & (out_port == OUT_LOCAL) & ~head_taken
            if act is not None:
                # budget-halted PEs neither request output ports nor accrue
                # stall statistics — their whole tick is frozen.
                req = req & act[:, None]
                stall_local = stall_local & act[:, None]
            grants = jnp.zeros((n, PORTS), dtype=jnp.bool_)
            for o in range(4):  # separable output-side arbitration (unrolled)
                cand_o = req & (out_port == o) & credit_ok[:, o][:, None]
                g = _pick_one(cand_o, st.rr + o)
                grants = grants | g
            stall_net = req & ~grants

            # removals: granted heads + the executed slot.  Stable compaction of
            # each (pe, port) FIFO (≤2 removals per FIFO per cycle: one head in
            # transit, one slot ejected).
            removed = sel_exec3 | (grants[:, :, None]
                                   & (jnp.arange(DEPTH) == 0)[None, None, :])
            keep = slot_v & ~removed                              # (N,5,D)
            buf = _fifo_compact(st.buf, keep)
            buf_n = keep.sum(axis=2).astype(jnp.int32)
            # clear reached Valiant waypoints in-place on remaining heads.
            popped0 = removed[:, :, 0]
            buf = buf.at[:, :, 0, F_VIA].set(
                jnp.where(clear_via & ~popped0, -1, buf[:, :, 0, F_VIA]))
            # in-place interception write-back: the transformed message replaces
            # the (un-removed, un-granted) head and routes onward next cycle.
            icept_port = jnp.argmax(sel_icept, axis=1)      # (N,)
            buf = _fifo_set(buf, icept_port, 0, nxt_a, was_icept)

            # transfers: sender-side view — the message leaving each PE through
            # each directional output port.
            send_v = jnp.zeros((n, 4), dtype=jnp.bool_)
            send_m = jnp.zeros((n, 4, MSG_F), dtype=jnp.int32)
            for o in range(4):
                sel_o = grants & (out_port == o)                  # (N,5)
                send_v = send_v.at[:, o].set(sel_o.any(axis=1))
                send_m = send_m.at[:, o, :].set(
                    jnp.einsum("npf,np->nf", heads, sel_o.astype(jnp.int32)))
            # receiver-side gather: input port q of PE r is fed by neighbor
            # nbr[r, q] transmitting through its output opp[q].  Pure gather —
            # no duplicate-scatter hazards; ≤1 arrival per (pe, port).
            for q in range(4):
                s = nbr[:, q]                                     # sender id
                o = int(opp_np[q])                                # sender output
                has = (s >= 0) & send_v[jnp.clip(s, 0), o]
                m_in = send_m[jnp.clip(s, 0), o, :]
                m_in = m_in.at[:, F_HOPS].add(1)
                pos_d = jnp.clip(buf_n[:, q], 0, DEPTH - 1)
                buf = _fifo_set(buf, q, pos_d, m_in, has)
                buf_n = buf_n.at[:, q].add(has.astype(jnp.int32))

        # ==================== INJECTION (AM NIC, §3.3.1) ====================
        with jax.named_scope("cycle.inject"):
            inj_space = buf_n[:, P_INJ] < DEPTH
            if active is not None:
                inj_space = inj_space & active
            if act is not None:
                inj_space = inj_space & act
            have_dyn = pend_n > 0
            have_stat = st.amq_head < st.amq_len
            inj_dyn = inj_space & have_dyn
            inj_stat = inj_space & ~have_dyn & have_stat
            dyn_msg = jnp.take_along_axis(
                pend, pend_h[:, None, None].repeat(MSG_F, 2), 1)[:, 0, :]
            stat_msg = jnp.take_along_axis(
                st.amq, jnp.clip(st.amq_head, 0, st.amq.shape[1] - 1)
                [:, None, None].repeat(MSG_F, 2), 1)[:, 0, :]
            inj_msg = jnp.where(inj_dyn[:, None], dyn_msg, stat_msg)

            def inj_valiant():
                # TIA-Valiant: ROMM-style randomized *minimal-path* routing
                # (paper cites [33, 48]) — the waypoint is drawn inside the
                # src→dst bounding box, so each leg keeps the same per-axis
                # direction signs and the west-first turn model stays
                # deadlock-free.  Anchored (-2)/self messages are exempt.
                h = (sub_local.astype(jnp.uint32) * jnp.uint32(2654435761)
                     + st.cycle.astype(jnp.uint32) * jnp.uint32(40503))
                dstp = jnp.clip(inj_msg[:, F_DST0], 0)
                dx = dstp % w - xs
                dy = dstp // w - ys
                rx = (h % (jnp.abs(dx).astype(jnp.uint32) + 1)).astype(jnp.int32)
                ry = ((h >> 8) % (jnp.abs(dy).astype(jnp.uint32) + 1)) \
                    .astype(jnp.int32)
                # West-first legality across the two legs: a waypoint with
                # via_x > dst_x would force a W hop *after* leg 1's N/S hops —
                # an illegal turn into W (deadlock, observed as a credit cycle).
                # For westbound traffic pin via_x = dst_x (all W hops happen
                # first, inside leg 1) and randomize only y; eastbound keeps
                # full in-box randomization (no W hops at all).
                rx = jnp.where(dx < 0, jnp.abs(dx), rx)
                via_pe = (ys + jnp.sign(dy) * ry) * w + (xs + jnp.sign(dx) * rx)
                eligible = (inj_msg[:, F_VIA] == -1) & \
                    (inj_msg[:, F_DST0] != pe_ids) & (via_pe != pe_ids) & \
                    (via_pe != inj_msg[:, F_DST0])
                return inj_msg.at[:, F_VIA].set(
                    jnp.where(eligible, via_pe, inj_msg[:, F_VIA]))

            inj_msg = pick_mode(val_on, inj_valiant, lambda: inj_msg)
            do_inj = inj_dyn | inj_stat
            net_inj = do_inj
            posi = jnp.clip(buf_n[:, P_INJ], 0, DEPTH - 1)
            buf = _fifo_set(buf, P_INJ, posi, inj_msg, net_inj)
            buf_n = buf_n.at[:, P_INJ].add(net_inj.astype(jnp.int32))
            # consume sources
            pend_h = (pend_h + inj_dyn.astype(jnp.int32)) % PEND_CAP
            pend_n = pend_n - inj_dyn.astype(jnp.int32)
            amq_head = st.amq_head + inj_stat.astype(jnp.int32)

        # ==================== STATS =========================================
        with jax.named_scope("cycle.stats"):
            # All per-PE: totals are reductions at result-extraction time, and
            # under sub-mesh packing each co-tenant's slice freezes at its own
            # idle point (hops are attributed to the sending PE — a hop's two
            # endpoints always belong to the same sub-mesh).
            busy = mv | mv_alu | can_emit
            st_busy = st.st_busy + busy.astype(jnp.int32)
            st_exec = st.st_exec + (mv & ~parks).astype(jnp.int32) \
                + mv_alu.astype(jnp.int32)
            st_enroute = st.st_enroute + sel_icept.any(axis=1).astype(jnp.int32)
            st_stall = st.st_stall + (stall_net | stall_local).astype(jnp.int32)
            st_hops = st.st_hops + grants.sum(axis=1).astype(jnp.int32)
            st_inj = st.st_inj + do_inj.astype(jnp.int32)

            # budget-halted PEs also freeze their cycle counter and round-robin
            # pointer, preserving the rr ≡ cycle (mod PORTS) alignment that
            # drives arbitration when a sliced run later resumes.
            tick = jnp.int32(1) if act is None else act.astype(jnp.int32)
            return MachineState(
                buf=buf, buf_n=buf_n, amq=st.amq, amq_head=amq_head,
                amq_len=st.amq_len, pend=pend, pend_h=pend_h, pend_n=pend_n,
                mem_val=mem_val,
                mem_meta=st.mem_meta, stream_on=stream_on, stream_msg=stream_msg,
                stream_base=stream_base, stream_left=stream_left, swq=swq,
                swq_h=swq_h, swq_n=swq_n, rr=(st.rr + tick) % PORTS,
                cycle=st.cycle + tick,
                st_busy=st_busy, st_exec=st_exec, st_enroute=st_enroute,
                st_stall=st_stall, st_hops=st_hops, st_inj=st_inj)

    return cycle


def is_idle(st: MachineState, active: jnp.ndarray | None = None
            ) -> jnp.ndarray:
    """Global idle detection (§3.1.4): no work anywhere, nothing in flight.

    ``active`` optionally masks the PE axis (traced geometry: padded PEs
    beyond a lane's width*height are ignored — they hold zero state by
    invariant, so the mask is defensive, not a semantic change).
    """
    if active is None:
        return ((st.buf_n.sum() == 0) & (st.pend_n.sum() == 0)
                & (~st.stream_on.any()) & (st.swq_n.sum() == 0)
                & (st.amq_head >= st.amq_len).all())
    a = active
    return (((st.buf_n * a[:, None]).sum() == 0)
            & ((st.pend_n * a).sum() == 0)
            & (~(st.stream_on & a).any())
            & ((st.swq_n * a).sum() == 0)
            & ((st.amq_head >= st.amq_len) | ~a).all())


def lane_work(st: MachineState) -> jnp.ndarray:
    """(N,) outstanding-work count per PE: buffered flits + pending
    outputs + queued/active streams + un-injected static AMs.  A PE with
    zero work is idle; a *sub-lane* is idle when every PE of its group is
    (the per-PE decomposition of :func:`is_idle` — inactive padded PEs
    hold all-zero state, so no mask is needed)."""
    return (st.buf_n.sum(axis=1) + st.pend_n + st.swq_n
            + st.stream_on.astype(jnp.int32)
            + (st.amq_head < st.amq_len).astype(jnp.int32))


def group_idle(st: MachineState, sub_ids: jnp.ndarray) -> jnp.ndarray:
    """(N,) bool: True where the PE's own sub-lane has no work anywhere.

    ``sub_ids`` assigns each PE a sub-lane slot (all-zero for unpacked
    lanes, where this reduces to the global idle test broadcast).  Each
    PE then freezes its cycle counter and statistics exactly when its own
    sub-lane idles — co-tenants of a packed super-lane keep stepping.
    """
    n = sub_ids.shape[0]
    gw = jax.ops.segment_sum(lane_work(st), sub_ids, num_segments=n)
    return (gw == 0)[sub_ids]


@dataclasses.dataclass
class RunResult:
    cycles: int
    mem_val: np.ndarray
    utilization: float          # instructions issued / (cycles × N) —
                                # useful work per PE-cycle (Fig. 13)
    busy_frac: float            # fraction of PE-cycles with ≥1 unit active
    per_pe_busy: np.ndarray     # (N,) busy-cycle counts (load-balance map)
    executed: int
    enroute: int                # opportunistically executed (Fig. 11 r-axis)
    enroute_frac: float
    hops: int
    injected: int
    stall_per_port: np.ndarray  # (N,5) congestion proxy (Fig. 14)
    completed: bool

    def to_json(self) -> dict:
        """JSON-serializable metrics row — the ONE serialization path
        shared by the BENCH artifacts, golden drift reports and the sweep
        service, so a renamed metric cannot silently fork the formats.

        ``mem_val`` (the result memory image) is deliberately omitted:
        artifacts track metrics, not payloads.  ``stall_per_port`` is
        reduced to per-port totals (the Fig. 14 congestion axis).
        """
        stall = np.asarray(self.stall_per_port)
        return dict(
            cycles=int(self.cycles),
            utilization=float(self.utilization),
            busy_frac=float(self.busy_frac),
            executed=int(self.executed),
            enroute=int(self.enroute),
            enroute_frac=float(self.enroute_frac),
            hops=int(self.hops),
            injected=int(self.injected),
            stall_total=int(stall.sum()),
            stall_per_port=[int(v) for v in stall.sum(axis=0)],
            per_pe_busy=[int(v) for v in np.asarray(self.per_pe_busy)],
            completed=bool(self.completed),
        )


# ----------------------------------------------------------------------------
# Batched on-device execution engine (design-space sweeps, Figs. 11–17)
# ----------------------------------------------------------------------------
# Compiled engines keyed by the static ``MachineConfig`` (plus the chunk
# length and the module-level FIFO constants, which are baked into the
# trace).  With traced modes (the default) the three mode flags are
# *stripped from the key*: the execution mode is runtime data, so every
# (workload x mode) sweep point on one fabric geometry reuses both the
# Python-level engine and — because the program and mode are traced
# arguments — the single underlying XLA executable.
_ENGINE_CACHE: dict = {}

# "run to completion" cycle budget for the engine's traced per-PE
# bound (np.int32 so every caller — run_many and the sliced sweep service
# — hits the same int32 specialization of the jitted engine; max_cycles
# always caps first).
ENGINE_UNBOUNDED = np.int32(np.iinfo(np.int32).max)


def unbounded_budget(batch: int, n_pes: int) -> np.ndarray:
    """A ``(B, N)`` engine budget that never halts anything: every PE may
    retire up to INT32_MAX cycles this call (``cfg.max_cycles`` always
    caps first).  The engine's budget argument is per-PE so callers can
    bound individual (sub-)lanes — a deadline — while co-tenants keep
    stepping; this helper is the 'no deadlines' value."""
    return np.full((batch, n_pes), ENGINE_UNBOUNDED, np.int32)


def _engine_key_cfg(cfg: MachineConfig) -> MachineConfig:
    """Canonicalize a config for engine-cache lookup.

    Traced-mode engines do not specialize on the mode flags, and
    traced-geometry engines do not specialize on the mesh shape (only on
    the padded PE-axis length, carried separately in the key), so configs
    differing only in mode and/or width x height collapse onto one cache
    entry (and one XLA executable).  Static engines keep the full config.
    """
    if cfg.traced_modes:
        cfg = dataclasses.replace(cfg, opportunistic=True, dual_issue=True,
                                  valiant=False)
    if cfg.traced_geometry:
        cfg = dataclasses.replace(cfg, width=0, height=0)
    return cfg


def _engine_key(cfg: MachineConfig, n_max: int, chunk: int,
                n_devices: int = 1) -> tuple:
    """The full engine-cache key (exposed for tests).

    ``n_devices`` is 1 for the plain vmapped engine AND for
    ``shard=True`` on a single-device host (the sharded path falls back
    to the plain engine there, so opting into sharding never compiles a
    second executable).  Only a real multi-device mesh — which changes
    the partitioning of the executable — keys separately.
    """
    return (_engine_key_cfg(cfg), int(n_max), chunk, int(n_devices),
            PEND_CAP, STREAM_THROTTLE, NET_RESERVE)


def clear_engine_cache() -> None:
    """Drop all cached compiled engines (tests / benchmarking cold paths)."""
    _ENGINE_CACHE.clear()


def enable_persistent_compile_cache() -> str:
    """Turn on JAX's on-disk compilation cache for sweep entry points.

    The in-memory engine cache amortizes compiles within a process; this
    extends it across processes so re-running a sweep skips the one-time
    engine compile entirely.  The directory is ``JAX_COMPILATION_CACHE_DIR``
    when that is set (JAX reads it itself; no other directory is set),
    else ``.jax_cache`` at the root of the checkout — a fixed path, since
    the path is part of the cache key.  Returns the directory in use;
    raises if JAX refuses the settings.
    """
    import os
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def engine_cache_size() -> int:
    return len(_ENGINE_CACHE)


def lane_sharding(n_devices: int):
    """``NamedSharding`` splitting a leading lane axis over the first
    ``n_devices`` devices — the 1-D ``("lanes",)`` mesh the sharded
    engine's ``shard_map`` runs on.  An explicit device subset, because
    a caller may shard over fewer devices than the host exposes.  Lane
    arrays placed with it already sit where the engine runs them, so no
    chip-to-chip copy precedes a call and the donated state aliases."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.jax_compat import make_mesh
    mesh = make_mesh((n_devices,), ("lanes",),
                     devices=jax.devices()[:n_devices])
    return NamedSharding(mesh, PartitionSpec("lanes"))


def init_lanes(cfg: MachineConfig, static_ams, amq_len, mem_val, mem_meta,
               sharding=None) -> MachineState:
    """:func:`init_state` over a leading lane axis.  With a ``sharding``
    (:func:`lane_sharding`) every leaf is created already split over the
    lane mesh, so each device holds only its own lanes' state."""
    init = jax.vmap(functools.partial(init_state, cfg))
    if sharding is None:
        return init(static_ams, amq_len, mem_val, mem_meta)
    args = jax.device_put((static_ams, amq_len, mem_val, mem_meta), sharding)
    return jax.jit(init, out_shardings=sharding)(*args)


def _get_engine(cfg: MachineConfig, chunk: int, n_max: int | None = None,
                n_devices: int = 1):
    """Batched runner ``engine(prog, modes, geoms, sub_ids, local_ids, st,
    budget) -> (st, overflowed, idle, ticks)``.

    ``prog`` is (B, P, CFG_F), ``modes`` a (B,) int32 per-lane mode bitmask
    (ignored by static-mode engines), ``geoms`` a (B, 2) int32 per-lane
    ``(width, height)`` vector (ignored by static-geometry engines),
    ``sub_ids`` / ``local_ids`` (B, N) int32 per-PE sub-lane slot ids and
    sub-mesh-local PE ids (all-zero / arange for unpacked lanes) and
    ``st`` a MachineState whose leaves carry a leading batch dimension with
    PE axes of length ``n_max``.  The whole run happens in ONE device
    call: a ``lax.while_loop`` over jitted chunks of ``chunk`` cycles,
    terminating when every lane is idle (or capped, or a lane trips the
    pending-FIFO guard).

    ``budget`` is a *traced* (B, N) int32 bound on the number of
    simulated CYCLES each PE may retire in this call — the
    wave-resumable hook the sweep service slices time with, and (being
    per-PE) the per-(sub-)lane deadline mechanism: a lane whose rows
    carry a smaller budget freezes exactly at that bound while
    co-tenant rectangles keep stepping.  The bound is denominated in
    cycles (not loop iterations) so that fast-forwarded runs, which
    retire many cycles per wall tick, account compressed cycles against
    the same budget as plain runs: a PE whose ``cycle`` counter has
    advanced ``budget`` cycles past its value at call entry makes NO
    further state transition this call (its tick is an exact no-op, see
    :func:`_make_cycle`'s ``halt``).  Running the engine twice with
    budget b then b' is therefore bit-identical to one call with b + b':
    the loop carry is the machine state itself.  ``run_many`` passes
    :func:`unbounded_budget` (INT32_MAX everywhere) to run to completion
    (the ``max_cycles`` cap fires first); being traced, the bound costs
    no recompile either way.  Freezing is per *sub-lane*: a sub-lane (the
    whole lane, when unpacked) that reaches idle stops advancing its PEs'
    cycle counters and stats while co-tenant sub-meshes keep stepping —
    so per-(sub-)lane metrics match a solo :func:`run` exactly.

    With ``cfg.fast_forward`` (the default) each wall tick additionally
    attempts an event-compressed advance (:mod:`repro.core.fastforward`):
    a sub-lane whose only future event is a lone in-flight message
    delivery teleports that message to its arrival position and bumps
    cycle counters by the exact hop distance in one masked vector step.
    The compression is bit-identity-by-construction — any sub-lane the
    analysis can't prove quiet steps plainly — so cycles and per-PE
    stats match the plain engine everywhere.

    ``idle`` is returned per-PE ((B, N) bool, uniform within a sub-lane):
    callers read a sub-lane's completion off any of its PEs.  ``ticks``
    is a (B,) int32 of WALL loop ticks executed (chunk iterations x
    chunk, uniform per device shard) — the telemetry hook behind
    ``dead_step_fraction``: compressed runs retire more cycles than they
    spend wall ticks.

    With ``n_devices > 1`` the whole engine body — chunked while-loop
    included — is wrapped in ``shard_map`` over a 1-D ``("lanes",)``
    device mesh: every argument and result splits on its leading lane
    axis (``B`` must be a multiple of ``n_devices``; ``run_many`` pads
    with inert lanes).  Lanes are fully independent (the vmapped step
    never communicates across lanes), so each device loops until ITS
    shard of lanes is idle — no cross-device sync per chunk, and
    per-lane state transitions are the exact integer program of the
    unsharded engine: sharded metrics are bit-identical.

    Beside the cycle's own scopes (:func:`_make_cycle`), the engine's
    parts run under ``engine.freeze`` (the idle/halt masking of
    ``lane_step``), ``engine.ff_probe`` (the lone-flight probe),
    ``engine.ff_step`` (the fast-forward rewrite) and ``engine.guard``
    (the pending-FIFO check).
    """
    n_max = cfg.n_pes if n_max is None else int(n_max)
    key = _engine_key(cfg, n_max, chunk, n_devices)
    eng = _ENGINE_CACHE.get(key)
    if eng is not None:
        return eng
    cyc = _make_cycle(cfg, n_max)
    if cfg.fast_forward:
        from repro.core.fastforward import make_fast_forward, make_lone_probe
        ffwd = make_fast_forward(cfg, n_max)
        lone_probe = jax.vmap(make_lone_probe(n_max))
    else:
        ffwd = None
        lone_probe = None

    def make_step(use_ff: bool):
        def lane_step(prog, mode, geom, sub_id, local_id, c0, budget, st):
            # Step unconditionally — on an idle sub-lane the transition
            # is a natural no-op for every state array (idle is
            # absorbing: nothing buffered, queued, streaming, or left to
            # inject) — and freeze only the cycle counters and
            # statistics of idle sub-lanes' PEs.  A per-lane lax.cond
            # would lower to a select over EVERY leaf under vmap,
            # copying the multi-MB queue arrays each cycle; masking the
            # cheap observable leaves keeps per-cycle cost independent
            # of queue capacities.
            with jax.named_scope("engine.freeze"):
                spent = st.cycle - c0
                halt = spent >= budget
                alive = (~group_idle(st, sub_id)) \
                    & (st.cycle < cfg.max_cycles) & ~halt
            st2 = cyc(prog, mode, geom, st, local_id, halt=halt)

            def keep(new, old):
                return jnp.where(alive, new, old)

            with jax.named_scope("engine.freeze"):
                st2 = st2._replace(
                    # rr frozen too: an idle sub-lane is an exact state
                    # fixpoint, so a sliced run's final state matches the
                    # unbounded run's bit for bit (and rr stays congruent
                    # to cycle mod PORTS everywhere).
                    rr=keep(st2.rr, st.rr),
                    cycle=keep(st2.cycle, st.cycle),
                    st_busy=keep(st2.st_busy, st.st_busy),
                    st_exec=keep(st2.st_exec, st.st_exec),
                    st_enroute=keep(st2.st_enroute, st.st_enroute),
                    st_stall=jnp.where(alive[:, None], st2.st_stall,
                                       st.st_stall),
                    st_hops=keep(st2.st_hops, st.st_hops),
                    st_inj=keep(st2.st_inj, st.st_inj),
                )
            if use_ff:
                with jax.named_scope("engine.ff_step"):
                    st2 = ffwd(prog, mode, geom, sub_id, budget - spent,
                               st, st2)
            return st2

        # budget maps like the state: one (N,) row per lane
        return jax.vmap(lane_step, in_axes=(0, 0, 0, 0, 0, 0, 0, 0))

    step = make_step(False)
    step_ff = make_step(True) if ffwd is not None else None
    batch_idle = jax.vmap(lambda sub_id, s: group_idle(s, sub_id))

    def engine_fn(prog, modes, geoms, sub_ids, local_ids, st, budget):
        cycle0 = st.cycle

        def cond(carry):
            s, over, it = carry
            # a lane is live while any of its PEs still advances: its
            # sub-lane has work left, its cycle counter is below the
            # cap, and it has budget left this call.  (A capped-but-busy
            # sub-lane no longer keeps the lane live — its co-tenants'
            # own counters reach the cap too.)
            live = (~batch_idle(sub_ids, s)) & (s.cycle < cfg.max_cycles) \
                & (s.cycle - cycle0 < budget)
            return live.any() & ~over.any()

        def chunk_scan(stp, s):
            def sub(s, _):
                return stp(prog, modes, geoms, sub_ids, local_ids,
                           cycle0, budget, s), ()
            return jax.lax.scan(sub, s, None, length=chunk)[0]

        def body(carry):
            s, over, it = carry
            if step_ff is None:
                s = chunk_scan(step, s)
            else:
                # two-speed chunk dispatch: the fast-forward tick costs
                # extra HLOs per cycle (segment reductions + the
                # teleport rewrite), which is pure overhead while the
                # fabric is congested.  A batch-level lax.cond — a REAL
                # branch, unlike per-lane conds under vmap — picks the
                # compressed chunk only when some live sub-lane is
                # currently in lone flight (a cheap probe, amortized
                # over the whole chunk).  The probe steers performance
                # only: both chunk bodies are bit-identical by
                # construction, so a mid-chunk misprediction costs
                # ticks, never correctness.
                with jax.named_scope("engine.ff_probe"):
                    lone = (lone_probe(sub_ids, s)
                            & (s.cycle < cfg.max_cycles)
                            & (s.cycle - cycle0 < budget))
                s = jax.lax.cond(lone.any(),
                                 functools.partial(chunk_scan, step_ff),
                                 functools.partial(chunk_scan, step), s)
            # pending-FIFO high-water check at chunk granularity (the
            # consumption-guarantee invariant, see PEND_CAP above).  PEs
            # already frozen at max_cycles are exempt: they keep being
            # stepped while other (sub-)lanes run (their non-stat state is
            # undefined once completed=False), and their churn must not
            # abort the healthy lanes.
            with jax.named_scope("engine.guard"):
                high = (s.pend_n >= PEND_CAP - 2) \
                    & (s.cycle < cfg.max_cycles)
                over = over | high.any(axis=1)
            return s, over, it + 1

        over0 = jnp.zeros((st.cycle.shape[0],), jnp.bool_)
        st, over, it = jax.lax.while_loop(cond, body,
                                          (st, over0, jnp.int32(0)))
        ticks = jnp.full((st.cycle.shape[0],), it * chunk, jnp.int32)
        return st, over, batch_idle(sub_ids, st), ticks

    if n_devices > 1:
        from repro.jax_compat import shard_map_unchecked
        sharding = lane_sharding(n_devices)
        spec = sharding.spec
        # A single spec per argument/result acts as a pytree prefix, so
        # every MachineState leaf splits on its leading lane axis too.
        # The (B, N) budget splits with its lanes: each device bounds
        # its own shard's PEs (its lanes may idle or exhaust their
        # budgets earlier, exactly like the unsharded engine).
        engine_fn = shard_map_unchecked(
            engine_fn, sharding.mesh, in_specs=(spec,) * 7,
            out_specs=(spec, spec, spec, spec))
    engine = jax.jit(engine_fn, donate_argnums=5)

    _ENGINE_CACHE[key] = engine
    return engine


def _pe_slice_result(st_host: dict, done: bool, b: int,
                     ids: np.ndarray) -> RunResult:
    """Metrics of the PE set ``ids`` of batch lane ``b`` (host arrays).

    ``ids`` lists the PEs in the (sub-)lane's own row-major order, so a
    packed sub-mesh reports arrays laid out exactly like its solo run.
    Every statistic is per-PE in ``MachineState``; totals are reductions
    over the slice.
    """
    n = ids.shape[0]
    cycles = int(st_host["cycle"][b][ids].max())
    per_pe_busy = st_host["st_busy"][b][ids]
    executed = int(st_host["st_exec"][b][ids].sum())
    enroute = int(st_host["st_enroute"][b][ids].sum())
    return RunResult(
        cycles=cycles,
        mem_val=st_host["mem_val"][b][ids],
        utilization=executed / max(1, cycles * n),
        busy_frac=float(per_pe_busy.sum()) / max(1, cycles * n),
        per_pe_busy=per_pe_busy,
        executed=executed,
        enroute=enroute,
        enroute_frac=enroute / max(1, executed),
        hops=int(st_host["st_hops"][b][ids].sum()),
        injected=int(st_host["st_inj"][b][ids].sum()),
        stall_per_port=st_host["st_stall"][b][ids],
        completed=done,
    )


def _host_stats(st: MachineState, cycle: np.ndarray | None = None) -> dict:
    """Pull the result-bearing state leaves to host numpy once; ``cycle``
    is the cycle counters where the caller has already read them."""
    return dict(
        cycle=np.asarray(st.cycle if cycle is None else cycle),
        st_busy=np.asarray(st.st_busy),
        st_exec=np.asarray(st.st_exec), st_enroute=np.asarray(st.st_enroute),
        st_hops=np.asarray(st.st_hops), st_inj=np.asarray(st.st_inj),
        st_stall=np.asarray(st.st_stall), mem_val=np.asarray(st.mem_val),
    )


# The engine-call counters: ``stepped`` and ``plain`` as
# :class:`repro.core.sweep.EngineTelemetry` defines them, and the split of
# ``stepped`` into live / finished / tail / pad PE-ticks.
TICK_COUNTERS = ("stepped_pe_ticks", "plain_pe_ticks", "live_pe_ticks",
                 "finished_pe_ticks", "tail_pe_ticks", "pad_pe_ticks")


def engine_call_ticks(ticks, cycle0, cycle1, lane_rows, n_shards: int,
                      chunk: int) -> dict:
    """Account for every PE-tick one engine call stepped.

    Host arrays in the engine's (device) lane order: ``ticks`` (B,) wall
    ticks, uniform within each of the ``n_shards`` consecutive device
    shards; ``cycle0`` / ``cycle1`` (B, N) each PE's cycle counter at the
    call's start (None: all 0) and end; ``lane_rows`` (B, N) bool, the
    rows that belong to a (sub-)lane.  Per shard, with ``adv`` a lane
    row's cycle advance capped at the shard's ticks and ``M`` the largest
    ``adv`` in the shard:

    * ``live_pe_ticks`` = sum of ``adv``: ticks that simulated a cycle;
    * ``finished_pe_ticks`` = sum of ``M - adv``: the row's lane had
      finished, or was capped or halted, while others still stepped;
    * ``tail_pe_ticks`` = sum of ``ticks - M``: the chunk granularity;
    * ``pad_pe_ticks`` = ticks x the rows of no lane (PEs beyond a lane's
      mesh, super-lane rows left empty, inert shard-pad lanes).

    The four sum to ``stepped_pe_ticks`` exactly.  ``plain_pe_ticks`` is
    what the tick-per-cycle engine would step to reach the same counters
    (the largest advance rounded up to the chunk).
    """
    t = np.asarray(ticks, np.int64)
    adv_all = np.asarray(cycle1, np.int64)
    if cycle0 is not None:
        adv_all = adv_all - np.asarray(cycle0, np.int64)
    lane_rows = np.asarray(lane_rows, bool)
    b, n = adv_all.shape
    per = b // n_shards
    out = dict.fromkeys(TICK_COUNTERS, 0)
    for g0 in range(0, b, per):
        g = slice(g0, g0 + per)
        it = int(t[g0])
        rows = lane_rows[g]
        adv = np.minimum(adv_all[g], it)[rows]
        m = int(adv.max(initial=0))
        live = int(adv.sum())
        n_rows = int(rows.sum())
        out["stepped_pe_ticks"] += it * per * n
        out["plain_pe_ticks"] += (-(-int(adv_all[g].max(initial=0)) // chunk)
                                  * chunk * per * n)
        out["live_pe_ticks"] += live
        out["finished_pe_ticks"] += m * n_rows - live
        out["tail_pe_ticks"] += (it - m) * n_rows
        out["pad_pe_ticks"] += it * (per * n - n_rows)
    return out


def _lane_rows(workloads, lane_geoms: np.ndarray, n_max: int) -> np.ndarray:
    """(B, N) bool in input order: the PE rows that carry a (sub-)lane —
    each placement's rectangle of a packed batch, else each lane's own
    width*height."""
    rows = np.zeros((workloads.batch, n_max), bool)
    if workloads.plan is not None:
        for sub in workloads.plan.placements:
            w_sup = workloads.plan.super_geoms[sub.super_lane][0]
            rows[sub.super_lane, sub.pe_ids(w_sup)] = True
    else:
        for b, (w, h) in enumerate(lane_geoms):
            rows[b, :int(w) * int(h)] = True
    return rows


def _validate_deadlines(deadlines, n: int) -> list:
    """Normalize a per-lane deadline sequence: length n, entries None or
    a positive cycle count (int32 range)."""
    dls = list(deadlines)
    if len(dls) != n:
        raise ValueError(f"{len(dls)} deadlines for {n} lanes")
    out = []
    for i, d in enumerate(dls):
        if d is None:
            out.append(None)
            continue
        d = int(d)
        if not 0 < d <= int(ENGINE_UNBOUNDED):
            raise ValueError(f"deadline[{i}]={d}: expected a positive "
                             "int32 cycle count (or None)")
        out.append(d)
    return out


def _run_many_impl(cfg: MachineConfig, workloads, *, modes=None, geoms=None,
                   chunk: int = 512, pack: bool = False,
                   super_geom=None, pack_stats: dict | None = None,
                   shard: bool = False, cycle_hints=None,
                   shard_stats: dict | None = None,
                   telemetry: dict | None = None,
                   deadlines=None
                   ) -> list[RunResult]:
    """Simulate B workloads in a single batched on-device run.

    Shared plumbing behind :func:`run_many` (the legacy kwargs surface)
    and :func:`repro.core.sweep.sweep` (the structured request/report
    surface) — both are thin shells over this function, which is what
    keeps them bit-identical by construction.

    Args:
      cfg: shared static machine parameters.  ``mem_words`` is widened
        automatically when a lane's padded memory image is larger (padding
        is semantically inert — see :mod:`repro.core.batch`).
      workloads: a :class:`repro.core.batch.BatchedWorkloads`, or a sequence
        of compiled workloads (anything with ``prog`` / ``static_ams`` /
        ``amq_len`` / ``mem_val`` / ``mem_meta``, e.g.
        :class:`repro.core.compiler.CompiledWorkload`) to stack and pad.
      modes: optional per-lane fabric modes — a sequence of
        :data:`FABRIC_MODES` names and/or mode bitmasks, one per lane.
        Defaults to the batch's own ``modes`` (if stacked with some), else
        every lane runs the mode described by ``cfg``'s flags.  Mixing
        modes in one batch requires ``cfg.traced_modes`` (the default);
        the whole grid then shares one compiled engine.
      geoms: optional per-lane mesh geometries — a sequence of
        ``(width, height)`` pairs, one per lane.  Defaults to the batch's
        own ``geoms`` (compiled workloads record theirs, so mixed-size
        sequences just work), else every lane runs on ``cfg``'s mesh.
        Mixing sizes in one batch requires ``cfg.traced_geometry`` (the
        default); all PE axes are padded to the batch maximum and the
        whole (workload x mode x size) grid shares one compiled engine.
      pack: co-schedule small lanes as disjoint sub-meshes of shared
        super-lanes (:func:`repro.core.batch.pack_schedule`) so the
        padded PE axis carries useful work instead of dead rows.  The
        schedule may split the batch into a few sequential *waves*
        (similar-runtime lanes share a wave; every wave reuses the same
        compiled engine).  Needs compiled workloads (each records its
        mesh) and the traced engine axes; results still come back one
        per input workload, in input order, bit-identical to their solo
        runs.
      super_geom: optional ``(width, height)`` of the packing mesh
        (default: the batch's maximum lane width x maximum lane height).
        Only meaningful with ``pack=True``.
      pack_stats: optional dict that ``pack=True`` fills with the
        schedule's ``n_waves`` / ``n_super_lanes`` /
        ``packing_efficiency`` / ``unpacked_efficiency``.
      shard: split the lane axis over ``jax.devices()`` via
        ``shard_map`` — lanes are embarrassingly parallel, so a B-lane
        sweep runs B/D lanes per device with per-lane metrics
        bit-identical to the unsharded (and solo) runs.  Lanes are
        balanced across devices by :func:`repro.core.batch.plan_shards`
        (mesh-area runtime proxy, or ``cycle_hints``) and the batch is
        padded to a multiple of the device count with inert empty
        lanes.  The device count is capped at the batch size (a device
        needs at least one real lane).  On a single-device host this is
        a no-op: the plain engine (same cache entry) runs unchanged.
        Composes with ``pack=True`` by sharding each wave's
        super-lanes.
      cycle_hints: optional per-input-lane measured cycle counts (e.g.
        ``[r.cycles for r in a_prior_run]``) replacing the mesh-area
        runtime proxy in BOTH the wave planner (``pack=True``) and the
        shard balancer (``shard=True``).
      shard_stats: optional dict that ``shard=True`` fills with
        ``n_devices`` / ``lanes_per_device`` / ``n_pad_lanes`` and the
        per-device lane ``plan``.
      deadlines: optional per-input-lane cycle deadlines (None entries =
        unbounded).  A lane with a deadline makes NO state transition
        past that many simulated cycles: it comes back frozen exactly at
        the bound with ``completed=False`` (cycle counters, statistics
        and the budget-halt gate are the engine's exact slicing
        semantics, so the frozen state is bit-identical to what a
        budget-sliced run would hold there).  Co-tenant sub-lanes and
        other lanes are unaffected — the budget is per-PE.
      telemetry: optional dict accumulating engine-efficiency counters
        across every engine call this run makes (one per wave under
        ``pack=True``): ``stepped_pe_ticks`` (wall PE-steps executed),
        ``plain_pe_ticks`` (PE-steps the plain tick-per-cycle engine
        would execute for the same final cycle counts), their split into
        ``live`` / ``finished`` / ``tail`` / ``pad_pe_ticks``
        (:func:`engine_call_ticks`) and ``engine_calls``.
        ``dead_step_fraction`` is ``1 - stepped/plain`` — exactly 0 for
        ``fast_forward=False`` engines by construction.

    Host phases run under the spans of :mod:`repro.core.spans`:
    ``pack.plan`` (hints, wave plan, certification) and one
    ``sweep.wave`` per wave when packed; per engine call ``sweep.place``
    (stacking, shard plan, budget, device placement, initial state),
    ``engine.dispatch``, ``engine.wait`` and ``sweep.unpack``.

    Returns:
      One :class:`RunResult` per lane, in input order — metrics are exactly
      what a solo :func:`run` of that workload would report (PE-indexed
      arrays restricted to the lane's own width*height mesh).  A lane that
      hits ``cfg.max_cycles`` without reaching idle returns
      ``completed=False`` with its cycle counter and statistics frozen at
      the cap; its ``mem_val`` (like any non-completed run's) is undefined.

    Raises:
      RuntimeError: if any lane trips the pending-FIFO overflow guard
        (the consumption-guarantee invariant).
    """
    from repro.core.batch import (BatchedWorkloads, pack_schedule,
                                  stack_workloads)
    if pack:
        if isinstance(workloads, BatchedWorkloads):
            raise ValueError(
                "pack=True needs the raw sequence of compiled workloads; "
                "this batch is already stacked (packing re-bases lanes "
                "into super-meshes, which stacking discards)")
        if not (cfg.traced_geometry and cfg.traced_modes):
            raise ValueError("pack=True requires the traced engine axes "
                             "(cfg.traced_geometry and cfg.traced_modes)")
        if geoms is not None:
            raise ValueError("pack=True places lanes itself; per-lane "
                             "geoms cannot be overridden")
        wls = list(workloads)
        if deadlines is not None:
            deadlines = _validate_deadlines(deadlines, len(wls))
        with span("pack.plan"):
            if cycle_hints is not None:
                # validate eagerly: the wave planner's homogeneous-batch
                # shortcut can skip shard_loads, and the per-wave hint
                # aggregation below indexes by input lane.
                from repro.core.batch import validate_hints
                cycle_hints = validate_hints(cycle_hints, len(wls))
            else:
                # No measured oracle: the static cost model supplies the
                # planners' default load signal for heterogeneous batches
                # (repro.analysis.estimate_cycles, replacing the
                # inverse-mesh-area proxy).  Hints steer scheduling only;
                # lane results are bit-identical either way.
                from repro.core.batch import static_cycle_hints
                cycle_hints = static_cycle_hints(wls)
            # A sharded schedule may run up to one super-lane per device
            # side by side without coupling their makespans, so the wave
            # planner gets the device count as its parallel width (capped
            # at the lane count like the shard plan itself).
            parallel = min(len(jax.devices()), len(wls)) if shard else 1
            batches, waves, stats = pack_schedule(wls, modes=modes,
                                                  super_geom=super_geom,
                                                  cycle_hints=cycle_hints,
                                                  parallel=parallel)
            # Certify the isolation property co-tenancy rests on: after
            # rebasing, no AM or meta_pe word may target a PE outside its
            # own sub-lane rectangle (west-first routes never leave the
            # src->dst bbox, so rectangle containment => no cross-lane
            # traffic).  Cheap vectorized scan; catches both packer bugs
            # and post-pack corruption before any cycle runs.
            from repro.analysis.checks import (check_packed_batch,
                                               raise_on_findings)
            for wb in batches:
                raise_on_findings(
                    check_packed_batch(wb),
                    context="packed batch failed rectangle-confinement "
                            "certification")
        if pack_stats is not None:
            pack_stats.update(stats)
        results: list = [None] * len(wls)
        wave_shard_stats: list[dict] = []
        for wb, wave in zip(batches, waves):
            hints_w = None
            if cycle_hints is not None:
                # a super-lane runs for its slowest co-tenant, so its
                # hint is the max over the sub-lanes it hosts (padded
                # inert super-lanes keep 0).
                hints_w = [0.0] * wb.batch
                for p in wb.plan.placements:
                    hints_w[p.super_lane] = max(
                        hints_w[p.super_lane],
                        float(cycle_hints[wave[p.lane]]))
            ws: dict | None = {} if shard_stats is not None else None
            # per-wave deadlines, in the wave's own lane order — the
            # inner (packed) call maps them onto sub-lane PE rows below
            dls_w = (None if deadlines is None
                     else [deadlines[i] for i in wave])
            try:
                with span("sweep.wave"):
                    wave_res = _run_many_impl(
                        cfg, wb, chunk=chunk, shard=shard,
                        cycle_hints=hints_w, shard_stats=ws,
                        telemetry=telemetry, deadlines=dls_w)
            except RuntimeError as e:
                supers = getattr(e, "lanes", None)
                if supers is None:
                    raise
                # translate the failing super-lanes into input workloads
                culprits = sorted(
                    wave[p.lane] for p in wb.plan.placements
                    if p.super_lane in supers)
                raise RuntimeError(
                    "pending-FIFO overflow: consumption guarantee "
                    "violated (simulator invariant; packed input lanes "
                    f"{culprits})") from e
            if ws is not None:
                wave_shard_stats.append(ws)
            for i, r in zip(wave, wave_res):
                results[i] = r
        if shard_stats is not None:
            # aggregate over waves (each wave shards independently):
            # the headline numbers describe the widest wave, pads sum,
            # and the full per-wave plans are kept.
            shard_stats.update(
                n_devices=max(w["n_devices"] for w in wave_shard_stats),
                lanes_per_device=max(w["lanes_per_device"]
                                     for w in wave_shard_stats),
                n_pad_lanes=sum(w["n_pad_lanes"]
                                for w in wave_shard_stats),
                plan=[w["plan"] for w in wave_shard_stats])
        return results
    with span("sweep.place"):
        if not isinstance(workloads, BatchedWorkloads):
            workloads = list(workloads)
            if cycle_hints is None and shard:
                # Default the shard balancer's load signal from the static
                # cost model (homogeneous batches included: LPT over
                # per-lane estimates beats the uniform area proxy there).
                from repro.core.batch import static_cycle_hints
                cycle_hints = static_cycle_hints(workloads, geoms,
                                                 homogeneous=True)
            workloads = stack_workloads(workloads, geoms=geoms)
            geoms = None        # now carried on the batch
        n_max = workloads.n_pes
        if geoms is None:
            geoms = workloads.geoms
        if geoms is None:
            # no geometry information anywhere: every lane runs on cfg's mesh,
            # so the (unpadded) batch must have been compiled for exactly it.
            if n_max != cfg.n_pes:
                raise ValueError(f"batch compiled for {n_max} PEs but cfg "
                                 f"has {cfg.n_pes}")
            lane_geoms = np.tile(np.array([[cfg.width, cfg.height]], np.int32),
                                 (workloads.batch, 1))
        else:
            lane_geoms = np.asarray(geoms, np.int32)
            if lane_geoms.shape != (workloads.batch, 2):
                raise ValueError(f"geoms shape {lane_geoms.shape} for "
                                 f"{workloads.batch} lanes (want (B, 2))")
            if (lane_geoms[:, 0] * lane_geoms[:, 1] > n_max).any():
                raise ValueError("lane geometry exceeds the batch PE axis "
                                 f"({n_max} PEs)")
            if not cfg.traced_geometry:
                if ((lane_geoms[:, 0] != cfg.width)
                        | (lane_geoms[:, 1] != cfg.height)).any():
                    raise ValueError(
                        "per-lane geometries differing from the config require "
                        "cfg.traced_geometry=True (static engines bake the "
                        "mesh into the trace)")
                if n_max != cfg.n_pes:
                    raise ValueError(f"batch padded to {n_max} PEs but the "
                                     f"static-geometry cfg has {cfg.n_pes}")
        if workloads.mem_words > cfg.mem_words:
            cfg = dataclasses.replace(cfg, mem_words=workloads.mem_words)

        if modes is None:
            modes = workloads.modes
        if modes is None:
            lane_modes = np.full((workloads.batch,), mode_code(cfg), np.int32)
        else:
            lane_modes = np.asarray([resolve_mode(m) for m in modes], np.int32)
            if lane_modes.shape[0] != workloads.batch:
                raise ValueError(f"{lane_modes.shape[0]} modes for "
                                 f"{workloads.batch} lanes")
        if not cfg.traced_modes and (lane_modes != mode_code(cfg)).any():
            raise ValueError("per-lane modes differing from the config flags "
                             "require cfg.traced_modes=True (static engines "
                             "bake the mode into the trace)")

        if workloads.sub_ids is not None:
            sub_ids = np.asarray(workloads.sub_ids, np.int32)
            local_ids = np.asarray(workloads.local_ids, np.int32)
        else:
            sub_ids = np.zeros((workloads.batch, n_max), np.int32)
            local_ids = np.tile(np.arange(n_max, dtype=np.int32),
                                (workloads.batch, 1))

        if cycle_hints is not None:
            # validate regardless of device count: a malformed hints list
            # must fail identically on a 1-device laptop and the forced-
            # multi-device CI job (plan_shards only runs on the latter).
            from repro.core.batch import validate_hints
            cycle_hints = validate_hints(cycle_hints, workloads.batch)

        # --- per-PE cycle budget (deadlines) ------------------------------
        # The engine's budget argument is (B, N) int32: INT32_MAX everywhere
        # by default, a lane's own deadline on its rows otherwise.  Packed
        # batches map each deadline onto its sub-lane rectangle, so a
        # deadline-frozen sub-lane never stalls its co-tenants.
        budget = unbounded_budget(workloads.batch, n_max)
        if deadlines is not None:
            if workloads.plan is not None:
                deadlines = _validate_deadlines(
                    deadlines, len(workloads.plan.placements))
                for sub in workloads.plan.placements:
                    dl = deadlines[sub.lane]
                    if dl is not None:
                        w_sup = workloads.plan.super_geoms[sub.super_lane][0]
                        budget[sub.super_lane, sub.pe_ids(w_sup)] = dl
            else:
                deadlines = _validate_deadlines(deadlines, workloads.batch)
                for b, dl in enumerate(deadlines):
                    if dl is not None:
                        budget[b, :] = dl

        # --- lane-axis device sharding ------------------------------------
        # Lanes never interact, so the batch shards freely over devices: the
        # plan balances real lanes by runtime estimate, the lane arrays are
        # gathered into device-major order (inert all-zero 1x1 lanes — idle
        # at cycle 0 — pad B to a multiple of the device count), and results
        # are gathered back to input order below.  One device (or shard
        # off): the plain engine, identical cache entry.  The device count
        # is capped at the batch size — a device below one real lane could
        # only step inert pads (and hosts that force absurd device counts,
        # e.g. the 512 fake host devices repro.launch.dryrun installs for
        # the LLM dry-runs, must not explode a small sweep into a 512-lane
        # mesh).
        n_dev = min(len(jax.devices()), workloads.batch) if shard else 1
        order = inv = None
        if shard and n_dev > 1:
            from repro.core.batch import plan_shards, shard_loads
            geom_list = [tuple(g) for g in lane_geoms]
            loads = cycle_hints
            if loads is None:
                # the inverse-area proxy calls a 1x1 mesh the LONGEST lane,
                # but a lane with nothing to inject (e.g. a wave-padding
                # inert lane) is idle at cycle 0 — zero its load so the
                # balancer spreads the real work instead.
                work = np.asarray(workloads.amq_len).sum(axis=1)
                loads = [0.0 if w == 0 else l
                         for w, l in zip(work, shard_loads(geom_list))]
            dev_plan = plan_shards(geom_list, n_dev, cycle_hints=loads)
            order = [i for dev in dev_plan for i in dev]
            inv = np.empty((workloads.batch,), np.int64)
            for pos, lane in enumerate(order):
                if lane >= 0:
                    inv[lane] = pos
        if shard_stats is not None:
            shard_stats.update(
                n_devices=n_dev,
                lanes_per_device=(len(order) // n_dev if order is not None
                                 else workloads.batch),
                n_pad_lanes=(len(order) - workloads.batch
                             if order is not None else 0),
                plan=(dev_plan if order is not None
                      else [list(range(workloads.batch))]))

        # sharded: every lane array goes straight to the device that runs
        # its lanes (device-major order matches the lane mesh's split)
        sharding = lane_sharding(n_dev) if order is not None else None

        def lanes(a, pad_row=None):
            a = np.asarray(a, np.int32)
            if order is None:
                return jnp.asarray(a)
            out = np.zeros((len(order),) + a.shape[1:], np.int32)
            for pos, lane in enumerate(order):
                if lane >= 0:
                    out[pos] = a[lane]
                elif pad_row is not None:
                    out[pos] = pad_row
            return jax.device_put(out, sharding)

        st = init_lanes(cfg, lanes(workloads.static_ams),
                        lanes(workloads.amq_len), lanes(workloads.mem_val),
                        lanes(workloads.mem_meta), sharding=sharding)
        args = (lanes(workloads.prog), lanes(lane_modes),
                lanes(lane_geoms, pad_row=np.array([1, 1], np.int32)),
                lanes(sub_ids),
                lanes(local_ids, pad_row=np.arange(n_max, dtype=np.int32)),
                st,
                lanes(budget, pad_row=np.full(
                    (n_max,), int(ENGINE_UNBOUNDED), np.int32)))
    with span("engine.dispatch"):
        engine = _get_engine(cfg, chunk, n_max,
                             n_devices=n_dev if order is not None else 1)
        outs = engine(*args)
    with span("engine.wait"):
        st, over, idle, ticks = jax.block_until_ready(outs)
    with span("sweep.unpack"):
        host = _host_stats(st)
        if telemetry is not None:
            # every stepped PE-tick of this call, in device order (ticks
            # is uniform per device shard); cycles start at 0 here
            rows = _lane_rows(workloads, lane_geoms, n_max)
            if inv is not None:
                rows_dev = np.zeros((len(order), n_max), bool)
                rows_dev[inv] = rows
                rows = rows_dev
            acc = engine_call_ticks(
                np.asarray(ticks), None, host["cycle"], rows,
                n_dev if order is not None else 1, chunk)
            for k, v in acc.items():
                telemetry[k] = telemetry.get(k, 0) + v
            telemetry["engine_calls"] = telemetry.get("engine_calls", 0) + 1
        over = np.asarray(over)
        idle = np.asarray(idle)                  # (B, N) per-PE group idle
        if inv is not None:
            # gather back to input-lane order (drops the inert pad lanes):
            # every downstream consumer — overflow naming, plan un-packing,
            # per-lane slicing — indexes by input lane again.
            over = over[inv]
            idle = idle[inv]
            host = {k: v[inv] for k, v in host.items()}
        if over.any():
            bad = np.nonzero(over)[0].tolist()
            err = RuntimeError("pending-FIFO overflow: consumption guarantee "
                               f"violated (simulator invariant; lanes {bad})")
            err.lanes = bad  # structured, so pack=True can name input lanes
            raise err
        if workloads.plan is not None:
            # un-pack: one result per ORIGINAL lane, gathered from its
            # sub-mesh rectangle (plan order is input order by construction).
            out = []
            for sub in workloads.plan.placements:
                w_sup = workloads.plan.super_geoms[sub.super_lane][0]
                ids = sub.pe_ids(w_sup)
                out.append(_pe_slice_result(
                    host, bool(idle[sub.super_lane, ids[0]]),
                    sub.super_lane, ids))
            return out
        return [_pe_slice_result(
            host, bool(idle[b, 0]), b,
            np.arange(int(lane_geoms[b, 0] * lane_geoms[b, 1])))
                for b in range(workloads.batch)]


def run_many(cfg: MachineConfig, workloads, *, modes=None, geoms=None,
             chunk: int = 512, pack: bool = False,
             super_geom=None, pack_stats: dict | None = None,
             shard: bool = False, cycle_hints=None,
             shard_stats: dict | None = None,
             deadlines=None
             ) -> list[RunResult]:
    """Simulate B workloads in a single batched on-device run.

    See :func:`_run_many_impl` for the full argument contract.  Prefer
    the structured surface — :class:`repro.core.sweep.SweepRequest` in,
    :class:`repro.core.sweep.SweepReport` out::

        from repro.core.sweep import SweepRequest, sweep
        report = sweep(cfg, SweepRequest(workloads=wls, pack=True))
        report.lanes            # the RunResults, in input order
        report.pack.n_waves     # was: pack_stats out-param dict

    The mutable out-param dicts ``pack_stats=`` / ``shard_stats=`` are
    deprecated in favor of ``SweepReport.pack`` / ``SweepReport.shard``;
    passing either emits a :class:`DeprecationWarning` (results stay
    bit-identical — this shim and :func:`repro.core.sweep.sweep` call the
    same implementation).
    """
    if pack_stats is not None or shard_stats is not None:
        import warnings
        warnings.warn(
            "run_many(pack_stats=..., shard_stats=...) out-param dicts are "
            "deprecated; use repro.core.sweep.sweep(cfg, SweepRequest(...)) "
            "and read SweepReport.pack / SweepReport.shard instead",
            DeprecationWarning, stacklevel=2)
    return _run_many_impl(cfg, workloads, modes=modes, geoms=geoms,
                          chunk=chunk, pack=pack, super_geom=super_geom,
                          pack_stats=pack_stats, shard=shard,
                          cycle_hints=cycle_hints, shard_stats=shard_stats,
                          deadlines=deadlines)


def run(cfg: MachineConfig, prog: np.ndarray, static_ams: np.ndarray,
        amq_len: np.ndarray, mem_val: np.ndarray, mem_meta: np.ndarray,
        *, chunk: int = 512) -> RunResult:
    """Execute until global idle (or ``cfg.max_cycles``).

    Thin B=1 wrapper over :func:`run_many`: same engine, same compile
    cache, identical metrics.
    """
    (res,) = _run_many_impl(
        cfg, [(prog, static_ams, amq_len, mem_val, mem_meta)], chunk=chunk)
    return res
