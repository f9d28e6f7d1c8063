"""Pending-FIFO reservation discipline (the consumption guarantee, §3.4).

The three producers into the pending FIFO — decode output (reserves 1
slot), compute output (reserves 2: its own push plus a same-cycle decode
push) and the stream unit (gated at STREAM_THROTTLE on the
post-execution-push count) — are gated so that occupancy provably never
exceeds PEND_CAP; a message from the network needs NET_RESERVE free
slots, and parks in the wait queue short of them.  These property tests
shrink the FIFO to a few slots, drive congested workloads through the
raw cycle transition, and assert the invariant at EVERY cycle
(run_many's chunked guard only samples it at chunk boundaries)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compiler, machine
from repro.core.machine import MachineConfig

WINDOW = 16   # cycles per jitted step; the per-cycle max is scanned out


def test_pend_occupancy_never_exceeds_cap(monkeypatch):
    # network executions stop at PEND_CAP - NET_RESERVE = 8, above the
    # stream throttle, as they do at full size
    monkeypatch.setattr(machine, "PEND_CAP", 16)
    monkeypatch.setattr(machine, "STREAM_THROTTLE", 6)  # <= PEND_CAP - 3
    cfg = MachineConfig(mem_words=1024, max_cycles=50_000)
    a = compiler.random_sparse(24, 24, 0.5, np.random.default_rng(1))
    x = np.random.default_rng(2).integers(-4, 5, size=(24,))
    wl = compiler.build_spmv(a, x, cfg)

    st = machine.init_state(cfg, wl.static_ams, wl.amq_len, wl.mem_val,
                            wl.mem_meta)
    cyc = machine._make_cycle(cfg)

    @jax.jit
    def step_window(prog, mode, geom, st):
        def sub(s, _):
            s2 = cyc(prog, mode, geom, s)
            return s2, jnp.max(s2.pend_n)
        st, occ = jax.lax.scan(sub, st, None, length=WINDOW)
        return st, jnp.max(occ)   # max over every cycle in the window

    prog = jnp.asarray(wl.prog, jnp.int32)
    mode = jnp.int32(machine.mode_code(cfg))
    geom = jnp.asarray([cfg.width, cfg.height], jnp.int32)
    max_occ, idle = 0, False
    for _ in range(cfg.max_cycles // WINDOW):
        st, occ = step_window(prog, mode, geom, st)
        max_occ = max(max_occ, int(occ))
        assert max_occ <= machine.PEND_CAP, "pending FIFO overflowed"
        assert max_occ < machine.PEND_CAP - 2, "guard depth reached"
        if bool(machine.is_idle(st)):
            idle = True
            break
    assert idle, "congested run never reached global idle"

    # The run was genuinely congested: occupancy climbed past the stream
    # throttle (execution pushes landed on top of a throttled stream) ...
    assert max_occ > machine.STREAM_THROTTLE
    # ... and the tight gating still preserved the program's semantics.
    assert wl.check(np.asarray(st.mem_val))


def test_hot_pe_fan_in_completes_under_tia(monkeypatch):
    """Fan-in at a hot PE under TIA (the smallest case that overflowed
    before the network reserve): every row of a 12x16 operand hits the
    two columns whose x lives on PE 0.  Each request PE 0 serves comes
    back through its own inject port as an anchored MUL, so a PE that
    kept taking requests filled its FIFO with MULs that could never
    execute.  With the reserve, requests the FIFO has no room for park in
    the wait queue; the run reaches idle, matches numpy, and the
    occupancy stays inside the cap at every cycle."""
    monkeypatch.setattr(machine, "PEND_CAP", 16)
    monkeypatch.setattr(machine, "STREAM_THROTTLE", 6)
    cfg = MachineConfig(width=2, height=2, mem_words=256, max_cycles=20_000,
                        opportunistic=False, dual_issue=False)
    rng = np.random.default_rng(0)
    a = np.zeros((12, 16), np.int64)
    a[:, :2] = rng.integers(1, 4, size=(12, 2))
    x = rng.integers(-3, 4, size=(16,))
    wl = compiler.build_spmv(a, x, cfg, strategy="rows")

    st = machine.init_state(cfg, wl.static_ams, wl.amq_len, wl.mem_val,
                            wl.mem_meta)
    cyc = machine._make_cycle(cfg)

    @jax.jit
    def step_window(prog, mode, geom, st):
        def sub(s, _):
            s2 = cyc(prog, mode, geom, s)
            return s2, (jnp.max(s2.pend_n), jnp.max(s2.swq_n))
        st, (occ, parked) = jax.lax.scan(sub, st, None, length=WINDOW)
        return st, jnp.max(occ), jnp.max(parked)

    prog = jnp.asarray(wl.prog, jnp.int32)
    mode = jnp.int32(machine.MODE_TIA)
    geom = jnp.asarray([cfg.width, cfg.height], jnp.int32)
    max_occ = max_parked = 0
    for _ in range(cfg.max_cycles // WINDOW):
        st, occ, parked = step_window(prog, mode, geom, st)
        max_occ, max_parked = max(max_occ, int(occ)), max(max_parked,
                                                           int(parked))
        assert max_occ < machine.PEND_CAP - 2, "guard depth reached"
        if bool(machine.is_idle(st)):
            break
    assert bool(machine.is_idle(st)), "fan-in run never reached idle"
    assert max_parked > 0           # requests did park (SpMV has no streams)
    assert wl.check(np.asarray(st.mem_val))
    # the run_many path agrees, with its guard checked every cycle
    (res,) = machine.run_many(cfg, [wl], modes=["tia"], chunk=1)
    assert res.completed and wl.check(res.mem_val)
