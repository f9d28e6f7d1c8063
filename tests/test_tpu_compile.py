"""Compile the simulator's device programs for a described TPU v5e.

Nothing runs: each test lowers a program at the shapes the paper-scale
Fig. 17 sweep gives it and compiles it for a v5e chip that is described,
not attached, so whatever the chip's compiler refuses fails here first.
Every program must also fit one chip's 16 GB (arguments plus
temporaries).  The topology is described inside a fixture — never while
a module is imported — because only one process at a time may load the
TPU library; keep every such compile in this one file.
"""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import machine
from repro.core.batch import pack_schedule, static_cycle_hints

HBM_BYTES = 16 * 10**9      # one v5e chip
CHUNK = 512                 # the sweep() default


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
    machine.clear_engine_cache()


@pytest.fixture(scope="module")
def fig17():
    """``(cfg, lanes)`` of the paper-scale Fig. 17 grid."""
    from benchmarks import fig17_scaling
    lanes = [wl for _, _, wl in
             fig17_scaling.build_grid(fig17_scaling._builders())]
    return fig17_scaling._size_cfg(8, 8), lanes


def _wave(lanes, parallel=1):
    """The first packed wave of the grid's schedule (every wave of a
    schedule is padded to the same shapes)."""
    batches, _, _ = pack_schedule(lanes, cycle_hints=static_cycle_hints(lanes),
                                  parallel=parallel)
    return batches[0]


def _engine_args(cfg, wb, sharding, batch=None):
    """Shapes of one engine call on packed wave ``wb``, placed with
    ``sharding`` (``batch`` pads the lane axis like the shard planner)."""
    b = wb.batch if batch is None else batch
    n = wb.n_pes

    def lanes(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct((b,) + tuple(shape[1:]), dtype,
                                    sharding=sharding)

    st = jax.eval_shape(
        lambda *a: machine.init_lanes(cfg, *a), wb.static_ams, wb.amq_len,
        wb.mem_val, wb.mem_meta)
    st = jax.tree.map(lambda x: lanes(x.shape, x.dtype), st)
    return (lanes(wb.prog.shape), lanes((b,)), lanes((b, 2)),
            lanes((b, n)), lanes((b, n)), st, lanes((b, n)))


def _fits_one_chip(compiled):
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < HBM_BYTES, f"{used} bytes per device"
    return mem


@pytest.fixture(scope="module")
def v5e_engine(fig17, one_chip, no_compile_cache):
    """``compiled(fast_forward) -> (compiled engine, args)`` for the
    grid's first packed wave on one v5e, each compiled once per module."""
    import dataclasses
    cfg, lanes = fig17
    wb = _wave(lanes)
    assert wb.n_pes == 64
    done = {}

    def compiled(fast_forward):
        if fast_forward not in done:
            c = dataclasses.replace(cfg, fast_forward=fast_forward)
            engine = machine._get_engine(c, CHUNK, wb.n_pes)
            args = _engine_args(c, wb, one_chip)
            done[fast_forward] = engine.lower(*args).compile(), args
        return done[fast_forward]
    return compiled


@pytest.mark.parametrize("fast_forward", [True, False],
                         ids=["fast_forward", "plain"])
def test_engine_compiles_for_v5e(v5e_engine, fast_forward):
    compiled, _ = v5e_engine(fast_forward)
    # the donated machine state is updated in place
    assert _fits_one_chip(compiled).alias_size_in_bytes > 0
    # fast-forward is one real branch (lax.cond) around the chunk scan
    assert ("conditional" in compiled.as_text()) == fast_forward


_HLO_DEF = re.compile(r"%([\w.\-]+) = \w+\[([\d,]*)\]")
_HLO_GATHER_SCATTER = re.compile(
    r"%([\w.\-]+) = [^=\n]*?\b(?:gather|scatter)\(([^)]*)\)")


def _gathers_scatters_touching(hlo, tail, size):
    """Names of the gathers and scatters in ``hlo`` whose result or an
    operand has trailing dims ``tail`` or ``size`` elements in all (a
    fusion may flatten the array)."""
    shapes = {m.group(1): tuple(int(d) for d in m.group(2).split(",") if d)
              for m in _HLO_DEF.finditer(hlo)}

    def hit(name):
        s = shapes.get(name)
        return s is not None and (s[-len(tail):] == tail
                                  or math.prod(s) == size)
    return [m.group(1) for m in _HLO_GATHER_SCATTER.finditer(hlo)
            if any(hit(x) for x in
                   [m.group(1)] + re.findall(r"%([\w.\-]+)", m.group(2)))]


@pytest.mark.parametrize("fast_forward", [True, False],
                         ids=["fast_forward", "plain"])
def test_engine_updates_fifos_without_sort_gather_scatter(v5e_engine,
                                                          fast_forward):
    """The input-port FIFOs ``buf`` (lanes, PEs, 5, DEPTH, MSG_F) are
    compacted and written with selects: on the chip a sort, gather or
    scatter over them walks every element, once per simulated cycle."""
    compiled, args = v5e_engine(fast_forward)
    hlo = compiled.as_text()
    assert "sort(" not in hlo
    buf = args[5].buf.shape
    assert buf[2:] == (machine.PORTS, machine.DEPTH, machine.MSG_F)
    assert _gathers_scatters_touching(hlo, buf[2:], math.prod(buf)) == []


def test_service_install_compiles_for_v5e(fig17, one_chip, no_compile_cache):
    from repro.serve import SweepService
    cfg, lanes = fig17
    svc = SweepService(cfg, template=lanes, n_supers=4)
    try:
        def placed(x, dtype=None):
            return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype,
                                        sharding=one_chip)
        st = jax.tree.map(placed, svc._st)
        b, n = svc._sub_ids.shape
        args = (st, placed(np.zeros((b, n), bool)), placed(svc._st.amq),
                placed(svc._st.amq_len), placed(svc._st.mem_val),
                placed(svc._st.mem_meta))
        compiled = svc._install.lower(*args).compile()
    finally:
        svc.shutdown()
    _fits_one_chip(compiled)


def test_sharded_engine_compiles_for_four_v5e(fig17, topo, no_compile_cache,
                                              monkeypatch):
    from jax.sharding import NamedSharding, PartitionSpec
    cfg, lanes = fig17
    wb = _wave(lanes, parallel=4)
    monkeypatch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
    try:
        sharding = machine.lane_sharding(4)
        assert sharding == NamedSharding(sharding.mesh,
                                         PartitionSpec("lanes"))
        engine = machine._get_engine(cfg, CHUNK, wb.n_pes, n_devices=4)
        args = _engine_args(cfg, wb, sharding, batch=-(-wb.batch // 4) * 4)
        compiled = engine.lower(*args).compile()
    finally:
        machine.clear_engine_cache()
    # each chip updates its own lanes' donated state in place, and lanes
    # never talk: no collective at all, each chip loops until its own
    # lanes are idle
    assert _fits_one_chip(compiled).alias_size_in_bytes > 0
    hlo = compiled.as_text()
    for op in ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter"):
        assert op not in hlo, op
