"""The tick's input-port FIFO update, element for element against the
sort / gather / scatter form it replaced.

``machine._fifo_compact`` (stable compaction after removals) and
``machine._fifo_set`` (one message written at a per-PE port and slot)
are selects against the static depth and port count.  Each case here
holds them to the old form on random 15-field messages: ``argsort`` +
``take_along_axis`` + zero fill for compaction, ``.at[pe, port,
slot].set`` for the interception write-back, the arrivals and the
injection write.
"""
import itertools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.machine import (DEPTH, MSG_F, P_INJ, PORTS, _fifo_compact,
                                _fifo_set)

N = 16          # PEs per case


def _old_compact(buf, keep):
    order = jnp.argsort(
        jnp.where(keep, jnp.arange(DEPTH)[None, None, :], DEPTH + 1), axis=2)
    out = jnp.take_along_axis(buf, order[..., None].repeat(MSG_F, 3), axis=2)
    return jnp.where(
        (jnp.arange(DEPTH)[None, None, :] < keep.sum(2)[..., None])[..., None],
        out, 0)


def _old_set(buf, port, slot, msg, on):
    pe = jnp.arange(buf.shape[0])
    cur = buf[pe, port, slot, :]
    return buf.at[pe, port, slot, :].set(jnp.where(on[:, None], msg, cur))


def _msgs(rng, *shape):
    """Random int32 messages of shape ``shape + (MSG_F,)``, full range."""
    return jnp.asarray(rng.integers(np.iinfo(np.int32).min,
                                    np.iinfo(np.int32).max,
                                    size=shape + (MSG_F,), dtype=np.int32))


def _same(new, old):
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


# every occupancy, and every set of occupied slots the tick can remove: the
# head granted to an output port, a slot ejected to either functional unit,
# or any of these together
_REMOVALS = [(occ, rm) for occ in range(DEPTH + 1)
             for r in range(occ + 1)
             for rm in itertools.combinations(range(occ), r)]


@pytest.mark.parametrize("occ,removed", _REMOVALS,
                         ids=[f"occ{o}-rm{''.join(map(str, r)) or 'none'}"
                              for o, r in _REMOVALS])
def test_compaction_matches_sort_and_gather(occ, removed):
    rng = np.random.default_rng(occ * 100 + len(removed))
    # slots past the occupancy hold stale words: the fill must not leak them
    buf = _msgs(rng, N, PORTS, DEPTH)
    keep = np.arange(DEPTH) < occ
    keep[list(removed)] = False
    keep = jnp.broadcast_to(jnp.asarray(keep), (N, PORTS, DEPTH))
    new = _fifo_compact(buf, keep)
    _same(new, _old_compact(buf, keep))
    kept = [k for k in range(occ) if k not in removed]
    for j, k in enumerate(kept):
        _same(new[:, :, j], buf[:, :, k])
    _same(new[:, :, len(kept):], 0)


@pytest.mark.parametrize("seed", range(3))
def test_compaction_mixed_keep_masks(seed):
    rng = np.random.default_rng(seed)
    buf = _msgs(rng, 4 * N, PORTS, DEPTH)
    keep = jnp.asarray(rng.random((4 * N, PORTS, DEPTH)) < 0.5)
    _same(_fifo_compact(buf, keep), _old_compact(buf, keep))


@pytest.mark.parametrize("port", range(PORTS))
def test_interception_write_back(port):
    """The transformed message replaces slot 0 of the intercepted port;
    PEs that intercepted nothing carry ``argmax`` of an empty row, 0."""
    rng = np.random.default_rng(port)
    buf = _msgs(rng, N, PORTS, DEPTH)
    nxt = _msgs(rng, N)
    was = jnp.asarray(rng.random(N) < 0.5)
    icept_port = jnp.where(was, port, 0).astype(jnp.int32)
    _same(_fifo_set(buf, icept_port, 0, nxt, was),
          _old_set(buf, icept_port, 0, nxt, was))


@pytest.mark.parametrize("q,pos", list(itertools.product(range(4),
                                                         range(DEPTH))))
def test_arrival_write(q, pos):
    """An arrival on directional port ``q`` lands at the tail slot."""
    rng = np.random.default_rng(10 * q + pos)
    buf = _msgs(rng, N, PORTS, DEPTH)
    m_in = _msgs(rng, N)
    has = jnp.asarray(rng.random(N) < 0.5)
    pos_d = jnp.full((N,), pos, jnp.int32)
    _same(_fifo_set(buf, q, pos_d, m_in, has),
          _old_set(buf, q, pos_d, m_in, has))


@pytest.mark.parametrize("pos", range(DEPTH))
def test_injection_write(pos):
    rng = np.random.default_rng(pos)
    buf = _msgs(rng, N, PORTS, DEPTH)
    inj = _msgs(rng, N)
    on = jnp.asarray(rng.random(N) < 0.5)
    posi = jnp.full((N,), pos, jnp.int32)
    _same(_fifo_set(buf, P_INJ, posi, inj, on),
          _old_set(buf, P_INJ, posi, inj, on))


@pytest.mark.parametrize("seed", range(4))
def test_tick_update_sequence(seed):
    """Compaction, interception, four arrivals and injection in the tick's
    order, with random occupancies, removals, ports and tail slots."""
    rng = np.random.default_rng(100 + seed)
    n = 4 * N
    buf0 = _msgs(rng, n, PORTS, DEPTH)
    occ = rng.integers(0, DEPTH + 1, size=(n, PORTS))
    keep = jnp.asarray((np.arange(DEPTH) < occ[..., None])
                       & (rng.random((n, PORTS, DEPTH)) < 0.6))
    was = jnp.asarray(rng.random(n) < 0.3)
    icept_port = jnp.where(was, rng.integers(0, PORTS, n), 0).astype(jnp.int32)
    nxt = _msgs(rng, n)
    arrivals = [(jnp.asarray(rng.random(n) < 0.5), _msgs(rng, n))
                for _ in range(4)]
    on_inj = jnp.asarray(rng.random(n) < 0.5)
    inj = _msgs(rng, n)

    def update(compact, put):
        buf = compact(buf0, keep)
        buf_n = keep.sum(axis=2).astype(jnp.int32)
        buf = put(buf, icept_port, 0, nxt, was)
        for q, (has, m_in) in enumerate(arrivals):
            buf = put(buf, q, jnp.clip(buf_n[:, q], 0, DEPTH - 1), m_in, has)
            buf_n = buf_n.at[:, q].add(has.astype(jnp.int32))
        posi = jnp.clip(buf_n[:, P_INJ], 0, DEPTH - 1)
        return put(buf, P_INJ, posi, inj, on_inj)

    _same(update(_fifo_compact, _fifo_set), update(_old_compact, _old_set))
