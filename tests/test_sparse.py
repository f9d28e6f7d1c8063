"""Sparse formats / ops / partitioning / dispatch — unit + property tests."""
import os
import subprocess
import sys

import numpy as np
import pytest
from repro.testing import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core import partition
from repro.sparse import dispatch, ops
from repro.sparse.formats import BCSR, CSR

RNG = np.random.default_rng(11)


def _rand_sparse(m, n, d, rng=RNG):
    return ((rng.random((m, n)) < d)
            * rng.standard_normal((m, n))).astype(np.float32)


# ----------------------------------------------------------------- formats --
@given(m=st.integers(1, 24), n=st.integers(1, 24),
       d=st.floats(0.0, 0.9), seed=st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_csr_roundtrip(m, n, d, seed):
    a = _rand_sparse(m, n, d, np.random.default_rng(seed))
    c = CSR.from_dense(a, cap=m * n + 1)
    np.testing.assert_allclose(np.asarray(c.to_dense()), a)


def test_bcsr_roundtrip():
    a = np.zeros((16, 256), np.float32)
    a[:8, :128] = RNG.standard_normal((8, 128))
    a[8:, 128:] = RNG.standard_normal((8, 128))
    b = BCSR.from_dense(a, block=(8, 128), cap=4)
    np.testing.assert_allclose(np.asarray(b.to_dense()), a)


# --------------------------------------------------------------------- ops --
@given(seed=st.integers(0, 2**31 - 1), d=st.floats(0.05, 0.6))
@settings(max_examples=15, deadline=None)
def test_spmv_matches_dense(seed, d):
    rng = np.random.default_rng(seed)
    a = _rand_sparse(17, 23, d, rng)
    x = rng.standard_normal(23).astype(np.float32)
    c = CSR.from_dense(a, cap=17 * 23)
    np.testing.assert_allclose(np.asarray(ops.spmv(c, jnp.asarray(x))),
                               a @ x, rtol=1e-4, atol=1e-4)


def test_spmm_and_spmspm():
    a = _rand_sparse(16, 24, 0.3)
    b = _rand_sparse(24, 12, 0.3)
    ca, cb = CSR.from_dense(a, cap=512), CSR.from_dense(b, cap=512)
    bm = RNG.standard_normal((24, 8)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(ops.spmm(ca, jnp.asarray(bm))),
                               a @ bm, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(ops.spmspm_via_dense(ca, cb)),
                               a @ b, rtol=1e-4, atol=1e-4)


def test_spmadd_sddmm():
    a, b = _rand_sparse(14, 14, 0.3), _rand_sparse(14, 14, 0.3)
    ca, cb = CSR.from_dense(a, cap=256), CSR.from_dense(b, cap=256)
    np.testing.assert_allclose(np.asarray(ops.spmadd(ca, cb)), a + b,
                               rtol=1e-5, atol=1e-5)
    ad = RNG.standard_normal((14, 6)).astype(np.float32)
    bd = RNG.standard_normal((6, 14)).astype(np.float32)
    mask = CSR.from_dense((RNG.random((14, 14)) < 0.3).astype(np.float32),
                          cap=256)
    got = np.asarray(ops.sddmm(jnp.asarray(ad), jnp.asarray(bd), mask))
    dm = ad @ bd
    nnz = int(mask.nnz)
    ri = np.asarray(mask.row_ids)[:nnz]
    ci = np.asarray(mask.col)[:nnz]
    np.testing.assert_allclose(got[:nnz], dm[ri, ci], rtol=1e-4, atol=1e-4)


def test_bcsr_spmm():
    a = np.zeros((16, 256), np.float32)
    a[:8, :128] = RNG.standard_normal((8, 128))
    a[8:, 128:] = RNG.standard_normal((8, 128))
    b = RNG.standard_normal((256, 32)).astype(np.float32)
    c = BCSR.from_dense(a, block=(8, 128), cap=8)
    np.testing.assert_allclose(np.asarray(ops.bcsr_spmm(c, jnp.asarray(b))),
                               a @ b, rtol=1e-3, atol=1e-3)


# --------------------------------------------------------------- partition --
@given(seed=st.integers(0, 2**31 - 1), parts=st.sampled_from([2, 4, 8, 16]))
@settings(max_examples=20, deadline=None)
def test_nnz_balance_invariant(seed, parts):
    """Property: every PE's nnz load stays within 2x of the mean, and every
    row is assigned exactly once (Alg. 1 objective)."""
    rng = np.random.default_rng(seed)
    m = 64
    lens = rng.integers(0, 30, size=m)
    rowptr = np.concatenate([[0], np.cumsum(lens)])
    if rowptr[-1] == 0:
        return
    pl = partition.nnz_balanced_rows(rowptr, parts)
    assert pl.row_to_pe.shape == (m,)
    assert sorted(np.concatenate(pl.pe_rows).tolist()) == list(range(m))
    nzmax = lens.max()
    mean = rowptr[-1] / parts
    assert pl.nnz_per_pe.max() <= mean + nzmax  # contiguity bound


def test_dissimilarity_cluster_balances():
    rng = np.random.default_rng(0)
    a = (rng.random((64, 64)) < 0.2).astype(np.int64)
    rowptr = np.concatenate([[0], np.cumsum((a != 0).sum(1))])
    col = np.nonzero(a)[1]
    pl = partition.dissimilarity_cluster(rowptr, col, 16, n_cols=64)
    assert pl.imbalance() < 2.0
    assert sorted(np.concatenate(pl.pe_rows).tolist()) == list(range(64))


def test_expert_placement_lpt():
    load = [10, 1, 1, 1, 9, 8, 1, 1]
    out = partition.expert_placement(load, 4)
    per_dev = np.zeros(4)
    for e, d in enumerate(out):
        per_dev[d] += load[e]
    assert per_dev.max() <= 12  # LPT bound far below naive 19


# ---------------------------------------------------------------- dispatch --
def test_bucketize_roundtrip():
    rng = np.random.default_rng(2)
    dest = jnp.asarray(rng.integers(0, 4, size=(33,)), jnp.int32)
    vals = jnp.asarray(rng.standard_normal(33), jnp.float32)
    idx, valid, rank, kept = dispatch.bucketize(dest, 4, 16)
    assert bool(kept.all())
    picked = jnp.where(valid, vals[idx], 0)
    back = dispatch.unbucketize(picked, dest, rank, kept)
    np.testing.assert_allclose(np.asarray(back), np.asarray(vals))


def test_bucketize_overflow_backpressure():
    dest = jnp.zeros((10,), jnp.int32)      # all to shard 0
    idx, valid, rank, kept = dispatch.bucketize(dest, 2, 4)
    assert int(kept.sum()) == 4             # capacity enforced
    assert int(valid.sum()) == 4


def test_steal_overflow_rebalances():
    dest = jnp.zeros((12,), jnp.int32)
    load = jnp.asarray([12, 0, 0, 0])
    new = dispatch.steal_overflow(dest, load, capacity=4)
    counts = np.bincount(np.asarray(new), minlength=4)
    assert counts[0] == 4                   # kept up to capacity
    assert counts[1:].sum() == 8            # overflow went to idle shards
    assert counts.max() <= 4


@pytest.mark.slow
def test_spmv_sharded_single_device():
    a = _rand_sparse(24, 24, 0.35)
    x = RNG.standard_normal(24).astype(np.float32)
    mesh = jax.make_mesh((1,), ("data",), devices=jax.devices()[:1])
    sh = dispatch.shard_csr_rows(a, 1)
    y = dispatch.spmv_sharded(mesh, sh, x, capacity=int(sh["cap"]))
    np.testing.assert_allclose(y, a @ x, rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_spmv_sharded_multidevice_subprocess():
    """8-way shard_map dispatch in a subprocess (keeps this process at one
    device, per the harness contract)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax
from repro.sparse import dispatch
rng = np.random.default_rng(1)
a = np.zeros((64, 64), np.float32)
for i in range(64):
    d = min(0.9, 0.02 + (i % 7) * 0.12)
    a[i] = (rng.random(64) < d) * rng.standard_normal(64)
x = rng.standard_normal(64).astype(np.float32)
from repro.jax_compat import make_mesh
mesh = make_mesh((8,), ("data",), devices=jax.devices())
sh = dispatch.shard_csr_rows(a, 8)
y = dispatch.spmv_sharded(mesh, sh, x, capacity=int(sh["cap"]))
assert np.allclose(y, a @ x, atol=1e-4), "mismatch"
print("OK")
"""
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600,
                         env={**os.environ, "PYTHONPATH": src,
                              "JAX_PLATFORMS": "cpu"})
    assert "OK" in out.stdout, out.stderr[-2000:]
