"""SpMV y = A x on a power-law sparse A (pruned-weight surrogate)."""
import numpy as np

from chipbench.gen.sparse import dense_ints, powerlaw_sparse, revalue


def generate(p, shape, value):
    a = powerlaw_sparse(p["m"], p["n"], shape, p["density"])
    x = dense_ints((p["n"],), shape)
    return dict(a=revalue(a, value, 1, 4), x=revalue(x, value, -3, 4))


def build(d, cfg, strategy):
    from repro.core import compiler
    return compiler.build_spmv(d["a"], d["x"], cfg, strategy=strategy)


def reference(d, dtype=np.int64):
    return d["a"].astype(dtype) @ d["x"].astype(dtype)
