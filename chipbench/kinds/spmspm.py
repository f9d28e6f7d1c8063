"""SpMSpM C = A B, both operands power-law sparse."""
import numpy as np

from chipbench.gen.sparse import powerlaw_sparse, revalue


def generate(p, shape, value):
    a = powerlaw_sparse(p["m"], p["k"], shape, p["density_a"])
    b = powerlaw_sparse(p["k"], p["n"], shape, p["density_b"])
    return dict(a=revalue(a, value, 1, 4), b=revalue(b, value, 1, 4))


def build(d, cfg, strategy):
    from repro.core import compiler
    return compiler.build_spmspm(d["a"], d["b"], cfg, strategy=strategy)


def reference(d, dtype=np.int64):
    return d["a"].astype(dtype) @ d["b"].astype(dtype)
