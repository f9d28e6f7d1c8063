"""SpM+SpM C = A + B."""
import numpy as np

from chipbench.gen.sparse import powerlaw_sparse, revalue


def generate(p, shape, value):
    a = powerlaw_sparse(p["m"], p["n"], shape, p["density"])
    b = powerlaw_sparse(p["m"], p["n"], shape, p["density"])
    return dict(a=revalue(a, value, 1, 4), b=revalue(b, value, 1, 4))


def build(d, cfg, strategy):
    from repro.core import compiler
    return compiler.build_spmadd(d["a"], d["b"], cfg, strategy=strategy)


def reference(d, dtype=np.int64):
    return d["a"].astype(dtype) + d["b"].astype(dtype)
