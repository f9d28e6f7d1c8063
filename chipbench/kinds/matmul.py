"""Dense matmul C = A B."""
import numpy as np

from chipbench.gen.sparse import dense_ints, revalue


def generate(p, shape, value):
    a = dense_ints((p["m"], p["k"]), shape)
    b = dense_ints((p["k"], p["n"]), shape)
    return dict(a=revalue(a, value, -3, 4), b=revalue(b, value, -3, 4))


def build(d, cfg, strategy):
    from repro.core import compiler
    return compiler.build_matmul(d["a"], d["b"], cfg, strategy=strategy)


def reference(d, dtype=np.int64):
    return d["a"].astype(dtype) @ d["b"].astype(dtype)
