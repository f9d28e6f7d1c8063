"""Dense MV y = A x."""
import numpy as np

from chipbench.gen.sparse import dense_ints, revalue


def generate(p, shape, value):
    a = dense_ints((p["m"], p["n"]), shape)
    x = dense_ints((p["n"],), shape)
    return dict(a=revalue(a, value, -3, 4), x=revalue(x, value, -3, 4))


def build(d, cfg, strategy):
    from repro.core import compiler
    return compiler.build_mv(d["a"], d["x"], cfg, strategy=strategy)


def reference(d, dtype=np.int64):
    return d["a"].astype(dtype) @ d["x"].astype(dtype)
