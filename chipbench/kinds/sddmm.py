"""SDDMM: (A B) sampled at a sparse-attention mask's nonzeros, in
row-major order of the mask."""
import numpy as np

from chipbench.gen.sparse import attention_mask, dense_ints, revalue


def generate(p, shape, value):
    s, k = p["s"], p["d"]
    a, b = dense_ints((s, k), shape), dense_ints((k, s), shape)
    return dict(a=revalue(a, value, -3, 4), b=revalue(b, value, -3, 4),
                mask=attention_mask(s, shape, p["mask_density"]))


def build(d, cfg, strategy):
    from repro.core import compiler
    return compiler.build_sddmm(d["a"], d["b"], d["mask"], cfg,
                                strategy=strategy)


def reference(d, dtype=np.int64):
    return (d["a"].astype(dtype) @ d["b"].astype(dtype))[d["mask"] != 0]
