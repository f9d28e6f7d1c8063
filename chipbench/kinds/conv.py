"""Valid 2-D convolution, x (H, W, Cin) with w (kh, kw, Cin, Cout); the
answer is laid out as (output pixel in row-major order, Cout)."""
import numpy as np

from chipbench.gen.sparse import dense_ints, revalue


def generate(p, shape, value):
    x = dense_ints((p["h"], p["w"], p["cin"]), shape, -2, 3)
    w = dense_ints((p["kh"], p["kw"], p["cin"], p["cout"]), shape, -2, 3)
    return dict(x=revalue(x, value, -2, 3), w=revalue(w, value, -2, 3))


def build(d, cfg, strategy):
    from repro.core import compiler
    return compiler.build_conv(d["x"], d["w"], cfg, strategy=strategy)


def reference(d, dtype=np.int64):
    x, w = d["x"].astype(dtype), d["w"].astype(dtype)
    h, wid, _ = x.shape
    kh, kw, _, cout = w.shape
    oh, ow = h - kh + 1, wid - kw + 1
    out = np.zeros((oh, ow, cout), dtype=dtype)
    for i in range(kh):
        for j in range(kw):
            out += np.einsum("yxc,co->yxo", x[i:i + oh, j:j + ow, :],
                             w[i, j]).astype(dtype)
    return out.reshape(oh * ow, cout)
