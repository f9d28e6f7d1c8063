"""One integer PageRank push step: every vertex sends rank // out-degree
to each out-neighbour, and each vertex's answer is the sum it receives."""
import numpy as np

from chipbench.gen.graphs import small_world_graph


def generate(p, shape, value):
    """The graph is the lane's shape; every vertex starts at ``rank``."""
    rp, col = small_world_graph(p["nv"], p["k"], shape)
    rank = np.full(p["nv"], p["rank"], dtype=np.int64)
    return dict(rowptr=rp, col=col, rank=rank)


def build(d, cfg, strategy):
    from repro.core import compiler
    return compiler.build_pagerank(d["rowptr"], d["col"], d["rank"], cfg,
                                   strategy=strategy)


def reference(d, dtype=np.int64):
    rp, col = d["rowptr"], d["col"]
    deg = np.diff(rp)
    share = np.zeros(deg.shape, dtype=dtype)
    has = deg > 0
    share[has] = d["rank"].astype(dtype)[has] // deg[has].astype(dtype)
    acc = np.zeros(deg.shape, dtype=dtype)
    np.add.at(acc, col, np.repeat(share, deg))
    return acc
