"""BFS levels from vertex 0 of a small-world graph."""
from collections import deque

import numpy as np

from chipbench.gen.graphs import small_world_graph

UNREACHED = 0x7FFF   # the fabric's 16-bit "no level" word


def generate(p, shape, value):
    """The graph is the lane's shape, and BFS has no values."""
    rp, col = small_world_graph(p["nv"], p["k"], shape)
    return dict(rowptr=rp, col=col)


def build(d, cfg, strategy):
    from repro.core import compiler
    return compiler.build_bfs(d["rowptr"], d["col"], 0, cfg,
                              strategy=strategy)


def reference(d, dtype=np.int64):
    rp, col = d["rowptr"], d["col"]
    unset = np.array(UNREACHED).astype(dtype)   # wraps in narrow words
    level = np.full(rp.shape[0] - 1, unset, dtype=dtype)
    level[0] = 0
    todo = deque([0])
    while todo:
        u = todo.popleft()
        for w in col[rp[u]:rp[u + 1]]:
            if level[w] == unset:
                level[w] = level[u] + dtype(1)
                todo.append(int(w))
    return level
