"""Single-source shortest paths from vertex 0 of a small-world graph with
integer edge weights."""
import heapq

import numpy as np

from chipbench.gen.graphs import small_world_graph

UNREACHED = 0x7FFF   # the fabric's 16-bit "infinite distance" word


def generate(p, shape, value):
    """The weights decide which edges relax, so they are drawn with the
    graph, as the lane's shape."""
    rp, col = small_world_graph(p["nv"], p["k"], shape)
    wgt = shape.integers(1, p["max_weight"] + 1, size=col.shape)
    return dict(rowptr=rp, col=col, wgt=wgt.astype(np.int64))


def build(d, cfg, strategy):
    from repro.core import compiler
    return compiler.build_sssp(d["rowptr"], d["col"], d["wgt"], 0, cfg,
                               strategy=strategy)


def reference(d, dtype=np.int64):
    """Dijkstra, with distances held in ``dtype`` words."""
    rp, col, wgt = d["rowptr"], d["col"], d["wgt"].astype(dtype)
    unset = np.array(UNREACHED).astype(dtype)   # wraps in narrow words
    dist = np.full(rp.shape[0] - 1, unset, dtype=dtype)
    dist[0] = 0
    heap = [(0, 0)]
    while heap:
        du, u = heapq.heappop(heap)
        if du > dist[u]:
            continue
        for e in range(rp[u], rp[u + 1]):
            w, nd = int(col[e]), dist[u] + wgt[e]
            if nd < dist[w]:
                dist[w] = nd
                heapq.heappush(heap, (int(nd), w))
    return dist
