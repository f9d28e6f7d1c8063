"""Graph generators (paper §4.2: small-world surrogates for infect-dublin),
seeded by the caller.  Copied from the repository's benchmark generators
so that the benchmark's traffic cannot change when those are edited."""
from __future__ import annotations

import numpy as np


def small_world_graph(nv: int, k: int, rng: np.random.Generator,
                      p: float = 0.3) -> tuple[np.ndarray, np.ndarray]:
    """Connected Watts-Strogatz graph as CSR ``(rowptr, col)``, both int64,
    neighbours sorted.  The graph's own seed is drawn from ``rng``."""
    import networkx as nx
    g = nx.connected_watts_strogatz_graph(
        nv, k, p, seed=int(rng.integers(2 ** 31)))
    rp = np.zeros((nv + 1,), dtype=np.int64)
    cols: list[int] = []
    for v in range(nv):
        nbrs = sorted(g.neighbors(v))
        rp[v + 1] = rp[v] + len(nbrs)
        cols.extend(nbrs)
    return rp, np.array(cols, dtype=np.int64)
