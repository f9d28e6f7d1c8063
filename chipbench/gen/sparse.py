"""Sparse operand generators (paper §4.2 surrogates), seeded by the caller.

Copied from the repository's benchmark generators so that the benchmark's
traffic cannot change when those are edited.  The paper evaluates
pruned-ResNet-50 matrices and a ViTCoD sparse-attention mask; offline,
power-law row and column skew stands in for magnitude pruning, and a
diagonal band plus global tokens for the attention mask.
"""
from __future__ import annotations

import numpy as np


def powerlaw_sparse(m: int, n: int, rng: np.random.Generator,
                    density: float, alpha: float = 1.8,
                    col_alpha: float = 1.2) -> np.ndarray:
    """(m, n) int64 matrix with values in 1..3 at about ``density``:
    power-law row lengths and column popularity (hot rows, hot columns)."""
    target = int(round(m * n * density))
    raw = rng.pareto(alpha, size=m) + 1
    lens = np.maximum(1, (raw / raw.sum() * target).astype(int))
    lens = np.minimum(lens, n)
    colw = rng.pareto(col_alpha, size=n) + 1
    colp = colw / colw.sum()
    a = np.zeros((m, n), dtype=np.int64)
    for i in range(m):
        cols = rng.choice(n, size=lens[i], replace=False, p=colp)
        a[i, cols] = rng.integers(1, 4, size=lens[i])
    return a


def attention_mask(s: int, rng: np.random.Generator,
                   density: float) -> np.ndarray:
    """(s, s) 0/1 mask: a causal diagonal band plus random global tokens."""
    m = np.zeros((s, s), dtype=np.int64)
    band = max(1, int(s * density * 0.5))
    for i in range(s):
        m[i, max(0, i - band):i + 1] = 1
    n_glob = max(1, int(s * density * 0.3))
    m[:, rng.choice(s, size=n_glob, replace=False)] = 1
    return m


def dense_ints(shape, rng: np.random.Generator, lo: int = -3,
               hi: int = 4) -> np.ndarray:
    """Dense int64 operand with values in ``[lo, hi)``."""
    return rng.integers(lo, hi, size=shape).astype(np.int64)


def revalue(a: np.ndarray, rng: np.random.Generator, lo: int,
            hi: int) -> np.ndarray:
    """``a`` with every nonzero entry drawn anew from the nonzero integers
    of ``[lo, hi)``.  The zero pattern, which is what the compiler lays
    out and the fabric works through, stays as it is."""
    vals = np.array([v for v in range(lo, hi) if v != 0], dtype=np.int64)
    out = np.array(a, dtype=np.int64)
    nz = out != 0
    out[nz] = rng.choice(vals, size=int(nz.sum()))
    return out
