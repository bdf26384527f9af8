"""Plain reference of the densest-subgraph answer: the P-Bahmani peel.

A straightforward numpy implementation of what ``pbahmani_np`` computes,
written from the algorithm (Bahmani, Kumar, Vassilvitskii 2012, eps = 0)
and importing nothing of the program: every pass fails each live vertex
whose degree is at most twice the live density, and the answer is the best
density over the live subgraphs, the first vertex set that reached it, and
the number of passes. Densities are compared as float32, the precision the
service states for its answers.

``control=True`` is the benchmark's control: the same peel with every
density rounded to bfloat16, the next precision below float32. A
comparison that cannot tell it from the program is too weak.
"""
from __future__ import annotations

import numpy as np


def _bf16(x: float) -> float:
    import ml_dtypes

    return float(np.asarray(x, np.float32).astype(ml_dtypes.bfloat16))


def peel(n_nodes: int, keys: np.ndarray, control: bool = False):
    """(density as float32, member mask [n_nodes], passes) of the graph
    whose undirected edges are the distinct keys ``u * n_nodes + v``."""
    rho = _bf16 if control else float
    keys = np.asarray(keys, np.int64)
    u, v = keys // n_nodes, keys % n_nodes
    s = np.concatenate([u, v])
    d = np.concatenate([v, u])
    deg = np.bincount(s, minlength=n_nodes).astype(np.int64)
    active = deg > 0
    n_v = int(active.sum())
    n_e = int(keys.size)
    best = rho(n_e / max(n_v, 1))
    best_mask = active.copy()
    passes = 0
    while n_v > 0:
        thr = 2.0 * rho(n_e / n_v)
        failed = active & (deg <= thr)
        live = active[s] & active[d]
        fs = failed[s] & live
        fd = failed[d] & live
        n_e -= int((fs | fd).sum()) // 2
        delta = np.bincount(d[fs], minlength=n_nodes)
        active &= ~failed
        deg = np.where(active, deg - delta, 0)
        n_v -= int(failed.sum())
        passes += 1
        if n_v > 0:
            r = rho(n_e / n_v)
            if r > best:
                best = r
                best_mask = active.copy()
    return np.float32(best), best_mask, passes
