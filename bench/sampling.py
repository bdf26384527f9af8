"""Seeded edge samplers for the traffic generators.

Copies, not imports, of the program's generators, so that a later change
to the program cannot move the yardstick: the Kronecker sampler follows
``repro.graphs.generators.rmat`` (Graph500 initiator A/B/C, one quadrant per
bit) and adds Graph500's vertex-label permutation. Edges are undirected
keys ``u * n + v`` with ``u < v``; self-loops are not edges.
"""
from __future__ import annotations

import numpy as np


def kronecker_keys(rng: np.random.Generator, scale: int, m: int,
                   a: float, b: float, c: float) -> np.ndarray:
    """``m`` i.i.d. Kronecker edge draws (self-loops redrawn), in arrival
    order, as undirected keys; repeats are kept, a stream may resend an
    edge. Each bit picks one initiator quadrant: (0,0) with probability a,
    (0,1) b, (1,0) c, (1,1) the rest. Vertex labels then go through one
    seeded random permutation, as Graph500's generator does, so the hubs
    are spread over the id range rather than packed at the low ids."""
    if not 0 < scale <= 30:
        raise ValueError(f"scale {scale} outside 1..30")
    n = 1 << scale
    label = rng.permutation(n).astype(np.int32)
    a32, ab, abc = np.float32(a), np.float32(a + b), np.float32(a + b + c)
    out = np.empty(0, np.int64)
    while out.size < m:
        k = int((m - out.size) * 1.05) + 64
        src = np.zeros(k, np.int32)  # scale <= 30
        dst = np.zeros(k, np.int32)
        for _ in range(scale):
            r = rng.random(k, dtype=np.float32)
            sbit = r >= ab
            dbit = (r >= a32) != sbit
            dbit ^= r >= abc
            src <<= 1
            src |= sbit
            dst <<= 1
            dst |= dbit
        keep = src != dst
        src, dst = label[src[keep]], label[dst[keep]]
        lo = np.minimum(src, dst).astype(np.int64)
        hi = np.maximum(src, dst)
        out = np.concatenate([out, lo * n + hi])
    return out[:m]


def pairs(keys: np.ndarray, n: int) -> np.ndarray:
    keys = np.asarray(keys, np.int64)
    return np.stack([keys // n, keys % n], axis=1)
