"""CBDS-P: Core-Based Dense Subgraph, parallel (paper Algorithm 2).

Phase 1: k-core decomposition with per-level density tracking (kcore.py)
         -> densest core S* = {v : coreness >= k*}, a 2-approximation.
Phase 2: batch-augment S* with "legitimate" outside vertices. A vertex v with
         e(v -> S~) > rho(S~) strictly increases the density when added
         (paper §3.2: delta rho = (n·e~ − e)/(n(n+1)) > 0). The paper selects,
         in parallel, all v with e(v -> S*) > max_density, then adds the edges
         among the selected set itself (the pairwise loop, lines 76-87), and
         reports the improved density — guaranteed >= rho(S*), hence strictly
         better than the plain 2-approximation whenever any vertex qualifies.

TPU adaptation: the paper's per-thread ``eligible_vector``/``legit_vector`` +
critical sections become two segment-reductions over the edge list:
  e_into_S[v]   = sum over edges (v,u) of S_mask[u]        (one segment_sum)
  cross(L)      = sum over edges of L[src] & L[dst] / 2    (one masked sum)
Self-edges are absent by the simple-graph convention (DESIGN.md §1); the
paper's 0.5 self-edge counting is therefore a no-op here.

Beyond-paper extension: ``rounds > 1`` iterates phase 2 — after absorbing the
legit set, recompute e(v -> S~) against the enlarged S~ and absorb again.
Each round is monotone non-decreasing in density, so the result remains a
valid (and usually strictly better) lower bound for rho*. The paper runs one
round; rounds=1 is the faithful setting and the default.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.density import ratio
from repro.core.kcore import _kcore_jit, kcore_np
from repro.graphs.graph import Graph


class CBDSResult(NamedTuple):
    density: jax.Array       # f32 [] final max_density
    core_density: jax.Array  # f32 [] densest-core density (phase-1 2-approx)
    k_star: jax.Array        # int32 [] max_density_core
    member_mask: jax.Array   # bool [V] final approximate densest subgraph
    n_legit: jax.Array       # int32 [] vertices absorbed by phase 2


# repro: proof
def _augment_once(
    member: jax.Array,
    m_v: jax.Array,
    m_e: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    n_nodes: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One phase-2 round. Returns (member', m_v', m_e', n_added).

    The legitimacy test ``e_into > rho`` is evaluated in exact integer
    arithmetic: for integer e_into, ``e_into > m_e / m_v`` iff
    ``e_into > m_e // m_v``. The float32 rho used previously could round
    across an integer boundary once m_v grows past ~2^23, silently
    absorbing (or rejecting) boundary vertices differently from the
    float64 NumPy reference — pinned by the rounds=3 regression test.
    """
    src_c = jnp.minimum(src, n_nodes - 1)
    dst_c = jnp.minimum(dst, n_nodes - 1)
    valid = (src < n_nodes) & (dst < n_nodes)

    # e_into_S[v]: edges from v into the current member set (paper's `legits`)
    into = valid & member[dst_c] & ~member[src_c]
    e_into = jax.ops.segment_sum(
        into.astype(jnp.int32), jnp.minimum(src, n_nodes), num_segments=n_nodes + 1
    )[:n_nodes]

    legit = ~member & (e_into > m_e // jnp.maximum(m_v, 1))
    n_added = jnp.sum(legit.astype(jnp.int32))

    # intermediate_edges = edges(legit -> S) + edges within the legit set
    inter_into = jnp.sum(jnp.where(legit, e_into, 0))
    legit_pair = valid & legit[src_c] & legit[dst_c]
    inter_cross = jnp.sum(legit_pair.astype(jnp.int32)) // 2

    member_new = member | legit
    m_e_new = m_e + inter_into + inter_cross
    m_v_new = m_v + n_added
    return member_new, m_v_new, m_e_new, n_added


@partial(jax.jit, static_argnames=("n_nodes", "rounds"))
def _cbds_jit(
    src: jax.Array,
    dst: jax.Array,
    n_nodes: int,
    n_edges: jax.Array,
    rounds: int,
) -> CBDSResult:
    core = _kcore_jit(src, dst, n_nodes, n_edges)
    k_star = core.best_k
    member = core.coreness >= k_star
    m_v = core.best_n_v
    m_e = core.best_n_e
    core_density = core.best_density

    n_legit_total = jnp.asarray(0, jnp.int32)
    for _ in range(rounds):  # static unroll; rounds is small (default 1)
        member, m_v, m_e, n_added = _augment_once(member, m_v, m_e, src, dst, n_nodes)
        n_legit_total = n_legit_total + n_added

    density = ratio(m_e, m_v)
    density = jnp.maximum(density, core_density)
    return CBDSResult(
        density=density,
        core_density=core_density,
        k_star=k_star,
        member_mask=member,
        n_legit=n_legit_total,
    )


def cbds_p(graph: Graph, rounds: int = 1) -> dict:
    """Run CBDS-P. rounds=1 is the paper-faithful configuration."""
    res = _cbds_jit(
        jnp.asarray(graph.src), jnp.asarray(graph.dst), graph.n_nodes,
        jnp.asarray(graph.n_edges, jnp.int32), int(rounds),
    )
    return {
        "density": float(res.density),
        "core_density": float(res.core_density),
        "k_star": int(res.k_star),
        "member_mask": np.asarray(res.member_mask),
        "n_legit": int(res.n_legit),
    }


# ---------------------------------------------------------------------------
# NumPy reference
# ---------------------------------------------------------------------------
def cbds_np(graph: Graph, rounds: int = 1) -> dict:
    coreness, core_density, k_star, m_v, m_e = kcore_np(graph)
    n = graph.n_nodes
    s = graph.src[: graph.n_directed].astype(np.int64)
    d = graph.dst[: graph.n_directed].astype(np.int64)
    member = coreness >= k_star
    n_legit = 0
    for _ in range(rounds):
        # exact integer form of e_into > m_e/m_v (see _augment_once)
        into = member[d] & ~member[s]
        e_into = np.bincount(s[into], minlength=n)
        legit = ~member & (e_into > m_e // max(m_v, 1))
        if not legit.any():
            break
        inter = int(e_into[legit].sum()) + int((legit[s] & legit[d]).sum()) // 2
        m_e += inter
        m_v += int(legit.sum())
        member |= legit
        n_legit += int(legit.sum())
    density = max(m_e / max(m_v, 1), core_density)
    return {
        "density": float(density),
        "core_density": float(core_density),
        "k_star": int(k_star),
        "member_mask": member,
        "n_legit": n_legit,
    }


__all__ = ["CBDSResult", "cbds_p", "cbds_np"]
