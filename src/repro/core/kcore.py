"""k-core decomposition (CBDS-P phase 1), adapted from PKC (Kabir & Madduri).

PKC processes levels k = 0, 1, 2, ... with per-thread work queues (``buff``)
and atomic degree decrements. The TPU-native version (DESIGN.md §2) replaces
the queues with a *level-synchronous fixpoint*: at level k, repeatedly fail
every live vertex with deg <= k and subtract its edge contributions via
``segment_sum``, until no vertex fails; then k += 1. k-core decomposition is
confluent, so this computes identical coreness values.

Following the paper's modification of PKC, the sweep also records, for every
k, the density of the (k+1)-core that remains once level k completes — the
argmax over k is the densest core (phase 2's starting point; a 2-approximation
to the densest subgraph by Tatti 2019 + monotonicity).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.density import ratio
from repro.core.dispatch import (
    assert_exact_envelope, peel_delta,
)
from repro.graphs.graph import Graph


class CoreState(NamedTuple):
    k: jax.Array             # int32 [] current level
    deg: jax.Array           # int32 [V]
    active: jax.Array        # bool  [V]
    coreness: jax.Array      # int32 [V]
    n_v: jax.Array           # int32 [] live vertices
    n_e: jax.Array           # int32 [] live undirected edges
    best_density: jax.Array  # f32   [] densest core seen
    best_k: jax.Array        # int32 [] its core index k*
    best_n_v: jax.Array      # int32 [] |S*| (m_v in the paper)
    best_n_e: jax.Array      # int32 [] |E(S*)| (m_e in the paper)


def _level_fixpoint(
    state: CoreState, src: jax.Array, dst: jax.Array, n_nodes: int,
    kernel: bool = False,
) -> CoreState:
    """Remove all vertices of degree <= k until none remain (inner while).
    ``kernel`` routes the degree decrement through the Pallas segment-sum
    tier (core/dispatch.py) — bit-identical coreness either way."""

    def cond(s: CoreState) -> jax.Array:
        return jnp.any(s.active & (s.deg <= s.k))

    def body(s: CoreState) -> CoreState:
        failed = s.active & (s.deg <= s.k)
        src_c = jnp.minimum(src, n_nodes - 1)
        dst_c = jnp.minimum(dst, n_nodes - 1)
        valid = (src < n_nodes) & (dst < n_nodes)
        live_edge = valid & s.active[src_c] & s.active[dst_c]
        fail_s = failed[src_c] & live_edge
        fail_d = failed[dst_c] & live_edge
        removed_directed = jnp.sum((fail_s | fail_d).astype(jnp.int32))
        delta_to_dst = peel_delta(fail_s, dst, n_nodes, kernel)
        active_new = s.active & ~failed
        return s._replace(
            deg=jnp.where(active_new, s.deg - delta_to_dst, 0).astype(jnp.int32),
            active=active_new,
            coreness=jnp.where(failed, s.k, s.coreness).astype(jnp.int32),
            n_v=s.n_v - jnp.sum(failed.astype(jnp.int32)),
            n_e=s.n_e - removed_directed // 2,
        )

    return jax.lax.while_loop(cond, body, state)


# repro: unaudited -- static one-shot analysis entry point; dispatched outside audited engine ops, so it is deliberately absent from compile_count()
@partial(jax.jit, static_argnames=("n_nodes", "kernel"))
def _kcore_jit(
    src: jax.Array, dst: jax.Array, n_nodes: int, n_edges: jax.Array,
    kernel: bool = False,
) -> CoreState:
    ones = jnp.ones_like(src, dtype=jnp.int32)
    deg = jax.ops.segment_sum(ones, src, num_segments=n_nodes + 1)[:n_nodes].astype(jnp.int32)
    state = CoreState(
        k=jnp.asarray(0, jnp.int32),
        deg=deg,
        active=jnp.ones(n_nodes, dtype=bool),
        coreness=jnp.zeros(n_nodes, dtype=jnp.int32),
        n_v=jnp.asarray(n_nodes, jnp.int32),
        n_e=n_edges.astype(jnp.int32),
        best_density=jnp.asarray(0.0, jnp.float32),
        best_k=jnp.asarray(0, jnp.int32),
        best_n_v=jnp.asarray(0, jnp.int32),
        best_n_e=jnp.asarray(0, jnp.int32),
    )

    def cond(s: CoreState) -> jax.Array:
        return s.n_v > 0

    def body(s: CoreState) -> CoreState:
        # graph remaining on *entry* to level k is the k-core; record its
        # density (paper Alg. 2, the `single` block after each level).
        density = ratio(s.n_e, s.n_v)
        better = (density > s.best_density) & (s.n_v > 0)
        s = s._replace(
            best_density=jnp.where(better, density, s.best_density),
            best_k=jnp.where(better, s.k, s.best_k),
            best_n_v=jnp.where(better, s.n_v, s.best_n_v),
            best_n_e=jnp.where(better, s.n_e, s.best_n_e),
        )
        s = _level_fixpoint(s, src, dst, n_nodes, kernel)
        return s._replace(k=s.k + 1)

    return jax.lax.while_loop(cond, body, state)


def kcore_decompose(
    graph: Graph, kernel: bool | None = None,
) -> tuple[np.ndarray, float, int, int, int]:
    """Returns (coreness [V], best_core_density, k*, m_v, m_e).

    The densest core is {v : coreness[v] >= k*}; its density is a
    2-approximation of rho* (lower-bounded by the largest core's density).
    ``kernel`` selects the Pallas segment-sum tier (None = deploy default);
    kernel mode feeds the cached dst-sorted view so the band-skip
    precondition holds — identical outputs either way.
    """
    kernel = bool(kernel)
    if kernel:
        assert_exact_envelope(graph.src.shape[0], graph.n_nodes)
        src_h, dst_h = graph.dst_sorted()
    else:
        src_h, dst_h = graph.src, graph.dst
    final = _kcore_jit(
        jnp.asarray(src_h), jnp.asarray(dst_h), graph.n_nodes,
        jnp.asarray(graph.n_edges, jnp.int32), kernel,
    )
    return (
        np.asarray(final.coreness),
        float(final.best_density),
        int(final.best_k),
        int(final.best_n_v),
        int(final.best_n_e),
    )


# ---------------------------------------------------------------------------
# NumPy reference (oracle vs networkx.core_number in tests)
# ---------------------------------------------------------------------------
def kcore_np(graph: Graph) -> tuple[np.ndarray, float, int, int, int]:
    n = graph.n_nodes
    s = graph.src[: graph.n_directed].astype(np.int64)
    d = graph.dst[: graph.n_directed].astype(np.int64)
    deg = np.bincount(s, minlength=n).astype(np.int64)
    active = np.ones(n, dtype=bool)
    coreness = np.zeros(n, dtype=np.int64)
    n_v, n_e = n, graph.n_edges
    best_density, best_k, best_nv, best_ne = 0.0, 0, 0, 0
    k = 0
    while n_v > 0:
        if n_v > 0:
            density = n_e / n_v
            if density > best_density:
                best_density, best_k, best_nv, best_ne = density, k, n_v, n_e
        while True:
            failed = active & (deg <= k)
            if not failed.any():
                break
            live = active[s] & active[d]
            fs = failed[s] & live
            fd = failed[d] & live
            n_e -= int((fs | fd).sum()) // 2
            delta = np.bincount(d[fs], minlength=n)
            active &= ~failed
            deg = np.where(active, deg - delta, 0)
            coreness[failed] = k
            n_v -= int(failed.sum())
        k += 1
    return coreness.astype(np.int32), float(best_density), best_k, best_nv, best_ne


__all__ = ["CoreState", "kcore_decompose", "kcore_np"]
