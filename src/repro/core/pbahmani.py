"""P-Bahmani: parallel (2+2eps)-approximate densest subgraph (paper Alg. 1).

TPU-native formulation (DESIGN.md §2): the paper's two "parts" per pass map to

  part 1 (parallel fail-scan)   -> masked vector compare over all vertices
  part 2 (atomic degree update) -> one ``segment_sum`` over the edge list
  barrier                       -> the functional data dependence in the body

State is fixed-shape (degree array + masks + scalars), so the whole algorithm
is a single ``lax.while_loop`` — O(log_{1+eps} n) iterations of the pass body.
``pbahmani_pass`` exposes one pass for the multi-pod dry-run and the
shard_map distributed engine (core/distributed.py).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.density import peel_threshold, ratio
from repro.core.dispatch import (
    assert_exact_envelope, peel_delta,
)
from repro.graphs.graph import Graph


class PeelState(NamedTuple):
    """Carry of the peeling loop. All arrays fixed-shape.

    deg:      int32 [V]   current degree of live vertices (0 for removed)
    active:   bool  [V]   live mask (the paper's ``active`` set)
    n_v, n_e: int32 []    live vertex / undirected edge counts
    best_density: f32 []  max density over all intermediate subgraphs
    best_mask: bool [V]   vertex set achieving best_density
    passes:   int32 []    pass counter (paper: O(log_{1+eps} n))
    """

    deg: jax.Array
    active: jax.Array
    n_v: jax.Array
    n_e: jax.Array
    best_density: jax.Array
    best_mask: jax.Array
    passes: jax.Array


def init_state(src: jax.Array, dst: jax.Array, n_nodes: int, n_edges: int) -> PeelState:
    del dst
    ones = jnp.ones_like(src, dtype=jnp.int32)
    deg = jax.ops.segment_sum(ones, src, num_segments=n_nodes + 1)[:n_nodes]
    active = deg > 0  # isolated vertices never contribute to density
    n_v = jnp.sum(active.astype(jnp.int32))
    n_e = jnp.asarray(n_edges, jnp.int32)
    rho0 = ratio(n_e, n_v)
    return PeelState(
        deg=deg.astype(jnp.int32),
        active=active,
        n_v=n_v,
        n_e=n_e,
        best_density=rho0,
        best_mask=active,
        passes=jnp.asarray(0, jnp.int32),
    )


def pbahmani_pass(
    state: PeelState, src: jax.Array, dst: jax.Array, n_nodes: int,
    eps: float, kernel: bool = False,
) -> PeelState:
    """One peeling pass: fail every live vertex with deg <= 2(1+eps)·rho.

    Edge-centric (load-balanced by construction — every edge does O(1) work,
    replacing the paper's task-queue skew mitigation). ``kernel`` selects
    the Pallas segment-sum tier for the part-2 degree update
    (core/dispatch.py); results are bit-identical either way.
    """
    thr = peel_threshold(state.n_e, state.n_v, eps)
    failed = state.active & (state.deg.astype(jnp.float32) <= thr)

    src_c = jnp.minimum(src, n_nodes - 1)
    dst_c = jnp.minimum(dst, n_nodes - 1)
    valid = (src < n_nodes) & (dst < n_nodes)
    live_edge = valid & state.active[src_c] & state.active[dst_c]

    fail_s = failed[src_c] & live_edge
    fail_d = failed[dst_c] & live_edge
    # paper part 2: atomicSub on neighbor degrees -> one deterministic
    # reduction onto dst. fail_s aggregated on *dst* counts, per survivor,
    # its failed neighbors (the mirror entry of every (u failed -> v) edge
    # lands the same information symmetrically).
    delta_to_dst = peel_delta(fail_s, dst, n_nodes, kernel)

    removed_directed = jnp.sum((fail_s | fail_d).astype(jnp.int32))
    n_e_new = state.n_e - removed_directed // 2

    active_new = state.active & ~failed
    deg_new = jnp.where(active_new, state.deg - delta_to_dst, 0).astype(jnp.int32)
    n_v_new = state.n_v - jnp.sum(failed.astype(jnp.int32))

    rho_new = jnp.where(n_v_new > 0, ratio(n_e_new, n_v_new), 0.0)
    better = rho_new > state.best_density
    best_density = jnp.where(better, rho_new, state.best_density)
    best_mask = jnp.where(better, active_new, state.best_mask)

    return PeelState(
        deg=deg_new,
        active=active_new,
        n_v=n_v_new,
        n_e=n_e_new,
        best_density=best_density,
        best_mask=best_mask,
        passes=state.passes + 1,
    )


@partial(jax.jit, static_argnames=("n_nodes", "eps", "kernel"))
def _pbahmani_jit(
    src: jax.Array, dst: jax.Array, n_nodes: int, n_edges: jax.Array,
    eps: float, kernel: bool = False,
) -> PeelState:
    state = init_state(src, dst, n_nodes, n_edges)

    def cond(s: PeelState) -> jax.Array:
        return s.n_v > 0

    def body(s: PeelState) -> PeelState:
        return pbahmani_pass(s, src, dst, n_nodes, eps, kernel)

    return jax.lax.while_loop(cond, body, state)


def pbahmani(
    graph: Graph, eps: float = 0.0, pruned: bool = False,
    refine_rounds: int = 0, kernel: bool | None = None,
) -> tuple[float, np.ndarray, int]:
    """Run P-Bahmani. Returns (best_density, best_mask, passes).

    Guarantee (Bahmani et al. 2012): best_density >= rho*(G) / (2 + 2·eps).

    ``pruned=True`` routes through the candidate-pruning subsystem
    (core/prune.py): the peel continues inside a compacted pow-2 subproblem
    once the live set fits, returning the bit-identical triple at a fraction
    of the lane work (the exactness invariant proven in prune.py and
    asserted in tests/test_prune.py).

    ``refine_rounds > 0`` feeds the peel result through that many
    weighted-peel refinement rounds (repro.refine): the returned density is
    never below the peel's (exact-rational guard) and typically near-exact
    — use :func:`repro.refine.refine` directly for the duality-gap
    certificate and the anytime ``target_gap`` loop. ``passes`` then counts
    the seed peel's passes plus every refinement round's.

    ``kernel=None`` resolves to the scatter tier; ``True`` selects the
    Pallas segment-sum tier (compiled on a TPU, interpreted elsewhere) —
    the edge lanes are then fed from ``graph.dst_sorted()`` (the cached
    host-side sort) so the kernel's band-skip precondition holds without
    any in-jit argsort, and the triple is bit-identical to the scatter
    path.
    """
    if graph.n_nodes == 0:
        return 0.0, np.zeros(0, dtype=bool), 0
    kernel = bool(kernel)
    if kernel:
        assert_exact_envelope(graph.src.shape[0], graph.n_nodes)
    if pruned:
        from repro.core.prune import pbahmani_pruned

        out = pbahmani_pruned(graph, eps=eps, kernel=kernel)
    else:
        if kernel:
            src_h, dst_h = graph.dst_sorted()
            src, dst = jnp.asarray(src_h), jnp.asarray(dst_h)
        else:
            src = jnp.asarray(graph.src)
            dst = jnp.asarray(graph.dst)
        final = _pbahmani_jit(
            src, dst, graph.n_nodes, jnp.asarray(graph.n_edges, jnp.int32),
            float(eps), kernel)
        out = (
            float(final.best_density),
            np.asarray(final.best_mask),
            int(final.passes),
        )
    if refine_rounds > 0:
        from repro.refine.engine import refine

        # negative target: run exactly refine_rounds rounds (deterministic)
        res = refine(graph, target_gap=-1.0, max_rounds=int(refine_rounds),
                     eps=eps, seed=out, kernel=kernel)
        return res.density, res.mask, res.passes
    return out


# ---------------------------------------------------------------------------
# NumPy reference (bit-for-bit oracle for tests; also the fast host path)
# ---------------------------------------------------------------------------
def pbahmani_np(graph: Graph, eps: float = 0.0) -> tuple[float, np.ndarray, int]:
    n = graph.n_nodes
    s = graph.src[: graph.n_directed].astype(np.int64)
    d = graph.dst[: graph.n_directed].astype(np.int64)
    deg = np.bincount(s, minlength=n).astype(np.int64)
    active = deg > 0
    n_v = int(active.sum())
    n_e = graph.n_edges
    best = n_e / max(n_v, 1)
    best_mask = active.copy()
    passes = 0
    while n_v > 0:
        rho = n_e / n_v
        thr = 2.0 * (1.0 + eps) * rho
        failed = active & (deg <= thr)
        live = active[s] & active[d]
        fs = failed[s] & live
        fd = failed[d] & live
        n_e -= int((fs | fd).sum()) // 2
        delta = np.bincount(d[fs], minlength=n)
        active &= ~failed
        deg = np.where(active, deg - delta, 0)
        n_v -= int(failed.sum())
        passes += 1
        if n_v > 0:
            rho_new = n_e / n_v
            if rho_new > best:
                best = rho_new
                best_mask = active.copy()
    return float(best), best_mask, passes


__all__ = ["PeelState", "init_state", "pbahmani_pass", "pbahmani", "pbahmani_np"]
