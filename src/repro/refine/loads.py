"""Edge-load state for iterated weighted peeling (Greedy++ / Frank-Wolfe).

The eps-approximate peel (core/pbahmani.py) stops at a 2(1+eps) guarantee;
the paper's second contribution — "better results than a 2-approximation" —
is the gap this module closes. One *refinement round* is a full peel of the
graph with the key

    key(v) = load(v) + deg(v)

instead of deg(v): the iterated-greedy scheme of Greedy++ (Boob et al.),
whose parallel threshold-batched form Sukprasert et al. (arXiv:2311.04333)
show converges to near-exact density, and which the unified analysis of the
load-balancing LP (Harb et al. / arXiv:2406.04738 framing) interprets as
Frank-Wolfe with uniform averaging: each round produces an *orientation*
(every live edge charged to exactly one endpoint) and ``loads / T`` after T
rounds is the running average of T feasible LP points.

Load accounting (the invariant everything else rests on)
--------------------------------------------------------
When a batch F of vertices fails in one pass, every live edge with >= 1
endpoint in F dies and is charged to exactly one endpoint:

  * one endpoint in F          -> charged to that endpoint;
  * both endpoints in F        -> charged to the smaller vertex id
    (equivalent to removing F sequentially in ascending-id order, so every
    round is a legitimate sequential greedy trajectory).

Hence after T rounds ``sum(loads) == T * |E|`` and ``loads / T`` is a
feasible fractional edge-assignment: for the optimum S*, every edge inside
S* charges a vertex of S*, so

    max_v loads(v) / T  >=  |E(S*)| / |S*|  =  rho*(G)

— the LP-duality upper bound certify.py turns into an anytime certificate.
All state is int32 (loads are counts), so every round is exact integer
arithmetic: the vmapped multi-tenant variants below are bit-identical to
the single-tenant recurrence lane for lane, and the dense (GEMV) variant is
bit-identical to the COO variant because every float32 sum is over integers
< 2^24 (the repo-wide exactness argument of stream/fused.py).

Threshold: ``(1+eps) * (sum_live loads + 2|E_live|) / |V_live|`` — the
average key, degenerating to Bahmani's ``2(1+eps)rho`` at loads == 0 (round
1 with zero loads IS the standard peel). At least the min-key vertex always
passes the threshold mathematically; the explicit ``key <= min_key`` guard
makes termination robust to float32 rounding of billion-scale load sums.
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.density import ratio
from repro.core.dispatch import peel_delta
from repro.core.distributed import SHARDED_JITS


class RefinePeelState(NamedTuple):
    """Carry of one weighted-peel round. All arrays fixed-shape.

    deg:      int32 [V]  live degree (0 once removed)
    loads:    int32 [V]  accumulated edge loads (across rounds + this round)
    active:   bool  [V]  live mask
    n_v, n_e: int32 []   live vertex / undirected edge counts
    load_sum: int32 []   sum of loads over live vertices
    best_density: f32 [] best density seen (f32, same precision model as
                         the eps-peel; the exact fraction is best_ne/best_nv)
    best_ne, best_nv: int32 []  integer counts of the best subgraph — the
                         primal side of the exact-rational certificate
    best_mask: bool [V]  vertex set achieving the best density
    passes:   int32 []   cumulative pass counter (across rounds)
    """

    deg: jax.Array
    loads: jax.Array
    active: jax.Array
    n_v: jax.Array
    n_e: jax.Array
    load_sum: jax.Array
    best_density: jax.Array
    best_ne: jax.Array
    best_nv: jax.Array
    best_mask: jax.Array
    passes: jax.Array


def refine_threshold(load_sum: jax.Array, n_e: jax.Array, n_v: jax.Array,
                     eps: float) -> jax.Array:
    """(1+eps) * average key over live vertices, float32. Shared verbatim by
    the COO and dense pass bodies so their trajectories stay bit-identical."""
    return (1.0 + eps) * ratio(load_sum + 2 * n_e, n_v)


def _fold_best(state: RefinePeelState, n_e_new, n_v_new, active_new):
    """Strict-> best tracking off the new live set (f32 compare, exact ints
    carried alongside for the certificate)."""
    rho_new = jnp.where(n_v_new > 0, ratio(n_e_new, n_v_new), 0.0)
    better = rho_new > state.best_density
    return (
        jnp.where(better, rho_new, state.best_density),
        jnp.where(better, n_e_new, state.best_ne),
        jnp.where(better, n_v_new, state.best_nv),
        jnp.where(better, active_new, state.best_mask),
    )


def refine_pass(
    state: RefinePeelState, src: jax.Array, dst: jax.Array, n_nodes: int,
    eps: float, kernel: bool = False,
) -> RefinePeelState:
    """One weighted peeling pass over the symmetric COO arrays: fail every
    live vertex with load+deg <= threshold (or achieving the live minimum),
    charge each dying edge to exactly one failing endpoint (smaller id wins
    a tie), and decrement survivor degrees — ``pbahmani_pass`` plus loads.
    ``kernel`` routes both reductions through the Pallas segment-sum tier
    (core/dispatch.py); the trajectory is bit-identical either way."""
    key = (state.loads + state.deg).astype(jnp.float32)
    thr = refine_threshold(state.load_sum, state.n_e, state.n_v, eps)
    min_key = jnp.min(jnp.where(state.active, key, jnp.inf))
    failed = state.active & ((key <= thr) | (key <= min_key))

    src_c = jnp.minimum(src, n_nodes - 1)
    dst_c = jnp.minimum(dst, n_nodes - 1)
    valid = (src < n_nodes) & (dst < n_nodes)
    live_edge = valid & state.active[src_c] & state.active[dst_c]
    fail_s = failed[src_c] & live_edge
    fail_d = failed[dst_c] & live_edge

    # survivor degree decrement: mirror-entry aggregation as in pbahmani_pass
    # repro: allow RPR304 -- traced body; 2^24 envelope asserted by the host callers (refine.engine.refine, stream.delta)
    delta_to_dst = peel_delta(fail_s, dst, n_nodes, kernel)
    # edge charging: (u->v) charges u iff u failed and (v survived or u<v);
    # exactly one of the two directed entries charges, so each undirected
    # edge is counted once. Aggregated on *dst* via the mirror identity
    # (lane (v->u) has fail_s'=fail_d, fail_d'=fail_s, src_c'=dst_c, so its
    # src-side charge is exactly this lane's assign_d) — both reductions
    # then run over the dst-sorted layout the kernel tier needs, and the
    # integer result is identical to the historical src-side aggregation.
    assign_d = fail_d & (~fail_s | (dst_c < src_c))
    # repro: allow RPR304 -- traced body; envelope asserted by host callers
    inc = peel_delta(assign_d, dst, n_nodes, kernel)

    removed_directed = jnp.sum((fail_s | fail_d).astype(jnp.int32))
    n_e_new = state.n_e - removed_directed // 2
    active_new = state.active & ~failed
    deg_new = jnp.where(active_new, state.deg - delta_to_dst, 0).astype(
        jnp.int32)
    n_v_new = state.n_v - jnp.sum(failed.astype(jnp.int32))
    loads_new = (state.loads + inc).astype(jnp.int32)
    load_sum_new = state.load_sum - jnp.sum(
        jnp.where(failed, state.loads, 0))

    best_density, best_ne, best_nv, best_mask = _fold_best(
        state, n_e_new, n_v_new, active_new)
    return RefinePeelState(
        deg=deg_new, loads=loads_new, active=active_new, n_v=n_v_new,
        n_e=n_e_new, load_sum=load_sum_new, best_density=best_density,
        best_ne=best_ne, best_nv=best_nv, best_mask=best_mask,
        passes=state.passes + 1,
    )


def refine_round_body(
    src, dst, deg, n_edges, loads, best_density, best_ne, best_nv,
    best_mask, passes, n_nodes: int, eps: float, kernel: bool = False,
):
    """One full refinement round from the maintained degree array. Returns
    (loads, best_density, best_ne, best_nv, best_mask, passes); the host
    turns ``loads`` into the top-k0 dual bound (certify.dual_fraction)."""
    active = deg > 0
    n_v = jnp.sum(active.astype(jnp.int32))
    state = RefinePeelState(
        deg=deg.astype(jnp.int32),
        loads=loads.astype(jnp.int32),
        active=active,
        n_v=n_v,
        n_e=n_edges.astype(jnp.int32),
        load_sum=jnp.sum(jnp.where(active, loads, 0)).astype(jnp.int32),
        best_density=best_density.astype(jnp.float32),
        best_ne=best_ne.astype(jnp.int32),
        best_nv=best_nv.astype(jnp.int32),
        best_mask=best_mask,
        passes=passes.astype(jnp.int32),
    )
    final = jax.lax.while_loop(
        lambda s: s.n_v > 0,
        lambda s: refine_pass(s, src, dst, n_nodes, eps, kernel),
        state,
    )
    return (final.loads, final.best_density, final.best_ne, final.best_nv,
            final.best_mask, final.passes)


@partial(jax.jit, static_argnames=("n_nodes", "eps", "kernel"))
def _refine_round_jit(src, dst, deg, n_edges, loads, best_density, best_ne,
                      best_nv, best_mask, passes, n_nodes: int, eps: float,
                      kernel: bool = False):
    return refine_round_body(src, dst, deg, n_edges, loads, best_density,
                             best_ne, best_nv, best_mask, passes, n_nodes,
                             eps, kernel)


@partial(jax.jit, static_argnames=("n_nodes", "eps", "kernel"))
def _batched_refine_round_jit(src, dst, deg, n_edges, loads, best_density,
                              best_ne, best_nv, best_mask, passes,
                              n_nodes: int, eps: float,
                              kernel: bool = False):
    """Fused multi-tenant refinement round: vmap of ``refine_round_body``
    over a leading tenant axis. The batched ``while_loop`` freezes converged
    lanes through ``select`` (a lane with n_v == 0 is an exact no-op pass),
    and every op is per-lane exact int32, so each lane's outputs are
    bit-identical to ``_refine_round_jit`` on its row (the Pallas tier vmaps
    cleanly — ``kernel=True`` batches the one-hot segsum per lane)."""
    return jax.vmap(
        lambda s, d, g, ne, lo, bd, be, bv, bm, p: refine_round_body(
            s, d, g, ne, lo, bd, be, bv, bm, p, n_nodes, eps, kernel)
    )(src, dst, deg, n_edges, loads, best_density, best_ne, best_nv,
      best_mask, passes)


# ---------------------------------------------------------------------------
# sharded variant — refinement rounds over mesh-partitioned edge lanes
# ---------------------------------------------------------------------------
def _sharded_refine_pass(state: RefinePeelState, src_l, dst_l, n_nodes: int,
                         eps: float, axes) -> RefinePeelState:
    """``refine_pass`` as seen by one shard: both ``peel_delta`` reductions
    become per-shard segment-sums followed by one psum each (exact int32 —
    the mirror-identity charging argument is order-invariant, so the
    trajectory is bit-identical to the single-device pass), and the
    removed-edge count is psum'd the same way. vmappable over a leading
    tenant axis inside a shard_map body, like ``_peel_pass_body``."""
    key = (state.loads + state.deg).astype(jnp.float32)
    thr = refine_threshold(state.load_sum, state.n_e, state.n_v, eps)
    min_key = jnp.min(jnp.where(state.active, key, jnp.inf))
    failed = state.active & ((key <= thr) | (key <= min_key))

    src_c = jnp.minimum(src_l, n_nodes - 1)
    dst_c = jnp.minimum(dst_l, n_nodes - 1)
    valid = (src_l < n_nodes) & (dst_l < n_nodes)
    live_edge = valid & state.active[src_c] & state.active[dst_c]
    fail_s = failed[src_c] & live_edge
    fail_d = failed[dst_c] & live_edge

    delta_to_dst = jax.lax.psum(jax.ops.segment_sum(
        fail_s.astype(jnp.int32), jnp.minimum(dst_l, n_nodes),
        num_segments=n_nodes + 1)[:n_nodes], axes)
    assign_d = fail_d & (~fail_s | (dst_c < src_c))
    inc = jax.lax.psum(jax.ops.segment_sum(
        assign_d.astype(jnp.int32), jnp.minimum(dst_l, n_nodes),
        num_segments=n_nodes + 1)[:n_nodes], axes)

    removed_directed = jax.lax.psum(
        jnp.sum((fail_s | fail_d).astype(jnp.int32)), axes)
    n_e_new = state.n_e - removed_directed // 2
    active_new = state.active & ~failed
    deg_new = jnp.where(active_new, state.deg - delta_to_dst, 0).astype(
        jnp.int32)
    n_v_new = state.n_v - jnp.sum(failed.astype(jnp.int32))
    loads_new = (state.loads + inc).astype(jnp.int32)
    load_sum_new = state.load_sum - jnp.sum(
        jnp.where(failed, state.loads, 0))

    best_density, best_ne, best_nv, best_mask = _fold_best(
        state, n_e_new, n_v_new, active_new)
    return RefinePeelState(
        deg=deg_new, loads=loads_new, active=active_new, n_v=n_v_new,
        n_e=n_e_new, load_sum=load_sum_new, best_density=best_density,
        best_ne=best_ne, best_nv=best_nv, best_mask=best_mask,
        passes=state.passes + 1,
    )


def _sharded_refine_round_body(src_l, dst_l, deg, n_edges, loads,
                               best_density, best_ne, best_nv, best_mask,
                               passes, n_nodes: int, eps: float, axes):
    """Per-shard ``refine_round_body``: same init from the maintained degree
    array, while_loop of the sharded pass."""
    active = deg > 0
    n_v = jnp.sum(active.astype(jnp.int32))
    state = RefinePeelState(
        deg=deg.astype(jnp.int32),
        loads=loads.astype(jnp.int32),
        active=active,
        n_v=n_v,
        n_e=n_edges.astype(jnp.int32),
        load_sum=jnp.sum(jnp.where(active, loads, 0)).astype(jnp.int32),
        best_density=best_density.astype(jnp.float32),
        best_ne=best_ne.astype(jnp.int32),
        best_nv=best_nv.astype(jnp.int32),
        best_mask=best_mask,
        passes=passes.astype(jnp.int32),
    )
    final = jax.lax.while_loop(
        lambda s: s.n_v > 0,
        lambda s: _sharded_refine_pass(s, src_l, dst_l, n_nodes, eps, axes),
        state,
    )
    return (final.loads, final.best_density, final.best_ne, final.best_nv,
            final.best_mask, final.passes)


@lru_cache(maxsize=None)
def _make_sharded_refine_round(mesh, n_nodes: int, eps: float):
    """Cached jitted sharded analog of ``_refine_round_jit``: refinement
    rounds run directly on the engine's resident sharded slot arrays (the
    ISSUE 9 bugfix — no more single-device re-upload per refined query).
    Same signature as the single-device round minus the statics."""
    axes = tuple(mesh.axis_names)

    def body(src_l, dst_l, deg, n_edges, loads, bd, be, bv, bm, ps):
        return _sharded_refine_round_body(
            src_l, dst_l, deg, n_edges, loads, bd, be, bv, bm, ps,
            n_nodes, eps, axes)

    run = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axes), P(axes)) + (P(),) * 8,
        out_specs=(P(),) * 6, check_vma=False))
    SHARDED_JITS.append(run)
    return run


@lru_cache(maxsize=None)
def _make_sharded_batched_refine_round(mesh, n_nodes: int, eps: float):
    """Fused+sharded refinement round: the per-tenant sharded round vmapped
    over the leading tenant axis inside ONE shard_map program — a bucket's
    refinement rounds pay one psum per pass for the whole group (the
    ``_batched_refine_round_jit`` of the sharded tier)."""
    axes = tuple(mesh.axis_names)

    def body(src_l, dst_l, deg, n_edges, loads, bd, be, bv, bm, ps):
        return jax.vmap(
            lambda s, d, g, ne, lo, b1, b2, b3, b4, p:
            _sharded_refine_round_body(
                s, d, g, ne, lo, b1, b2, b3, b4, p, n_nodes, eps, axes)
        )(src_l, dst_l, deg, n_edges, loads, bd, be, bv, bm, ps)

    run = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axes), P(None, axes)) + (P(),) * 8,
        out_specs=(P(),) * 6, check_vma=False))
    SHARDED_JITS.append(run)
    return run


# ---------------------------------------------------------------------------
# dense (GEMV) variant — the fused small-tenant fast path
# ---------------------------------------------------------------------------
def _dense_refine_pass(state: RefinePeelState, adj: jax.Array,
                       adj_tri: jax.Array, eps: float) -> RefinePeelState:
    """The exact integer recurrence of ``refine_pass`` with the edge-lane
    segment sums replaced by matvecs off the dense adjacency stack
    (stream/fused.py keeps one for buckets under DENSE_NODE_CAP).
    ``adj_tri`` is ``adj`` masked to column index > row index: ``adj_tri @
    failed`` counts, for each failing vertex, its failing neighbors it wins
    the smaller-id tie against. Every float32 sum is over integers < 2^24,
    hence exact — the trajectory is bit-identical to the COO pass."""
    key = (state.loads + state.deg).astype(jnp.float32)
    thr = refine_threshold(state.load_sum, state.n_e, state.n_v, eps)
    min_key = jnp.min(jnp.where(state.active, key, jnp.inf))
    failed = state.active & ((key <= thr) | (key <= min_key))

    f = failed.astype(jnp.float32)
    a = state.active.astype(jnp.float32)
    af = adj @ f  # failing-neighbor counts (exact integers)
    # HIGHEST on the vdots over counts (> 256): see stream/fused._dense_pass
    hi = jax.lax.Precision.HIGHEST
    removed_directed = (
        2.0 * jnp.vdot(f, adj @ a, precision=hi)
        - jnp.vdot(f, af, precision=hi)).astype(jnp.int32)
    n_e_new = state.n_e - removed_directed // 2
    active_new = state.active & ~failed
    deg_new = jnp.where(active_new, state.deg - af.astype(jnp.int32), 0)
    n_v_new = state.n_v - jnp.sum(failed.astype(jnp.int32))
    tie_wins = (adj_tri @ f).astype(jnp.int32)
    inc = jnp.where(failed, state.deg - af.astype(jnp.int32) + tie_wins, 0)
    loads_new = (state.loads + inc).astype(jnp.int32)
    load_sum_new = state.load_sum - jnp.sum(
        jnp.where(failed, state.loads, 0))

    best_density, best_ne, best_nv, best_mask = _fold_best(
        state, n_e_new, n_v_new, active_new)
    return RefinePeelState(
        deg=deg_new.astype(jnp.int32), loads=loads_new, active=active_new,
        n_v=n_v_new, n_e=n_e_new, load_sum=load_sum_new,
        best_density=best_density, best_ne=best_ne, best_nv=best_nv,
        best_mask=best_mask, passes=state.passes + 1,
    )


def dense_refine_round_body(
    adj, deg, n_edges, loads, best_density, best_ne, best_nv, best_mask,
    passes, eps: float,
):
    n_nodes = deg.shape[0]
    tri = (jnp.arange(n_nodes)[:, None] < jnp.arange(n_nodes)[None, :])
    adj_tri = adj * tri.astype(jnp.float32)  # adj is constant over the round
    active = deg > 0
    n_v = jnp.sum(active.astype(jnp.int32))
    state = RefinePeelState(
        deg=deg.astype(jnp.int32),
        loads=loads.astype(jnp.int32),
        active=active,
        n_v=n_v,
        n_e=n_edges.astype(jnp.int32),
        load_sum=jnp.sum(jnp.where(active, loads, 0)).astype(jnp.int32),
        best_density=best_density.astype(jnp.float32),
        best_ne=best_ne.astype(jnp.int32),
        best_nv=best_nv.astype(jnp.int32),
        best_mask=best_mask,
        passes=passes.astype(jnp.int32),
    )
    final = jax.lax.while_loop(
        lambda s: s.n_v > 0,
        lambda s: _dense_refine_pass(s, adj, adj_tri, eps),
        state,
    )
    return (final.loads, final.best_density, final.best_ne, final.best_nv,
            final.best_mask, final.passes)


@partial(jax.jit, static_argnames=("eps",))
def _batched_dense_refine_round_jit(adj, deg, n_edges, loads, best_density,
                                    best_ne, best_nv, best_mask, passes,
                                    eps: float):
    """vmap of the dense round over the gathered group rows — refinement
    rounds for a whole dense bucket cost one batched-GEMV loop instead of T
    serial scatter loops (the fused throughput win of bench_refine.py)."""
    return jax.vmap(
        lambda A, g, ne, lo, bd, be, bv, bm, p: dense_refine_round_body(
            A, g, ne, lo, bd, be, bv, bm, p, eps)
    )(adj, deg, n_edges, loads, best_density, best_ne, best_nv, best_mask,
      passes)


# counted by DeltaEngine.compile_count(): the zero-steady-state-recompile
# contract covers refinement rounds too
REFINE_JITS = [_refine_round_jit, _batched_refine_round_jit,
               _batched_dense_refine_round_jit]

__all__ = [
    "RefinePeelState",
    "refine_threshold",
    "refine_pass",
    "refine_round_body",
    "dense_refine_round_body",
    "_refine_round_jit",
    "_batched_refine_round_jit",
    "_batched_dense_refine_round_jit",
    "_make_sharded_refine_round",
    "_make_sharded_batched_refine_round",
    "REFINE_JITS",
]
