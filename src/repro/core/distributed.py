"""Distributed densest-subgraph engine: shard_map over an edge-sharded mesh.

The pod-scale formulation of the paper's shared-memory algorithm
(DESIGN.md §2): edges are sharded across every mesh axis (the device pool is
one big flat worker set for graph work); the |V|-sized degree/mask state is
replicated. One peeling pass is

    per-device   local_delta[v] = sum over local edges (u,v) of failed[u]
    cross-chip   delta = psum(local_delta)         <- the paper's atomicSub
    replicated   deg' = deg - delta; masks, counts, density bookkeeping

i.e. the paper's part-1/part-2 split with the barrier realized as one
all-reduce. The same engine runs P-Bahmani (threshold = 2(1+eps)·rho) and
the PKC level fixpoint (threshold = k), so CBDS-P phase 1 distributes for
free; phase 2 is two more segment-sums over the same sharded edges.

Fault tolerance: the loop state (deg/active/best/k/pass) is a tiny
checkpoint — ``launch.train.peel_with_restarts`` snapshots it every pass and
resumes after a simulated failure (tests/test_distributed.py).
"""
from __future__ import annotations

from functools import lru_cache
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.pbahmani import PeelState, init_state
from repro.core.density import peel_threshold, ratio
from repro.graphs.graph import Graph

# jitted entry points created by the cached sharded factories below (and by
# the sharded ingest in stream/delta.py and the sharded bucket peel in
# core/prune.py). DeltaEngine.compile_count() sums their cache sizes so the
# zero-recompile contract covers the sharded path too.
SHARDED_JITS: list = []


def edge_sharding(mesh) -> NamedSharding:
    """Edges sharded over ALL mesh axes (flat worker pool)."""
    return NamedSharding(mesh, P(tuple(mesh.axis_names)))


def stacked_edge_sharding(mesh) -> NamedSharding:
    """[T, lanes] tenant stacks: leading tenant axis replicated, lane axis
    sharded over ALL mesh axes — the fused-bucket layout where every shard
    holds its slot block for every tenant in the bucket."""
    return NamedSharding(mesh, P(None, tuple(mesh.axis_names)))


def replicated_sharding(mesh) -> NamedSharding:
    """Fully-replicated placement for |V|-sized state on the same mesh."""
    return NamedSharding(mesh, P())


def mesh_device_count(mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))


def flat_shard_index(mesh) -> jax.Array:
    """This device's index in the flattened (row-major) mesh — usable only
    inside a shard_map body. Matches the lane order of ``P(axis_names)``."""
    idx = jnp.asarray(0, jnp.int32)
    for name in mesh.axis_names:
        idx = idx * mesh.shape[name] + jax.lax.axis_index(name).astype(jnp.int32)
    return idx


def validate_stream_mesh(mesh, capacity: int) -> int:
    """The sharded streaming engine partitions pow-2 slot spaces, so the
    flat device count must be a power of two that divides every shard
    target (edge lanes 2*capacity, update batches, prune buckets)."""
    n_dev = mesh_device_count(mesh)
    if n_dev & (n_dev - 1):
        raise ValueError(
            f"sharded streaming needs a power-of-two device count, got {n_dev}")
    if n_dev > 2 * capacity:
        raise ValueError(
            f"mesh has {n_dev} devices but the buffer exposes only "
            f"{2 * capacity} edge lanes; raise the edge capacity")
    return n_dev


def shard_edges(graph: Graph, mesh):
    """Pad edge arrays to the device count and device_put them sharded."""
    n_dev = int(np.prod(list(mesh.shape.values())))
    e = graph.src.shape[0]
    pad = (-e) % n_dev
    sentinel = graph.n_nodes
    src = np.concatenate([graph.src, np.full(pad, sentinel, np.int32)])
    dst = np.concatenate([graph.dst, np.full(pad, sentinel, np.int32)])
    sh = edge_sharding(mesh)
    return jax.device_put(src, sh), jax.device_put(dst, sh)


def _local_delta(failed, active, src_l, dst_l, n_nodes, axes):
    """Per-device failed-neighbor counts + removed-edge count; psum'd."""
    src_c = jnp.minimum(src_l, n_nodes - 1)
    dst_c = jnp.minimum(dst_l, n_nodes - 1)
    valid = (src_l < n_nodes) & (dst_l < n_nodes)
    live = valid & active[src_c] & active[dst_c]
    fail_s = failed[src_c] & live
    fail_d = failed[dst_c] & live
    delta = jax.ops.segment_sum(
        fail_s.astype(jnp.int32), jnp.minimum(dst_l, n_nodes),
        num_segments=n_nodes + 1)[:n_nodes]
    removed = jnp.sum((fail_s | fail_d).astype(jnp.int32))
    delta = jax.lax.psum(delta, axes)       # the cross-chip "atomicSub"
    removed = jax.lax.psum(removed, axes)
    return delta, removed


def _peel_pass_body(state: PeelState, src_l, dst_l, n_nodes, eps,
                    axes) -> PeelState:
    """One peel pass as seen by a single shard: the pbahmani_pass
    recurrence with the degree scatter realized as ``_local_delta``'s psum.
    Factored out of ``make_peel_pass`` so the fused bucket tier can vmap
    it over a leading tenant axis *inside* one shard_map program — the
    psum batching rule turns T per-tenant all-reduces into one [T, V]
    collective without changing any per-tenant integer."""
    thr = peel_threshold(state.n_e, state.n_v, eps)
    failed = state.active & (state.deg.astype(jnp.float32) <= thr)
    delta, removed = _local_delta(failed, state.active, src_l, dst_l,
                                  n_nodes, axes)
    active_new = state.active & ~failed
    deg_new = jnp.where(active_new, state.deg - delta, 0).astype(jnp.int32)
    n_e_new = state.n_e - removed // 2
    n_v_new = state.n_v - jnp.sum(failed.astype(jnp.int32))
    rho_new = jnp.where(n_v_new > 0, ratio(n_e_new, n_v_new), 0.0)
    better = rho_new > state.best_density
    return PeelState(
        deg=deg_new, active=active_new, n_v=n_v_new, n_e=n_e_new,
        best_density=jnp.where(better, rho_new, state.best_density),
        best_mask=jnp.where(better, active_new, state.best_mask),
        passes=state.passes + 1,
    )


def make_peel_pass(mesh, n_nodes: int, eps: float):
    """Returns a jittable (state, src_sharded, dst_sharded) -> state pass."""
    axes = tuple(mesh.axis_names)

    def body(state: PeelState, src_l, dst_l) -> PeelState:
        return _peel_pass_body(state, src_l, dst_l, n_nodes, eps, axes)

    state_spec = PeelState(deg=P(), active=P(), n_v=P(), n_e=P(),
                           best_density=P(), best_mask=P(), passes=P())
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(state_spec, P(axes), P(axes)),
                         out_specs=state_spec, check_vma=False)


@lru_cache(maxsize=None)
def make_sharded_warm_peel(mesh, n_nodes: int, eps: float):
    """Cached jitted sharded analog of ``stream.delta._warm_peel_jit``.

    (src, dst, deg, n_edges, prev_mask) -> (final PeelState, warm_rho) with
    src/dst sharded over the mesh and the |V|-sized state replicated. The
    peel body is the same integer/f32 recurrence as ``pbahmani_pass`` with
    the degree scatter realized as psum (exact int32), so the result is
    bit-identical to the single-device warm peel on any device count —
    the sharded==single-device parity oracle in tests/test_shard.py.
    """
    axes = tuple(mesh.axis_names)
    peel_pass = make_peel_pass(mesh, n_nodes, eps)

    def warm_count_body(src_l, dst_l, mask):
        src_c = jnp.minimum(src_l, n_nodes - 1)
        dst_c = jnp.minimum(dst_l, n_nodes - 1)
        valid = (src_l < n_nodes) & (dst_l < n_nodes)
        live = valid & mask[src_c] & mask[dst_c]
        return jax.lax.psum(jnp.sum(live.astype(jnp.int32)), axes)

    warm_count = jax.shard_map(
        warm_count_body, mesh=mesh, in_specs=(P(axes), P(axes), P()),
        out_specs=P(), check_vma=False)

    @jax.jit
    def run(src, dst, deg, n_edges, prev_mask):
        active = deg > 0
        n_v = jnp.sum(active.astype(jnp.int32))
        n_e = n_edges.astype(jnp.int32)
        rho0 = ratio(n_e, n_v)
        state = PeelState(
            deg=deg.astype(jnp.int32), active=active, n_v=n_v, n_e=n_e,
            best_density=rho0, best_mask=active,
            passes=jnp.asarray(0, jnp.int32))
        final = jax.lax.while_loop(
            lambda s: s.n_v > 0, lambda s: peel_pass(s, src, dst), state)
        warm_e = warm_count(src, dst, prev_mask) // 2
        warm_v = jnp.sum(prev_mask.astype(jnp.int32))
        warm_rho = jnp.where(warm_v > 0, ratio(warm_e, warm_v), 0.0)
        return final, warm_rho

    SHARDED_JITS.append(run)
    return run


def _warm_peel_shard_body(src_l, dst_l, deg, n_edges, prev_mask,
                          n_nodes, eps, axes):
    """Per-shard, per-tenant warm peel: the exact recurrence of
    ``make_sharded_warm_peel.run`` with the shard_map wrapper factored out
    so the batched variant below can vmap it over a leading tenant axis."""
    active = deg > 0
    n_v = jnp.sum(active.astype(jnp.int32))
    n_e = n_edges.astype(jnp.int32)
    rho0 = ratio(n_e, n_v)
    state = PeelState(
        deg=deg.astype(jnp.int32), active=active, n_v=n_v, n_e=n_e,
        best_density=rho0, best_mask=active,
        passes=jnp.asarray(0, jnp.int32))
    final = jax.lax.while_loop(
        lambda s: s.n_v > 0,
        lambda s: _peel_pass_body(s, src_l, dst_l, n_nodes, eps, axes), state)
    src_c = jnp.minimum(src_l, n_nodes - 1)
    dst_c = jnp.minimum(dst_l, n_nodes - 1)
    valid = (src_l < n_nodes) & (dst_l < n_nodes)
    live = valid & prev_mask[src_c] & prev_mask[dst_c]
    warm_e = jax.lax.psum(jnp.sum(live.astype(jnp.int32)), axes) // 2
    warm_v = jnp.sum(prev_mask.astype(jnp.int32))
    warm_rho = jnp.where(warm_v > 0, ratio(warm_e, warm_v), 0.0)
    return final, warm_rho


@lru_cache(maxsize=None)
def make_sharded_batched_warm_peel(mesh, n_nodes: int, eps: float):
    """The fused+sharded bucket peel: ONE shard_map program whose body
    vmaps the per-tenant warm peel over the leading tenant axis.

    (src [T, lanes], dst [T, lanes], deg [T, V], n_edges [T],
    prev_mask [T, V]) -> (stacked PeelState, warm_rho [T]) with the lane
    axis sharded over the mesh and everything |V|-sized replicated. Inside
    the body every ``psum`` sees the whole [T, V] delta stack (vmap's
    batching rule for named-axis collectives), so a bucket of T tenants
    pays ONE all-reduce per pass where T solo sharded tenants paid T —
    the collective amortization this tier exists for. Converged lanes are
    frozen by while_loop batching's select (the `_batched_warm_peel_jit`
    mechanism), so each tenant's (density, mask, passes) stays
    bit-identical to its solo run on any device count.
    """
    axes = tuple(mesh.axis_names)

    def body(src_l, dst_l, deg, n_edges, prev_mask):
        return jax.vmap(
            lambda s, d, g, ne, pm: _warm_peel_shard_body(
                s, d, g, ne, pm, n_nodes, eps, axes)
        )(src_l, dst_l, deg, n_edges, prev_mask)

    state_spec = PeelState(deg=P(), active=P(), n_v=P(), n_e=P(),
                           best_density=P(), best_mask=P(), passes=P())
    run = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axes), P(None, axes), P(), P(), P()),
        out_specs=(state_spec, P()), check_vma=False))
    SHARDED_JITS.append(run)
    return run


@lru_cache(maxsize=None)
def _make_pbahmani_run(mesh, n_nodes: int, eps: float,
                       max_passes: int | None):
    """Cached jitted distributed P-Bahmani loop: every shape determinant
    (mesh, |V|, eps, pass cap) is a factory key, the edge count is a
    traced argument — repeated graphs of the same shape family reuse one
    executable, and the auditor sees it via SHARDED_JITS."""
    peel_pass = make_peel_pass(mesh, n_nodes, eps)

    @jax.jit
    def run(src, dst, n_edges):
        state = init_state(src, dst, n_nodes, n_edges)

        def cond(s):
            c = s.n_v > 0
            if max_passes is not None:
                c = c & (s.passes < max_passes)
            return c

        return jax.lax.while_loop(cond, lambda s: peel_pass(s, src, dst), state)

    SHARDED_JITS.append(run)
    return run


def pbahmani_distributed(graph: Graph, mesh, eps: float = 0.0,
                         max_passes: int | None = None
                         ) -> tuple[float, np.ndarray, int]:
    """Multi-device P-Bahmani. Same results as core.pbahmani (tested)."""
    src, dst = shard_edges(graph, mesh)
    run = _make_pbahmani_run(mesh, graph.n_nodes, eps, max_passes)
    final = run(src, dst, jnp.asarray(graph.n_edges, jnp.int32))
    return float(final.best_density), np.asarray(final.best_mask), int(final.passes)


# ---------------------------------------------------------------------------
# distributed k-core (CBDS-P phase 1) and phase-2 augmentation
# ---------------------------------------------------------------------------
class DistCoreState(NamedTuple):
    k: jax.Array
    deg: jax.Array
    active: jax.Array
    coreness: jax.Array
    n_v: jax.Array
    n_e: jax.Array
    best_density: jax.Array
    best_k: jax.Array
    best_n_v: jax.Array
    best_n_e: jax.Array


def make_kcore_level(mesh, n_nodes: int):
    axes = tuple(mesh.axis_names)

    def body(s: DistCoreState, src_l, dst_l) -> DistCoreState:
        failed = s.active & (s.deg <= s.k)
        delta, removed = _local_delta(failed, s.active, src_l, dst_l,
                                      n_nodes, axes)
        active_new = s.active & ~failed
        return s._replace(
            deg=jnp.where(active_new, s.deg - delta, 0).astype(jnp.int32),
            active=active_new,
            coreness=jnp.where(failed, s.k, s.coreness).astype(jnp.int32),
            n_v=s.n_v - jnp.sum(failed.astype(jnp.int32)),
            n_e=s.n_e - removed // 2,
        )

    spec = DistCoreState(*(P() for _ in DistCoreState._fields))
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(spec, P(axes), P(axes)),
                         out_specs=spec, check_vma=False)


@lru_cache(maxsize=None)
def _make_cbds_run(mesh, n_nodes: int, rounds: int):
    """Cached jitted distributed CBDS-P (phases 1+2); mesh/|V|/rounds are
    factory keys, the edge count is traced. Registered in SHARDED_JITS so
    the recompile auditor attributes its cache growth."""
    n = n_nodes
    axes = tuple(mesh.axis_names)
    level = make_kcore_level(mesh, n)

    def augment_body(member, m_v, m_e, src_l, dst_l):
        src_c = jnp.minimum(src_l, n - 1)
        dst_c = jnp.minimum(dst_l, n - 1)
        valid = (src_l < n) & (dst_l < n)
        into = valid & member[dst_c] & ~member[src_c]
        e_into = jax.ops.segment_sum(
            into.astype(jnp.int32), jnp.minimum(src_l, n),
            num_segments=n + 1)[:n]
        e_into = jax.lax.psum(e_into, axes)
        # exact integer form of e_into > m_e/m_v (see cbds._augment_once)
        legit = ~member & (e_into > m_e // jnp.maximum(m_v, 1))
        inter_into = jnp.sum(jnp.where(legit, e_into, 0))
        legit_pair = valid & legit[src_c] & legit[dst_c]
        inter_cross = jax.lax.psum(
            jnp.sum(legit_pair.astype(jnp.int32)), axes) // 2
        member_new = member | legit
        n_add = jnp.sum(legit.astype(jnp.int32))
        return (member_new, m_v + n_add,
                m_e + inter_into + inter_cross, n_add)

    augment = jax.shard_map(
        augment_body, mesh=mesh,
        in_specs=(P(), P(), P(), P(axes), P(axes)),
        out_specs=(P(), P(), P(), P()), check_vma=False)

    @jax.jit
    def run(src, dst, n_edges):
        ones = jnp.ones_like(src, dtype=jnp.int32)
        # initial degrees: distributed histogram over sharded edges
        def deg_body(src_l):
            d = jax.ops.segment_sum(
                jnp.ones_like(src_l, jnp.int32), jnp.minimum(src_l, n),
                num_segments=n + 1)[:n]
            return jax.lax.psum(d, axes)
        deg = jax.shard_map(deg_body, mesh=mesh, in_specs=(P(axes),),
                            out_specs=P(), check_vma=False)(src)
        del ones
        s0 = DistCoreState(
            k=jnp.asarray(0, jnp.int32), deg=deg,
            active=jnp.ones(n, dtype=bool),
            coreness=jnp.zeros(n, jnp.int32),
            n_v=jnp.asarray(n, jnp.int32),
            n_e=n_edges.astype(jnp.int32),
            best_density=jnp.asarray(0.0, jnp.float32),
            best_k=jnp.asarray(0, jnp.int32),
            best_n_v=jnp.asarray(0, jnp.int32),
            best_n_e=jnp.asarray(0, jnp.int32))

        def outer_cond(s):
            return s.n_v > 0

        def outer(s):
            density = ratio(s.n_e, s.n_v)
            better = (density > s.best_density) & (s.n_v > 0)
            s = s._replace(
                best_density=jnp.where(better, density, s.best_density),
                best_k=jnp.where(better, s.k, s.best_k),
                best_n_v=jnp.where(better, s.n_v, s.best_n_v),
                best_n_e=jnp.where(better, s.n_e, s.best_n_e))
            s = jax.lax.while_loop(
                lambda t: jnp.any(t.active & (t.deg <= t.k)),
                lambda t: level(t, src, dst), s)
            return s._replace(k=s.k + 1)

        core = jax.lax.while_loop(outer_cond, outer, s0)
        member = core.coreness >= core.best_k
        m_v, m_e = core.best_n_v, core.best_n_e
        n_legit = jnp.asarray(0, jnp.int32)
        for _ in range(rounds):
            member, m_v, m_e, n_add = augment(member, m_v, m_e, src, dst)
            n_legit = n_legit + n_add
        density = ratio(m_e, m_v)
        return (core, member, jnp.maximum(density, core.best_density),
                n_legit)

    SHARDED_JITS.append(run)
    return run


def cbds_distributed(graph: Graph, mesh, rounds: int = 1) -> dict:
    """Multi-device CBDS-P (phases 1+2). Matches core.cbds (tested)."""
    src, dst = shard_edges(graph, mesh)
    run = _make_cbds_run(mesh, graph.n_nodes, rounds)
    core, member, density, n_legit = run(
        src, dst, jnp.asarray(graph.n_edges, jnp.int32))
    return {
        "density": float(density),
        "core_density": float(core.best_density),
        "k_star": int(core.best_k),
        "member_mask": np.asarray(member),
        "coreness": np.asarray(core.coreness),
        "n_legit": int(n_legit),
    }


__all__ = ["edge_sharding", "stacked_edge_sharding", "replicated_sharding",
           "shard_edges", "make_peel_pass", "make_sharded_warm_peel",
           "make_sharded_batched_warm_peel", "mesh_device_count",
           "flat_shard_index", "validate_stream_mesh", "SHARDED_JITS",
           "pbahmani_distributed", "cbds_distributed", "DistCoreState",
           "make_kcore_level"]
