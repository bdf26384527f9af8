"""Candidate pruning: exactness-preserving compacted peel (ISSUE 2 tentpole).

Every ``pbahmani`` pass sweeps the full padded edge arrays, but the live set
shrinks geometrically (a 4k-node power-law graph drops 4096 -> 1091 -> 275
live vertices in two passes) — so almost all lanes of almost all passes are
dead weight. This module peels a *compacted fixed-shape subproblem* instead:

  1. a density lower bound rho~ is bootstrapped on the current graph
     (Bahmani-style: the live graph's own density, the previous epoch's best
     mask re-evaluated on the current edges, and the densities of the
     iterated ceil(rho~)-cores — every candidate is an achieved subgraph
     density, hence a sound lower bound on rho*);
  2. the existing k-core machinery (``kcore._level_fixpoint``) runs to the
     ceil(rho~)-core (Sukprasert et al., arXiv:2311.04333), yielding the
     candidate set whose size/fraction the engine reports as pruning stats
     (bucket sizing itself tracks the *observed* pass-0 handoff — the core
     bounds where the trajectory's dense tail lives, but the handoff set is
     what must physically fit); the analysis runs at epoch cadence only,
     amortized against the refresh's cold peel;
  3. the peel's pass-0 survivor set is computed from the maintained degree
     array (vertex-width only), its induced edges are compacted *on the
     host* — the edge buffer's undirected slot arrays already live there —
     into a pow-2 bucket (remapped COO + order-preserving vertex index map),
     and the peel runs entirely inside the bucket, with a second,
     bucket-width compaction ladder for the late tail of the trajectory.

Host-side compaction is a deliberate inversion of the device-resident
ingest path: a query must materialize a result on the host anyway, the
degree pull is |V| int32 (16KB at 4k nodes), and filtering ~|E| host slots
costs microseconds in numpy — while a device-side stream compaction costs a
full-width cumsum + scatter, which profiling puts at ~1.5x the price of an
entire peel pass. With the host doing the remap, the device executes *zero*
full-lane-width operations on the pruned query path, and the host knows the
exact subproblem size before dispatch, so a bucket fit-miss re-sizes the
plan instead of wasting a query.

Exactness-preservation invariant
--------------------------------
The pruned peel returns the *bit-identical* (density, mask, passes) triple
of the unpruned cold peel. Proof sketch:

  * Pass 0 is simulated exactly: ``failed0 = active & (deg <= thr0)`` uses
    the same int32 degrees and the same float32 threshold
    ``2(1+eps)·|E|/|V|`` (host numpy float32 replicates the jitted scalar
    arithmetic operation for operation); the survivor count and surviving
    edge count are exact integers.
  * A peel pass depends only on the *induced* live subgraph plus the scalar
    state (n_v, n_e, best, passes). Compaction is an order-preserving
    relabeling of the live vertices and their induced edges, so every
    integer the recurrence reads is unchanged; ``segment_sum`` over int32
    is exact under lane reordering, and every float32 scalar (rho,
    threshold, best comparisons) is computed from identical integers —
    hence bit-identical, pass for pass.
  * Best tracking uses the same strict ``>`` at every merge point (host
    merge of the pass-0/1 states, ladder merge inside the bucket), so the
    earliest argmax state wins exactly as in the unpruned trajectory.

Note rho~ itself never gates correctness: it drives the candidate metrics
and bucket reuse. A naive "re-peel the ceil(rho~)-core from its own
density" does NOT preserve the peel output (the core is denser, so the
threshold schedule — and hence the trajectory — diverges on >50% of random
graphs). Exactness comes from preserving the trajectory, not from core
containment.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.density import degrees_from_coo, ratio, subgraph_density
from repro.core.dispatch import assert_exact_envelope
from repro.core.distributed import (
    DistCoreState, SHARDED_JITS, _peel_pass_body, edge_sharding,
    make_kcore_level, make_peel_pass, mesh_device_count,
)
from repro.core.kcore import CoreState, _level_fixpoint
from repro.core.pbahmani import PeelState, pbahmani_pass
from repro.graphs.graph import Graph
from repro.kernels.compact import stream_compact
from repro.utils.num import next_pow2

MIN_BUCKET_V = 64     # smallest compacted vertex space (pow-2 buckets above)
MIN_BUCKET_E = 256    # smallest compacted lane count
LADDER_RATIO = 8      # second-level bucket = first-level bucket / ratio
BUCKET_SLACK = 1.5    # headroom over the observed handoff size
# mid-epoch bucket shrink fires only when the freshly-sized buckets are at
# least this factor below the plan's; with BUCKET_SLACK regrow this leaves a
# >2.5x swing between shrink and regrow, so oscillating graphs cannot thrash
BUCKET_SHRINK_HYSTERESIS = 4


@dataclass(frozen=True)
class PrunePlan:
    """Per-tenant pruning decision, rebuilt at epoch cadence.

    rho_lb / k / candidate counts come from the iterated ceil(rho~)-core;
    buckets are the static shapes the pruned executable is compiled for.
    """

    rho_lb: float            # sound lower bound on rho* (achieved density)
    k: int                   # prune level: candidates = ceil(rho_lb)-core
    n_candidates: int        # |ceil(rho_lb)-core|
    n_candidate_edges: int   # |E(core)|
    candidate_fraction: float  # |core| / graph vertex count (not padding)
    bucket_v: int            # compacted vertex-space size (pow-2)
    bucket_e: int            # compacted lane count (pow-2, holds 2|E| lanes)
    bucket_v2: int           # second-level ladder bucket
    bucket_e2: int
    enabled: bool
    node_width: int = 0      # sizing basis, kept for in-flight regrow
    lane_width: int = 0
    n_vertices: int = 0      # candidate_fraction denominator
    from_observed: bool = False  # buckets sized from a real handoff (mid-
                                 # epoch shrink only trusts observed sizing;
                                 # first-shot plans adapt at the refresh)

    @property
    def buckets(self) -> tuple[int, int, int, int]:
        return (self.bucket_v, self.bucket_e, self.bucket_v2, self.bucket_e2)


# ---------------------------------------------------------------------------
# rho~ bootstrap + candidate core (plan analysis)
# ---------------------------------------------------------------------------
def _ceil_level(rho: jax.Array) -> jax.Array:
    return jnp.maximum(jnp.ceil(rho).astype(jnp.int32), 1)


@partial(jax.jit, static_argnames=("n_nodes", "kernel"))
def _plan_jit(
    src: jax.Array,
    dst: jax.Array,
    prev_mask: jax.Array,
    n_edges: jax.Array,
    n_nodes: int,
    kernel: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Bootstrap rho~ and shrink to the ceil(rho~)-core.

    Returns (rho_lb, k, candidate_mask, n_candidates, n_candidate_edges).
    rho_lb only ever takes values of densities achieved by actual subgraphs
    of the *current* graph (live graph, re-validated previous mask, iterated
    cores), so rho_lb <= rho* always — the pruning-safety condition.
    """
    deg = degrees_from_coo(src, n_nodes)
    active = deg > 0
    n_v = jnp.sum(active.astype(jnp.int32))
    n_e = n_edges.astype(jnp.int32)
    rho0 = ratio(n_e, n_v)
    # previous epoch's best mask, re-evaluated on the current edges: a sound
    # warm start for rho~ even after deletions (it is a *current* subgraph)
    warm_rho = subgraph_density(src, dst, prev_mask, n_nodes)
    rho_lb = jnp.maximum(rho0, warm_rho)

    state = CoreState(
        k=jnp.asarray(-1, jnp.int32),  # level already completed (none)
        deg=deg.astype(jnp.int32),
        active=active,
        coreness=jnp.zeros(n_nodes, dtype=jnp.int32),
        n_v=n_v,
        n_e=n_e,
        best_density=rho_lb,
        best_k=jnp.asarray(0, jnp.int32),
        best_n_v=n_v,
        best_n_e=n_e,
    )

    def cond(c: CoreState) -> jax.Array:
        # keep shrinking while the bound justifies a deeper core
        return (c.n_v > 0) & (c.k < _ceil_level(c.best_density) - 1)

    def body(c: CoreState) -> CoreState:
        c = c._replace(k=_ceil_level(c.best_density) - 1)
        c = _level_fixpoint(c, src, dst, n_nodes, kernel)  # kcore sweep
        rho_c = jnp.where(c.n_v > 0, ratio(c.n_e, c.n_v), 0.0)
        return c._replace(best_density=jnp.maximum(c.best_density, rho_c))

    final = jax.lax.while_loop(cond, body, state)
    return final.best_density, final.k + 1, final.active, final.n_v, final.n_e


@lru_cache(maxsize=None)
def make_sharded_plan(mesh, n_nodes: int):
    """Cached jitted sharded analog of ``_plan_jit``: the degree histogram,
    the previous-mask re-evaluation, and every level of the ceil(rho~)-core
    fixpoint run as per-shard segment-sums with the cross-shard reduction
    one psum — same integers as the single-device analysis, so the plan
    (rho_lb, k, candidate counts) is identical on any device count."""
    axes = tuple(mesh.axis_names)

    def stats_body(src_l, dst_l, mask):
        deg = jax.ops.segment_sum(
            jnp.ones_like(src_l, jnp.int32), jnp.minimum(src_l, n_nodes),
            num_segments=n_nodes + 1)[:n_nodes]
        deg = jax.lax.psum(deg, axes)
        src_c = jnp.minimum(src_l, n_nodes - 1)
        dst_c = jnp.minimum(dst_l, n_nodes - 1)
        valid = (src_l < n_nodes) & (dst_l < n_nodes)
        live = valid & mask[src_c] & mask[dst_c]
        warm_cnt = jax.lax.psum(jnp.sum(live.astype(jnp.int32)), axes)
        return deg, warm_cnt

    stats = jax.shard_map(
        stats_body, mesh=mesh, in_specs=(P(axes), P(axes), P()),
        out_specs=(P(), P()), check_vma=False)

    # the level sweep is exactly the distributed k-core pass (CBDS phase 1);
    # DistCoreState and kcore.CoreState share the same fields, so the plan
    # loop can run on make_kcore_level's state directly
    level = make_kcore_level(mesh, n_nodes)

    @jax.jit
    def run(src, dst, prev_mask, n_edges):
        deg, warm_cnt = stats(src, dst, prev_mask)
        active = deg > 0
        n_v = jnp.sum(active.astype(jnp.int32))
        n_e = n_edges.astype(jnp.int32)
        rho0 = ratio(n_e, n_v)
        warm_v = jnp.sum(prev_mask.astype(jnp.int32))
        warm_e = warm_cnt // 2
        warm_rho = jnp.where(warm_v > 0, ratio(warm_e, warm_v), 0.0)
        rho_lb = jnp.maximum(rho0, warm_rho)
        state = DistCoreState(
            k=jnp.asarray(-1, jnp.int32),
            deg=deg.astype(jnp.int32),
            active=active,
            coreness=jnp.zeros(n_nodes, dtype=jnp.int32),
            n_v=n_v,
            n_e=n_e,
            best_density=rho_lb,
            best_k=jnp.asarray(0, jnp.int32),
            best_n_v=n_v,
            best_n_e=n_e,
        )

        def cond(c: DistCoreState) -> jax.Array:
            return (c.n_v > 0) & (c.k < _ceil_level(c.best_density) - 1)

        def body(c: DistCoreState) -> DistCoreState:
            c = c._replace(k=_ceil_level(c.best_density) - 1)
            c = jax.lax.while_loop(
                lambda t: jnp.any(t.active & (t.deg <= t.k)),
                lambda t: level(t, src, dst), c)
            rho_c = jnp.where(c.n_v > 0, ratio(c.n_e, c.n_v), 0.0)
            return c._replace(best_density=jnp.maximum(c.best_density, rho_c))

        final = jax.lax.while_loop(cond, body, state)
        return final.best_density, final.k + 1, final.active, final.n_v, final.n_e

    SHARDED_JITS.append(run)
    return run


def build_plan(
    rho_lb: float,
    k: int,
    n_candidates: int,
    n_candidate_edges: int,
    node_width: int,
    lane_width: int,
    observed: tuple[int, int] | None = None,
    n_vertices: int | None = None,
) -> PrunePlan:
    """Size the compaction buckets for a (node_width, lane_width) graph.

    ``observed`` is the previous epoch's handoff (survivor count, live
    lanes); buckets track it with ``BUCKET_SLACK`` headroom so steady-state
    queries reuse one compiled executable. The vertex bucket may reach the
    full (pow-2) vertex space — vertex-width ops are cheap; the latency win
    is in the lane bucket, which must stay strictly below the full lane
    width for pruning to pay off.
    """
    # the whole exactness story (scatter AND kernel tier) rides on int32
    # counts surviving f32 accumulation exactly; reject out-of-envelope
    # shapes here, before any executable is sized for them
    assert_exact_envelope(node_width, lane_width)
    cap_v = max(next_pow2(node_width), MIN_BUCKET_V)
    cap_e = max(next_pow2(lane_width) // 2, MIN_BUCKET_E)
    if observed is not None:
        h_nv, h_lanes = observed
        bv = next_pow2(max(int(h_nv * BUCKET_SLACK), MIN_BUCKET_V))
        be = next_pow2(max(int(h_lanes * BUCKET_SLACK), MIN_BUCKET_E))
    else:
        bv = max(cap_v // 2, MIN_BUCKET_V)
        be = cap_e
    bv = min(bv, cap_v)
    be = min(be, cap_e)
    bv2 = max(bv // LADDER_RATIO, MIN_BUCKET_V)
    be2 = max(be // LADDER_RATIO, MIN_BUCKET_E)
    enabled = be < lane_width
    n_vertices = node_width if n_vertices is None else int(n_vertices)
    return PrunePlan(
        rho_lb=float(rho_lb),
        k=int(k),
        n_candidates=int(n_candidates),
        n_candidate_edges=int(n_candidate_edges),
        candidate_fraction=float(n_candidates) / max(n_vertices, 1),
        bucket_v=int(bv),
        bucket_e=int(be),
        bucket_v2=int(min(bv2, bv)),
        bucket_e2=int(min(be2, be)),
        enabled=bool(enabled),
        node_width=int(node_width),
        lane_width=int(lane_width),
        n_vertices=n_vertices,
        from_observed=observed is not None,
    )


def maybe_shrink_plan(
    plan: PrunePlan, n_v1: int, lanes1: int
) -> PrunePlan | None:
    """Mid-epoch bucket shrink (ISSUE 3 bugfix: plans only ever *regrew*
    mid-epoch, so contracting graphs kept peeling inside peak-size buckets
    until the next refresh). Returns a right-sized plan when the observed
    handoff fits buckets ``BUCKET_SHRINK_HYSTERESIS``x smaller on either
    axis, else None. Shrinking only changes static shapes — bit-identity
    holds for every bucket choice (module docstring).

    First-shot plans (sized conservatively, before any handoff was seen)
    never shrink mid-epoch: their slack is intentional warmup headroom, and
    the first refresh right-sizes them anyway — shrinking them on the very
    next query would recompile on graphs that never contracted."""
    if not plan.from_observed:
        return None
    bv = next_pow2(max(int(n_v1 * BUCKET_SLACK), MIN_BUCKET_V))
    be = next_pow2(max(int(lanes1 * BUCKET_SLACK), MIN_BUCKET_E))
    if (bv * BUCKET_SHRINK_HYSTERESIS > plan.bucket_v
            and be * BUCKET_SHRINK_HYSTERESIS > plan.bucket_e):
        return None
    new = build_plan(
        plan.rho_lb, plan.k, plan.n_candidates, plan.n_candidate_edges,
        node_width=plan.node_width, lane_width=plan.lane_width,
        observed=(n_v1, lanes1), n_vertices=plan.n_vertices or None,
    )
    if not new.enabled or new.buckets == plan.buckets:
        return None
    return new


# ---------------------------------------------------------------------------
# device side: bucket peel with a second-level compaction ladder
# ---------------------------------------------------------------------------
def _compact_edges(
    src: jax.Array,
    dst: jax.Array,
    live_v: jax.Array,
    n_nodes: int,
    bucket_v: int,
    bucket_e: int,
    kernel: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Device-side remap of the subgraph induced by ``live_v`` into bucket
    arrays (used for the in-bucket ladder step, where the cumsum is cheap).
    ``kernel`` routes the lane compaction through the Pallas prefix-sum
    stream-compaction kernel (kernels/compact.py) instead of the XLA
    cumsum+scatter; both pack survivors as a dense prefix in lane order
    (overflow lanes drop, exactly like ``mode="drop"``), so the outputs are
    bit-identical — and a dst-sorted parent bucket hands a dst-sorted child
    to the next rung, because ``perm`` is monotone and order is preserved.
    Returns (perm, bucket_src, bucket_dst)."""
    src_c = jnp.minimum(src, n_nodes - 1)
    dst_c = jnp.minimum(dst, n_nodes - 1)
    valid = (src < n_nodes) & (dst < n_nodes)
    live = valid & live_v[src_c] & live_v[dst_c]
    perm = jnp.cumsum(live_v.astype(jnp.int32)) - 1
    if kernel:
        packed = stream_compact(
            jnp.stack(
                [perm[src_c].astype(jnp.int32), perm[dst_c].astype(jnp.int32)],
                axis=1),
            live, out_size=bucket_e, fill=bucket_v)
        return perm, packed[:, 0], packed[:, 1]
    live_i = live.astype(jnp.int32)
    pos = jnp.where(live, jnp.cumsum(live_i) - 1, bucket_e)
    b_src = jnp.full(bucket_e, bucket_v, jnp.int32).at[pos].set(
        perm[src_c].astype(jnp.int32), mode="drop"
    )
    b_dst = jnp.full(bucket_e, bucket_v, jnp.int32).at[pos].set(
        perm[dst_c].astype(jnp.int32), mode="drop"
    )
    return perm, b_src, b_dst


def _peel_to_end(
    state: PeelState, src: jax.Array, dst: jax.Array, n_nodes: int,
    eps: float, kernel: bool = False,
) -> PeelState:
    return jax.lax.while_loop(
        lambda s: s.n_v > 0,
        lambda s: pbahmani_pass(s, src, dst, n_nodes, eps, kernel),
        state,
    )


def _staged_peel(
    state: PeelState,
    src: jax.Array,
    dst: jax.Array,
    n_nodes: int,
    eps: float,
    bucket_v: int,
    bucket_e: int,
    kernel: bool = False,
) -> PeelState:
    """Peel at the current width until the live set fits (bucket_v,
    bucket_e), compact, and finish inside the smaller bucket. The returned
    state is in the *current* (n_nodes-wide) space; bit-identical to
    ``_peel_to_end`` on the same input by the invariant in the module
    docstring (the ``kernel`` tier included — see ``_compact_edges``)."""

    def unfits(s: PeelState) -> jax.Array:
        return (s.n_v > 0) & ((s.n_v > bucket_v) | (2 * s.n_e > bucket_e))

    s1 = jax.lax.while_loop(
        unfits, lambda s: pbahmani_pass(s, src, dst, n_nodes, eps, kernel),
        state
    )
    perm, b_src, b_dst = _compact_edges(
        src, dst, s1.active, n_nodes, bucket_v, bucket_e, kernel
    )
    if kernel:
        # survivors land as a dense prefix, so the live mask is arange<n_v
        # and the degree pull is the same stream compaction (fill = 0 ==
        # what the scatter writes in dead slots) — bit-identical arrays
        b_deg = stream_compact(s1.deg, s1.active, out_size=bucket_v, fill=0)
        b_active = jnp.arange(bucket_v, dtype=jnp.int32) < s1.n_v
    else:
        vslot = jnp.where(s1.active, perm, bucket_v)
        b_deg = jnp.zeros(bucket_v, jnp.int32).at[vslot].set(
            s1.deg, mode="drop")
        b_active = jnp.zeros(bucket_v, bool).at[vslot].set(True, mode="drop")
    s2 = _peel_to_end(
        PeelState(
            deg=b_deg,
            active=b_active,
            n_v=s1.n_v,
            n_e=s1.n_e,
            best_density=s1.best_density,
            best_mask=jnp.zeros(bucket_v, dtype=bool),
            passes=s1.passes,
        ),
        b_src, b_dst, bucket_v, eps, kernel,
    )
    improved = s2.best_density > s1.best_density
    mask_back = s1.active & s2.best_mask[jnp.minimum(perm, bucket_v - 1)]
    # the peel runs to an empty live set, so the terminal deg/active are
    # identically zero — return them as such (what _peel_to_end would hold)
    return s1._replace(
        deg=jnp.zeros_like(s1.deg),
        active=jnp.zeros_like(s1.active),
        best_density=s2.best_density,
        best_mask=jnp.where(improved, mask_back, s1.best_mask),
        passes=s2.passes,
        n_v=s2.n_v,
        n_e=s2.n_e,
    )


def _bucket_peel_body(
    b_src: jax.Array,
    b_dst: jax.Array,
    n_v: jax.Array,
    n_e: jax.Array,
    best_density: jax.Array,
    passes: jax.Array,
    eps: float,
    bucket_v: int,
    bucket_v2: int,
    bucket_e2: int,
    kernel: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Peel the compacted subproblem to completion (with the ladder).

    The host compaction emits compact ids as a dense prefix, so the live
    mask is ``arange < n_v`` and degrees are one bucket-width histogram —
    no full-lane-width work happens on device at all. ``kernel`` routes the
    per-pass degree updates and the ladder compaction through the Pallas
    tier (the host emits the bucket COO dst-sorted, and the ladder
    preserves that order, so the band-skip precondition holds rung to
    rung); the returned triple is bit-identical either way.
    """
    b_deg = degrees_from_coo(b_src, bucket_v)
    b_active = jnp.arange(bucket_v, dtype=jnp.int32) < n_v
    final = _staged_peel(
        PeelState(
            deg=b_deg,
            active=b_active,
            n_v=n_v.astype(jnp.int32),
            n_e=n_e.astype(jnp.int32),
            best_density=best_density.astype(jnp.float32),
            best_mask=jnp.zeros(bucket_v, dtype=bool),
            passes=passes.astype(jnp.int32),
        ),
        b_src, b_dst, bucket_v, eps, bucket_v2, bucket_e2, kernel,
    )
    return final.best_density, final.best_mask, final.passes


@partial(jax.jit, static_argnames=(
    "eps", "bucket_v", "bucket_e", "bucket_v2", "bucket_e2", "kernel"))
def _bucket_peel_jit(
    b_src, b_dst, n_v, n_e, best_density, passes,
    eps: float, bucket_v: int, bucket_e: int, bucket_v2: int, bucket_e2: int,
    kernel: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    del bucket_e  # cache-key only: b_src already carries the lane shape
    return _bucket_peel_body(b_src, b_dst, n_v, n_e, best_density, passes,
                             eps, bucket_v, bucket_v2, bucket_e2, kernel)


@partial(jax.jit, static_argnames=(
    "eps", "bucket_v", "bucket_e", "bucket_v2", "bucket_e2", "kernel"))
def _batched_bucket_peel_jit(
    b_src, b_dst, n_v, n_e, best_density, passes,
    eps: float, bucket_v: int, bucket_e: int, bucket_v2: int, bucket_e2: int,
    kernel: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused multi-tenant bucket peel (ISSUE 4): vmap of the single-tenant
    ``_bucket_peel_body`` over a leading tenant axis of same-bucket
    compacted subproblems. The batched ``while_loop`` freezes converged
    lanes through ``select`` and every op is per-lane (exact int32 segment
    sums, elementwise f32 scalars), so each lane's triple is bit-identical
    to ``_bucket_peel_jit`` on its row; an all-sentinel pad lane (n_v = 0)
    converges at entry. One executable per (group, bucket) shape."""
    del bucket_e
    return jax.vmap(
        lambda s, d, v, e, bd, p: _bucket_peel_body(
            s, d, v, e, bd, p, eps, bucket_v, bucket_v2, bucket_e2, kernel)
    )(b_src, b_dst, n_v, n_e, best_density, passes)


@lru_cache(maxsize=None)
def _make_sharded_bucket_peel(mesh, eps: float, bucket_v: int, bucket_e: int,
                              bucket_v2: int, bucket_e2: int):
    """Cached jitted sharded analog of ``_bucket_peel_jit``: the bucket's
    edge lanes are partitioned across the mesh, each pass is a
    ``make_peel_pass`` body (per-shard segment-sum, psum'd scalar state),
    and the second-level ladder compacts *per shard* — each device packs its
    own live lanes into a local ``bucket_e2``-lane bucket (safe: the global
    live lane count is <= bucket_e2 at the switch point, so no shard can
    overflow). Lane order differs from the single-device ladder but int32
    segment-sums are order-invariant, so the returned (density, mask,
    passes) triple is bit-identical to ``_bucket_peel_jit`` on any device
    count."""
    axes = tuple(mesh.axis_names)
    n_dev = int(np.prod(list(mesh.shape.values())))
    if bucket_e % n_dev:
        raise ValueError(
            f"bucket_e={bucket_e} not divisible by {n_dev} devices")
    peel1 = make_peel_pass(mesh, bucket_v, eps)
    peel2 = make_peel_pass(mesh, bucket_v2, eps)

    def deg_body(src_l):
        d = jax.ops.segment_sum(
            jnp.ones_like(src_l, jnp.int32), jnp.minimum(src_l, bucket_v),
            num_segments=bucket_v + 1)[:bucket_v]
        return jax.lax.psum(d, axes)

    deg_hist = jax.shard_map(deg_body, mesh=mesh, in_specs=(P(axes),),
                             out_specs=P(), check_vma=False)

    def compact_body(src_l, dst_l, live_v):
        src_c = jnp.minimum(src_l, bucket_v - 1)
        dst_c = jnp.minimum(dst_l, bucket_v - 1)
        valid = (src_l < bucket_v) & (dst_l < bucket_v)
        live = valid & live_v[src_c] & live_v[dst_c]
        perm = jnp.cumsum(live_v.astype(jnp.int32)) - 1
        pos = jnp.where(live, jnp.cumsum(live.astype(jnp.int32)) - 1,
                        bucket_e2)
        b_src = jnp.full(bucket_e2, bucket_v2, jnp.int32).at[pos].set(
            perm[src_c].astype(jnp.int32), mode="drop")
        b_dst = jnp.full(bucket_e2, bucket_v2, jnp.int32).at[pos].set(
            perm[dst_c].astype(jnp.int32), mode="drop")
        return b_src, b_dst

    compact = jax.shard_map(
        compact_body, mesh=mesh, in_specs=(P(axes), P(axes), P()),
        out_specs=(P(axes), P(axes)), check_vma=False)

    @jax.jit
    def run(b_src, b_dst, n_v, n_e, best_density, passes):
        b_deg = deg_hist(b_src)
        b_active = jnp.arange(bucket_v, dtype=jnp.int32) < n_v
        state = PeelState(
            deg=b_deg,
            active=b_active,
            n_v=n_v.astype(jnp.int32),
            n_e=n_e.astype(jnp.int32),
            best_density=best_density.astype(jnp.float32),
            best_mask=jnp.zeros(bucket_v, dtype=bool),
            passes=passes.astype(jnp.int32),
        )

        def unfits(s: PeelState) -> jax.Array:
            return (s.n_v > 0) & ((s.n_v > bucket_v2) | (2 * s.n_e > bucket_e2))

        s1 = jax.lax.while_loop(
            unfits, lambda s: peel1(s, b_src, b_dst), state)
        b2_src, b2_dst = compact(b_src, b_dst, s1.active)
        perm = jnp.cumsum(s1.active.astype(jnp.int32)) - 1
        vslot = jnp.where(s1.active, perm, bucket_v2)
        b_deg2 = jnp.zeros(bucket_v2, jnp.int32).at[vslot].set(
            s1.deg, mode="drop")
        b_act2 = jnp.zeros(bucket_v2, bool).at[vslot].set(True, mode="drop")
        s2 = jax.lax.while_loop(
            lambda s: s.n_v > 0, lambda s: peel2(s, b2_src, b2_dst),
            PeelState(
                deg=b_deg2, active=b_act2, n_v=s1.n_v, n_e=s1.n_e,
                best_density=s1.best_density,
                best_mask=jnp.zeros(bucket_v2, dtype=bool),
                passes=s1.passes))
        improved = s2.best_density > s1.best_density
        mask_back = s1.active & s2.best_mask[jnp.minimum(perm, bucket_v2 - 1)]
        best_mask = jnp.where(improved, mask_back, s1.best_mask)
        return s2.best_density, best_mask, s2.passes

    SHARDED_JITS.append(run)
    return run


@lru_cache(maxsize=None)
def _make_sharded_batched_bucket_peel(mesh, eps: float, bucket_v: int,
                                      bucket_e: int, bucket_v2: int,
                                      bucket_e2: int):
    """Fused+sharded bucket peel: the whole per-tenant sequence of
    ``_make_sharded_bucket_peel`` (degree histogram, first-level peel,
    per-shard ladder compact, second-level peel, strict-``>`` merge back)
    vmapped over a leading tenant axis inside ONE shard_map program, so a
    same-bucket group of T tenants pays one psum per pass instead of T.
    Each tenant's triple is bit-identical to ``_bucket_peel_jit`` on its
    row (the single-tenant sharded docstring's order-invariance argument,
    plus while_loop batching's select-freeze for converged tenants)."""
    axes = tuple(mesh.axis_names)
    n_dev = mesh_device_count(mesh)
    if bucket_e % n_dev:
        raise ValueError(
            f"bucket_e={bucket_e} not divisible by {n_dev} devices")

    def tenant(b_src_l, b_dst_l, n_v, n_e, best_density, passes):
        d = jax.ops.segment_sum(
            jnp.ones_like(b_src_l, jnp.int32), jnp.minimum(b_src_l, bucket_v),
            num_segments=bucket_v + 1)[:bucket_v]
        b_deg = jax.lax.psum(d, axes)
        b_active = jnp.arange(bucket_v, dtype=jnp.int32) < n_v
        state = PeelState(
            deg=b_deg,
            active=b_active,
            n_v=n_v.astype(jnp.int32),
            n_e=n_e.astype(jnp.int32),
            best_density=best_density.astype(jnp.float32),
            best_mask=jnp.zeros(bucket_v, dtype=bool),
            passes=passes.astype(jnp.int32),
        )

        def unfits(s: PeelState) -> jax.Array:
            return (s.n_v > 0) & ((s.n_v > bucket_v2) | (2 * s.n_e > bucket_e2))

        s1 = jax.lax.while_loop(
            unfits,
            lambda s: _peel_pass_body(s, b_src_l, b_dst_l, bucket_v, eps,
                                      axes),
            state)
        # per-shard ladder compact (compact_body of the single-tenant run)
        src_c = jnp.minimum(b_src_l, bucket_v - 1)
        dst_c = jnp.minimum(b_dst_l, bucket_v - 1)
        valid = (b_src_l < bucket_v) & (b_dst_l < bucket_v)
        live = valid & s1.active[src_c] & s1.active[dst_c]
        perm = jnp.cumsum(s1.active.astype(jnp.int32)) - 1
        pos = jnp.where(live, jnp.cumsum(live.astype(jnp.int32)) - 1,
                        bucket_e2)
        b2_src = jnp.full(bucket_e2, bucket_v2, jnp.int32).at[pos].set(
            perm[src_c].astype(jnp.int32), mode="drop")
        b2_dst = jnp.full(bucket_e2, bucket_v2, jnp.int32).at[pos].set(
            perm[dst_c].astype(jnp.int32), mode="drop")
        vslot = jnp.where(s1.active, perm, bucket_v2)
        b_deg2 = jnp.zeros(bucket_v2, jnp.int32).at[vslot].set(
            s1.deg, mode="drop")
        b_act2 = jnp.zeros(bucket_v2, bool).at[vslot].set(True, mode="drop")
        s2 = jax.lax.while_loop(
            lambda s: s.n_v > 0,
            lambda s: _peel_pass_body(s, b2_src, b2_dst, bucket_v2, eps,
                                      axes),
            PeelState(
                deg=b_deg2, active=b_act2, n_v=s1.n_v, n_e=s1.n_e,
                best_density=s1.best_density,
                best_mask=jnp.zeros(bucket_v2, dtype=bool),
                passes=s1.passes))
        improved = s2.best_density > s1.best_density
        mask_back = s1.active & s2.best_mask[jnp.minimum(perm, bucket_v2 - 1)]
        best_mask = jnp.where(improved, mask_back, s1.best_mask)
        return s2.best_density, best_mask, s2.passes

    def body(b_src_l, b_dst_l, n_v, n_e, best_density, passes):
        # every per-tenant output crosses the psums inside ``tenant``
        return jax.vmap(
            lambda s, d, v, e, bd, p: tenant(s, d, v, e, bd, p)
        )(b_src_l, b_dst_l, n_v, n_e, best_density, passes)

    run = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axes), P(None, axes), P(), P(), P(), P()),
        out_specs=(P(), P(), P()), check_vma=False))
    SHARDED_JITS.append(run)
    return run


# ---------------------------------------------------------------------------
# host side: pass-0 simulation, compaction, and state merge
# ---------------------------------------------------------------------------
def _pass0_host(
    deg: np.ndarray, n_edges: int, eps: float
) -> tuple[np.ndarray, np.ndarray, int, np.float32]:
    """Replicate the peel's pass 0 in host float32: same ints, same f32
    threshold arithmetic as ``pbahmani_pass`` / ``peel_threshold``.
    Returns (active0, survivors, n_v0, rho0)."""
    active0 = deg > 0
    n_v0 = int(active0.sum())
    rho0 = np.float32(n_edges) / np.float32(max(n_v0, 1))
    thr0 = np.float32(2.0 * (1.0 + eps)) * rho0
    failed0 = active0 & (deg.astype(np.float32) <= thr0)
    return active0, active0 & ~failed0, n_v0, rho0


def _induced_slots(u: np.ndarray, v: np.ndarray, live_v: np.ndarray) -> np.ndarray:
    """Indices of undirected slots whose endpoints both survive ``live_v``
    (sentinel slots are dropped via the appended always-False row)."""
    lv = np.concatenate([live_v, np.zeros(1, dtype=bool)])
    return np.flatnonzero(lv[u] & lv[v])


def _emit_buckets(
    u: np.ndarray,
    v: np.ndarray,
    idx: np.ndarray,
    live_v: np.ndarray,
    bucket_v: int,
    bucket_e: int,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Remap the slots ``idx`` into sentinel(=bucket_v)-padded symmetric COO
    bucket arrays, **emitted dst-sorted**: the kernel tier's band-skip
    precondition, and — because the in-bucket compaction ladder preserves
    lane order under a monotone relabel — it survives every ladder rung
    without re-sorting. The scatter path's reductions are order-invariant
    int32 sums, so reordering lanes changes nothing there. Returns (perm,
    bucket_src, bucket_dst)."""
    k = idx.size
    if 2 * k > bucket_e or int(live_v.sum()) > bucket_v:
        raise ValueError("subproblem does not fit the requested buckets")
    perm = np.cumsum(live_v.astype(np.int64)) - 1
    bu = perm[u[idx]].astype(np.int32)
    bv_ = perm[v[idx]].astype(np.int32)
    bs = np.concatenate([bu, bv_])
    bd = np.concatenate([bv_, bu])
    order = np.argsort(bd, kind="stable")
    b_src = np.full(bucket_e, bucket_v, np.int32)
    b_dst = np.full(bucket_e, bucket_v, np.int32)
    b_src[:2 * k] = bs[order]
    b_dst[:2 * k] = bd[order]
    return perm, b_src, b_dst


def compact_candidates(
    u: np.ndarray,
    v: np.ndarray,
    live_v: np.ndarray,
    bucket_v: int,
    bucket_e: int,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Host-side fused compaction of the undirected slot arrays ``u, v``
    (sentinel-padded, sentinel == len(live_v)) to the subgraph induced by
    ``live_v``. Returns (perm, bucket_src, bucket_dst, live_lanes) with the
    bucket arrays in symmetric COO, sentinel(=bucket_v)-padded; ``perm`` is
    the order-preserving vertex index map (full id -> compact id, valid
    where ``live_v``)."""
    idx = _induced_slots(u, v, live_v)
    perm, b_src, b_dst = _emit_buckets(u, v, idx, live_v, bucket_v, bucket_e)
    return perm, b_src, b_dst, 2 * idx.size


@dataclass
class PrunedDispatch:
    """A host-prepared compacted subproblem awaiting its device bucket peel.

    Produced by :func:`prepare_pruned_peel`, consumed by
    :func:`merge_pruned_peel` once the device returns the bucket triple.
    The split exists so the fused multi-tenant layer (stream/fused.py) can
    prepare many tenants, group the dispatches by ``plan.buckets`` — plans
    grouped by bucket shape share one vmapped executable — and run each
    group as a single ``_batched_bucket_peel_jit`` call."""

    b_src: np.ndarray        # [bucket_e] sentinel(=bucket_v)-padded COO
    b_dst: np.ndarray
    n_v1: int                # pass-0 survivor count
    n_e1: int                # surviving undirected edges
    best_d1: np.float32      # best density after the host pass-0/1 merge
    eps: float
    plan: PrunePlan          # may have regrown/shrunk relative to the input
    perm: np.ndarray         # full id -> compact id (valid where ``a1``)
    a1: np.ndarray           # pass-0 survivor mask (full vertex space)
    active0: np.ndarray      # pass-0 live mask
    better1: bool            # host pass-1 density beat pass-0's
    observed: tuple[int, int]  # (n_v1, lanes1) handoff for bucket sizing


def prepare_pruned_peel(
    u: np.ndarray,
    v: np.ndarray,
    deg: np.ndarray,
    n_edges: int,
    eps: float,
    plan: PrunePlan,
) -> (PrunedDispatch
      | tuple[float, np.ndarray, int, tuple[int, int], PrunePlan] | None):
    """Host half of the pruned query: pass-0 simulation + compaction.

    Returns a :class:`PrunedDispatch` ready for the device bucket peel, or
    the finished result tuple directly for the trivial empty-graph case, or
    ``None`` when the survivor set fits no legal bucket (the caller runs
    its unpruned path)."""
    n_nodes = deg.shape[0]
    active0, a1, n_v0, rho0 = _pass0_host(deg, n_edges, eps)
    if n_v0 == 0:
        return float(rho0), active0, 0, (0, 0), plan
    n_v1 = int(a1.sum())
    idx = _induced_slots(u, v, a1)
    lanes1 = 2 * idx.size
    if n_v1 > plan.bucket_v or lanes1 > plan.bucket_e:
        # regrow to the observed size (pow-2 + slack) on the plan's own
        # sizing basis; the host knows the exact subproblem size before
        # dispatch, so no query is wasted
        plan = build_plan(
            plan.rho_lb, plan.k, plan.n_candidates, plan.n_candidate_edges,
            node_width=plan.node_width or n_nodes,
            lane_width=plan.lane_width or u.shape[0] * 2,
            observed=(n_v1, lanes1), n_vertices=plan.n_vertices or None,
        )
        if (not plan.enabled or n_v1 > plan.bucket_v
                or lanes1 > plan.bucket_e):
            return None
    else:
        shrunk = maybe_shrink_plan(plan, n_v1, lanes1)
        if shrunk is not None:
            plan = shrunk
    perm, b_src, b_dst = _emit_buckets(u, v, idx, a1, plan.bucket_v,
                                       plan.bucket_e)
    n_e1 = lanes1 // 2
    rho1 = (np.float32(n_e1) / np.float32(max(n_v1, 1))
            if n_v1 > 0 else np.float32(0.0))
    better1 = bool(rho1 > rho0)
    best_d1 = rho1 if better1 else rho0
    return PrunedDispatch(
        b_src=b_src, b_dst=b_dst, n_v1=n_v1, n_e1=n_e1,
        best_d1=np.float32(best_d1), eps=float(eps), plan=plan, perm=perm,
        a1=a1, active0=active0, better1=better1, observed=(n_v1, lanes1),
    )


def merge_pruned_peel(
    pd: PrunedDispatch, d_b, mask_b, passes_b
) -> tuple[float, np.ndarray, int, tuple[int, int], PrunePlan]:
    """Host merge of the device bucket triple back into the full vertex
    space — the exact strict-``>`` merge of the unpruned trajectory."""
    density = np.float32(d_b)
    passes = int(passes_b)
    if density > pd.best_d1:  # strict >: earliest best wins, as unpruned
        mask_b = np.asarray(mask_b)
        mask = pd.a1 & mask_b[np.minimum(pd.perm, pd.plan.bucket_v - 1)]
    else:
        mask = pd.a1 if pd.better1 else pd.active0
    return float(density), mask, passes, pd.observed, pd.plan


def pruned_peel_host(
    u: np.ndarray,
    v: np.ndarray,
    deg: np.ndarray,
    n_edges: int,
    eps: float,
    plan: PrunePlan,
    mesh=None,
    kernel: bool = False,
) -> tuple[float, np.ndarray, int, tuple[int, int], PrunePlan] | None:
    """The full pruned query: host pass-0 + compaction, device bucket peel,
    host merge. ``u, v`` are undirected host slot arrays (sentinel-padded),
    ``deg`` the exact int32 degree array (len == vertex space == sentinel).

    Returns (density, mask, passes, observed_handoff, plan) — ``plan`` may
    have grown if the observed survivor set missed the given buckets, or
    *shrunk* if the graph contracted past the hysteresis (the host sees the
    exact size before dispatch, so no query is ever wasted; bit-identity
    holds for every bucket choice). Returns ``None`` when the survivor set
    cannot fit any legal bucket (pruning would not pay off); the caller
    runs its unpruned path.

    With ``mesh`` the bucket peel runs sharded: bucket lanes partitioned
    over the mesh devices via ``_make_sharded_bucket_peel`` — same triple,
    one tenant's candidate set spanning the mesh. ``kernel`` selects the
    Pallas segment-sum tier inside the single-device bucket peel (the
    bucket COO is emitted dst-sorted either way); the sharded path stays
    on per-shard scatter — lanes are mesh-partitioned, not band-local.
    """
    prep = prepare_pruned_peel(u, v, deg, n_edges, eps, plan)
    if prep is None or isinstance(prep, tuple):
        return prep
    pd = prep
    plan = pd.plan
    if mesh is None:
        d_b, mask_b, passes_b = _bucket_peel_jit(
            jnp.asarray(pd.b_src), jnp.asarray(pd.b_dst),
            jnp.asarray(pd.n_v1, jnp.int32), jnp.asarray(pd.n_e1, jnp.int32),
            jnp.asarray(pd.best_d1, jnp.float32), jnp.asarray(1, jnp.int32),
            float(eps), *plan.buckets, kernel,
        )
    else:
        if plan.bucket_e % mesh_device_count(mesh):
            # the candidate set is smaller than one lane per device can
            # express — pruning cannot pay off on this mesh; fall back to
            # the (always shardable) full-width path instead of raising
            return None
        run = _make_sharded_bucket_peel(mesh, float(eps), *plan.buckets)
        sh = edge_sharding(mesh)
        d_b, mask_b, passes_b = run(
            jax.device_put(pd.b_src, sh), jax.device_put(pd.b_dst, sh),
            jnp.asarray(pd.n_v1, jnp.int32), jnp.asarray(pd.n_e1, jnp.int32),
            jnp.asarray(pd.best_d1, jnp.float32), jnp.asarray(1, jnp.int32),
        )
    return merge_pruned_peel(pd, d_b, mask_b, passes_b)


def plan_for_graph(
    graph: Graph, prev_mask: np.ndarray | None = None,
    observed: tuple[int, int] | None = None,
    kernel: bool = False,
) -> PrunePlan:
    """Analyze a static graph: rho~ bootstrap + candidate core + buckets.
    ``kernel`` routes the analysis' core fixpoint through the Pallas tier
    (fed the cached dst-sorted view) — the plan integers are identical."""
    n = graph.n_nodes
    if n == 0 or graph.n_edges == 0:
        return build_plan(0.0, 1, 0, 0, max(n, 1), max(graph.src.shape[0], 1))
    pm = (jnp.zeros(n, dtype=bool) if prev_mask is None
          else jnp.asarray(prev_mask, dtype=bool))
    src_h, dst_h = graph.dst_sorted() if kernel else (graph.src, graph.dst)
    rho_lb, k, _, n_cand, ne_cand = _plan_jit(
        jnp.asarray(src_h), jnp.asarray(dst_h), pm,
        jnp.asarray(graph.n_edges, jnp.int32), n, kernel,
    )
    return build_plan(
        float(rho_lb), int(k), int(n_cand), int(ne_cand),
        node_width=n, lane_width=graph.src.shape[0], observed=observed,
        n_vertices=n,
    )


def pbahmani_pruned(
    graph: Graph, eps: float = 0.0, plan: PrunePlan | None = None,
    kernel: bool | None = None,
) -> tuple[float, np.ndarray, int]:
    """Candidate-pruned P-Bahmani: bit-identical to ``pbahmani(graph, eps)``
    (density, mask AND pass count), at bucket-width device cost. ``kernel``
    selects the Pallas segment-sum tier for the bucket peel (None = deploy
    default) — same triple either way."""
    kernel = bool(kernel)
    if plan is None:
        plan = plan_for_graph(graph, kernel=kernel)
    if not plan.enabled or graph.n_nodes == 0:
        from repro.core.pbahmani import pbahmani

        return pbahmani(graph, eps=eps, kernel=kernel)
    half = graph.n_directed // 2
    # undirected slot view, one sentinel pad slot so empty graphs stay valid
    u = np.concatenate([
        graph.src[:half].astype(np.int64),
        np.asarray([graph.n_nodes], np.int64),
    ])
    v = np.concatenate([
        graph.dst[:half].astype(np.int64),
        np.asarray([graph.n_nodes], np.int64),
    ])
    res = pruned_peel_host(
        u, v, graph.degrees().astype(np.int32), graph.n_edges, float(eps),
        plan, kernel=kernel,
    )
    if res is None:
        from repro.core.pbahmani import pbahmani

        return pbahmani(graph, eps=eps, kernel=kernel)
    density, mask, passes, _, _ = res
    return float(density), mask, passes


__all__ = [
    "PrunePlan",
    "PrunedDispatch",
    "prepare_pruned_peel",
    "merge_pruned_peel",
    "build_plan",
    "maybe_shrink_plan",
    "make_sharded_plan",
    "plan_for_graph",
    "compact_candidates",
    "pruned_peel_host",
    "pbahmani_pruned",
    "MIN_BUCKET_V",
    "MIN_BUCKET_E",
    "BUCKET_SHRINK_HYSTERESIS",
]
