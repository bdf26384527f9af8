"""Fused multi-tenant execution: vmap-batched peels over capacity buckets.

The registry has always shared *executables* across tenants in the same
capacity bucket, but every query still launched one program per tenant — at
"millions of users" scale dispatch overhead and per-pass reduction latency
dominate small tenants, exactly the regime the source paper's shared-memory
parallelism targets. This module shares the *launch* too (ISSUE 4):

  * :class:`TenantBatch` stacks the device state of every tenant in one
    (node_capacity, edge_capacity, eps) bucket into leading-axis arrays —
    ``[T, 2*capacity]`` edge slots, ``[T, node_capacity]`` degrees and
    warm-seed masks — where ``T`` is a pow-2 lane count. Each tenant owns
    one lane; join and evict are a cheap row swap through one jitted
    lane-write program with a *traced* lane index, so bucket membership
    churn never recompiles anything.
  * ingest, the warm peel, and the pruned bucket peel each run as a single
    ``vmap``-ed jitted program per bucket (``_batched_apply_jit``,
    ``_batched_warm_peel_jit`` in delta.py, ``_batched_bucket_peel_jit`` in
    core/prune.py — the multi-graph analogue of Bahmani et al.'s
    pass-efficiency argument). jax batches the peel's ``while_loop`` by
    running the pass body while ANY lane is live and freezing converged
    lanes through ``select`` — the per-tenant early-exit mask that keeps a
    straggler from serializing anyone's *result* (its lanes ride along
    converged, at vector width).
  * :class:`FusedEngine` is a drop-in :class:`~repro.stream.delta.DeltaEngine`
    whose device state lives in its bucket's lanes. Every per-lane op is
    the exact single-tenant recurrence (same int32 segment sums, same f32
    scalars), so a fused tenant's (density, mask, passes) triple is
    *bit-identical* to an unbatched engine fed the same stream — the
    invariant asserted per query in tests/test_tenants.py and
    benchmarks/bench_tenants.py.
  * :func:`query_group` answers many tenants with at most one batched warm
    peel and one batched bucket peel per bucket, the latter in the plan
    shape the bucket holds (``TenantBatch.peel_buckets``); the service's
    coalescing window and ``top_k_densest`` route through it.

Cost model: a fused flush gathers only the *queried* lanes into a pow-2
group (``_lane_gather_jit``) before peeling, so one tenant's query costs
one lane of work, not the whole stack; a 16-tenant sweep costs one program
whose passes bound is the max over members — the aggregate-throughput win
measured in benchmarks/bench_tenants.py (>=3x at 16 small tenants vs
sequential dispatch).

Sharded tenants fuse too (ISSUE 9): a bucket whose tenants are mesh-sharded
keeps its slot stacks as ``[T, lanes]`` arrays with the *lane* axis sharded
over the mesh (``stacked_edge_sharding``) and vmaps the per-shard pass
bodies *inside* one shard_map program (``make_sharded_batched_warm_peel``,
``_make_sharded_batched_apply``, ``_make_sharded_batched_bucket_peel``,
``_make_sharded_batched_refine_round``). Named-axis collectives commute
with ``vmap`` — the batching rule all-reduces the whole ``[T, V]`` delta
stack at once — so T sharded tenants pay ONE ``psum`` per pass where solo
sharded engines paid T; per-tenant triples stay bit-identical to the solo
single-device engine on any device count. The mesh is part of the pool's
bucket key, so differently-sharded tenants never share a stack.
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import replace as dc_replace

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.density import peel_threshold, ratio
from repro.core.distributed import (
    make_sharded_batched_warm_peel, mesh_device_count,
)
from repro.core.pbahmani import PeelState
from repro.core.prune import (
    _batched_bucket_peel_jit, _make_sharded_batched_bucket_peel,
    hold_group_buckets, merge_pruned_peel, prepare_pruned_peel,
)
from repro.obs.audit import AUDITOR
from repro.obs.trace import get_tracer, span, tally
from repro.refine.certify import (
    better_fraction, dual_fraction, make_certificate, max_fraction,
)
from repro.refine.engine import DEFAULT_TARGET_GAP
from repro.refine.loads import (
    _batched_dense_refine_round_jit, _batched_refine_round_jit,
    _make_sharded_batched_refine_round,
)
from repro.stream.buffer import MIN_CAPACITY, next_pow2
from repro.stream.delta import (
    DeltaEngine, QueryResult, _apply_batch_body, _batched_apply_jit,
    _batched_warm_peel_jit, _make_sharded_batched_apply,
    _make_sharded_deg_rows_gather, _make_sharded_lane_gather,
    _make_sharded_lane_write, _make_sharded_mask_rows_write,
    _make_sharded_row_view, _make_sharded_stack_sync, MIN_BATCH,
)

MIN_LANES = 4  # smallest lane stack; doubles when a bucket fills
# buckets whose (pow-2) vertex space fits under this bound additionally
# maintain a dense [T, V, V] float32 adjacency stack and peel through
# GEMV-based passes — the paper's shared-memory adjacency model at vector
# width. The scatter-based pass is serial per edge on CPU (no SIMD win
# from batching), while a batched matvec vectorizes across the whole
# bucket; every value involved is an integer < 2^24, so float32 matmul
# accumulation is exact and the trajectory stays bit-identical. Memory is
# the gate: V=512 is 1 MiB per lane.
DENSE_NODE_CAP = 512


# ---------------------------------------------------------------------------
# lane-management jitted entry points (counted by DeltaEngine.compile_count)
# ---------------------------------------------------------------------------
@jax.jit
def _lane_write_jit(src, dst, deg, mask, lane, r_src, r_dst, r_deg, r_mask):
    """Row swap: write one tenant's full state into lane ``lane``. The lane
    index is *traced*, so every join/evict/resync in a bucket reuses one
    executable — membership churn never recompiles."""
    return (src.at[lane].set(r_src), dst.at[lane].set(r_dst),
            deg.at[lane].set(r_deg), mask.at[lane].set(r_mask))


@jax.jit
def _mask_rows_write_jit(mask_stack, lanes, masks):
    """Scatter G updated warm-seed masks into their lanes (pow-2 padded;
    OOB pad lanes dropped)."""
    return mask_stack.at[lanes].set(masks, mode="drop")


@jax.jit
def _lane_gather_jit(src, dst, deg, mask, lanes):
    """Gather the queried lanes into a dense pow-2 group for the batched
    warm peel — a flush costs work proportional to the group, not the
    whole stack."""
    return src[lanes], dst[lanes], deg[lanes], mask[lanes]


@jax.jit
def _adj_lane_write_jit(adj, lane, row):
    return adj.at[lane].set(row)


@jax.jit
def _rows_gather_jit(stack, lanes):
    """Gather selected lanes of one stacked array (adjacency rows for the
    dense peel, degree rows for the pruned host prepare) — flush cost stays
    proportional to the queried group, not the whole stack."""
    return stack[lanes]


@partial(jax.jit, static_argnames=("n_nodes",))
def _batched_apply_dense_jit(src, dst, deg, adj, slots, su, sv, du, dv, w,
                             n_nodes: int):
    """Dense-bucket ingest as ONE program (ISSUE 5 satellite; previously
    the COO scatter and the adjacency scatter dispatched separately): the
    vmapped slot/histogram update of ``_batched_apply_jit`` fused with the
    adjacency pair-scatter of the signed weights (+1/-1 insert/delete, 0
    padding; sentinel endpoints index out of bounds and drop). Exact
    float32 integers, so the dense state tracks the COO state bit for
    bit."""
    def body(a, b, c, A, d, e, f, g, h, i):
        a, b, c = _apply_batch_body(a, b, c, d, e, f, g, h, i, n_nodes)
        wf = i.astype(jnp.float32)
        A = A.at[g, h].add(wf, mode="drop").at[h, g].add(wf, mode="drop")
        return a, b, c, A

    return jax.vmap(body)(src, dst, deg, adj, slots, su, sv, du, dv, w)


def _dense_pass(state: PeelState, adj: jax.Array, eps: float) -> PeelState:
    """One peeling pass off the dense adjacency — the exact integer
    recurrence of ``pbahmani_pass`` with the edge-lane segment sums
    replaced by matvecs (``adj @ failed`` is the paper's atomicSub round as
    one GEMV). Every float32 sum is over integers bounded by 2|E| < 2^24,
    hence order-independent and exact: the (density, mask, passes)
    trajectory is bit-identical to the lane-based pass."""
    thr = peel_threshold(state.n_e, state.n_v, eps)
    failed = state.active & (state.deg.astype(jnp.float32) <= thr)
    f = failed.astype(jnp.float32)
    a = state.active.astype(jnp.float32)
    af = adj @ f  # failed-neighbor counts (exact integers)
    # counts reach V-1 = 511, and a default-precision f32 dot may run on the
    # TPU's MXU with bf16 operands, exact only up to 256. A v5e gave exact
    # vdots here even so (the Pallas segsum dot did not); HIGHEST makes
    # exactness a guarantee rather than a property of the lowering
    hi = jax.lax.Precision.HIGHEST
    removed_directed = (
        2.0 * jnp.vdot(f, adj @ a, precision=hi)
        - jnp.vdot(f, af, precision=hi)).astype(jnp.int32)
    n_e_new = state.n_e - removed_directed // 2
    active_new = state.active & ~failed
    deg_new = jnp.where(active_new, state.deg - af.astype(jnp.int32), 0)
    n_v_new = state.n_v - jnp.sum(failed.astype(jnp.int32))
    rho_new = jnp.where(n_v_new > 0, ratio(n_e_new, n_v_new), 0.0)
    better = rho_new > state.best_density
    return PeelState(
        deg=deg_new.astype(jnp.int32),
        active=active_new,
        n_v=n_v_new,
        n_e=n_e_new,
        best_density=jnp.where(better, rho_new, state.best_density),
        best_mask=jnp.where(better, active_new, state.best_mask),
        passes=state.passes + 1,
    )


def _dense_warm_peel_body(adj, deg, n_edges, prev_mask, eps: float):
    """Dense analog of ``_warm_peel_body``: same init off the maintained
    degrees, same loop, same prev-mask re-evaluation (pm' A pm / 2 is the
    induced directed count, exactly ``induced_edge_count``)."""
    active = deg > 0
    n_v = jnp.sum(active.astype(jnp.int32))
    n_e = n_edges.astype(jnp.int32)
    rho0 = ratio(n_e, n_v)
    state = PeelState(
        deg=deg.astype(jnp.int32),
        active=active,
        n_v=n_v,
        n_e=n_e,
        best_density=rho0,
        best_mask=active,
        passes=jnp.asarray(0, jnp.int32),
    )
    final = jax.lax.while_loop(
        lambda s: s.n_v > 0, lambda s: _dense_pass(s, adj, eps), state)
    pm = prev_mask.astype(jnp.float32)
    warm_e = jnp.vdot(pm, adj @ pm, precision=jax.lax.Precision.HIGHEST
                      ).astype(jnp.int32) // 2
    warm_v = jnp.sum(prev_mask.astype(jnp.int32))
    warm_rho = jnp.where(warm_v > 0, ratio(warm_e, warm_v), 0.0)
    return final, warm_rho


@partial(jax.jit, static_argnames=("eps",))
def _batched_dense_warm_peel_jit(adj, deg, n_edges, prev_mask, eps: float):
    """vmap of the dense warm peel over the gathered group rows — the fused
    program that makes 16 small tenants cost one batched-GEMV loop instead
    of 16 serial scatter loops."""
    return jax.vmap(
        lambda A, d, ne, pm: _dense_warm_peel_body(A, d, ne, pm, eps)
    )(adj, deg, n_edges, prev_mask)


FUSED_JITS = [_lane_write_jit, _mask_rows_write_jit, _lane_gather_jit,
              _adj_lane_write_jit, _rows_gather_jit,
              _batched_apply_dense_jit, _batched_dense_warm_peel_jit]


# ---------------------------------------------------------------------------
# the per-bucket lane stack
# ---------------------------------------------------------------------------
class TenantBatch:
    """Stacked device state for every tenant in one capacity bucket.

    ``kernel`` routes the batched warm/bucket/refine peels through the
    Pallas segment-sum tier. Fused lanes keep the *unsorted* resident
    layout (per-lane sorted views are a ROADMAP follow-up): the kernel
    recomputes its bands from the data each call, so results stay
    bit-identical — only the band-skip win is smaller than the unbatched
    engine's sorted path. The flag is part of the pool's bucket key, since
    it is a static argument of every batched program.

    ``mesh`` makes the stack *sharded*: the slot arrays' lane axis is
    distributed over the mesh and every batched program runs
    vmap-inside-shard_map, paying one collective per pass for the whole
    bucket. The dense [T, V, V] tier is replicated-only and stays off for
    sharded buckets (its GEMV passes have no sharded analogue here)."""

    def __init__(self, node_capacity: int, edge_capacity: int, eps: float,
                 lanes: int = MIN_LANES, kernel: bool = False, mesh=None):
        self.node_capacity = int(node_capacity)
        self.edge_capacity = int(edge_capacity)
        self.eps = float(eps)
        self.kernel = bool(kernel)
        self.mesh = mesh
        self.sharded = mesh is not None
        self.lanes = max(next_pow2(lanes), MIN_LANES)
        # small vertex spaces additionally keep the dense adjacency stack
        # and peel through batched GEMVs (see DENSE_NODE_CAP)
        self.dense = self.node_capacity <= DENSE_NODE_CAP and mesh is None
        self.lane_of: dict[str, int] = {}
        self._free = list(range(self.lanes - 1, -1, -1))
        self.lane_generation: dict[int, int] = {}
        self.n_ingests = 0      # ingest batches absorbed
        self.n_ingest_dispatches = 0  # programs launched for them — equal
                                      # to n_ingests since the dense-bucket
                                      # COO+adjacency fusion (one program
                                      # per ingest, dense or sparse)
        self.n_group_peels = 0  # fused query flushes
        self._peel_buckets: tuple | None = None  # see peel_buckets
        self._alloc(self.lanes)

    def peel_buckets(self, shapes) -> tuple[int, int, int, int]:
        """The plan-bucket shape of this bucket's batched pruned peel for
        members whose ``PrunePlan.buckets`` are ``shapes``: the shape held
        from earlier flushes, grown to fit them and shrunk with the plans'
        own hysteresis (``core.prune.hold_group_buckets``). A flush peels
        all its pruned members in this one shape, so the executable depends
        on the pow-2 group size, not on which tenants were asked."""
        self._peel_buckets = hold_group_buckets(self._peel_buckets, shapes)
        return self._peel_buckets

    @property
    def n_shards(self) -> int:
        return mesh_device_count(self.mesh) if self.sharded else 1

    def _commit_stacks(self, src, dst, deg, mask) -> None:
        """Round-trip host stacks through the identity shard_map program so
        every resident sharded array carries the committed stacked sharding
        the batched entry points expect (the ``_make_sharded_resync``
        laundering convention, lifted to lane stacks)."""
        self._src, self._dst, self._deg, self._prev_mask = (
            _make_sharded_stack_sync(self.mesh)(
                jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32),
                jnp.asarray(deg, jnp.int32), jnp.asarray(mask, dtype=bool)))

    def _alloc(self, lanes: int) -> None:
        sent = self.node_capacity
        if self.sharded:
            self._commit_stacks(
                np.full((lanes, 2 * self.edge_capacity), sent, np.int32),
                np.full((lanes, 2 * self.edge_capacity), sent, np.int32),
                np.zeros((lanes, self.node_capacity), np.int32),
                np.zeros((lanes, self.node_capacity), bool))
            self._adj = None
            return
        self._src = jnp.full((lanes, 2 * self.edge_capacity), sent, jnp.int32)
        self._dst = jnp.full((lanes, 2 * self.edge_capacity), sent, jnp.int32)
        self._deg = jnp.zeros((lanes, self.node_capacity), jnp.int32)
        self._prev_mask = jnp.zeros((lanes, self.node_capacity), bool)
        self._adj = (jnp.zeros((lanes, sent, sent), jnp.float32)
                     if self.dense else None)

    def _grow(self) -> None:
        """Double the lane count (a capacity event, like buffer growth —
        the shapes change, so the next programs compile once for the new
        stack width; steady state is unaffected)."""
        old = self.lanes
        src, dst = np.asarray(self._src), np.asarray(self._dst)
        deg, mask = np.asarray(self._deg), np.asarray(self._prev_mask)
        adj = np.asarray(self._adj) if self.dense else None
        self.lanes = old * 2
        if self.sharded:
            # prefix-copy on host, then one laundering upload of the
            # doubled stacks (a grow is a compile event either way)
            sent = self.node_capacity
            ns = np.full((self.lanes, 2 * self.edge_capacity), sent,
                         np.int32)
            nd = np.full((self.lanes, 2 * self.edge_capacity), sent,
                         np.int32)
            ng = np.zeros((self.lanes, self.node_capacity), np.int32)
            nm = np.zeros((self.lanes, self.node_capacity), bool)
            ns[:old], nd[:old], ng[:old], nm[:old] = src, dst, deg, mask
            self._commit_stacks(ns, nd, ng, nm)
        else:
            self._alloc(self.lanes)
            self._src = self._src.at[:old].set(src)
            self._dst = self._dst.at[:old].set(dst)
            self._deg = self._deg.at[:old].set(deg)
            self._prev_mask = self._prev_mask.at[:old].set(mask)
            if self.dense:
                self._adj = self._adj.at[:old].set(adj)
        self._free = list(range(self.lanes - 1, old - 1, -1)) + self._free

    # -- membership ---------------------------------------------------------
    def join(self, name: str) -> int:
        """Allocate a lane for ``name`` (caller writes the state)."""
        if name in self.lane_of:
            return self.lane_of[name]
        if not self._free:
            self._grow()
        lane = self._free.pop()
        self.lane_of[name] = lane
        return lane

    def evict(self, name: str) -> None:
        """Free ``name``'s lane and blank it (same row-write executable as
        a join — an evict/join pair is two dispatches, zero compiles)."""
        lane = self.lane_of.pop(name, None)
        if lane is None:
            return
        sent = np.full(2 * self.edge_capacity, self.node_capacity, np.int32)
        self.write_lane(lane, sent, sent,
                        np.zeros(self.node_capacity, np.int32),
                        np.zeros(self.node_capacity, bool), generation=-1)
        self.lane_generation.pop(lane, None)
        self._free.append(lane)

    def write_lane(self, lane: int, src, dst, deg, mask,
                   generation: int) -> None:
        write = (_make_sharded_lane_write(self.mesh) if self.sharded
                 else _lane_write_jit)
        self._src, self._dst, self._deg, self._prev_mask = write(
            self._src, self._dst, self._deg, self._prev_mask,
            jnp.asarray(lane, jnp.int32), jnp.asarray(src, jnp.int32),
            jnp.asarray(dst, jnp.int32), jnp.asarray(deg, jnp.int32),
            jnp.asarray(mask, dtype=bool))
        if self.dense:
            nc = self.node_capacity
            adj = np.zeros((nc, nc), np.float32)
            src = np.asarray(src)
            valid = src < nc
            np.add.at(adj, (src[valid], np.asarray(dst)[valid]), 1.0)
            self._adj = _adj_lane_write_jit(
                self._adj, jnp.asarray(lane, jnp.int32), jnp.asarray(adj))
        self.lane_generation[lane] = generation

    def set_mask_rows(self, lanes, masks) -> None:
        """Scatter updated warm-seed masks. Always padded to the full lane
        count (OOB pad lanes drop): how many masks a flush updates is
        data-dependent, and a count-sized pad would compile one executable
        per count — a constant [lanes, V] shape keeps the zero-recompile
        contract at the cost of copying a few kilobytes of padding."""
        k = len(lanes)
        li = np.full(self.lanes, self.lanes, np.int32)
        li[:k] = lanes
        mm = np.zeros((self.lanes, self.node_capacity), bool)
        mm[:k] = masks
        write = (_make_sharded_mask_rows_write(self.mesh) if self.sharded
                 else _mask_rows_write_jit)
        tally("h2d_bytes", li.nbytes + mm.nbytes)
        self._prev_mask = write(
            self._prev_mask, jnp.asarray(li), jnp.asarray(mm))

    # -- fused programs -----------------------------------------------------
    def ingest(self, rows: dict[int, tuple]) -> int:
        """One fused scatter+histogram over all lanes with pending update
        rows (other lanes ride along as exact no-ops). Returns the padded
        batch width dispatched."""
        b = max(max(r[0].shape[0] for r in rows.values()), MIN_BATCH)
        lanes, cap, sent = self.lanes, self.edge_capacity, self.node_capacity
        slots = np.full((lanes, b), 2 * cap, np.int32)
        su = np.full((lanes, b), sent, np.int32)
        sv = np.full((lanes, b), sent, np.int32)
        du = np.full((lanes, b), sent, np.int32)
        dv = np.full((lanes, b), sent, np.int32)
        w = np.zeros((lanes, b), np.int32)
        for lane, (r_slots, r_su, r_sv, r_du, r_dv, r_w) in rows.items():
            k = r_slots.shape[0]
            slots[lane, :k] = r_slots
            su[lane, :k] = r_su
            sv[lane, :k] = r_sv
            du[lane, :k] = r_du
            dv[lane, :k] = r_dv
            w[lane, :k] = r_w
        rows_h = (slots, su, sv, du, dv, w)
        tally("h2d_bytes", sum(a.nbytes for a in rows_h))
        args = tuple(jnp.asarray(a) for a in rows_h)
        if self.dense:
            # one fused program: COO scatter + histogram + adjacency scatter
            self._src, self._dst, self._deg, self._adj = (
                _batched_apply_dense_jit(
                    self._src, self._dst, self._deg, self._adj, *args,
                    self.node_capacity))
        elif self.sharded:
            self._src, self._dst, self._deg = _make_sharded_batched_apply(
                self.mesh, self.node_capacity)(
                    self._src, self._dst, self._deg, *args)
        else:
            self._src, self._dst, self._deg = _batched_apply_jit(
                self._src, self._dst, self._deg, *args, self.node_capacity)
        self.n_ingests += 1
        self.n_ingest_dispatches += 1
        return b

    def peel_rows(self, lanes: np.ndarray, n_edges: np.ndarray):
        """Batched warm peel over the queried lanes (pow-2 group, padded by
        duplicating the first member so pad lanes add no extra passes).
        Returns the stacked (PeelState, warm_rho) for the group rows."""
        g = int(lanes.size)
        gp = next_pow2(max(g, 1))
        li = np.full(gp, int(lanes[0]), np.int32)
        li[:g] = lanes
        ne = np.full(gp, int(n_edges[0]), np.int32)
        ne[:g] = n_edges
        tally("h2d_bytes", li.nbytes + ne.nbytes)
        gather = (_make_sharded_lane_gather(self.mesh) if self.sharded
                  else _lane_gather_jit)
        src_g, dst_g, deg_g, mask_g = gather(
            self._src, self._dst, self._deg, self._prev_mask, jnp.asarray(li))
        if self.dense:
            adj_g = _rows_gather_jit(self._adj, jnp.asarray(li))
            return _batched_dense_warm_peel_jit(
                adj_g, deg_g, jnp.asarray(ne), mask_g, self.eps)
        if self.sharded:
            return make_sharded_batched_warm_peel(
                self.mesh, self.node_capacity, self.eps)(
                    src_g, dst_g, deg_g, jnp.asarray(ne), mask_g)
        return _batched_warm_peel_jit(
            src_g, dst_g, deg_g, jnp.asarray(ne), mask_g,
            self.node_capacity, self.eps, self.kernel)

    def gather_deg_rows(self, lanes) -> jax.Array:
        """Degree rows for a pow-2 group of lanes (the pruned host prepare
        reads these per member)."""
        lanes = np.asarray(lanes, np.int32)
        tally("h2d_bytes", lanes.nbytes)
        gather = (_make_sharded_deg_rows_gather(self.mesh) if self.sharded
                  else _rows_gather_jit)
        return gather(self._deg, jnp.asarray(lanes))

    def __repr__(self) -> str:  # pragma: no cover
        return (f"TenantBatch(|V|={self.node_capacity}, "
                f"cap={self.edge_capacity}, eps={self.eps}, "
                f"lanes={len(self.lane_of)}/{self.lanes})")


class FusedPool:
    """(node_capacity, edge_capacity, eps, kernel, mesh) -> TenantBatch
    map. One pool per registry: tenants that bucket together land in the
    same lane stack and therefore the same fused programs. The mesh is part
    of the key (a ``jax.sharding.Mesh`` hashes by devices + axis names), so
    sharded and replicated tenants — or tenants on different meshes —
    never share a stack: every argument that determines a fused
    executable's shape or placement must appear here (the RPR501
    bucket-key completeness rule lints exactly this)."""

    def __init__(self):
        self.batches: dict[tuple, TenantBatch] = {}

    def batch_for(self, node_capacity: int, edge_capacity: int,
                  eps: float, kernel: bool = False, mesh=None) -> TenantBatch:
        key = (int(node_capacity), int(edge_capacity), float(eps),
               bool(kernel), mesh)
        batch = self.batches.get(key)
        if batch is None:
            batch = self.batches[key] = TenantBatch(
                key[0], key[1], key[2], kernel=key[3], mesh=mesh)
        return batch

    def place(self, eng: "FusedEngine") -> None:
        """Ensure ``eng`` owns a lane in the batch matching its *current*
        buffer capacity — a capacity change (grow/shrink) migrates the
        tenant between buckets (evict + join: two row swaps)."""
        batch = self.batch_for(eng.node_capacity, eng.buffer.capacity,
                               eng.eps, eng.kernel, mesh=eng.mesh)
        if eng.batch is batch:
            return
        if eng.batch is not None:
            eng.batch.evict(eng.name)
        eng._lane = batch.join(eng.name)
        eng.batch = batch


# ---------------------------------------------------------------------------
# the drop-in engine
# ---------------------------------------------------------------------------
class FusedEngine(DeltaEngine):
    """A DeltaEngine whose device state is a lane of a shared TenantBatch.

    Host bookkeeping (EdgeBuffer, staleness, plans, metrics) is inherited
    unchanged; every device dispatch routes through the bucket's stacked
    arrays. Single queries run as a group of one (same batched executables,
    compiled once per bucket); ``query_group`` fuses many tenants' queries
    into one flush."""

    def __init__(self, name: str, pool: FusedPool, n_nodes: int,
                 eps: float = 0.0, capacity: int = MIN_CAPACITY,
                 refresh_every: int = 32, pruned: bool = True,
                 sharded: bool = False, mesh=None,
                 kernel: bool | None = None):
        super().__init__(n_nodes, eps=eps, capacity=capacity,
                         refresh_every=refresh_every, pruned=pruned,
                         sharded=sharded, mesh=mesh, kernel=kernel)
        self.name = str(name)
        self.pool = pool
        self.batch: TenantBatch | None = None
        self._lane: int | None = None
        self.fused = True
        self.tenant = str(name)
        self.kind = "fused+sharded" if self.sharded else "fused"

    def _audit_shape(self) -> tuple:
        # the lane-stack width is a dispatch-shape determinant for every
        # batched program this engine's ops can launch (a lane-stack grow
        # legitimately compiles once for the new width)
        lanes = self.batch.lanes if self.batch is not None else 0
        return super()._audit_shape() + (lanes,)

    # -- device-state plumbing ---------------------------------------------
    def _sync_views(self) -> None:
        """Materialize this lane's rows as the ``_src``/``_dst``/``_deg``/
        ``_prev_mask`` attributes the inherited host paths read (plan
        rebuild, pruned prepare, cbds). Row slices share the unbatched
        engines' executable shapes, so those paths stay cache hits; on a
        sharded bucket the gather runs through ``_make_sharded_row_view``,
        whose output shardings match ``_make_sharded_resync`` — the
        inherited sharded entry points see the solo engine's placement."""
        if self.sharded:
            batch = self.batch
            self._src, self._dst, self._deg, self._prev_mask = (
                _make_sharded_row_view(self.mesh)(
                    batch._src, batch._dst, batch._deg, batch._prev_mask,
                    jnp.asarray(self._lane, jnp.int32)))
            return
        self._src = self.batch._src[self._lane]
        self._dst = self.batch._dst[self._lane]
        self._deg = self.batch._deg[self._lane]
        self._prev_mask = self.batch._prev_mask[self._lane]

    def _resync_device(self) -> None:
        prev = np.asarray(self._prev_mask)
        src, dst, deg = self.buffer.resident_state(self.node_capacity)
        self.pool.place(self)  # capacity changes migrate buckets here
        self.batch.write_lane(self._lane, src, dst, deg, prev,
                              self.buffer.generation)
        self._generation = self.buffer.generation
        self._sync_views()

    def _dispatch_batch(self, slots, su, sv, du, dv, w) -> None:
        row = (slots, su, sv, du, dv, w)
        if getattr(self, "_staging", False):
            self._staged_row = row  # collected by ingest_group
            return
        self.batch.ingest({self._lane: row})

    def release(self) -> None:
        """Give the lane back (registry eviction / removal)."""
        if self.batch is not None:
            self.batch.evict(self.name)
            self.batch = None
            self._lane = None
            self._generation = -1

    # -- inherited paths that need fresh row views --------------------------
    def _rebuild_plan(self) -> None:
        self._sync_views()
        super()._rebuild_plan()

    def _run_pruned_peel(self):
        self._sync_views()
        res = super()._run_pruned_peel()
        if res is not None:
            self._push_prev_mask()
        return res

    def _push_prev_mask(self) -> None:
        self.batch.set_mask_rows([self._lane],
                                 np.asarray(self._prev_mask)[None, :])

    def _cold_full_peel(self):
        """Epoch re-anchor through the batched peel (group of one). The
        maintained-state init is bit-identical to ``init_state``'s cold
        histogram, so the triple matches the unbatched ``_pbahmani_jit``."""
        final, _ = self.batch.peel_rows(
            np.asarray([self._lane], np.int32),
            np.asarray([self.buffer.n_edges], np.int32))
        row = jax.tree_util.tree_map(lambda x: x[0], final)
        self.batch.set_mask_rows([self._lane],
                                 np.asarray(row.best_mask)[None, :])
        return row

    # -- queries ------------------------------------------------------------
    def query(self, refine: bool = False, target_gap: float | None = None,
              max_refine_rounds: int = 64) -> QueryResult:
        if refine:
            # group of one through the batched refinement flush: same
            # executables as a full bucket sweep, compiled once per shape
            return query_group(
                {self.name: self}, refine=True, target_gap=target_gap,
                max_refine_rounds=max_refine_rounds)[self.name]
        if self._cached_query is not None:
            return self._cached_query
        if self._generation < 0:
            self._resync_device()
        if self.stale:
            return self.refresh()
        return query_group({self.name: self})[self.name]

    def cbds(self, rounds: int = 1) -> dict:
        if self._generation < 0:
            self._resync_device()
        self._sync_views()
        return super().cbds(rounds)

    def __repr__(self) -> str:  # pragma: no cover
        return (f"FusedEngine({self.name!r}, |V|={self.n_nodes}, "
                f"|E|={self.buffer.n_edges}, lane={self._lane}, "
                f"batch={self.batch!r})")


# ---------------------------------------------------------------------------
# fused flushes
# ---------------------------------------------------------------------------
def _pruned_result(density: float, mask: np.ndarray,
                   passes: int) -> QueryResult:
    return QueryResult(density=density, mask=mask, passes=passes,
                       warm_density=density, warm_mask=mask.copy(),
                       refreshed=False, pruned=True)


def _flush(batch: TenantBatch, members, refine: bool = False,
           target_gap: float | None = None,
           max_refine_rounds: int = 64) -> dict[str, QueryResult]:
    """One fused flush for ``members`` (same bucket, warm path): at most one
    batched bucket peel per plan-bucket shape plus one batched warm peel.
    Per-tenant results are bit-identical to each engine's unbatched query
    (same host prepare/merge, vmapped device recurrence). With ``refine``
    the peel results seed one batched refinement-round loop for the whole
    group (``_refine_flush``); the exact peel results still land in each
    engine's plain query cache.

    Observability: the flush is one span + one audit record attributed to
    the *bucket* (tenant ``bucket:VxE``) — its dispatch shapes are group
    properties (lane-stack width, pow-2 group sizes, plan-bucket shapes),
    not any single member's. Its children ``fused_prepare`` (plan rebuilds,
    degree-row pull, host prepare and merge of pruned members),
    ``fused_bucket_peel`` (one batched bucket peel and its read-back),
    ``fused_warm_peel`` (``peel_rows`` and its read-back) and
    ``fused_mask_write`` name the phases; the flush span counts the
    members by path (``n_warm``, ``n_pruned``, ``n_fallback``: pruned
    members sent to the warm peel) and the passes: ``lane_passes`` sums
    padded group lanes x passes over the batched peels, ``member_passes``
    the passes their members' own answers report, so their ratio is the
    share of lane-passes spent on converged or padding lanes. The
    per-member latency share carries the flush's ``compiled`` flag into
    each engine's first-call/steady split."""
    label = f"bucket:{batch.node_capacity}x{batch.edge_capacity}"
    with span("fused_flush", tenant=label, engine="fused") as sp:
        AUDITOR.sync()  # member refreshes/plan state ran under their own keys
        out, refined, cached, audit_shape, counts = _flush_body(
            batch, members, refine, target_gap, max_refine_rounds)
        compiled = AUDITOR.record(label, "fused_flush", audit_shape)
        sp.set("members", len(members)).set("compiled", compiled)
        for key, value in counts.items():
            sp.set(key, value)
        if refine:
            sp.set("path", "refined")
        share = sp.elapsed_ms / max(len(members), 1)
    # per-member feed into the metrics registry: the flush span is labeled
    # with the *bucket*, so each tenant's SLO series (latency share,
    # peel-pass/refine-round counters, certified-gap gauge) is fed here —
    # the same series an unbatched engine's spans produce
    tracer = get_tracer()
    reg = tracer.registry
    feed = tracer.enabled and reg.enabled
    for name, eng in members:
        if name not in cached:  # a cache hit is not a new peel query
            q = out[name]
            q.latency_ms = share
            q.compiled = compiled
            eng._note_query_ms(share, compiled)
            eng._cached_query = q
            if feed:
                hist = "query_first_call_ms" if compiled else "query_ms"
                reg.histogram(hist, tenant=eng.tenant,
                              engine=eng.kind).observe(share)
                if q.passes:
                    reg.counter("peel_passes_total", tenant=eng.tenant,
                                engine=eng.kind).inc(int(q.passes))
        if refined is not None:
            r = refined[name]
            r.latency_ms = share
            r.compiled = compiled
            eng._cached_refined = r
            if feed:
                if r.refine_rounds:
                    reg.counter("refine_rounds_total", tenant=eng.tenant,
                                engine=eng.kind).inc(int(r.refine_rounds))
                if r.certificate is not None:
                    reg.gauge("certified_gap", tenant=eng.tenant,
                              engine=eng.kind).set(float(r.certificate.rel_gap))
    return refined if refined is not None else out


def _flush_body(batch: TenantBatch, members, refine: bool,
                target_gap: float | None, max_refine_rounds: int):
    out: dict[str, QueryResult] = {}
    warm: list = []
    dispatches: list = []
    mask_writes: list = []  # (lane, full-width mask) warm-seed updates
    n_fallback = 0
    lane_passes = 0  # padded group lanes x passes, over the batched peels
    member_passes = 0  # the batched members' own passes
    # a member with a valid memoized peel (possible only on the refined
    # path — plain query_group short-circuits those before the flush)
    # reuses it as the refinement seed instead of re-peeling its lane
    cached: set[str] = set()
    live: list = []
    for name, eng in members:
        if eng._cached_query is not None:
            cached.add(name)
            out[name] = eng._cached_query
        else:
            live.append((name, eng))
    with span("fused_prepare"):
        for name, eng in live:
            if eng.pruned and eng._plan is None:
                eng._rebuild_plan()
        # pull only the queried pruned lanes' degree rows, not the stack
        pruned_lanes = [eng._lane for _, eng in live
                        if eng.pruned and eng._plan.enabled]
        deg_rows: dict[int, np.ndarray] = {}
        if pruned_lanes:
            gp = next_pow2(len(pruned_lanes))
            li = np.full(gp, pruned_lanes[0], np.int32)
            li[: len(pruned_lanes)] = pruned_lanes
            rows = np.asarray(batch.gather_deg_rows(li))
            tally("d2h_bytes", rows.nbytes)
            deg_rows = {lane: rows[i] for i, lane in enumerate(pruned_lanes)}
        for name, eng in live:
            if not (eng.pruned and eng._plan.enabled):
                warm.append((name, eng))
                continue
            u, v = eng.buffer.host_view()
            prep = prepare_pruned_peel(
                u, v, deg_rows[eng._lane], eng.buffer.n_edges, eng.eps,
                eng._plan)
            if prep is None or (
                    # mirror pruned_peel_host's mesh guard: bucket lanes
                    # that don't shard evenly re-peel unpruned instead
                    not isinstance(prep, tuple) and batch.sharded
                    and prep.plan.bucket_e % batch.n_shards):
                eng.metrics.n_prune_fallbacks += 1
                eng._plan = dc_replace(eng._plan, enabled=False)
                warm.append((name, eng))
                n_fallback += 1
            elif isinstance(prep, tuple):
                density, mask, passes = eng._absorb_pruned_result(*prep)
                mask_writes.append((eng._lane, np.asarray(eng._prev_mask)))
                out[name] = _pruned_result(density, mask, passes)
            else:
                dispatches.append((name, eng, prep))

    # one vmapped bucket peel for every pruned member, in the bucket's
    # held plan shape (TenantBatch.peel_buckets)
    buckets = gp = None
    if dispatches:
        buckets = batch.peel_buckets(
            [pd.plan.buckets for _, _, pd in dispatches])
        with span("fused_bucket_peel"):
            bucket_v, bucket_e = buckets[0], buckets[1]
            gp = next_pow2(len(dispatches))
            b_src = np.full((gp, bucket_e), bucket_v, np.int32)
            b_dst = np.full((gp, bucket_e), bucket_v, np.int32)
            n_v = np.zeros(gp, np.int32)
            n_e = np.zeros(gp, np.int32)
            best = np.zeros(gp, np.float32)
            for i, (_, _, pd) in enumerate(dispatches):
                # the member's sentinel (its own bucket_v) becomes the group's
                k, own = pd.b_src.size, pd.plan.bucket_v
                b_src[i, :k] = np.where(pd.b_src == own, bucket_v, pd.b_src)
                b_dst[i, :k] = np.where(pd.b_dst == own, bucket_v, pd.b_dst)
                n_v[i], n_e[i], best[i] = pd.n_v1, pd.n_e1, pd.best_d1
            tally("h2d_bytes", sum(a.nbytes for a in
                                   (b_src, b_dst, n_v, n_e, best)))
            if batch.sharded:
                d_b, mask_b, passes_b = _make_sharded_batched_bucket_peel(
                    batch.mesh, batch.eps, *buckets)(
                        jnp.asarray(b_src), jnp.asarray(b_dst),
                        jnp.asarray(n_v), jnp.asarray(n_e),
                        jnp.asarray(best),
                        jnp.ones(gp, jnp.int32))  # host simulated pass 0
            else:
                d_b, mask_b, passes_b = _batched_bucket_peel_jit(
                    jnp.asarray(b_src), jnp.asarray(b_dst), jnp.asarray(n_v),
                    jnp.asarray(n_e), jnp.asarray(best),
                    jnp.ones(gp, jnp.int32),  # host simulated pass 0
                    batch.eps, *buckets, batch.kernel)
            d_b, mask_b = np.asarray(d_b), np.asarray(mask_b)
            passes_b = np.asarray(passes_b)
            tally("d2h_bytes", d_b.nbytes + mask_b.nbytes + passes_b.nbytes)
            lane_passes += gp * int(passes_b.max())
            member_passes += int(passes_b[: len(dispatches)].sum())
        with span("fused_prepare"):
            for i, (name, eng, pd) in enumerate(dispatches):
                merged = merge_pruned_peel(pd, d_b[i], mask_b[i], passes_b[i])
                density, mask, passes = eng._absorb_pruned_result(*merged)
                mask_writes.append((eng._lane, np.asarray(eng._prev_mask)))
                out[name] = _pruned_result(density, mask, passes)

    if warm:
        with span("fused_warm_peel"):
            lanes = np.asarray([eng._lane for _, eng in warm], np.int32)
            ne = np.asarray([eng.buffer.n_edges for _, eng in warm], np.int32)
            final, warm_rho = batch.peel_rows(lanes, ne)
            bd = np.asarray(final.best_density)
            wr = np.asarray(warm_rho)
            bm = np.asarray(final.best_mask)
            ps = np.asarray(final.passes)
            tally("d2h_bytes", bd.nbytes + wr.nbytes + bm.nbytes + ps.nbytes)
            lane_passes += ps.size * int(ps.max())
            member_passes += int(ps[: len(warm)].sum())
        for i, (name, eng) in enumerate(warm):
            density, wrho = float(bd[i]), float(wr[i])
            mask = bm[i][: eng.n_nodes].copy()
            if wrho > density:
                warm_density = wrho
                warm_mask = np.asarray(eng._prev_mask)[: eng.n_nodes].copy()
                # keep the stronger candidate as next query's warm seed
            else:
                warm_density = density
                warm_mask = mask.copy()
                eng._prev_mask = jnp.asarray(bm[i])
                mask_writes.append((eng._lane, bm[i]))
            out[name] = QueryResult(
                density=density, mask=mask, passes=int(ps[i]),
                warm_density=warm_density, warm_mask=warm_mask,
                refreshed=False)

    if mask_writes:
        with span("fused_mask_write"):
            batch.set_mask_rows([lane for lane, _ in mask_writes],
                                np.stack([m for _, m in mask_writes]))
    batch.n_group_peels += 1
    refined = None
    if refine:
        refined = _refine_flush(batch, members, out, target_gap,
                                max_refine_rounds)
    # every shape determinant of this flush's dispatches, for the audit key:
    # lane-stack width (gather inputs), pow-2 gather/peel/refine group
    # sizes, and the bucket peel's plan shape
    audit_shape = (
        batch.node_capacity, batch.edge_capacity, batch.eps, batch.lanes,
        batch.kernel, batch.n_shards,
        next_pow2(len(pruned_lanes)) if pruned_lanes else 0,
        next_pow2(len(warm)) if warm else 0,
        buckets, gp,
        next_pow2(max(len(members), 1)) if refine else 0,
    )
    counts = {
        "n_warm": len(warm),
        "n_pruned": len(live) - len(warm),
        "n_fallback": n_fallback,
        "lane_passes": lane_passes,
        "member_passes": member_passes,
    }
    return out, refined, cached, audit_shape, counts


def _refine_flush(batch: TenantBatch, members, peel_out,
                  target_gap: float | None,
                  max_rounds: int) -> dict[str, QueryResult]:
    """Batched refinement rounds for one bucket's queried lanes: loads live
    in leading-axis ``[G, V]`` arrays and every round is ONE vmapped
    program (dense GEMV rounds under DENSE_NODE_CAP, COO otherwise), with
    converged lanes frozen through ``select`` exactly like the batched
    peels. The loop runs until every member's certificate meets
    ``target_gap`` — lanes that met it early ride along and their
    certificates only tighten (running-min dual, monotone best), so a
    fused group's density is never worse than a solo refinement's; with a
    negative target (fixed-round mode) the group is bit-identical to
    per-tenant ``_refine_round_jit`` loops, the parity tests/test_refine.py
    asserts."""
    tg = DEFAULT_TARGET_GAP if target_gap is None else float(target_gap)
    max_rounds = max(int(max_rounds), 1)  # a certificate needs >= 1 round
    g = len(members)
    gp = next_pow2(max(g, 1))
    lanes = np.full(gp, members[0][1]._lane, np.int32)
    lanes[:g] = [eng._lane for _, eng in members]
    li = jnp.asarray(lanes)
    gather = (_make_sharded_lane_gather(batch.mesh) if batch.sharded
              else _lane_gather_jit)
    src_g, dst_g, deg_g, _ = gather(
        batch._src, batch._dst, batch._deg, batch._prev_mask, li)
    adj_g = _rows_gather_jit(batch._adj, li) if batch.dense else None

    nc = batch.node_capacity
    seeds = []
    best_mask = np.zeros((gp, nc), dtype=bool)
    best_ne = np.zeros(gp, np.int32)
    best_nv = np.zeros(gp, np.int32)
    best_density = np.zeros(gp, np.float32)
    passes0 = np.zeros(gp, np.int32)
    n_edges = np.zeros(gp, np.int32)
    for i, (name, eng) in enumerate(members):
        q = peel_out[name]
        mask_full = np.zeros(nc, dtype=bool)
        mask_full[: eng.n_nodes] = q.mask
        ne, nv = eng._mask_counts(mask_full)
        seeds.append((ne, nv, mask_full))
        best_mask[i] = mask_full
        best_ne[i], best_nv[i] = ne, nv
        best_density[i] = (np.float32(ne) / np.float32(nv) if nv
                           else np.float32(0.0))
        passes0[i] = q.passes
        n_edges[i] = eng.buffer.n_edges
    for i in range(g, gp):  # pad lanes duplicate member 0 and ride along
        best_mask[i] = best_mask[0]
        best_ne[i], best_nv[i] = best_ne[0], best_nv[0]
        best_density[i] = best_density[0]
        n_edges[i] = n_edges[0]

    loads = jnp.zeros((gp, nc), jnp.int32)
    bd = jnp.asarray(best_density)
    be = jnp.asarray(best_ne)
    bv = jnp.asarray(best_nv)
    bm = jnp.asarray(best_mask)
    ps = jnp.asarray(passes0)
    ne_j = jnp.asarray(n_edges)
    duals: list = [None] * g
    certs: list = [None] * g
    rounds = 0
    for t in range(1, int(max_rounds) + 1):
        if batch.dense:
            loads, bd, be, bv, bm, ps = _batched_dense_refine_round_jit(
                adj_g, deg_g, ne_j, loads, bd, be, bv, bm, ps, batch.eps)
        elif batch.sharded:
            loads, bd, be, bv, bm, ps = _make_sharded_batched_refine_round(
                batch.mesh, nc, batch.eps)(
                    src_g, dst_g, deg_g, ne_j, loads, bd, be, bv, bm, ps)
        else:
            loads, bd, be, bv, bm, ps = _batched_refine_round_jit(
                src_g, dst_g, deg_g, ne_j, loads, bd, be, bv, bm, ps,
                nc, batch.eps, batch.kernel)
        rounds = t
        loads_np = np.asarray(loads)
        be_np, bv_np = np.asarray(be), np.asarray(bv)
        done = True
        for i in range(g):
            b_ne, b_nv = max_fraction((int(be_np[i]), int(bv_np[i])),
                                      seeds[i][:2])
            num, den = dual_fraction(loads_np[i], t)
            if duals[i] is None or better_fraction(num, den, *duals[i]):
                duals[i] = (num, den)
            certs[i] = make_certificate(b_ne, b_nv, *duals[i])
            done = done and certs[i].rel_gap <= tg
        if done:
            break

    bm_np, ps_np = np.asarray(bm), np.asarray(ps)
    out = {}
    for i, (name, eng) in enumerate(members):
        cert = certs[i]
        seed_ne, seed_nv, seed_mask = seeds[i]
        if cert.best_ne == seed_ne and cert.best_nv == seed_nv:
            mask_full = seed_mask
        else:
            mask_full = bm_np[i]
        eng._refine_cert = cert
        eng._cert_mask = mask_full.copy()
        eng._cert_insert_slack = 0
        eng.metrics.n_refine_queries += 1
        eng.metrics.refine_rounds_total += rounds
        mask = mask_full[: eng.n_nodes].copy()
        out[name] = QueryResult(
            density=cert.density, mask=mask, passes=int(ps_np[i]),
            warm_density=cert.density, warm_mask=mask.copy(),
            refreshed=peel_out[name].refreshed,
            pruned=peel_out[name].pruned, certificate=cert,
            refine_rounds=rounds,
        )
    return out


def query_group(engines: dict[str, DeltaEngine], refine: bool = False,
                target_gap: float | None = None,
                max_refine_rounds: int = 64) -> dict[str, QueryResult]:
    """Answer a set of tenants' densest-subgraph queries with fused
    execution wherever possible: fused tenants — replicated or sharded —
    flush per-bucket (one batched warm peel + one batched bucket peel per
    plan shape); non-fused engines fall back to their own query path; a
    sharded bucket's flush issues one collective per pass for the whole
    group. Cached results are
    reused, and stale tenants take their epoch refresh individually first
    (the refresh is epoch-amortized by design).

    ``refine=True`` answers with *certified* densities instead: fused
    members of a bucket share one batched refinement-round loop per flush
    (leading-axis load arrays, ``select``-frozen convergence — see
    ``_refine_flush``); tenants whose cached certificate still proves
    equality on their current graph skip the flush entirely (the
    certified-skip path of delta.py)."""
    out: dict[str, QueryResult] = {}
    flushes: dict[TenantBatch, list] = defaultdict(list)
    tg = DEFAULT_TARGET_GAP if target_gap is None else float(target_gap)
    for name, eng in engines.items():
        if not isinstance(eng, FusedEngine):
            out[name] = (eng.query(refine=True, target_gap=target_gap,
                                   max_refine_rounds=max_refine_rounds)
                         if refine else eng.query())
            continue
        if refine:
            cached = eng._cached_refined
            if (cached is not None and cached.certificate is not None
                    and cached.certificate.rel_gap <= tg):
                out[name] = cached
                continue
            if (eng._generation < 0
                    or eng._generation != eng.buffer.generation):
                eng._resync_device()
            skip = eng._certified_skip()
            if skip is not None:
                out[name] = skip
                continue
            if eng.stale:
                eng.refresh()  # re-anchor; the refined flush runs below
            flushes[eng.batch].append((name, eng))
            continue
        if eng._cached_query is not None:
            out[name] = eng._cached_query
            continue
        if eng._generation < 0 or eng._generation != eng.buffer.generation:
            eng._resync_device()
        if eng.stale:
            out[name] = eng.refresh()
            continue
        flushes[eng.batch].append((name, eng))
    for batch, members in flushes.items():
        out.update(_flush(batch, members, refine=refine,
                          target_gap=target_gap,
                          max_refine_rounds=max_refine_rounds))
    return out


def ingest_group(updates: dict[str, tuple], engines: dict[str, DeltaEngine]):
    """Apply many tenants' update batches with one fused scatter per bucket:
    host staging (buffer bookkeeping, row padding) runs per tenant, then
    all staged rows in a bucket dispatch as a single ``[T, B]`` program.
    ``updates`` maps tenant -> (insert, delete); non-fused engines apply
    directly. Returns tenant -> UpdateStats."""
    stats = {}
    rows_by_batch: dict[TenantBatch, dict[int, tuple]] = defaultdict(dict)
    try:
        for name, (insert, delete) in updates.items():
            eng = engines[name]
            if not isinstance(eng, FusedEngine):
                stats[name] = eng.apply_updates(insert=insert, delete=delete)
                continue
            eng._staging = True
            eng._staged_row = None
            try:
                stats[name] = eng.apply_updates(insert=insert, delete=delete)
            finally:
                eng._staging = False
            if eng._staged_row is not None:
                rows_by_batch[eng.batch][eng._lane] = eng._staged_row
                eng._staged_row = None
    finally:
        # dispatch whatever staged even if a later tenant's batch raised
        # (e.g. out-of-range endpoints): a staged tenant's host buffer has
        # already committed, so its device lane MUST receive the row or
        # subsequent queries would silently peel stale degrees
        for batch, rows in rows_by_batch.items():
            label = f"bucket:{batch.node_capacity}x{batch.edge_capacity}"
            with span("fused_ingest", tenant=label, engine="fused") as sp:
                AUDITOR.sync()  # staged members recorded (no dispatch) above
                b = batch.ingest(rows)
                compiled = AUDITOR.record(
                    label, "fused_ingest",
                    (batch.node_capacity, batch.edge_capacity, batch.eps,
                     batch.lanes, batch.kernel, batch.n_shards, b))
                sp.set("n_lanes", len(rows)).set("compiled", compiled)
                sp.set("stack_lanes", batch.lanes).set("batch_width", b)
    return stats


__all__ = ["TenantBatch", "FusedPool", "FusedEngine", "query_group",
           "ingest_group", "MIN_LANES"]
