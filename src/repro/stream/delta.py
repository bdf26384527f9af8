"""Incremental densest-subgraph maintenance over an EdgeBuffer.

The static path pays O(|E|) twice per query: once on host (re-padding the
edge arrays) and once on device (the degree histogram inside
``_pbahmani_jit``). ``DeltaEngine`` keeps the graph *resident*: the symmetric
COO arrays live on device and each update batch is one fused jitted call
(``_apply_batch_jit``) that

  * patches the edge slots touched by the batch (scatter, ``mode="drop"``
    for the padding lanes), and
  * applies the degree delta as a ``segment_sum`` over just the batch
    endpoints — O(batch), not O(|E|); the paper's ``atomicAdd``/``atomicSub``
    pair collapses into one signed histogram.

Queries then run the peel loop from the *maintained* integer state
(``_warm_peel_jit``). Because degree maintenance is exact integer
arithmetic, the warm initial state is bit-identical to what a from-scratch
``init_state`` would compute, so the peel trajectory — and the reported
density — EQUALS a cold ``pbahmani`` recompute on the materialized graph
(the oracle property asserted in tests/test_stream.py). The previous best
mask is re-evaluated on the current graph inside the same jit call
(Sukprasert et al., arXiv:2311.04333 warm-start): its density is a valid
anytime lower bound that often beats the fresh peel right after deletions,
and is reported alongside (``warm_density``/``warm_mask``) without
perturbing the oracle-exact ``density``.

Shape discipline: batches are padded to power-of-two lengths and edge
arrays only double (buffer.py), so a long stream of same-capacity batches
compiles each executable once (compile-count assertion in tests). A
staleness counter triggers an *epoch refresh* when the accumulated weight
reaches ``refresh_every``: the buffer compacts its slots, device state is
rebuilt, and the query re-anchors through a cold peel. Batches weigh
``1 + DELETE_STALENESS_WEIGHT · deleted_fraction`` — insert-only streams
keep the historical cadence (weight exactly 1 per batch) while
delete-dominated streams, whose tombstone holes fragment the slot space
fastest, refresh proportionally earlier.

Candidate pruning (ISSUE 2): with ``pruned=True`` (the default) queries run
through ``core/prune.py`` — warm-start beyond seeding. At epoch cadence the
engine rebuilds a :class:`~repro.core.prune.PrunePlan`: the previous
epoch's best mask is re-evaluated on the current edges to bootstrap the
density lower bound rho~, the existing k-core fixpoint shrinks to the
ceil(rho~)-core (candidate fraction reported in metrics), and the plan's
pow-2 buckets size the compacted subproblem that ``pbahmani`` peels instead
of the full padded arrays. The invariant is *bit-identical density and
mask* (and pass count) versus the unpruned cold peel — see prune.py for
the proof sketch and tests/test_prune.py for the adversarial cases. In
pruned mode ``warm_density``/``warm_mask`` simply mirror the exact result
(the prev-mask re-evaluation moved into the plan bootstrap, off the
per-query hot path).

Sharding (ISSUE 3): with ``sharded=True`` every device-resident array and
every jitted entry point routes through the ``core/distributed.py``
shard_map engine — edge slots partitioned over a mesh exactly like
``shard_edges`` (per-device sentinel-padded shards), |V|-sized degree/mask
state replicated, and all cross-shard reductions (update histograms, peel
degree deltas, scalar density state) realized as one psum per pass: the
paper's atomicSub at pod scale. Since every reduction is exact int32, the
sharded engine's (density, mask, passes) triple is bit-identical to the
single-device engine on ANY device count — asserted on 1-device meshes and
fp32-checked on forced multi-device CPU meshes in tests/test_shard.py. The
mesh is injected at construction (``mesh=``) or defaults to one flat axis
over the local devices; tenants opt in individually through the registry.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from dataclasses import replace as dc_replace
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.cbds import _cbds_jit
from repro.core.density import induced_edge_count, ratio
from repro.core.dispatch import assert_exact_envelope
from repro.core.distributed import (
    SHARDED_JITS, _make_cbds_run, flat_shard_index, make_sharded_warm_peel,
    mesh_device_count, validate_stream_mesh,
)
from repro.core.pbahmani import PeelState, _pbahmani_jit, pbahmani_pass
from repro.core.prune import (
    PrunePlan, _batched_bucket_peel_jit, _bucket_peel_jit, _plan_jit,
    build_plan, make_sharded_plan, pruned_peel_host,
)
from repro.obs.audit import AUDITOR
from repro.obs.trace import span, tally
from repro.refine.certify import GapCertificate, make_certificate
from repro.refine.engine import DEFAULT_TARGET_GAP, refine_resident
from repro.refine.loads import REFINE_JITS
from repro.stream.buffer import EdgeBuffer, MIN_CAPACITY, next_pow2
from repro.utils.mesh import make_mesh_auto

MIN_BATCH = 64  # smallest padded update-batch shape (pow-2 buckets above)
DELETE_STALENESS_WEIGHT = 3.0  # an all-delete batch ages the epoch 4x


def _build_batch_row(ins, ins_slots, dele, del_slots, capacity: int,
                     sentinel: int, b_floor: int = MIN_BATCH):
    """Pad one effective update batch into the fixed-shape scatter row the
    jitted apply consumes: pow-2 length, OOB slot indices and zero weights
    in the padding lanes. Shared by the per-tenant dispatch and the fused
    multi-tenant ingest (stream/fused.py), where rows from many tenants
    stack into one [T, B] program."""
    n = ins.shape[0] + dele.shape[0]
    b = max(next_pow2(max(n, 1)), b_floor)
    slots = np.full(b, 2 * capacity, np.int32)  # OOB pad
    su = np.full(b, sentinel, np.int32)
    sv = np.full(b, sentinel, np.int32)
    du = np.full(b, sentinel, np.int32)
    dv = np.full(b, sentinel, np.int32)
    w = np.zeros(b, np.int32)
    # deletes first; an insert reusing a freed slot must win the scatter,
    # so drop the delete's slot write (its degree delta and the insert's
    # are independent — keyed on endpoints, not slots)
    m = dele.shape[0]
    if m:
        keep = ~np.isin(del_slots, ins_slots)
        dslots = np.where(keep, del_slots, 2 * capacity)
        slots[:m] = dslots
        du[:m], dv[:m] = dele[:, 0], dele[:, 1]
        w[:m] = -1
    k = ins.shape[0]
    if k:
        slots[m : m + k] = ins_slots
        su[m : m + k], sv[m : m + k] = ins[:, 0], ins[:, 1]
        du[m : m + k], dv[m : m + k] = ins[:, 0], ins[:, 1]
        w[m : m + k] = 1
    return slots, su, sv, du, dv, w


@lru_cache(maxsize=None)
def default_stream_mesh():
    """One flat mesh over the largest pow-2 prefix of the local devices,
    shared by every sharded tenant that doesn't inject its own (sharing the
    mesh is what lets same-bucket tenants share sharded executables)."""
    n = len(jax.devices())
    n = 1 << (n.bit_length() - 1)  # largest power of two <= n
    return make_mesh_auto((n,), ("shard",))


@lru_cache(maxsize=None)
def _make_sharded_resync(mesh):
    """Cached jitted identity that places (src, dst, deg, prev_mask) with
    the exact output shardings every other sharded entry point produces.
    Uploading with plain ``device_put`` leaves arrays whose sharding object
    differs from a jit output's in the compile-cache key — the first batch
    after a resync would silently recompile. Laundering the upload through
    this no-op keeps the hot path at one executable per shape."""
    axes = tuple(mesh.axis_names)

    def body(src_l, dst_l, deg, mask):
        return src_l, dst_l, deg, mask

    run = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(axes), P(axes), P(), P()),
        out_specs=(P(axes), P(axes), P(), P()), check_vma=False))
    SHARDED_JITS.append(run)
    return run


@lru_cache(maxsize=None)
def _make_sharded_mask_sync(mesh):
    """Cached jitted identity for a replicated |V| mask — same laundering
    rationale as ``_make_sharded_resync``, for the pruned path's host-built
    prev mask (a raw ``jnp.asarray`` would carry a different sharding into
    the plan/warm-peel cache keys and silently recompile them)."""
    run = jax.jit(jax.shard_map(
        lambda m: m, mesh=mesh, in_specs=(P(),), out_specs=P(),
        check_vma=False))
    SHARDED_JITS.append(run)
    return run


@lru_cache(maxsize=None)
def _make_sharded_apply(mesh, n_nodes: int):
    """Cached jitted sharded analog of ``_apply_batch_jit``: the edge-slot
    scatter runs per shard (each device drops writes outside its lane
    block), and the signed degree histogram is computed per shard over a
    slice of the batch then psum'd — the paper's atomicAdd/atomicSub pair
    as one all-reduce. Batch arrays are replicated (O(batch), tiny); the
    slot arrays are sharded over the mesh."""
    axes = tuple(mesh.axis_names)
    n_dev = mesh_device_count(mesh)

    def body(src_l, dst_l, deg, slots, su, sv, du, dv, w):
        lanes = src_l.shape[0]          # 2*capacity // n_dev
        me = flat_shard_index(mesh)
        base = me * lanes
        cap = (lanes * n_dev) // 2
        # mirror writes land at slot and slot+cap; translate to local lane
        # indices, routing misses (and the OOB padding marker) to `lanes`
        # which mode="drop" discards
        p1 = slots - base
        p2 = slots + cap - base
        p1 = jnp.where((p1 >= 0) & (p1 < lanes), p1, lanes)
        p2 = jnp.where((p2 >= 0) & (p2 < lanes), p2, lanes)
        src_l = src_l.at[p1].set(su, mode="drop").at[p2].set(sv, mode="drop")
        dst_l = dst_l.at[p1].set(sv, mode="drop").at[p2].set(su, mode="drop")
        b_local = w.shape[0] // n_dev
        start = (me * b_local).astype(jnp.int32)
        w_l = jax.lax.dynamic_slice(w, (start,), (b_local,))
        du_l = jax.lax.dynamic_slice(du, (start,), (b_local,))
        dv_l = jax.lax.dynamic_slice(dv, (start,), (b_local,))
        d_u = jax.ops.segment_sum(
            w_l, jnp.minimum(du_l, n_nodes), num_segments=n_nodes + 1)
        d_v = jax.ops.segment_sum(
            w_l, jnp.minimum(dv_l, n_nodes), num_segments=n_nodes + 1)
        d = jax.lax.psum(d_u[:n_nodes] + d_v[:n_nodes], axes)
        deg = (deg + d).astype(jnp.int32)
        return src_l, dst_l, deg

    run = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axes), P(axes), P(), P(), P(), P(), P(), P(), P()),
        out_specs=(P(axes), P(axes), P()), check_vma=False))
    SHARDED_JITS.append(run)
    return run


@lru_cache(maxsize=None)
def _make_sharded_batched_apply(mesh, n_nodes: int):
    """Fused+sharded ingest (ISSUE 9): the per-tenant scatter + signed
    degree histogram of ``_make_sharded_apply`` vmapped over a leading
    tenant axis inside ONE shard_map program — slot stacks [T, lanes] with
    the lane axis sharded, batch rows [T, B] replicated. The T per-tenant
    degree psums batch into one [T, V] all-reduce; each tenant's device
    state stays bit-identical to its solo sharded engine (exact int32
    histogram, identical scatter translation per lane block)."""
    axes = tuple(mesh.axis_names)
    n_dev = mesh_device_count(mesh)

    def body(src_l, dst_l, deg, slots, su, sv, du, dv, w):
        lanes = src_l.shape[1]          # 2*capacity // n_dev
        me = flat_shard_index(mesh)
        base = me * lanes
        cap = (lanes * n_dev) // 2
        b_local = w.shape[1] // n_dev
        start = (me * b_local).astype(jnp.int32)

        def one(src_t, dst_t, deg_t, slots_t, su_t, sv_t, du_t, dv_t, w_t):
            p1 = slots_t - base
            p2 = slots_t + cap - base
            p1 = jnp.where((p1 >= 0) & (p1 < lanes), p1, lanes)
            p2 = jnp.where((p2 >= 0) & (p2 < lanes), p2, lanes)
            src_t = src_t.at[p1].set(su_t, mode="drop").at[p2].set(
                sv_t, mode="drop")
            dst_t = dst_t.at[p1].set(sv_t, mode="drop").at[p2].set(
                su_t, mode="drop")
            w_l = jax.lax.dynamic_slice(w_t, (start,), (b_local,))
            du_l = jax.lax.dynamic_slice(du_t, (start,), (b_local,))
            dv_l = jax.lax.dynamic_slice(dv_t, (start,), (b_local,))
            d_u = jax.ops.segment_sum(
                w_l, jnp.minimum(du_l, n_nodes), num_segments=n_nodes + 1)
            d_v = jax.ops.segment_sum(
                w_l, jnp.minimum(dv_l, n_nodes), num_segments=n_nodes + 1)
            d = jax.lax.psum(d_u[:n_nodes] + d_v[:n_nodes], axes)
            return src_t, dst_t, (deg_t + d).astype(jnp.int32)

        return jax.vmap(one)(src_l, dst_l, deg, slots, su, sv, du, dv, w)

    run = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axes), P(None, axes), P(), P(), P(), P(), P(),
                  P(), P()),
        out_specs=(P(None, axes), P(None, axes), P()), check_vma=False))
    SHARDED_JITS.append(run)
    return run


# -- laundered stack ops for the fused+sharded TenantBatch -------------------
# Persistent [T, ...] bucket stacks mix with shard_map outputs on the hot
# path, so every mutation goes through a cached shard_map'd jit whose output
# shardings match the batched entry points above (the _make_sharded_resync
# laundering rationale, lifted to stacks). All appended to SHARDED_JITS.
@lru_cache(maxsize=None)
def _make_sharded_stack_sync(mesh):
    """Identity placement for (src, dst, deg, prev_mask) stacks — the
    alloc/grow upload path of a sharded TenantBatch."""
    axes = tuple(mesh.axis_names)
    run = jax.jit(jax.shard_map(
        lambda s, d, g, m: (s, d, g, m), mesh=mesh,
        in_specs=(P(None, axes), P(None, axes), P(), P()),
        out_specs=(P(None, axes), P(None, axes), P(), P()),
        check_vma=False))
    SHARDED_JITS.append(run)
    return run


@lru_cache(maxsize=None)
def _make_sharded_lane_write(mesh):
    """Swap one tenant's (row_src, row_dst, row_deg, row_mask) into lane
    ``lane`` of the stacks (traced lane index: joins/evictions at any lane
    reuse one executable)."""
    axes = tuple(mesh.axis_names)

    def body(src, dst, deg, mask, lane, r_src, r_dst, r_deg, r_mask):
        return (src.at[lane].set(r_src), dst.at[lane].set(r_dst),
                deg.at[lane].set(r_deg), mask.at[lane].set(r_mask))

    run = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axes), P(None, axes), P(), P(), P(),
                  P(axes), P(axes), P(), P()),
        out_specs=(P(None, axes), P(None, axes), P(), P()),
        check_vma=False))
    SHARDED_JITS.append(run)
    return run


@lru_cache(maxsize=None)
def _make_sharded_lane_gather(mesh):
    """Gather a pow-2 group of lanes as stacked (src, dst, deg, mask) —
    the peel-group input of ``make_sharded_batched_warm_peel``."""
    axes = tuple(mesh.axis_names)

    def body(src, dst, deg, mask, lanes):
        return src[lanes], dst[lanes], deg[lanes], mask[lanes]

    run = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axes), P(None, axes), P(), P(), P()),
        out_specs=(P(None, axes), P(None, axes), P(), P()),
        check_vma=False))
    SHARDED_JITS.append(run)
    return run


@lru_cache(maxsize=None)
def _make_sharded_row_view(mesh):
    """Gather ONE lane with exactly the output shardings of
    ``_make_sharded_resync`` — what ``FusedEngine._sync_views`` hands the
    inherited solo entry points (plan rebuild, pruned prepare, cbds), so
    those stay one executable across solo and fused placement."""
    axes = tuple(mesh.axis_names)

    def body(src, dst, deg, mask, lane):
        return src[lane], dst[lane], deg[lane], mask[lane]

    run = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axes), P(None, axes), P(), P(), P()),
        out_specs=(P(axes), P(axes), P(), P()), check_vma=False))
    SHARDED_JITS.append(run)
    return run


@lru_cache(maxsize=None)
def _make_sharded_mask_rows_write(mesh):
    """Scatter per-tenant result masks back into the replicated prev-mask
    stack (OOB pad lanes drop, as in ``_mask_rows_write_jit``)."""
    run = jax.jit(jax.shard_map(
        lambda ms, lanes, masks: ms.at[lanes].set(masks, mode="drop"),
        mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
        check_vma=False))
    SHARDED_JITS.append(run)
    return run


@lru_cache(maxsize=None)
def _make_sharded_deg_rows_gather(mesh):
    """Gather degree rows for a group of lanes (replicated stack — the
    pruned-flush host prepare reads degrees per member)."""
    run = jax.jit(jax.shard_map(
        lambda stack, lanes: stack[lanes], mesh=mesh,
        in_specs=(P(), P()), out_specs=P(), check_vma=False))
    SHARDED_JITS.append(run)
    return run


def _apply_batch_body(
    src: jax.Array,
    dst: jax.Array,
    deg: jax.Array,
    slots: jax.Array,   # int32 [B] slot index, OOB (=len(src)) for padding
    su: jax.Array,      # int32 [B] slot value u (sentinel for deletes/pad)
    sv: jax.Array,      # int32 [B] slot value v
    du: jax.Array,      # int32 [B] degree endpoint u (sentinel for padding)
    dv: jax.Array,      # int32 [B] degree endpoint v
    w: jax.Array,       # int32 [B] +1 insert / -1 delete / 0 padding
    n_nodes: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One update batch: edge-slot scatter + signed degree histogram.
    Shared by the single-tenant jit and the vmapped multi-tenant jit — an
    all-padding batch row (w=0, OOB slots) is an exact no-op, which is what
    lets idle lanes of a fused bucket ride along for free."""
    cap = src.shape[0] // 2
    src = src.at[slots].set(su, mode="drop").at[slots + cap].set(sv, mode="drop")
    dst = dst.at[slots].set(sv, mode="drop").at[slots + cap].set(su, mode="drop")
    d_u = jax.ops.segment_sum(w, jnp.minimum(du, n_nodes), num_segments=n_nodes + 1)
    d_v = jax.ops.segment_sum(w, jnp.minimum(dv, n_nodes), num_segments=n_nodes + 1)
    deg = (deg + d_u[:n_nodes] + d_v[:n_nodes]).astype(jnp.int32)
    return src, dst, deg


@partial(jax.jit, static_argnames=("n_nodes",))
def _apply_batch_jit(src, dst, deg, slots, su, sv, du, dv, w, n_nodes: int):
    return _apply_batch_body(src, dst, deg, slots, su, sv, du, dv, w, n_nodes)


@partial(jax.jit, static_argnames=("n_nodes",))
def _apply_batch_sorted_jit(src, dst, deg, p1, p2, su, sv, du, dv, w,
                            n_nodes: int):
    """O(batch) patch of the *dst-sorted* resident layout (kernel mode):
    the host translates each slot to its two symmetric-COO lane positions
    through the buffer's ``lane_perm`` snapshot (p1 = perm[slot], p2 =
    perm[slot + capacity]; OOB = 2*capacity marks padding, dropped). The
    degree histogram is the ordinary endpoint-keyed signed sum — identical
    integers to ``_apply_batch_jit``, only the lane positions differ."""
    src = src.at[p1].set(su, mode="drop").at[p2].set(sv, mode="drop")
    dst = dst.at[p1].set(sv, mode="drop").at[p2].set(su, mode="drop")
    d_u = jax.ops.segment_sum(w, jnp.minimum(du, n_nodes), num_segments=n_nodes + 1)
    d_v = jax.ops.segment_sum(w, jnp.minimum(dv, n_nodes), num_segments=n_nodes + 1)
    deg = (deg + d_u[:n_nodes] + d_v[:n_nodes]).astype(jnp.int32)
    return src, dst, deg


@partial(jax.jit, static_argnames=("n_nodes",))
def _batched_apply_jit(src, dst, deg, slots, su, sv, du, dv, w, n_nodes: int):
    """Fused multi-tenant ingest (ISSUE 4): one vmapped scatter+histogram
    over the leading tenant axis ([T, 2*cap] slots, [T, B] batch rows).
    Per-lane arithmetic is the exact ``_apply_batch_body`` recurrence, so
    each lane's device state is bit-identical to an unbatched engine's."""
    return jax.vmap(
        lambda a, b, c, d, e, f, g, h, i: _apply_batch_body(
            a, b, c, d, e, f, g, h, i, n_nodes)
    )(src, dst, deg, slots, su, sv, du, dv, w)


def _warm_peel_body(
    src: jax.Array,
    dst: jax.Array,
    deg: jax.Array,
    n_edges: jax.Array,
    prev_mask: jax.Array,
    n_nodes: int,
    eps: float,
    kernel: bool = False,
) -> tuple[PeelState, jax.Array]:
    """Peel from the maintained degree array (skips the O(|E|) histogram of
    ``init_state``; bit-identical state, hence identical result) and
    re-evaluate the previous best mask on the current graph. ``kernel``
    routes the per-pass degree update through the Pallas tier (callers in
    kernel mode keep the resident lanes dst-sorted) — same triple."""
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
        lambda s: s.n_v > 0,
        lambda s: pbahmani_pass(s, src, dst, n_nodes, eps, kernel),
        state,
    )
    warm_e = induced_edge_count(src, dst, prev_mask, n_nodes)
    warm_v = jnp.sum(prev_mask.astype(jnp.int32))
    warm_rho = jnp.where(warm_v > 0, ratio(warm_e, warm_v), 0.0)
    return final, warm_rho


@partial(jax.jit, static_argnames=("n_nodes", "eps", "kernel"))
def _warm_peel_jit(src, dst, deg, n_edges, prev_mask, n_nodes: int, eps: float,
                   kernel: bool = False):
    return _warm_peel_body(src, dst, deg, n_edges, prev_mask, n_nodes, eps,
                           kernel)


@partial(jax.jit, static_argnames=("n_nodes", "eps", "kernel"))
def _batched_warm_peel_jit(
    src, dst, deg, n_edges, prev_mask, n_nodes: int, eps: float,
    kernel: bool = False,
) -> tuple[PeelState, jax.Array]:
    """Fused multi-tenant warm peel (ISSUE 4): vmap of ``_warm_peel_body``
    over the leading tenant axis. jax batches the inner ``while_loop`` by
    running the pass body while ANY lane is live and freezing converged
    lanes through ``select`` — the per-tenant early-exit mask. Every op in
    the pass is per-lane (elementwise f32 scalars, exact int32 segment
    sums), so each lane's (density, mask, passes) triple is bit-identical
    to the unbatched ``_warm_peel_jit``; an empty lane (deg == 0) converges
    at pass 0 and never serializes the batch."""
    return jax.vmap(
        lambda s, d, g, ne, pm: _warm_peel_body(
            s, d, g, ne, pm, n_nodes, eps, kernel)
    )(src, dst, deg, n_edges, prev_mask)


def _jit_entry_points():
    """Every jitted entry point the streaming engines can dispatch — the
    registry the recompile auditor (repro.obs.audit) diffs around each op.
    ``SHARDED_JITS``/``REFINE_JITS``/``FUSED_JITS`` are live lists that the
    lru-cached factories append to, so the provider re-reads them each call;
    fused is imported lazily to avoid a module cycle."""
    from repro.stream import fused as _fused

    return [_apply_batch_jit, _apply_batch_sorted_jit, _warm_peel_jit,
            _pbahmani_jit, _cbds_jit, _bucket_peel_jit, _plan_jit,
            _batched_apply_jit, _batched_warm_peel_jit,
            _batched_bucket_peel_jit] + list(
        SHARDED_JITS) + list(REFINE_JITS) + list(_fused.FUSED_JITS)


AUDITOR.register_provider(_jit_entry_points, name="stream")


@dataclass
class UpdateStats:
    """Outcome of one ``apply_updates`` batch."""

    n_inserted: int
    n_deleted: int
    n_edges: int
    batch_capacity: int   # padded device batch shape actually dispatched
    regrew: bool          # buffer layout epoch changed (grow or tombstone
                          # compaction): device state was rebuilt whole
    latency_ms: float
    compiled: bool = False  # this batch compiled a new executable (audit)


@dataclass
class QueryResult:
    density: float            # oracle-exact: == cold pbahmani on this graph
                              # (refined queries: best certified density,
                              # >= the peel's, never above rho*)
    mask: np.ndarray          # bool [n_nodes] achieving ``density``
    passes: int
    warm_density: float       # max(density, prev-mask re-evaluation)
    warm_mask: np.ndarray     # mask achieving ``warm_density``
    refreshed: bool           # this query ran the epoch-refresh path
    latency_ms: float = 0.0
    pruned: bool = False      # peeled the compacted candidate subproblem
    # refinement (repro.refine, query(refine=True) only)
    certificate: GapCertificate | None = None
    refine_rounds: int = 0
    certified_skip: bool = False  # cached bound proved equality: no peel ran
    compiled: bool = False        # this query compiled a new executable, so
                                  # latency_ms is a first-call number (audit)


@dataclass
class EngineMetrics:
    n_update_batches: int = 0
    n_queries: int = 0
    n_refreshes: int = 0
    update_ms_total: float = 0.0
    query_ms_total: float = 0.0
    shape_buckets: set = field(default_factory=set)
    # candidate pruning (core/prune.py)
    n_pruned_queries: int = 0     # queries that peeled inside the buckets
    n_prune_fallbacks: int = 0    # bucket fit-misses (full-width branch)
    n_plan_builds: int = 0        # rho~ bootstrap + core fixpoint runs
    bucket_reuses: int = 0        # plan rebuilds that kept the same buckets
    candidate_fraction: float = 0.0  # |ceil(rho~)-core| / n_nodes
    prune_bucket_v: int = 0
    prune_bucket_e: int = 0
    # contracting-graph bookkeeping (ISSUE 3 bugfixes)
    n_buffer_shrinks: int = 0     # epoch refreshes that halved slot capacity
    n_bucket_shrinks: int = 0     # mid-epoch prune-bucket shrinks
    # near-optimal refinement (repro.refine)
    n_refine_queries: int = 0     # queries that ran refinement rounds
    refine_rounds_total: int = 0
    n_certified_skips: int = 0    # refined queries answered from the cached
                                  # certificate alone (no peel dispatched)
    # cold-vs-warm split (repro.obs audit layer): query_ms_total keeps the
    # historical combined number; the split un-conflates first-call compile
    # time from steady-state latency
    n_query_first_calls: int = 0
    query_first_call_ms_total: float = 0.0
    query_steady_ms_total: float = 0.0


class DeltaEngine:
    """Dynamic graph + online densest-subgraph queries for one tenant."""

    def __init__(
        self,
        n_nodes: int,
        eps: float = 0.0,
        capacity: int = MIN_CAPACITY,
        refresh_every: int = 32,
        pruned: bool = True,
        sharded: bool = False,
        mesh=None,
        kernel: bool | None = None,
    ):
        if n_nodes <= 0:
            raise ValueError("DeltaEngine needs n_nodes >= 1")
        self.n_nodes = int(n_nodes)
        # pad the vertex space to a power of two: tenants of similar size
        # share compiled executables (registry.py bucketing)
        self.node_capacity = max(next_pow2(self.n_nodes), 2)
        self.eps = float(eps)
        self.refresh_every = int(refresh_every)
        self.pruned = bool(pruned)
        self.sharded = bool(sharded)
        # kernel=None resolves to the scatter tier;
        # sharded engines stay on per-shard scatter — their lanes are
        # mesh-partitioned, not band-local, so the sorted-view machinery
        # below does not apply (ROADMAP follow-up)
        self.kernel = bool(kernel) and not self.sharded
        # observability identity: the registry overwrites ``tenant`` with the
        # registered name; spans and audit records are labeled with it
        self.tenant = "-"
        self.kind = "sharded" if self.sharded else "delta"
        # sharded=True routes all device state through the shard_map engine:
        # edge slots partitioned over the mesh (per-device sentinel-padded
        # shards), |V|-sized state replicated, scalar state psum'd — one
        # tenant's graph spans the mesh instead of one chip
        self.mesh = None
        n_dev = 1
        if self.sharded:
            self.mesh = mesh if mesh is not None else default_stream_mesh()
            n_dev = validate_stream_mesh(
                self.mesh, max(next_pow2(capacity), MIN_CAPACITY))
        # floor capacity (incl. epoch shrinks) at one lane block per device
        self.buffer = EdgeBuffer(self.node_capacity, capacity=capacity,
                                 min_capacity=max(MIN_CAPACITY, n_dev // 2))
        self.metrics = EngineMetrics()
        self._src = None          # device int32 [2*capacity], sentinel-padded
        self._dst = None
        self._deg = None          # device int32 [node_capacity]
        self._lane_perm = None    # kernel mode: unsorted lane -> sorted pos
        self._generation = -1     # buffer generation mirrored on device
        self._prev_mask = jnp.zeros(self.node_capacity, dtype=bool)
        self._staleness = 0.0     # delete-weighted batches since last refresh
        self._plan: PrunePlan | None = None
        self._last_handoff: tuple[int, int] | None = None
        self._cached_query: QueryResult | None = None
        # refinement state (repro.refine): the certificate + its mask
        # persist across updates — deletions keep the dual bound valid and
        # insertions shift it by the max incident count, which is what lets
        # a later refined query skip the peel when the bound proves equality
        self._cached_refined: QueryResult | None = None
        self._refine_cert: GapCertificate | None = None
        self._cert_mask: np.ndarray | None = None
        self._cert_insert_slack: int = 0

    # -- device-state management -------------------------------------------
    @property
    def sentinel(self) -> int:
        return self.node_capacity

    @property
    def n_shards(self) -> int:
        """Devices this tenant's edge slots are partitioned across."""
        return mesh_device_count(self.mesh) if self.mesh is not None else 1

    def _audit_shape(self) -> tuple:
        """Shape determinants of every executable this engine can dispatch
        (audit keys extend it per op — batch width, plan buckets). A compile
        under an already-seen (tenant, op, shape) key is a steady-state
        recompile; anything that legitimately changes dispatch shapes MUST
        appear here or the auditor raises false alarms."""
        return (self.node_capacity, 2 * self.buffer.capacity,
                self.eps, self.n_shards, self.kernel)

    def _note_query_ms(self, ms: float, compiled: bool) -> None:
        """Query-latency bookkeeping with the first-call/steady split."""
        self.metrics.n_queries += 1
        self.metrics.query_ms_total += ms
        if compiled:
            self.metrics.n_query_first_calls += 1
            self.metrics.query_first_call_ms_total += ms
        else:
            self.metrics.query_steady_ms_total += ms

    def _resync_device(self) -> None:
        """Full O(|E|) upload — on first use, regrow, or epoch compaction.
        Sharded engines place the slot arrays partitioned over the mesh and
        the degree array replicated, so no later call ever reshards. Kernel
        mode uploads the buffer's dst-sorted snapshot instead (the Pallas
        tier's band-skip precondition) and caches its lane permutation so
        later batches patch the sorted layout in O(batch)."""
        with span("engine_resync", tenant=self.tenant, engine=self.kind):
            if self.kernel:
                assert_exact_envelope(2 * self.buffer.capacity,
                                      self.node_capacity)
                src, dst, deg, lane_perm = self.buffer.dst_sorted_state(
                    self.node_capacity)
                self._lane_perm = lane_perm
            else:
                src, dst, deg = self.buffer.resident_state(self.node_capacity)
            tally("h2d_bytes", src.nbytes + dst.nbytes + deg.nbytes)
            if self.mesh is not None:
                prev = np.asarray(self._prev_mask)
                tally("d2h_bytes", prev.nbytes)
                tally("h2d_bytes", prev.nbytes)
                self._src, self._dst, self._deg, self._prev_mask = (
                    _make_sharded_resync(self.mesh)(src, dst, deg, prev))
            else:
                self._src = jnp.asarray(src)
                self._dst = jnp.asarray(dst)
                self._deg = jnp.asarray(deg)
            self._generation = self.buffer.generation

    def _check_endpoints(self, edges) -> int:
        """Raise on an out-of-range endpoint; returns the number of pairs."""
        if edges is None:
            return 0
        e = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
        if e.size and (e.min() < 0 or e.max() >= self.n_nodes):
            raise ValueError(
                f"edge endpoint out of range [0, {self.n_nodes}): "
                f"min={e.min()} max={e.max()}"
            )
        return e.shape[0]

    # -- ingest -------------------------------------------------------------
    def apply_updates(self, insert=None, delete=None) -> UpdateStats:
        labels = {"tenant": self.tenant, "engine": self.kind}
        with span("ingest", **labels) as sp:
            AUDITOR.sync()  # foreign cache growth is not this batch's fault
            if self._generation < 0:
                self._resync_device()

            with span("buffer_apply", **labels) as bsp:
                n_ins = self._check_endpoints(insert)
                n_del = self._check_endpoints(delete)
                gen_before = self.buffer.generation
                ins, ins_slots, dele, del_slots = self.buffer.apply(
                    insert, delete)
                # the counts ride here, not on ``ingest``: a count on both
                # would feed its counter twice
                bsp.set("n_inserted", int(ins.shape[0]))
                bsp.set("n_deleted", int(dele.shape[0]))
                # offered pairs with no effect: duplicates, self-loops and
                # live edges; deletes of absent edges
                bsp.set("n_dup_inserts", n_ins - int(ins.shape[0]))
                bsp.set("n_absent_deletes", n_del - int(dele.shape[0]))
            regrew = self.buffer.generation != gen_before

            if regrew:
                # capacity doubled or tombstones forced a compaction: the
                # slot layout moved, rebuild device state whole (and
                # invalidate the prune plan — its lane-width basis may be
                # stale)
                self._resync_device()
                self._plan = None
            else:
                # pow-2 batch pad; sharded engines also need the batch
                # divisible into per-device histogram slices (pow-2 shards).
                # The span times the enqueue; the scatter runs on after it
                with span("ingest_dispatch", **labels) as dsp:
                    row = _build_batch_row(
                        ins, ins_slots, dele, del_slots, self.buffer.capacity,
                        self.sentinel, b_floor=max(MIN_BATCH, self.n_shards))
                    b = row[0].shape[0]
                    self._dispatch_batch(*row)
                    dsp.set("batch_width", b)
                self.metrics.shape_buckets.add((2 * self.buffer.capacity, b))

            # staleness ages faster on delete-heavy batches: tombstone holes
            # are what the epoch compaction exists to clean up (insert-only
            # streams accumulate exactly 1 per batch — the historical
            # cadence)
            n_eff = int(ins.shape[0]) + int(dele.shape[0])
            del_frac = (int(dele.shape[0]) / n_eff) if n_eff else 0.0
            self._staleness += 1.0 + DELETE_STALENESS_WEIGHT * del_frac
            self._cached_query = None  # graph changed: next query recomputes
            self._cached_refined = None
            if self._refine_cert is not None and ins.shape[0]:
                # each inserted edge adds one unit of load to (at most) both
                # endpoints of the averaged orientation, so the dual bound
                # shifts by at most the max incident insert count — deletions
                # only free load and leave it valid as-is (certify.py)
                counts = np.bincount(ins.astype(np.int64).ravel())
                self._cert_insert_slack += int(counts.max())
            # the audit shape extends the engine key with the dispatched
            # batch width (a new pow-2 width legitimately compiles once); a
            # regrow rebuilt device state whole at the NEW capacity, which
            # _audit_shape already reflects
            shape = self._audit_shape() + (("resync",) if regrew else (b,))
            compiled = AUDITOR.record(self.tenant, "ingest", shape)
            sp.set("compiled", compiled)
            sp.set("kernel", self.kernel)
            ms = sp.elapsed_ms
        self.metrics.n_update_batches += 1
        self.metrics.update_ms_total += ms
        return UpdateStats(
            n_inserted=int(ins.shape[0]),
            n_deleted=int(dele.shape[0]),
            n_edges=self.buffer.n_edges,
            batch_capacity=0 if regrew else int(b),
            regrew=regrew,
            latency_ms=ms,
            compiled=compiled,
        )

    def _dispatch_batch(self, slots, su, sv, du, dv, w) -> None:
        """Apply one padded scatter row to the device-resident state. The
        fused multi-tenant engine overrides this to route the row into its
        bucket's stacked [T, ...] arrays (stream/fused.py). Kernel mode
        translates slot indices through the cached lane permutation so the
        patch lands in the dst-sorted layout — the patched lanes may sit
        out of sort order until the next resync re-sorts (a *performance*
        drift only; the kernel recomputes its bands from the data, so
        results stay bit-identical)."""
        if self.kernel:
            cap = self.buffer.capacity
            s = np.asarray(slots)
            real = s < cap  # pad marker is 2*cap
            sc = np.minimum(s, cap - 1)
            p1 = np.where(real, self._lane_perm[sc], 2 * cap).astype(np.int32)
            p2 = np.where(real, self._lane_perm[sc + cap],
                          2 * cap).astype(np.int32)
            tally("h2d_bytes", sum(a.nbytes for a in (p1, p2, su, sv, du,
                                                      dv, w)))
            self._src, self._dst, self._deg = _apply_batch_sorted_jit(
                self._src, self._dst, self._deg,
                jnp.asarray(p1), jnp.asarray(p2), jnp.asarray(su),
                jnp.asarray(sv), jnp.asarray(du), jnp.asarray(dv),
                jnp.asarray(w), self.node_capacity,
            )
            return
        tally("h2d_bytes", sum(a.nbytes for a in (slots, su, sv, du, dv, w)))
        if self.mesh is not None:
            apply_fn = _make_sharded_apply(self.mesh, self.node_capacity)
            self._src, self._dst, self._deg = apply_fn(
                self._src, self._dst, self._deg,
                jnp.asarray(slots), jnp.asarray(su), jnp.asarray(sv),
                jnp.asarray(du), jnp.asarray(dv), jnp.asarray(w),
            )
        else:
            self._src, self._dst, self._deg = _apply_batch_jit(
                self._src, self._dst, self._deg,
                jnp.asarray(slots), jnp.asarray(su), jnp.asarray(sv),
                jnp.asarray(du), jnp.asarray(dv), jnp.asarray(w),
                self.node_capacity,
            )

    # -- candidate pruning (core/prune.py) ----------------------------------
    def _rebuild_plan(self) -> None:
        """rho~ bootstrap + ceil(rho~)-core analysis + bucket sizing. The
        previous epoch's best mask seeds rho~ (re-evaluated on the current
        edges, so the bound stays sound after deletions); the last observed
        handoff sizes the buckets with slack, so steady-state epochs keep
        reusing one compiled executable (``bucket_reuses``)."""
        with span("prune_plan", tenant=self.tenant, engine=self.kind) as sp:
            if self.mesh is not None:
                rho_lb, k, _, n_cand, ne_cand = make_sharded_plan(
                    self.mesh, self.node_capacity)(
                    self._src, self._dst, self._prev_mask,
                    jnp.asarray(self.buffer.n_edges, jnp.int32),
                )
            else:
                rho_lb, k, _, n_cand, ne_cand = _plan_jit(
                    self._src, self._dst, self._prev_mask,
                    jnp.asarray(self.buffer.n_edges, jnp.int32),
                    self.node_capacity, self.kernel,
                )
            new = build_plan(
                float(rho_lb), int(k), int(n_cand), int(ne_cand),
                node_width=self.node_capacity,
                lane_width=2 * self.buffer.capacity,
                observed=self._last_handoff,
                n_vertices=self.n_nodes,
            )
            sp.set("candidate_fraction", new.candidate_fraction)
        if self._plan is not None and new.buckets == self._plan.buckets:
            self.metrics.bucket_reuses += 1
        self._plan = new
        self.metrics.n_plan_builds += 1
        self.metrics.candidate_fraction = new.candidate_fraction
        self.metrics.prune_bucket_v = new.bucket_v
        self.metrics.prune_bucket_e = new.bucket_e

    def _run_pruned_peel(self) -> tuple[float, np.ndarray, int] | None:
        """Host-compacted peel (prune.py): the device only ever touches the
        plan's buckets; the host filters the buffer's resident slot arrays
        against the pass-0 survivor set and remaps them. Returns (density,
        mask[:n_nodes], passes) — bit-identical to the unpruned cold peel —
        or ``None`` when the survivor set fits no legal bucket (caller runs
        the full-width path; counted as a prune fallback)."""
        u, v = self.buffer.host_view()
        res = pruned_peel_host(
            u, v, self._deg,
            self.buffer.n_edges, self.eps, self._plan, mesh=self.mesh,
            kernel=self.kernel,
        )
        if res is None:
            # survivor set fits no legal bucket this epoch: stop paying the
            # host filter per query until the refresh rebuilds the plan
            self.metrics.n_prune_fallbacks += 1
            self._plan = dc_replace(self._plan, enabled=False)
            return None
        return self._absorb_pruned_result(*res)

    def _absorb_pruned_result(
        self, density: float, mask: np.ndarray, passes: int,
        observed: tuple[int, int], plan: PrunePlan,
    ) -> tuple[float, np.ndarray, int]:
        """Post-dispatch bookkeeping for one pruned result (plan regrow /
        shrink accounting, prev-mask warm seed, metrics). Shared with the
        fused multi-tenant flush, which merges many tenants' batched bucket
        peels through the same path (stream/fused.py)."""
        self._last_handoff = observed
        if plan is not self._plan:  # in-flight bucket regrow or shrink
            if (plan.bucket_v < self._plan.bucket_v
                    or plan.bucket_e < self._plan.bucket_e):
                self.metrics.n_bucket_shrinks += 1
            self._plan = plan
            self.metrics.prune_bucket_v = plan.bucket_v
            self.metrics.prune_bucket_e = plan.bucket_e
        tally("h2d_bytes", mask.nbytes)
        if self.mesh is not None:
            self._prev_mask = _make_sharded_mask_sync(self.mesh)(
                jnp.asarray(mask))
        else:
            self._prev_mask = jnp.asarray(mask)
        self.metrics.n_pruned_queries += 1
        return density, mask[: self.n_nodes], passes

    # -- queries ------------------------------------------------------------
    @property
    def stale(self) -> bool:
        return self._staleness >= self.refresh_every

    def _cold_full_peel(self) -> PeelState:
        """Full-width peel re-anchor. Sharded engines route through the
        sharded warm peel from the exactly-resynced degree array — the
        maintained-state init is bit-identical to ``init_state``'s cold
        histogram, so the trajectory (and triple) matches ``_pbahmani_jit``."""
        if self.mesh is not None:
            final, _ = make_sharded_warm_peel(
                self.mesh, self.node_capacity, self.eps)(
                self._src, self._dst, self._deg,
                jnp.asarray(self.buffer.n_edges, jnp.int32), self._prev_mask)
            return final
        return _pbahmani_jit(
            self._src, self._dst, self.node_capacity,
            jnp.asarray(self.buffer.n_edges, jnp.int32), self.eps,
            self.kernel)

    def refresh(self) -> QueryResult:
        """Epoch refresh: compact the buffer (shrinking capacity when the
        graph contracted past the hysteresis), rebuild device state, rebuild
        the prune plan (warm-started from the previous epoch's density), and
        re-anchor with a cold peel — compacted when the plan allows."""
        with span("refresh", tenant=self.tenant, engine=self.kind) as sp:
            AUDITOR.sync()
            if self.buffer.epoch_compact(shrink=True):
                self.metrics.n_buffer_shrinks += 1
                self._plan = None  # lane-width sizing basis changed
            self._resync_device()
            self._staleness = 0.0
            out = None
            if self.pruned:
                self._rebuild_plan()
                if self._plan.enabled:
                    out = self._run_pruned_peel()
            if out is not None:
                density, mask, passes = out
                pruned_flag = True
            else:
                with span("peel_full", tenant=self.tenant,
                          engine=self.kind) as psp:
                    final = self._cold_full_peel()
                    self._prev_mask = final.best_mask
                    density = float(final.best_density)
                    full = np.asarray(final.best_mask)
                    tally("d2h_bytes", full.nbytes)
                    mask = full[: self.n_nodes]
                    passes = int(final.passes)
                    psp.set("passes", passes)
                pruned_flag = False
            buckets = (self._plan.buckets
                       if pruned_flag and self._plan is not None else None)
            compiled = AUDITOR.record(
                self.tenant, "refresh", self._audit_shape() + (buckets,))
            # the passes ride on the peel's own span (peel_full or
            # prune_merge), so the counter sees each answer once
            sp.set("density", density)
            sp.set("path", "pruned" if pruned_flag else "warm")
            sp.set("compiled", compiled)
            sp.set("kernel", self.kernel)
            if pruned_flag:
                sp.set("candidate_fraction", self.metrics.candidate_fraction)
            ms = sp.elapsed_ms
        self.metrics.n_refreshes += 1
        self._note_query_ms(ms, compiled)
        self._cached_query = QueryResult(
            density=density, mask=mask, passes=passes,
            warm_density=density, warm_mask=mask.copy(),
            refreshed=True, latency_ms=ms, pruned=pruned_flag,
            compiled=compiled,
        )
        return self._cached_query

    def query(self, refine: bool = False, target_gap: float | None = None,
              max_refine_rounds: int = 64) -> QueryResult:
        """Densest-subgraph query on the current graph. Warm path unless the
        staleness counter says the epoch is due; repeat queries on an
        unchanged graph return the memoized result.

        ``refine=True`` serves a *certified* density instead: the exact
        warm/pruned peel seeds weighted-peel refinement rounds
        (repro.refine) off the same resident device state, until the
        LP-duality gap closes below ``target_gap`` (relative to the dual
        bound; default ``repro.refine.DEFAULT_TARGET_GAP``) or
        ``max_refine_rounds`` is spent. The reported density is >= the
        peel's, never above rho*, and carries a :class:`GapCertificate`.
        When the previous certificate still *proves* equality on the
        current graph — deletions keep the dual bound valid; insertions
        shift it by their max incident count — the peel is skipped
        entirely and the query costs one host re-count (the ROADMAP
        early-exit-certificates item; ``certified_skip`` marks it)."""
        if refine:
            return self._query_refined(target_gap, max_refine_rounds)
        if self._cached_query is not None:
            return self._cached_query
        if self._generation < 0:
            self._resync_device()
        if self.stale:
            return self.refresh()
        with span("query", tenant=self.tenant, engine=self.kind) as sp:
            AUDITOR.sync()
            out = None
            if self.pruned:
                if self._plan is None:
                    self._rebuild_plan()
                out = self._run_pruned_peel() if self._plan.enabled else None
            if out is not None:
                density, mask, passes = out
                warm_density, warm_mask = density, mask.copy()
                pruned_flag = True
                # post-op plan: an in-flight bucket regrow already swapped it
                # in via _absorb_pruned_result, so this IS what dispatched
                buckets = self._plan.buckets
                sp.set("candidate_fraction", self.metrics.candidate_fraction)
            else:
                with span("peel_full", tenant=self.tenant,
                          engine=self.kind) as psp:
                    if self.mesh is not None:
                        final, warm_rho = make_sharded_warm_peel(
                            self.mesh, self.node_capacity, self.eps)(
                            self._src, self._dst, self._deg,
                            jnp.asarray(self.buffer.n_edges, jnp.int32),
                            self._prev_mask)
                    else:
                        final, warm_rho = _warm_peel_jit(
                            self._src, self._dst, self._deg,
                            jnp.asarray(self.buffer.n_edges, jnp.int32),
                            self._prev_mask, self.node_capacity, self.eps,
                            self.kernel,
                        )
                    density = float(final.best_density)
                    warm_rho = float(warm_rho)
                    full = np.asarray(final.best_mask)
                    tally("d2h_bytes", full.nbytes)
                    mask = full[: self.n_nodes]
                    passes = int(final.passes)
                    psp.set("passes", passes)
                    if warm_rho > density:
                        warm_density = warm_rho
                        prev = np.asarray(self._prev_mask)
                        tally("d2h_bytes", prev.nbytes)
                        warm_mask = prev[: self.n_nodes]
                        # keep the stronger candidate as next query's seed
                    else:
                        warm_density = density
                        warm_mask = mask.copy()
                        self._prev_mask = final.best_mask
                pruned_flag = False
                buckets = None
            compiled = AUDITOR.record(
                self.tenant, "query", self._audit_shape() + (buckets,))
            sp.set("density", density)
            sp.set("path", "pruned" if pruned_flag else "warm")
            sp.set("compiled", compiled)
            sp.set("kernel", self.kernel)
            ms = sp.elapsed_ms
        self._note_query_ms(ms, compiled)
        self._cached_query = QueryResult(
            density=density, mask=mask, passes=passes,
            warm_density=warm_density, warm_mask=warm_mask,
            refreshed=False, latency_ms=ms, pruned=pruned_flag,
            compiled=compiled,
        )
        return self._cached_query

    # -- near-optimal refinement (repro.refine) ------------------------------
    def _mask_counts(self, mask: np.ndarray) -> tuple[int, int]:
        """Exact integer (ne, nv) of ``mask`` (full vertex width) on the
        current graph, from the host slot arrays — O(|E|) numpy, no device
        dispatch (what makes the certified skip a peel-free query)."""
        u, v = self.buffer.host_view()
        lv = np.zeros(self.node_capacity + 1, dtype=bool)
        lv[: self.node_capacity] = mask
        return int((lv[u] & lv[v]).sum()), int(mask.sum())

    def _certified_skip(self) -> QueryResult | None:
        """Answer a refined query from the cached certificate alone when it
        still proves equality: the stored mask's density re-counted on the
        *current* edges must reach the stored dual bound shifted by the
        insert slack (exact integer comparison — a proof, so the returned
        density IS rho* of the current graph). Returns None otherwise."""
        cert = self._refine_cert
        if cert is None or self._cert_mask is None:
            return None
        with span("refine", tenant=self.tenant, engine=self.kind) as sp:
            ne, nv = self._mask_counts(self._cert_mask)
            if nv == 0:
                return None
            dual_num = cert.dual_num + self._cert_insert_slack * cert.dual_den
            if ne * cert.dual_den < dual_num * nv:
                return None  # bound no longer proves equality: full path
            new_cert = make_certificate(ne, nv, dual_num, cert.dual_den)
            self._refine_cert = new_cert  # re-anchored to the current graph
            self._cert_insert_slack = 0
            mask = self._cert_mask[: self.n_nodes].copy()
            sp.set("certified_skip", True).set("refine_rounds", 0)
            sp.set("certified_gap", new_cert.rel_gap)
            sp.set("path", "refined")
            ms = sp.elapsed_ms
        self._note_query_ms(ms, False)  # host-only: never a first call
        self.metrics.n_certified_skips += 1
        res = QueryResult(
            density=new_cert.density, mask=mask, passes=0,
            warm_density=new_cert.density, warm_mask=mask.copy(),
            refreshed=False, latency_ms=ms, certificate=new_cert,
            refine_rounds=0, certified_skip=True,
        )
        self._cached_refined = res
        return res

    def _refine_arrays(self):
        """(src, dst, deg) device arrays the refinement rounds consume —
        the resident state in every mode. Sharded engines hand their
        mesh-sharded slot arrays straight to the shard_map refine round
        (``refine_resident(mesh=...)``), closing the ISSUE 9 re-upload
        residual: no O(|E|) host round-trip per refined query."""
        return self._src, self._dst, self._deg

    def _query_refined(self, target_gap: float | None,
                       max_rounds: int) -> QueryResult:
        tg = DEFAULT_TARGET_GAP if target_gap is None else float(target_gap)
        cached = self._cached_refined
        if (cached is not None and cached.certificate is not None
                and cached.certificate.rel_gap <= tg):
            return cached
        if self._generation < 0:
            self._resync_device()
        skip = self._certified_skip()
        if skip is not None:
            return skip
        q = self.query()  # exact eps-peel seed (pruned/warm path)
        with span("refine", tenant=self.tenant, engine=self.kind) as sp:
            AUDITOR.sync()  # the seed query above recorded its own growth
            seed_mask = np.zeros(self.node_capacity, dtype=bool)
            seed_mask[: self.n_nodes] = q.mask
            seed_ne, seed_nv = self._mask_counts(seed_mask)
            src, dst, deg = self._refine_arrays()
            cert, mask_full, passes, rounds, _ = refine_resident(
                src, dst, deg, self.buffer.n_edges, self.node_capacity,
                self.eps, seed_ne, seed_nv, seed_mask, q.passes, tg,
                max_rounds, self.kernel, mesh=self.mesh)
            self._refine_cert = cert
            self._cert_mask = mask_full.copy()
            self._cert_insert_slack = 0
            compiled = AUDITOR.record(
                self.tenant, "refine", self._audit_shape())
            sp.set("refine_rounds", rounds)
            sp.set("certified_gap", cert.rel_gap)
            sp.set("path", "refined").set("compiled", compiled)
            sp.set("kernel", self.kernel)
            ms = sp.elapsed_ms
        self.metrics.n_refine_queries += 1
        self.metrics.refine_rounds_total += rounds
        self.metrics.query_ms_total += ms
        if compiled:
            self.metrics.query_first_call_ms_total += ms
        else:
            self.metrics.query_steady_ms_total += ms
        mask = mask_full[: self.n_nodes].copy()
        res = QueryResult(
            density=cert.density, mask=mask, passes=passes,
            warm_density=cert.density, warm_mask=mask.copy(),
            refreshed=q.refreshed, latency_ms=q.latency_ms + ms,
            pruned=q.pruned, certificate=cert, refine_rounds=rounds,
            compiled=compiled or q.compiled,
        )
        self._cached_refined = res
        return res

    def density(self) -> float:
        return self.query().density

    def cbds(self, rounds: int = 1) -> dict:
        """CBDS-P on the current graph. Sharded engines route through the
        ``core/distributed`` shard_map tier directly on the resident
        mesh-sharded slot arrays (the ISSUE 9 bugfix — the old path paid a
        fresh single-device upload per call); the dict is identical to the
        single-device ``_cbds_jit`` on the same graph (tested)."""
        if self._generation < 0:
            self._resync_device()
        if self.mesh is not None:
            core, member, density, n_legit = _make_cbds_run(
                self.mesh, self.node_capacity, int(rounds))(
                self._src, self._dst,
                jnp.asarray(self.buffer.n_edges, jnp.int32))
            return {
                "density": float(density),
                "core_density": float(core.best_density),
                "k_star": int(core.best_k),
                "member_mask": np.asarray(member)[: self.n_nodes],
                "n_legit": int(n_legit),
            }
        res = _cbds_jit(
            self._src, self._dst, self.node_capacity,
            jnp.asarray(self.buffer.n_edges, jnp.int32), int(rounds),
        )
        return {
            "density": float(res.density),
            "core_density": float(res.core_density),
            "k_star": int(res.k_star),
            "member_mask": np.asarray(res.member_mask)[: self.n_nodes],
            "n_legit": int(res.n_legit),
        }

    # -- introspection -------------------------------------------------------
    @property
    def n_edges(self) -> int:
        return self.buffer.n_edges

    @staticmethod
    def compile_count() -> int:
        """Total executables compiled for the engine's jitted entry points.
        Class-level: the jit caches are shared by every engine/tenant — that
        sharing is exactly what the registry's capacity bucketing buys.

        Delegates to the recompile auditor (repro.obs.audit), which owns the
        registry of entry points (``_jit_entry_points`` above: the static
        jits plus the growing SHARDED/REFINE/FUSED lists) — direct cache-size
        counting is deprecated because the scalar cannot say *which*
        tenant/op/shape compiled; ``AUDITOR.snapshot()`` can."""
        return AUDITOR.total_compile_count()

    def __repr__(self) -> str:  # pragma: no cover
        return (
            f"DeltaEngine(|V|={self.n_nodes}/{self.node_capacity}, "
            f"|E|={self.buffer.n_edges}, eps={self.eps}, "
            f"pruned={self.pruned}, shards={self.n_shards}, "
            f"stale_in={self.refresh_every - self._staleness:.1f})"
        )


__all__ = ["DeltaEngine", "QueryResult", "UpdateStats", "EngineMetrics",
           "MIN_BATCH", "DELETE_STALENESS_WEIGHT", "default_stream_mesh",
           "_batched_apply_jit", "_batched_warm_peel_jit"]
