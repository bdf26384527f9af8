"""Jit'd public wrappers around the Pallas segment-sum core.

Every op takes ``impl=`` selecting the backend:
  * ``"pallas"``  — the TPU kernel (compiled on a TPU, interpret mode
                    elsewhere: ``kernels.segsum.interpret_default``).
  * ``"xla"``     — the pure-jnp oracle (ref.py); used by the 512-device
                    dry-run so the lowered HLO stays backend-portable.

Edges must be sorted by the segment id for the Pallas path — ``Graph`` caches
a dst-sorted view (``graphs.graph.Graph.dst_sorted``) and ``EdgeBuffer``
maintains one per epoch (``stream.buffer.EdgeBuffer.dst_sorted_state``);
arbitrary callers can pass ``presorted=False`` to sort on the fly. That
fallback argsorts *inside every call* of the compiled program, so it emits
the ``kernel_unsorted_fallback_total`` obs counter (once per eager call, or
once per trace when invoked under an outer jit) — silent per-pass re-sorts
were exactly the bug that kept the kernel tier off the hot path (ISSUE 7).
"""
from __future__ import annotations

import contextlib
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.segsum import segment_sum_sorted

# ---------------------------------------------------------------------------
# vertex-partitioned aggregation hint (EXPERIMENTS.md §Perf hillclimb #2):
# with edges sharded across the mesh, an unconstrained segment_sum output
# makes GSPMD all-reduce the FULL [num_segments, D] histogram (11.3 GiB/layer
# for MACE on ogbn-products). Constraining the output to the node sharding
# turns it into a reduce-scatter (per-device payload /n_dev); the gathers
# where full rows are needed are D-sized and far cheaper.
# ---------------------------------------------------------------------------
_SEG_OUT_HINT: list = []  # stack of (mesh, axes, min_segments)


@contextlib.contextmanager
def segment_output_sharding(mesh, axes: tuple, min_segments: int = 65536):
    """Within this context, large segment_sum outputs are constrained to
    P(axes, None...) over ``mesh`` (node-partitioned aggregation)."""
    _SEG_OUT_HINT.append((mesh, tuple(axes), min_segments))
    try:
        yield
    finally:
        _SEG_OUT_HINT.pop()


def _apply_seg_hint(out, num_segments: int):
    if not _SEG_OUT_HINT:
        return out
    mesh, axes, min_seg = _SEG_OUT_HINT[-1]
    if num_segments < min_seg or num_segments % __import__("math").prod(
            mesh.shape[a] for a in axes) != 0:
        return out
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = P(axes, *(None,) * (out.ndim - 1))
    return jax.lax.with_sharding_constraint(out, NamedSharding(mesh, spec))


def _hint_active(num_segments: int) -> bool:
    if not _SEG_OUT_HINT:
        return False
    mesh, axes, min_seg = _SEG_OUT_HINT[-1]
    import math
    return (num_segments >= min_seg and
            num_segments % math.prod(mesh.shape[a] for a in axes) == 0)


def vp_segment_sum(values: jax.Array, seg_ids: jax.Array, num_segments: int):
    """Vertex-partitioned segment-sum (EXPERIMENTS.md §Perf hillclimb #2).

    REQUIRES edges pre-partitioned by destination block
    (graphs.partition.partition_by_dst_block): each device along the node
    axes owns one contiguous block of output rows, and the edges it holds
    target only that block. The scatter is then LOCAL; the only cross-chip
    reduction is a psum of [block, D] over the non-node axes (the edge
    sub-shards) — vs. a full [N, D] all-reduce for unpartitioned edges
    (measured 9x less traffic on mace:ogb_products).

    Uses the active segment_output_sharding hint for (mesh, node_axes).
    """
    from jax.sharding import PartitionSpec as P

    mesh, node_axes, _ = _SEG_OUT_HINT[-1]
    all_axes = tuple(mesh.axis_names)
    sub_axes = tuple(a for a in all_axes if a not in node_axes)
    import math
    n_blocks = math.prod(mesh.shape[a] for a in node_axes)
    block = num_segments // n_blocks

    squeeze = values.ndim == 1
    vals = values[:, None] if squeeze else values

    def local(vals_l, ids_l):
        idx = jnp.asarray(0, jnp.int32)
        for a in node_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        start = idx * block
        rel = ids_l.astype(jnp.int32) - start
        ok = (rel >= 0) & (rel < block)
        v = jnp.where(ok[:, None], vals_l.astype(jnp.float32), 0.0)
        out = jax.ops.segment_sum(v, jnp.clip(rel, 0, block - 1),
                                  num_segments=block)
        for a in sub_axes:
            out = jax.lax.psum(out, a)
        return out

    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(all_axes, None), P(all_axes)),
        out_specs=P(node_axes, None),
        check_vma=False,
    )(vals, seg_ids)
    return out[:, 0] if squeeze else out


def _note_unsorted(op: str) -> None:
    """Count a presorted=False call into the obs registry: the in-jit
    argsort is a hidden O(E log E) per-call cost, and the counter is how a
    deployment notices a hot path quietly re-sorting every pass. Fires once
    per eager call (or once per *trace* when the wrapper is invoked inside
    an outer jit — still enough to surface the compiled program's sort)."""
    try:  # host-only, never on the device path
        from repro.obs.trace import get_tracer
    except ImportError:  # pragma: no cover - obs is part of the repo
        return
    tracer = get_tracer()
    reg = tracer.registry
    if tracer.enabled and reg.enabled:
        reg.counter("kernel_unsorted_fallback_total", op=op).inc()


# repro: unaudited -- kernel-tier primitive; inlined into audited engine jits when called under trace
@partial(jax.jit, static_argnames=("num_segments", "impl", "presorted"))
def _segment_sum_jit(
    values: jax.Array,
    seg_ids: jax.Array,
    *,
    num_segments: int,
    impl: str,
    presorted: bool,
) -> jax.Array:
    if impl == "xla":
        return _ref.segment_sum_ref(values, seg_ids, num_segments)
    if not presorted:
        order = jnp.argsort(seg_ids)
        seg_ids = jnp.take(seg_ids, order)
        values = jnp.take(values, order, axis=0)
    return segment_sum_sorted(values, seg_ids, num_segments=num_segments)


def segment_sum(
    values: jax.Array,
    seg_ids: jax.Array,
    *,
    num_segments: int,
    impl: str = "pallas",
    presorted: bool = True,
) -> jax.Array:
    """Deterministic segment-sum. See module docstring for ``impl``.
    NOTE: the segment_output_sharding hint is applied by callers OUTSIDE
    this jit (it must not leak into the jit cache key)."""
    if not presorted:
        _note_unsorted("segment_sum")
    return _segment_sum_jit(values, seg_ids, num_segments=num_segments,
                            impl=impl, presorted=presorted)


# repro: unaudited -- kernel-tier primitive; inlined into audited engine jits when called under trace
@partial(jax.jit, static_argnames=("n_nodes", "impl", "presorted"))
def _peel_update_jit(
    src: jax.Array,
    dst: jax.Array,
    failed: jax.Array,
    *,
    n_nodes: int,
    impl: str,
    presorted: bool,
) -> jax.Array:
    if impl == "xla":
        return _ref.peel_update_ref(src, dst, failed, n_nodes).astype(
            jnp.int32)
    src_c = jnp.minimum(src, n_nodes - 1)
    valid = (src < n_nodes) & (dst < n_nodes)
    vals = (failed[src_c] & valid).astype(jnp.float32)
    if not presorted:
        order = jnp.argsort(dst)
        dst = jnp.take(dst, order)
        vals = jnp.take(vals, order)
    out = segment_sum_sorted(vals, dst, num_segments=n_nodes)
    # the peel recurrence is int32 (exact counts < 2^24 — asserted at plan
    # build by core.dispatch.assert_exact_envelope); cast at the op
    # boundary so kernel-path degrees are bit-identical to the scatter path
    return out.astype(jnp.int32)


def peel_update(
    src: jax.Array,
    dst: jax.Array,
    failed: jax.Array,
    *,
    n_nodes: int,
    impl: str = "pallas",
    presorted: bool = True,
) -> jax.Array:
    """Paper part 2 (the OpenMP atomicSub loop): per-vertex count of failed
    neighbors, **int32** (the peel recurrence's dtype). ``src``/``dst`` are
    the symmetric COO arrays (sentinel-padded); for the Pallas path they
    must be sorted by ``dst``."""
    if not presorted:
        _note_unsorted("peel_update")
    return _peel_update_jit(src, dst, failed, n_nodes=n_nodes, impl=impl,
                            presorted=presorted)


# repro: unaudited -- kernel-tier primitive; inlined into audited engine jits when called under trace
@partial(jax.jit, static_argnames=("num_segments", "impl", "presorted"))
def _segment_embed_jit(
    table: jax.Array,
    gather_ids: jax.Array,
    seg_ids: jax.Array,
    weights: jax.Array | None,
    *,
    num_segments: int,
    impl: str,
    presorted: bool,
) -> jax.Array:
    if impl == "xla":
        return _ref.segment_embed_ref(table, gather_ids, seg_ids, weights, num_segments)
    rows = jnp.take(table, jnp.minimum(gather_ids, table.shape[0] - 1), axis=0)
    rows = rows.astype(jnp.float32)
    if weights is not None:
        rows = rows * weights[:, None].astype(jnp.float32)
    valid = (gather_ids >= 0) & (gather_ids < table.shape[0])
    rows = jnp.where(valid[:, None], rows, 0.0)
    if not presorted:
        order = jnp.argsort(seg_ids)
        seg_ids = jnp.take(seg_ids, order)
        rows = jnp.take(rows, order, axis=0)
    return segment_sum_sorted(rows, seg_ids, num_segments=num_segments)


def segment_embed(
    table: jax.Array,
    gather_ids: jax.Array,
    seg_ids: jax.Array,
    weights: jax.Array | None = None,
    *,
    num_segments: int,
    impl: str = "pallas",
    presorted: bool = True,
) -> jax.Array:
    """Gather + weighted segment-sum: GNN message passing & EmbeddingBag.

    out[s, :] = sum over e with seg_ids[e]==s of weights[e] * table[gather_ids[e], :]
    """
    if not presorted:
        _note_unsorted("segment_embed")
    return _segment_embed_jit(table, gather_ids, seg_ids, weights,
                              num_segments=num_segments, impl=impl,
                              presorted=presorted)


__all__ = ["segment_sum", "peel_update", "segment_embed"]
