"""Pallas TPU kernel: tiled segment-sum (the paper's part-2 "atomicSub").

The hot spot of every algorithm in this repo — P-Bahmani's degree update,
PKC's level fixpoint, GNN message passing, and the recsys EmbeddingBag — is a
segment reduction over an edge list:

    out[v, :] = sum over edges e with seg_ids[e] == v of values[e, :]

On CPU the paper implements this with OpenMP atomics (``atomicSub``). TPUs
have no atomics; the native replacement (DESIGN.md §2) is a *deterministic
blocked reduction* shaped for the MXU:

  * edges are pre-sorted by segment id (host-side, once per graph) so each
    edge tile touches a narrow contiguous *band* of output rows;
  * the per-tile partial sum is a one-hot matmul
        partial[V_TILE, D] = onehot(seg - v0)[V_TILE, E_TILE] @ values[E_TILE, D]
    which runs on the MXU (the systolic array replaces the atomic scatter);
  * a scalar-prefetched band table (lo/hi vertex-block per edge tile) skips
    grid cells whose edge tile cannot touch the output block — with sorted
    edges the work drops from O(B_v · B_e) cells to O(B_v + B_e).

Grid: (num_v_blocks, num_e_tiles), e innermost and sequential ("arbitrary")
so output accumulation is race-free; v blocks are parallel.

VMEM footprint per grid cell (defaults V_TILE=256, E_TILE=512, D<=512 f32):
  values tile 512·D·4 B (≤1 MiB) + onehot 256·512·4 B (0.5 MiB)
  + out block 256·D·4 B (≤0.5 MiB)   « 16 MiB VMEM/core.
All matmul dims are multiples of 128 (MXU-aligned).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

V_TILE = 256  # output rows per block     (multiple of 8 sublanes & 128 MXU)
E_TILE = 512  # edges per tile            (lane-aligned, contraction dim)


def _segsum_kernel(band_lo_ref, band_hi_ref, seg_ref, val_ref, out_ref):
    """One (v-block i, e-tile j) grid cell."""
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # band skip: with sorted seg ids, tile j only overlaps blocks in
    # [band_lo[j], band_hi[j]] — everything else is a no-op grid cell.
    @pl.when((band_lo_ref[j] <= i) & (i <= band_hi_ref[j]))
    def _accumulate():
        v0 = i * V_TILE
        seg = seg_ref[0, :]  # (E_TILE,) int32, sorted
        local = seg - v0
        rows = jax.lax.broadcasted_iota(jnp.int32, (V_TILE, E_TILE), 0)
        onehot = (rows == local[None, :]).astype(jnp.float32)
        # MXU: (V_TILE, E_TILE) @ (E_TILE, D) — the deterministic "atomic add"
        # HIGHEST: values can be vertex ids (stream_compact), and a
        # default-precision f32 dot on the TPU rounds its operands to bf16,
        # which is exact only for integers up to 256
        part = jnp.dot(onehot, val_ref[...],
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        out_ref[...] += part


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def interpret_default() -> bool:
    """Pallas interpret mode off the TPU, compiled kernels on it: the
    platform decides, so a kernel never runs interpreted on a chip."""
    return jax.default_backend() != "tpu"


# repro: unaudited -- kernel-tier primitive; inlined into audited engine jits when called under trace
@functools.partial(jax.jit, static_argnames=("num_segments", "interpret"))
def segment_sum_sorted(
    values: jax.Array,
    seg_ids: jax.Array,
    *,
    num_segments: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Blocked segment-sum for edges **sorted by seg_ids**.

    Args:
      values:   [E, D] float32/bfloat16 (or [E] — treated as D=1).
      seg_ids:  [E] int32, sorted ascending; ids >= num_segments are padding.
      num_segments: output rows V.
      interpret: run the kernel body in interpret mode; ``None`` follows the
        platform (:func:`interpret_default`).

    Returns [num_segments, D] (or [num_segments] for 1-D values), float32.
    """
    if interpret is None:
        interpret = interpret_default()
    squeeze = values.ndim == 1
    if squeeze:
        values = values[:, None]
    e, d = values.shape

    e_pad = _round_up(max(e, 1), E_TILE)
    d_pad = _round_up(max(d, 1), 128)
    # +V_TILE tail block swallows padding/sentinel ids (>= num_segments)
    v_pad = _round_up(num_segments + 1, V_TILE)

    vals_p = jnp.zeros((e_pad, d_pad), jnp.float32).at[:e, :d].set(
        values.astype(jnp.float32))
    # clamp every out-of-range id into the sentinel tail block
    seg_p = jnp.full((e_pad,), v_pad - 1, jnp.int32).at[:e].set(
        jnp.minimum(seg_ids.astype(jnp.int32), v_pad - 1))
    seg_p = jnp.where(seg_p >= num_segments, v_pad - 1, seg_p)

    n_eb = e_pad // E_TILE
    n_vb = v_pad // V_TILE
    # [n_eb, 1, E_TILE] with the tile axis squeezed out of the block: the
    # TPU lowering refuses a (1, E_TILE) block over more than one row
    seg_3d = seg_p.reshape(n_eb, 1, E_TILE)

    # scalar-prefetch band table: vertex-block range each edge tile touches
    band_lo = (jnp.min(seg_3d, axis=(1, 2)) // V_TILE).astype(jnp.int32)
    band_hi = (jnp.max(seg_3d, axis=(1, 2)) // V_TILE).astype(jnp.int32)

    out = pl.pallas_call(
        _segsum_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # band_lo, band_hi
            grid=(n_vb, n_eb),
            in_specs=[
                pl.BlockSpec((None, 1, E_TILE),
                             lambda i, j, lo, hi: (j, 0, 0)),
                pl.BlockSpec((E_TILE, d_pad), lambda i, j, lo, hi: (j, 0)),
            ],
            out_specs=pl.BlockSpec((V_TILE, d_pad), lambda i, j, lo, hi: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((v_pad, d_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(band_lo, band_hi, seg_3d, vals_p)

    out = out[:num_segments, :d]
    return out[:, 0] if squeeze else out


__all__ = ["segment_sum_sorted", "interpret_default", "V_TILE", "E_TILE"]
