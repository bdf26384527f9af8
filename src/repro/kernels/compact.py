"""Pallas TPU kernel: tiled prefix sum + stream compaction (ROADMAP P0(a)).

Prune-bucket survivor compaction (core/prune.py) needs, per peel stage,
``pos = cumsum(live) - 1`` followed by a scatter of the survivors into a
dense pow-2 bucket. The XLA scatter round-trips through serialized
scatter-add HLO; the device-resident formulation here keeps both halves on
the MXU:

  * :func:`prefix_sum` — an inclusive scan over tiles of P_TILE lanes. The
    within-tile scan is a matmul against an upper-triangular ones matrix
    (``x[1, T] @ tri[T, T]`` — the systolic array does the T partial sums in
    one pass), and a (1, 1) SMEM scratch cell carries the running total
    across the sequential 1-D grid.
  * :func:`stream_compact` — compaction as a *sorted* segment sum:
    ``pos = cumsum(live) - 1`` is nondecreasing, so scattering survivors to
    their compacted slots is exactly ``segment_sum_sorted`` with seg ids
    ``pos`` (dead lanes contribute 0.0 to whatever slot they alias, leaving
    the sum unchanged). Values are shifted by ``fill`` so empty output
    slots come back as the sentinel, and every |value - fill| < 2^24 keeps
    the float32 sums exact integers — bit-identical to the
    ``.at[pos].set(..., mode="drop")`` scatter it replaces (overflow lanes
    with pos >= out_size land in the segsum sentinel tail and drop, the
    same semantics).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.segsum import (
    _round_up, interpret_default, segment_sum_sorted,
)

P_TILE = 512  # lanes per scan tile (lane-aligned, MXU contraction dim)


def _prefix_kernel(x_ref, out_ref, carry_ref):
    """One scan tile: within-tile inclusive cumsum via an MXU matmul, plus
    the running carry from every preceding tile (SMEM scalar, sequential
    grid)."""
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        carry_ref[0, 0] = 0.0

    x = x_ref[...]  # (1, P_TILE) f32
    rows = jax.lax.broadcasted_iota(jnp.int32, (P_TILE, P_TILE), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (P_TILE, P_TILE), 1)
    tri = (rows <= cols).astype(jnp.float32)  # upper-tri incl. diagonal
    # cs[0, t] = sum_{k <= t} x[0, k] — T partial sums in one MXU pass
    cs = jnp.dot(x, tri, preferred_element_type=jnp.float32)
    out_ref[...] = cs + carry_ref[0, 0]
    carry_ref[0, 0] = carry_ref[0, 0] + cs[0, P_TILE - 1]


# repro: unaudited -- kernel-tier primitive; audited indirectly through the engine jits that inline it (delta/refine providers), counting it here would double-book
@functools.partial(jax.jit, static_argnames=("interpret",))
def prefix_sum(x: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Inclusive prefix sum of a 1-D int32/bool array, exact int32 out.

    Exactness: the scan runs in float32, so the total must stay under the
    2^24 integer envelope — true for every caller (counts bounded by edge
    capacities, asserted at plan build via ``core.dispatch``).
    ``interpret=None`` follows the platform (``interpret_default``).
    """
    if interpret is None:
        interpret = interpret_default()
    (e,) = x.shape
    e_pad = _round_up(max(e, 1), P_TILE)
    xf = jnp.zeros((e_pad,), jnp.float32).at[:e].set(x.astype(jnp.float32))
    n_tiles = e_pad // P_TILE

    out = pl.pallas_call(
        _prefix_kernel,
        grid=(n_tiles,),
        # tile axis squeezed out of the block (see segsum.py): the TPU
        # lowering refuses a (1, P_TILE) block over more than one row
        in_specs=[pl.BlockSpec((None, 1, P_TILE), lambda j: (j, 0, 0))],
        out_specs=pl.BlockSpec((None, 1, P_TILE), lambda j: (j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles, 1, P_TILE), jnp.float32),
        scratch_shapes=[pltpu.SMEM((1, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(xf.reshape(n_tiles, 1, P_TILE))
    return out.reshape(-1)[:e].astype(jnp.int32)


# repro: unaudited -- kernel-tier primitive; inlined into audited engine jits when called under trace
@functools.partial(jax.jit, static_argnames=("out_size", "fill", "interpret"))
def stream_compact(
    values: jax.Array,
    live: jax.Array,
    *,
    out_size: int,
    fill: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Compact ``values[live]`` into a dense ``[out_size]`` (or
    ``[out_size, D]``) int32 array, empty slots = ``fill``.

    Equivalent to
    ``full(out_size, fill).at[cumsum(live)-1 where live].set(values[live],
    mode="drop")`` but device-resident end to end: one Pallas prefix sum +
    one Pallas sorted segment sum, no host round-trip and no scatter HLO.
    """
    pos = prefix_sum(live.astype(jnp.int32), interpret=interpret) - 1
    # pos is nondecreasing (cumsum), so the segsum band-skip precondition
    # holds; dead lanes keep their (aliased) pos but contribute exactly 0.0
    live_b = live.astype(bool)
    if values.ndim == 1:
        contrib = jnp.where(
            live_b, values.astype(jnp.float32) - float(fill), 0.0)
    else:
        contrib = jnp.where(
            live_b[:, None], values.astype(jnp.float32) - float(fill), 0.0)
    out = segment_sum_sorted(
        contrib, pos, num_segments=out_size, interpret=interpret)
    return (out + float(fill)).astype(jnp.int32)


__all__ = ["prefix_sum", "stream_compact", "P_TILE"]
