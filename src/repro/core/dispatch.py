"""Kernel-tier dispatch for the peel/refine hot loop (ISSUE 7 tentpole).

Every peel-family recurrence in this repo reduces a per-edge boolean onto
its destination vertex — the paper's part-2 atomicSub. Two device
implementations exist:

  * **scatter** — XLA ``jax.ops.segment_sum`` (serialized scatter-add HLO),
    the historical path and the CPU default;
  * **kernel** — the Pallas tier (``kernels.ops.peel_update`` /
    ``segment_sum``: tiled one-hot MXU matmul with band-table grid
    skipping), which needs dst-sorted COO lanes to hit its O(B_v + B_e)
    band-skip envelope.

:func:`peel_delta` is the single switch point both ``pbahmani_pass``,
``kcore._level_fixpoint`` and ``refine/loads.py`` route through; the
``kernel=`` knob is threaded (as a *static* jit argument — flipping it is a
legitimate one-time compile, audited under its own shape key) from
``pbahmani`` / ``kcore_decompose`` / ``DeltaEngine`` / ``GraphRegistry`` /
``StreamService`` down to here. ``kernel=None`` resolves to the scatter tier
on every platform; whether the kernel runs compiled or interpreted follows
the platform (``kernels.segsum.interpret_default``), never a flag.

Bit-identity argument (the invariant tests/test_oracle_properties.py and
benchmarks/bench_kernels.py assert): both paths sum the same 0/1
contributions per destination; the kernel's float32 accumulation is exact
for any count below 2^24 (``EXACT_ENVELOPE``, asserted against edge
capacities at plan-build/engine-init time), and ``peel_update`` casts back
to int32 at the op boundary — so (density, mask, passes) triples match bit
for bit with the knob on or off, on sorted or unsorted lanes (sortedness is
a *performance* precondition: bands are recomputed from data every call).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# float32 integer-exactness envelope: every count the kernel tier sums must
# stay strictly below 2^24 or float accumulation could round — the whole
# bit-identity contract rests on this bound.
EXACT_ENVELOPE = 1 << 24


# repro: proof
def assert_exact_envelope(*counts: int) -> None:
    """Fail fast (host-side, plan-build/engine-init time) if any capacity
    could push a kernel-path float32 sum past exact-integer range."""
    for c in counts:
        if int(c) >= EXACT_ENVELOPE:
            raise ValueError(
                f"capacity {int(c)} >= 2^24 breaks the kernel tier's "
                f"float32 exactness envelope; shard the tenant or force "
                f"kernel=False")


def peel_delta(
    fail: jax.Array, dst: jax.Array, n_nodes: int, kernel: bool
) -> jax.Array:
    """Sum a per-edge-lane boolean onto its dst vertex: int32 ``[n_nodes]``.

    The one switch point of the peel/refine hot loop. ``fail`` is any
    per-lane bool (failed-src edges for the degree decrement, charged edges
    for refine loads); sentinel lanes (dst >= n_nodes) drop on both paths.
    """
    if kernel:
        # the peel bodies fold liveness into ``fail`` before the reduction
        # (kernels.ops.peel_update bakes only the sentinel-validity mask),
        # so route the pre-masked lanes through the same Pallas segsum core
        # peel_update wraps — identical tiling, band table and exactness
        from repro.kernels.ops import segment_sum  # lazy: core <-> kernels

        out = segment_sum(fail.astype(jnp.float32), dst,
                          num_segments=n_nodes, impl="pallas",
                          presorted=True)
        return out.astype(jnp.int32)
    return jax.ops.segment_sum(
        fail.astype(jnp.int32), jnp.minimum(dst, n_nodes),
        num_segments=n_nodes + 1)[:n_nodes]


__all__ = ["EXACT_ENVELOPE",
           "assert_exact_envelope", "peel_delta"]
