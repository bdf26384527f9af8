"""Density primitives shared by all densest-subgraph algorithms.

Density follows the paper (Definition 1): rho(S) = |E(S)| / |S|.
All device-side helpers operate on the padded symmetric COO arrays produced by
:class:`repro.graphs.Graph` (sentinel vertex = n_nodes, see graphs/graph.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_MANT = 1 << 24  # f32 significand range: integers below 2^24 are exact


def ratio(num: jax.Array, den: jax.Array) -> jax.Array:
    """``f32(num) / f32(max(den, 1))`` rounded to nearest-even on every
    backend.

    XLA's TPU backend does not round f32 division to nearest: on a v5e,
    about a third of the quotients of random counts below 2^24, and a
    quarter of the quotients that are exact, came back one ulp off the IEEE
    quotient (3631/190 one ulp low). Every density, peel threshold and best-density comparison must
    agree bit for bit with the CPU and the numpy reference: a low quotient
    on an exact tie ``deg == 2·n_e/n_v`` fails no vertex of a regular
    subgraph, and the peel would never end. So the quotient is formed by
    integer long division: ``num`` (a nonnegative int32 count) is rounded
    to f32 first, exactly as the float division did, and ``den`` is a
    nonnegative int32 count below 2^24.
    """
    num = jnp.asarray(num, jnp.int32)
    den = jnp.maximum(jnp.asarray(den, jnp.int32), 1)
    mant, ex = jnp.frexp(num.astype(jnp.float32))
    m = (mant * _MANT).astype(jnp.int32)   # f32(num) == m * 2^(ex - 24)
    q = m // den
    r = m - q * den
    shift = jnp.zeros_like(q)
    # m >= 2^23 and den < 2^24, so 26 doublings bring q to [2^24, 2^25):
    # 24 significand bits plus one rounding bit, the rest sticky in r
    for _ in range(26):
        grow = q < _MANT
        bit = (2 * r >= den).astype(jnp.int32)
        q = jnp.where(grow, 2 * q + bit, q)
        r = jnp.where(grow, 2 * r - bit * den, r)
        shift = shift + grow.astype(jnp.int32)
    sig = q >> 1
    sig = sig + ((q & 1) & ((r != 0) | (sig & 1)).astype(jnp.int32))
    out = jnp.ldexp(sig.astype(jnp.float32), ex - 23 - shift)
    return jnp.where(num > 0, out, 0.0)


def degrees_from_coo(src: jax.Array, n_nodes: int) -> jax.Array:
    """int32 [n_nodes] degrees from symmetric directed src array (padded)."""
    ones = jnp.ones_like(src, dtype=jnp.int32)
    deg = jax.ops.segment_sum(ones, src, num_segments=n_nodes + 1)
    return deg[:n_nodes]


def masked_degrees(src: jax.Array, dst: jax.Array, mask: jax.Array, n_nodes: int) -> jax.Array:
    """Degrees within the subgraph induced by boolean vertex ``mask``."""
    src_c = jnp.minimum(src, n_nodes)
    live = mask[jnp.minimum(src, n_nodes - 1)] & mask[jnp.minimum(dst, n_nodes - 1)]
    live &= (src < n_nodes) & (dst < n_nodes)
    deg = jax.ops.segment_sum(live.astype(jnp.int32), src_c, num_segments=n_nodes + 1)
    return deg[:n_nodes]


def induced_edge_count(src: jax.Array, dst: jax.Array, mask: jax.Array, n_nodes: int) -> jax.Array:
    """|E(S)| for S = mask (undirected count), int32 scalar."""
    valid = (src < n_nodes) & (dst < n_nodes)
    s = jnp.minimum(src, n_nodes - 1)
    d = jnp.minimum(dst, n_nodes - 1)
    live = valid & mask[s] & mask[d]
    return jnp.sum(live.astype(jnp.int32)) // 2


def subgraph_density(src: jax.Array, dst: jax.Array, mask: jax.Array, n_nodes: int) -> jax.Array:
    """rho(S) as float32; 0 for empty S."""
    ne = induced_edge_count(src, dst, mask, n_nodes)
    nv = jnp.sum(mask.astype(jnp.int32))
    return jnp.where(nv > 0, ratio(ne, nv), 0.0)


def density_np(n_edges: int, n_nodes: int) -> float:
    return n_edges / max(n_nodes, 1)


def check_approx_bound(approx: float, exact: float, alpha: float, tol: float = 1e-5) -> bool:
    """Definition 3: alpha-approximation iff rho(S~) >= rho*/alpha."""
    return approx >= exact / alpha - tol


def peel_threshold(n_e: jax.Array, n_v: jax.Array, eps: float) -> jax.Array:
    """Bahmani peeling threshold 2(1+eps)·rho as float32 (see DESIGN §2 on
    precision: comparisons are float32; exact for bench-sized integer counts)."""
    return 2.0 * (1.0 + eps) * ratio(n_e, n_v)


__all__ = [
    "ratio",
    "degrees_from_coo",
    "masked_degrees",
    "induced_edge_count",
    "subgraph_density",
    "density_np",
    "check_approx_bound",
    "peel_threshold",
]
