"""TP-within-expert MoE (for n_experts < |model| axis, e.g. Grok-1's 8).

Every device holds all experts' d_ff/|model| slice; tokens stay local (no
all-to-all). Per device: sort local token-replicas by expert, grouped
``ragged_dot`` over the F-shard, then one ``psum`` over the model axis to
combine partial wo contractions — the same collective pattern as a TP MLP,
with exact active-FLOPs compute (no one-hot dispatch einsum).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.moe import MoEConfig, _route, _shared_ffn


def _moe_tp_local(x2d, router, wg, wi, wo, cfg: MoEConfig, axis: str | None):
    t, d = x2d.shape
    e = cfg.n_experts
    gates, idx, aux = _route(x2d, router, cfg)

    tk = t * cfg.top_k
    eid = idx.reshape(-1)
    gate_r = gates.reshape(-1)
    tok_r = jnp.repeat(jnp.arange(t, dtype=jnp.int32), cfg.top_k)

    order = jnp.argsort(eid, stable=True)
    xs = x2d[tok_r[order]].astype(cfg.compute_dtype)        # [tk, D]
    group_sizes = jnp.bincount(eid[order], length=e).astype(jnp.int32)

    g = jax.nn.silu(jax.lax.ragged_dot(xs, wg.astype(cfg.compute_dtype), group_sizes))
    h = g * jax.lax.ragged_dot(xs, wi.astype(cfg.compute_dtype), group_sizes)
    ys = jax.lax.ragged_dot(h, wo.astype(cfg.compute_dtype), group_sizes)  # partial over F-shard
    if axis is not None:
        ys = jax.lax.psum(ys, axis)

    y_rep = jnp.zeros_like(ys).at[order].set(ys)
    y = jax.ops.segment_sum(
        y_rep.astype(jnp.float32) * gate_r[:, None], tok_r, num_segments=t)
    return y.astype(x2d.dtype), aux


def moe_tp(x: jax.Array, p: dict, cfg: MoEConfig, *, mesh=None,
           dp: tuple[str, ...] = ("data",), tp: str = "model",
           sp: bool = False) -> tuple[jax.Array, jax.Array]:
    """[B,S,D] -> ([B,S,D], aux). Expert weights sharded over d_ff.

    ``sp`` is accepted for API parity with moe_ep but the tokens enter this
    layer sequence-GATHERED: d_ff and the sequence cannot shard the same
    axis (the psum over F-partials would mix different tokens). The
    enclosing pjit inserts the gather/scatter pair around the layer.
    """
    del sp
    b, s, d = x.shape
    if mesh is None:
        y2d, aux = _moe_tp_local(
            x.reshape(-1, d), p["router"], p["wg"], p["wi"], p["wo"], cfg, None)
        y = y2d.reshape(b, s, d)
    else:
        def body(xl, router, wg, wi, wo):
            bl, sl, _ = xl.shape
            y2d, aux_l = _moe_tp_local(
                xl.reshape(-1, d), router, wg, wi, wo, cfg, tp)
            aux_l = jax.lax.pmean(aux_l, tp)
            for a in dp:
                aux_l = jax.lax.pmean(aux_l, a)
            return y2d.reshape(bl, sl, d), aux_l

        spec_x = P(dp, None, None)
        y, aux = jax.shard_map(
            body, mesh=mesh,
            in_specs=(spec_x, P(), P(None, None, tp), P(None, None, tp),
                      P(None, tp, None)),
            out_specs=(spec_x, P()),
            check_vma=False,
        )(x, p["router"], p["wg"], p["wi"], p["wo"])

    if cfg.n_shared:
        y = y + _shared_ffn(x.reshape(-1, d), p, cfg).astype(x.dtype).reshape(b, s, d)
    return y, aux


__all__ = ["moe_tp"]
