"""Mixture-of-Experts layer: dense oracle + expert-parallel all-to-all path.

Two implementations with identical semantics (tested against each other):

* ``moe_dense`` — one-hot combine over all experts. O(T·E·D·F) compute; only
  for smoke-scale configs and as the numerical oracle.

* ``moe_ep`` — the production path, a ``shard_map`` over the mesh:
    1. per-device top-k routing of local tokens (router replicated);
    2. replicas bucketed by owner device (experts sharded over the ``model``
       axis, E_loc = E / |model|) into fixed-capacity send buffers;
    3. ``lax.all_to_all`` token exchange (THE MoE collective — the dry-run
       roofline counts it);
    4. local sort-by-expert + ``lax.ragged_dot`` grouped SwiGLU — exact
       active-FLOPs compute, no one-hot dispatch einsum (that formulation
       inflates HLO_FLOPs ~600× and is why we avoid GShard-style dispatch);
    5. all-to-all back, combine with renormalized gates.
  Tokens over capacity are dropped (standard; ``capacity_factor`` configures
  the slack — raise it for dropless-ish behaviour).

Shapes are static everywhere: sorting + fixed-capacity buffers replace the
data-dependent hash maps a CPU implementation would use — the same
adaptation DESIGN.md §2 applies to the paper's peeling sets.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_model: int
    d_ff: int                  # per-expert hidden
    n_shared: int = 0          # shared (always-on) experts
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.001
    compute_dtype: Any = jnp.float32


# ---------------------------------------------------------------------------
# routing (shared by both paths)
# ---------------------------------------------------------------------------
def _route(x2d: jax.Array, router: jax.Array, cfg: MoEConfig):
    """Returns (gates [T,k] f32 renormalized, idx [T,k] i32, aux_loss f32)."""
    logits = jnp.dot(x2d.astype(jnp.float32), router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                      # [T, E]
    gates, idx = jax.lax.top_k(probs, cfg.top_k)                 # [T, k]
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * mean_e(frac_tokens_e * mean_prob_e)
    one_hot = jax.nn.one_hot(idx[:, 0], cfg.n_experts, dtype=jnp.float32)
    aux = cfg.n_experts * jnp.mean(
        jnp.mean(one_hot, axis=0) * jnp.mean(probs, axis=0))
    return gates, idx, aux


def _shared_ffn(x2d: jax.Array, p: dict, cfg: MoEConfig) -> jax.Array:
    xc = x2d.astype(cfg.compute_dtype)
    g = jax.nn.silu(jnp.dot(xc, p["shared_wg"].astype(cfg.compute_dtype)))
    h = g * jnp.dot(xc, p["shared_wi"].astype(cfg.compute_dtype))
    return jnp.dot(h, p["shared_wo"].astype(cfg.compute_dtype))


# ---------------------------------------------------------------------------
# dense oracle
# ---------------------------------------------------------------------------
def moe_dense(x: jax.Array, p: dict, cfg: MoEConfig) -> tuple[jax.Array, jax.Array]:
    """[B,S,D] -> ([B,S,D], aux_loss). All-experts compute; oracle only."""
    b, s, d = x.shape
    x2d = x.reshape(-1, d)
    gates, idx, aux = _route(x2d, p["router"], cfg)
    comb = jnp.zeros((x2d.shape[0], cfg.n_experts), jnp.float32)
    for j in range(cfg.top_k):
        comb = comb + jax.nn.one_hot(idx[:, j], cfg.n_experts) * gates[:, j:j + 1]
    xc = x2d.astype(cfg.compute_dtype)
    gh = jax.nn.silu(jnp.einsum("td,edf->tef", xc, p["wg"].astype(cfg.compute_dtype)))
    hh = gh * jnp.einsum("td,edf->tef", xc, p["wi"].astype(cfg.compute_dtype))
    ye = jnp.einsum("tef,efd->ted", hh, p["wo"].astype(cfg.compute_dtype))
    y = jnp.einsum("ted,te->td", ye.astype(jnp.float32), comb)
    if cfg.n_shared:
        y = y + _shared_ffn(x2d, p, cfg).astype(jnp.float32)
    return y.astype(x.dtype).reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# expert-parallel all-to-all path
# ---------------------------------------------------------------------------
def _moe_local(x2d, router, wg, wi, wo, cfg: MoEConfig, model_size: int,
               axis: str | None):
    """Per-device body (runs under shard_map; axis=None => single device)."""
    t, d = x2d.shape
    e_loc = wg.shape[0]
    gates, idx, aux = _route(x2d, router, cfg)

    tk = t * cfg.top_k
    eid = idx.reshape(-1)                           # [tk] global expert id
    gate_r = gates.reshape(-1)                      # [tk]
    tok_r = jnp.repeat(jnp.arange(t, dtype=jnp.int32), cfg.top_k)
    peer = eid // e_loc                             # destination device

    cap = int(round(tk / model_size * cfg.capacity_factor))
    cap = max(8, -(-cap // 8) * 8)                  # >=8, multiple of 8

    # position of each replica inside its peer bucket (stable order)
    order = jnp.argsort(peer, stable=True)
    peer_s = peer[order]
    start = jnp.searchsorted(peer_s, jnp.arange(model_size))
    pos_s = jnp.arange(tk, dtype=jnp.int32) - start[peer_s]
    pos = jnp.zeros_like(pos_s).at[order].set(pos_s)   # unsorted view
    keep = pos < cap

    send = jnp.zeros((model_size, cap, d), x2d.dtype)
    send = send.at[peer, pos, :].set(
        jnp.where(keep[:, None], x2d[tok_r], 0.0), mode="drop")
    send_eid = jnp.full((model_size, cap), -1, jnp.int32)
    send_eid = send_eid.at[peer, pos].set(
        jnp.where(keep, eid % e_loc, -1), mode="drop")

    if axis is not None:
        recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0, tiled=False)
        recv_eid = jax.lax.all_to_all(send_eid, axis, split_axis=0, concat_axis=0, tiled=False)
    else:
        recv, recv_eid = send, send_eid

    r = model_size * cap
    xr = recv.reshape(r, d)
    er = recv_eid.reshape(r)
    er_sort_key = jnp.where(er < 0, e_loc, er)       # invalid slots last
    ord2 = jnp.argsort(er_sort_key, stable=True)
    xs = xr[ord2].astype(cfg.compute_dtype)
    es = er_sort_key[ord2]
    group_sizes = jnp.bincount(es, length=e_loc + 1)[:e_loc].astype(jnp.int32)

    g = jax.nn.silu(jax.lax.ragged_dot(xs, wg.astype(cfg.compute_dtype), group_sizes))
    h = g * jax.lax.ragged_dot(xs, wi.astype(cfg.compute_dtype), group_sizes)
    ys = jax.lax.ragged_dot(h, wo.astype(cfg.compute_dtype), group_sizes)
    ys = jnp.where((es < e_loc)[:, None], ys, 0.0)

    yr = jnp.zeros_like(ys).at[ord2].set(ys).reshape(model_size, cap, d)
    if axis is not None:
        back = jax.lax.all_to_all(yr, axis, split_axis=0, concat_axis=0, tiled=False)
    else:
        back = yr

    y_rep = back[peer, pos, :]                       # [tk, D]
    y_rep = jnp.where(keep[:, None], y_rep, 0.0) * gate_r[:, None].astype(back.dtype)
    y = jax.ops.segment_sum(y_rep.astype(jnp.float32), tok_r, num_segments=t)
    return y.astype(x2d.dtype), aux


def moe_ep(x: jax.Array, p: dict, cfg: MoEConfig, *, mesh=None,
           dp: tuple[str, ...] = ("data",), tp: str = "model",
           sp: bool = False) -> tuple[jax.Array, jax.Array]:
    """[B,S,D] -> ([B,S,D], aux). Experts sharded over ``tp``; tokens over
    ``dp`` (and over ``tp`` on the seq dim when ``sp`` — SP training).
    Without a mesh this runs the identical single-device body."""
    b, s, d = x.shape

    if mesh is None:
        y2d, aux = _moe_local(
            x.reshape(-1, d), p["router"], p["wg"], p["wi"], p["wo"],
            cfg, model_size=1, axis=None)
        y = y2d.reshape(b, s, d)
    else:
        model_size = mesh.shape[tp]

        def body(xl, router, wg, wi, wo):
            bl, sl, _ = xl.shape
            y2d, aux_l = _moe_local(
                xl.reshape(-1, d), router, wg, wi, wo, cfg,
                model_size=model_size, axis=tp)
            # aux is computed per shard: average across the whole mesh
            aux_l = jax.lax.pmean(aux_l, tp)
            for a in dp:
                aux_l = jax.lax.pmean(aux_l, a)
            return y2d.reshape(bl, sl, d), aux_l

        spec_x = P(dp, tp if sp else None, None)
        y, aux = jax.shard_map(
            body, mesh=mesh,
            in_specs=(spec_x, P(), P(tp, None, None), P(tp, None, None),
                      P(tp, None, None)),
            out_specs=(spec_x, P()),
            check_vma=False,
        )(x, p["router"], p["wg"], p["wi"], p["wo"])

    if cfg.n_shared:
        y = y + _shared_ffn(x.reshape(-1, d), p, cfg).astype(x.dtype).reshape(b, s, d)
    return y, aux


def init_moe_params(key: jax.Array, cfg: MoEConfig, n_layers: int,
                    param_dtype=jnp.float32) -> dict:
    """Stacked-over-layers MoE params."""
    ks = jax.random.split(key, 7)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    sc = d ** -0.5
    p = {
        "router": jax.random.normal(ks[0], (n_layers, d, e), param_dtype) * sc,
        "wg": jax.random.normal(ks[1], (n_layers, e, d, f), param_dtype) * sc,
        "wi": jax.random.normal(ks[2], (n_layers, e, d, f), param_dtype) * sc,
        "wo": jax.random.normal(ks[3], (n_layers, e, f, d), param_dtype) * (f ** -0.5),
    }
    if cfg.n_shared:
        fs = cfg.d_ff * cfg.n_shared
        p["shared_wg"] = jax.random.normal(ks[4], (n_layers, d, fs), param_dtype) * sc
        p["shared_wi"] = jax.random.normal(ks[5], (n_layers, d, fs), param_dtype) * sc
        p["shared_wo"] = jax.random.normal(ks[6], (n_layers, fs, d), param_dtype) * (fs ** -0.5)
    return p


__all__ = ["MoEConfig", "moe_dense", "moe_ep", "init_moe_params"]
