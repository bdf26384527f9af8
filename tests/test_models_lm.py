"""Transformer family: decode==prefill, flash==plain, MoE paths agree,
training reduces loss. All at smoke scale on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.layers import _attend, flash_attention
from repro.models.moe import MoEConfig, init_moe_params, moe_dense, moe_ep
from repro.models.moe_tp import moe_tp
from repro.models.transformer import (
    TransformerConfig, decode_step, forward, init_cache, init_params, loss_fn,
)
from repro.optim import adamw


@pytest.fixture(scope="module")
def gqa_cfg():
    return TransformerConfig(name="t", n_layers=3, d_model=32, n_heads=4,
                             n_kv_heads=2, d_ff=64, vocab=101, qkv_bias=True,
                             rope_theta=1e4)


@pytest.fixture(scope="module")
def mla_moe_cfg():
    return TransformerConfig(
        name="t2", n_layers=4, d_model=32, n_heads=4, n_kv_heads=4, d_ff=64,
        vocab=101, attn="mla", q_lora_rank=24, kv_lora_rank=16, qk_nope_dim=8,
        qk_rope_dim=4, v_head_dim=8, n_dense_layers=2, mtp=True,
        moe=MoEConfig(n_experts=4, top_k=2, d_model=32, d_ff=48, n_shared=1,
                      capacity_factor=4.0))


def _toks(shape, vocab=101, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, vocab)


@pytest.mark.parametrize("cfg_name", ["gqa_cfg", "mla_moe_cfg"])
def test_decode_matches_prefill(cfg_name, request):
    cfg = request.getfixturevalue(cfg_name)
    p = init_params(jax.random.PRNGKey(0), cfg)
    toks = _toks((2, 16))
    logits, _ = forward(p, toks, cfg)
    cache = init_cache(cfg, 2, 16)
    outs = []
    for t in range(12):
        lg, cache = decode_step(p, cache, toks[:, t], jnp.asarray(t), cfg)
        outs.append(lg)
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(logits[:, :12]),
                               rtol=6e-3, atol=6e-3)


@pytest.mark.parametrize("cfg_name", ["gqa_cfg", "mla_moe_cfg"])
def test_prefill_cache_continues(cfg_name, request):
    cfg = request.getfixturevalue(cfg_name)
    p = init_params(jax.random.PRNGKey(0), cfg)
    toks = _toks((2, 16))
    logits, _aux, cache = forward(p, toks, cfg, return_cache=True)
    padspec = ((0, 0), (0, 0), (0, 4)) + ((0, 0),) * (
        jax.tree.leaves(cache)[0].ndim - 3)
    cache = jax.tree.map(lambda x: jnp.pad(x, padspec[:x.ndim]), cache)
    nxt = jnp.full((2,), 5)
    lg, _ = decode_step(p, cache, nxt, jnp.asarray(16), cfg)
    ref_toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    ref, _ = forward(p, ref_toks, cfg)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(ref[:, -1]),
                               rtol=6e-3, atol=6e-3)


def test_sliding_window_decode(gqa_cfg):
    """Ring-buffer window cache == full cache when seq <= window."""
    from dataclasses import replace
    cfg_w = replace(gqa_cfg, sliding_window=32)
    p = init_params(jax.random.PRNGKey(0), cfg_w)
    toks = _toks((2, 20))
    cache_full = init_cache(gqa_cfg, 2, 20)
    cache_win = init_cache(cfg_w, 2, 64)   # window 32 => ring of 32
    assert jax.tree.leaves(cache_win)[0].shape[2] == 32
    for t in range(20):
        lg_f, cache_full = decode_step(p, cache_full, toks[:, t],
                                       jnp.asarray(t), gqa_cfg)
        lg_w, cache_win = decode_step(p, cache_win, toks[:, t],
                                      jnp.asarray(t), cfg_w)
    np.testing.assert_allclose(np.asarray(lg_w), np.asarray(lg_f),
                               rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("qc,kc", [(32, 32), (128, 32), (64, 128)])
def test_flash_matches_plain(qc, kc):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 24))
    k = jax.random.normal(ks[1], (2, 128, 2, 24))
    v = jax.random.normal(ks[2], (2, 128, 2, 16))
    o1 = flash_attention(q, k, v, causal=True, q_chunk=qc, k_chunk=kc)
    o2 = _attend(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=3e-4, atol=3e-4)


def test_moe_paths_agree():
    cfg = MoEConfig(n_experts=8, top_k=2, d_model=16, d_ff=32, n_shared=1,
                    capacity_factor=8.0)
    p = jax.tree.map(lambda a: a[0],
                     init_moe_params(jax.random.PRNGKey(0), cfg, 1))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 16))
    yd, _ = moe_dense(x, p, cfg)
    ye, _ = moe_ep(x, p, cfg)
    yt, _ = moe_tp(x, p, cfg)
    np.testing.assert_allclose(np.asarray(yd), np.asarray(ye), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(yd), np.asarray(yt), rtol=2e-4, atol=2e-4)


def test_moe_capacity_drops_are_bounded():
    """With tight capacity some tokens drop; output stays finite and close
    in norm (the framework trade documented in models/moe.py)."""
    cfg_tight = MoEConfig(n_experts=4, top_k=2, d_model=16, d_ff=32,
                          capacity_factor=1.0)
    p = jax.tree.map(lambda a: a[0],
                     init_moe_params(jax.random.PRNGKey(0), cfg_tight, 1))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 16))
    y, aux = moe_ep(x, p, cfg_tight)
    assert bool(jnp.all(jnp.isfinite(y)))
    assert float(jnp.abs(y).sum()) > 0


@pytest.mark.parametrize("cfg_name", ["gqa_cfg", "mla_moe_cfg"])
def test_train_reduces_loss(cfg_name, request):
    cfg = request.getfixturevalue(cfg_name)
    p = init_params(jax.random.PRNGKey(0), cfg)
    opt = adamw(1e-2, weight_decay=0.0)
    st = opt.init(p)
    toks = _toks((4, 16), seed=7)

    @jax.jit
    def step(p, st):
        loss, g = jax.value_and_grad(lambda q: loss_fn(q, toks, toks, cfg))(p)
        p2, st2 = opt.update(g, st, p)
        return p2, st2, loss

    losses = []
    for _ in range(12):
        p, st, loss = step(p, st)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.3, losses
    assert np.isfinite(losses).all()


def test_param_count_sane():
    """n_params/n_active_params used by the roofline: sanity at smoke scale."""
    cfg = TransformerConfig(name="c", n_layers=2, d_model=16, n_heads=2,
                            n_kv_heads=2, d_ff=32, vocab=64,
                            moe=MoEConfig(n_experts=4, top_k=2, d_model=16,
                                          d_ff=32), n_dense_layers=1)
    total = cfg.n_params()
    active = cfg.n_active_params()
    assert 0 < active < total
    # exactly: total - (E-k) * per_expert * n_moe_layers
    per_e = 3 * 16 * 32
    assert total - active == (4 - 2) * per_e * 1


def test_int8_kv_cache_decode(gqa_cfg):
    """int8 KV cache (EXPERIMENTS §Perf #3): <=3% rel error, identical
    greedy tokens vs the f32-cache decode."""
    from dataclasses import replace
    cfg8 = replace(gqa_cfg, kv_cache_dtype="int8")
    p = init_params(jax.random.PRNGKey(0), gqa_cfg)
    toks = _toks((2, 24))
    ref, _ = forward(p, toks, gqa_cfg)
    cache = init_cache(cfg8, 2, 24)
    assert cache["k"].dtype == jnp.int8 and "k_scale" in cache
    outs = []
    for t in range(24):
        lg, cache = decode_step(p, cache, toks[:, t], jnp.asarray(t), cfg8)
        outs.append(lg)
    dec = jnp.stack(outs, axis=1)
    rel = float(jnp.max(jnp.abs(dec - ref)) / jnp.max(jnp.abs(ref)))
    assert rel < 0.03, rel
    # near-ties may flip under quantization; random-init logits are ~flat,
    # so require high (not perfect) greedy agreement
    agree = float((jnp.argmax(dec, -1) == jnp.argmax(ref[:, :24], -1)).mean())
    assert agree >= 0.9, agree


def test_zero3_param_specs_cover_all_leaves():
    from repro.models.transformer import param_specs_zero3
    from repro.configs import get_arch
    from repro.utils.mesh import make_mesh_auto
    mesh = make_mesh_auto((1, 1), ("data", "model"))
    cfg = get_arch("qwen2.5-3b").smoke
    specs = param_specs_zero3(cfg, mesh)
    p = init_params(jax.random.PRNGKey(0), cfg)
    assert jax.tree.structure(specs) == jax.tree.structure(p)
