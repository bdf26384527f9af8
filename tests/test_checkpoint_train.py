"""Checkpoint manager + fault-tolerant train loop + data pipeline."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.data import lm_token_batches, recsys_batches
from repro.launch.train import LoopConfig, run_training
from repro.models.transformer import TransformerConfig, init_params, loss_fn
from repro.optim import adamw


# ---------------------------------------------------------------------------
# checkpoint manager
# ---------------------------------------------------------------------------
def test_roundtrip_and_prune(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    state = {"w": jnp.arange(12.0).reshape(3, 4), "step": 5,
             "nested": [jnp.ones(2), {"b": jnp.zeros(3)}]}
    for s in (10, 20, 30):
        mgr.save(s, state, blocking=True)
    assert mgr.all_steps() == [20, 30]
    target = jax.tree.map(lambda x: np.zeros_like(x) if hasattr(x, "shape") else 0,
                          state)
    step, restored = mgr.restore(target)
    assert step == 30
    np.testing.assert_array_equal(restored["w"], np.arange(12.0).reshape(3, 4))
    assert restored["step"] == 5


def test_atomic_no_partial_checkpoint(tmp_path):
    """A .tmp dir (simulated crash mid-save) is never listed as a step."""
    mgr = CheckpointManager(str(tmp_path), keep=5)
    mgr.save(1, {"a": jnp.ones(3)}, blocking=True)
    os.makedirs(tmp_path / "step_2.tmp")      # crashed save
    (tmp_path / "step_2.tmp" / "leaf_00000.npy").touch()
    assert mgr.all_steps() == [1]
    step, _ = mgr.restore({"a": np.zeros(3)})
    assert step == 1


def test_restore_shape_mismatch_raises(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"a": jnp.ones((3, 4))}, blocking=True)
    with pytest.raises(ValueError, match="shape"):
        mgr.restore({"a": np.zeros((4, 4))})


def test_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    mgr.save(7, {"a": jnp.full((1000, 100), 3.0)})
    mgr.wait()
    assert mgr.latest_step() == 7


# ---------------------------------------------------------------------------
# data pipeline determinism / resume
# ---------------------------------------------------------------------------
def test_lm_stream_resume_exact():
    a = lm_token_batches(100, 2, 8, seed=3)
    first = [next(a) for _ in range(5)]
    b = lm_token_batches(100, 2, 8, seed=3, start_step=3)
    resumed = next(b)
    np.testing.assert_array_equal(resumed["tokens"], first[3]["tokens"])


def test_recsys_stream_deterministic():
    from repro.configs import get_arch
    cfg = get_arch("dcn-v2").smoke
    a = next(recsys_batches(cfg, 4, seed=1))
    b = next(recsys_batches(cfg, 4, seed=1))
    np.testing.assert_array_equal(a["sparse_ids"], b["sparse_ids"])


# ---------------------------------------------------------------------------
# fault-tolerant loop: failure injection == uninterrupted run
# ---------------------------------------------------------------------------
def _tiny_setup(tmp_path, subdir):
    cfg = TransformerConfig(name="t", n_layers=2, d_model=16, n_heads=2,
                            n_kv_heads=2, d_ff=32, vocab=64)
    opt = adamw(1e-2, weight_decay=0.0)

    def init_state():
        p = init_params(jax.random.PRNGKey(0), cfg)
        return {"params": p, "opt": opt.init(p)}

    @jax.jit
    def step(state, batch):
        toks = jnp.asarray(batch["tokens"])
        labs = jnp.asarray(batch["labels"])
        loss, g = jax.value_and_grad(
            lambda q: loss_fn(q, toks, labs, cfg))(state["params"])
        p2, o2 = opt.update(g, state["opt"], state["params"])
        return {"params": p2, "opt": o2}, loss

    data = lambda start: lm_token_batches(64, 2, 8, seed=9, start_step=start)
    ckpt = CheckpointManager(str(tmp_path / subdir), keep=3) if subdir else None
    return step, init_state, data, ckpt


def test_loop_failure_recovery_bit_identical(tmp_path):
    step, init_state, data, ckpt = _tiny_setup(tmp_path, "a")
    cfg_loop = LoopConfig(total_steps=12, ckpt_every=4, log_every=100)

    # uninterrupted reference
    step2, init2, data2, _ = _tiny_setup(tmp_path, "")
    ref = run_training(step2, init2, data2, None, cfg_loop)

    # run with two injected failures
    fail_at = {6, 9}
    def injector(s):
        if s in fail_at:
            fail_at.discard(s)
            raise RuntimeError("simulated worker loss")
    res = run_training(step, init_state, data, ckpt, cfg_loop,
                       failure_injector=injector)
    assert res.restarts == 2
    # losses after recovery match the uninterrupted run exactly
    np.testing.assert_allclose(res.losses[-3:], ref.losses[-3:], rtol=1e-6)
    final_ref = jax.tree.leaves(ref.final_state["params"])
    final_got = jax.tree.leaves(res.final_state["params"])
    for a, b in zip(final_ref, final_got):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_loop_resumes_from_checkpoint(tmp_path):
    step, init_state, data, ckpt = _tiny_setup(tmp_path, "b")
    run_training(step, init_state, data, ckpt,
                 LoopConfig(total_steps=8, ckpt_every=4))
    # second invocation resumes, doesn't restart from zero
    res = run_training(step, init_state, data, ckpt,
                       LoopConfig(total_steps=12, ckpt_every=4))
    assert res.resumed_from == 8
    assert len(res.losses) == 4


def test_peel_with_restarts(tmp_path):
    from repro.launch.train import peel_with_restarts
    from repro.graphs.generators import planted_dense
    from repro.core import pbahmani_np

    from repro.utils.mesh import make_mesh_auto
    mesh = make_mesh_auto((1, 1), ("data", "model"))
    g, _, _ = planted_dense(400, 30, seed=2)
    ck = CheckpointManager(str(tmp_path / "peel"), keep=2)
    res = peel_with_restarts(g, mesh, eps=0.05, ckpt=ck, fail_at_pass=2)
    rho_ref, _, passes_ref = pbahmani_np(g, eps=0.05)
    assert res["density"] == pytest.approx(rho_ref, rel=1e-5)
    assert res["passes"] == passes_ref
