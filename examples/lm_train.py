"""LM training driver: a ~small transformer for a few hundred steps with the
full production loop — checkpointing, restart, straggler re-dispatch.

  PYTHONPATH=src python examples/lm_train.py [--steps 200] [--arch qwen2.5-3b]

The --arch flag picks whose SMOKE config to train (the full configs are
pod-scale; the loop/launcher code path is identical).
"""
import argparse
import sys
import tempfile

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import get_arch
from repro.data import lm_token_batches
from repro.launch.train import LoopConfig, run_training
from repro.models.transformer import init_params, loss_fn
from repro.optim import adamw, linear_warmup_cosine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt_dir", default=None)
    args = ap.parse_args()

    cfg = get_arch(args.arch).smoke
    print(f"training {cfg.name}: {cfg.n_layers}L d={cfg.d_model} "
          f"vocab={cfg.vocab} on batch={args.batch} seq={args.seq}")

    opt = adamw(linear_warmup_cosine(3e-3, 20, args.steps), weight_decay=0.01)

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

    data = lambda start: lm_token_batches(cfg.vocab, args.batch, args.seq,
                                          seed=0, start_step=start)
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="lm_ckpt_")
    ckpt = CheckpointManager(ckpt_dir, keep=3)
    res = run_training(step, init_state, data, ckpt,
                       LoopConfig(total_steps=args.steps, ckpt_every=50))
    k = max(len(res.losses) // 10, 1)
    print("loss curve:", " ".join(f"{l:.3f}" for l in res.losses[::k]))
    print(f"final loss {res.losses[-1]:.4f} | restarts={res.restarts} "
          f"redispatched={res.redispatched} | checkpoints in {ckpt_dir}")
    assert res.losses[-1] < res.losses[0], "did not learn"


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
