"""GNN + paper-technique integration: CBDS-P powers the data layer.

Trains a GCN node classifier on a synthetic community graph twice:
  (a) uniform neighbor sampling;
  (b) core-ordered sampling driven by the k-core decomposition (the paper's
      phase-1 output) — the DESIGN.md §5 integration point.

  PYTHONPATH=src python examples/gnn_community.py
"""
import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cbds_p, kcore_decompose
from repro.data import gnn_batch
from repro.graphs.generators import planted_dense
from repro.graphs.sampler import NeighborSampler
from repro.models.gnn import GCNConfig, gcn_forward, gcn_init, gcn_loss
from repro.optim import adamw


def main():
    # community graph: dense planted block = class 1, background = class 0
    g, planted_mask, rho = planted_dense(3000, 120, p_background=0.01,
                                         p_planted=0.5, seed=1)
    print(f"graph {g}; planted community rho={rho:.2f}")

    res = cbds_p(g)
    found = res["member_mask"]
    inter = (found & planted_mask).sum() / max(planted_mask.sum(), 1)
    print(f"CBDS-P recovers {100*inter:.1f}% of the planted community "
          f"(rho~={res['density']:.2f})")

    coreness, *_ = kcore_decompose(g)
    rng = np.random.default_rng(0)
    feat = rng.normal(size=(g.n_nodes, 16)).astype(np.float32)
    # features correlate weakly with membership; structure carries signal
    feat[:, :4] += planted_mask[:, None] * 1.5
    labels = planted_mask.astype(np.int32)

    cfg = GCNConfig(d_feat=16, d_hidden=32, n_classes=2)
    for name, core_bias in (("uniform", None), ("core-ordered", coreness)):
        sampler = NeighborSampler(g, (8, 4), coreness=core_bias, seed=0)
        params = gcn_init(jax.random.PRNGKey(0), cfg)
        opt = adamw(5e-3, weight_decay=0.0)
        st = opt.init(params)

        @jax.jit
        def step(params, st, batch):
            l, grads = jax.value_and_grad(gcn_loss)(params, batch, cfg)
            p2, st2 = opt.update(grads, st, params)
            return p2, st2, l

        losses = []
        planted_ids = np.where(planted_mask)[0]
        for it in range(80):
            seeds = np.concatenate([rng.integers(0, g.n_nodes, 48),
                                    rng.choice(planted_ids, 16)])
            blk = sampler.sample(seeds)
            ids = np.maximum(blk["node_ids"], 0)
            batch = {
                "node_feat": jnp.asarray(feat[ids]),
                "src": jnp.asarray(blk["src"]), "dst": jnp.asarray(blk["dst"]),
                "labels": jnp.asarray(labels[ids]),
                "label_mask": jnp.asarray(
                    (blk["node_ids"] >= 0) &
                    (np.arange(blk["n_nodes"]) < blk["n_seeds"])),
            }
            params, st, l = step(params, st, batch)
            losses.append(float(l))

        # full-graph eval
        full = gnn_batch(g, d_feat=16, n_classes=2, seed=0)
        full["node_feat"] = feat
        logits = gcn_forward(params, {k: jnp.asarray(v) if isinstance(v, np.ndarray)
                                      else v for k, v in full.items()}, cfg)
        pred = np.asarray(jnp.argmax(logits, -1))
        acc = (pred == labels).mean()
        planted_recall = (pred[planted_mask] == 1).mean()
        print(f"{name:13s}: loss {losses[0]:.3f} -> {losses[-1]:.3f}, "
              f"acc={100*acc:.1f}%, planted-recall={100*planted_recall:.1f}%")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
