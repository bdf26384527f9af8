"""Recsys integration (DESIGN.md §5): densest subgraph as a fraud detector
on the user-item interaction graph, next to a DCN-v2 CTR model.

A click-farm (dense bipartite block of colluding users x boosted items) is
planted in a sparse interaction graph; CBDS-P flags it. The DCN-v2 model
then trains on the de-fraued interaction stream.

  PYTHONPATH=src python examples/recsys_fraud.py
"""
import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cbds_p
from repro.data import recsys_batches
from repro.graphs.graph import Graph
from repro.models.recsys import DCNConfig, dcn_init, dcn_loss
from repro.optim import adamw


def main():
    rng = np.random.default_rng(0)
    n_users, n_items = 4000, 1500
    # sparse organic interactions
    organic = np.stack([rng.integers(0, n_users, 25_000),
                        n_users + rng.integers(0, n_items, 25_000)], 1)
    # click farm: 60 users x 40 items, near-complete bipartite block
    farm_u = rng.choice(n_users, 60, replace=False)
    farm_i = n_users + rng.choice(n_items, 40, replace=False)
    uu, ii = np.meshgrid(farm_u, farm_i)
    keep = rng.random(uu.size) < 0.8
    farm = np.stack([uu.ravel()[keep], ii.ravel()[keep]], 1)
    g = Graph.from_edges(np.concatenate([organic, farm]),
                         n_nodes=n_users + n_items)
    print(f"interaction graph {g}; planted farm: 60 users x 40 items")

    res = cbds_p(g)
    flagged = np.where(res["member_mask"])[0]
    flagged_users = set(flagged[flagged < n_users].tolist())
    recall = len(flagged_users & set(farm_u.tolist())) / len(farm_u)
    precision = (len(flagged_users & set(farm_u.tolist())) /
                 max(len(flagged_users), 1))
    print(f"CBDS-P flags {len(flagged)} vertices (rho~={res['density']:.2f}): "
          f"farm-user recall={100*recall:.0f}% precision={100*precision:.0f}%")

    # CTR model on the clean stream
    cfg = DCNConfig(table_rows=5000, embed_dim=8, n_cross_layers=2,
                    mlp=(64, 32))
    params = dcn_init(jax.random.PRNGKey(0), cfg)
    opt = adamw(1e-2, weight_decay=0.0)
    st = opt.init(params)

    @jax.jit
    def step(params, st, batch):
        l, grads = jax.value_and_grad(dcn_loss)(params, batch, cfg)
        p2, st2 = opt.update(grads, st, params)
        return p2, st2, l

    losses = []
    for b in recsys_batches(cfg, batch=512, seed=1):
        jb = {k: jnp.asarray(v) for k, v in b.items() if k != "step"}
        params, st, l = step(params, st, jb)
        losses.append(float(l))
        if len(losses) >= 40:
            break
    print(f"DCN-v2 CTR training: loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"over {len(losses)} steps")
    assert recall >= 0.9, "fraud detector missed the farm"


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
