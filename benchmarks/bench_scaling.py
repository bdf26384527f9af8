"""Paper Figs 7-19 analog: runtime scaling of the peeling engines.

The paper plots wall-time vs core count on a 64-core Xeon. This container
exposes one CPU core, so the hardware-scaling axis is replaced by two
measurable analogues (methodology in EXPERIMENTS.md §Reproduction):
  1. wall-time vs |E| for P-Bahmani(jax) / P-Bahmani(numpy) / Charikar /
     CBDS-P — the serial-baseline speedup the paper's figures demonstrate;
  2. pass-count vs eps (the work-reduction knob that gives the parallel
     version its depth advantage);
  3. structural scaling: per-device collective bytes of the distributed
     peel pass at shard counts 2^k (from lowered HLO, no hardware needed).
"""
from __future__ import annotations

import numpy as np

from repro.core import cbds_p, charikar, pbahmani, pbahmani_np
from repro.graphs.generators import barabasi_albert, rmat
from repro.utils.timing import time_fn


def runtime_vs_size(csv=True):
    if csv:
        print("graph,|V|,|E|,t_pbahmani_jax,t_pbahmani_np,t_charikar,t_cbds")
    rows = []
    for scale in (10, 12, 14):
        g = rmat(scale, edge_factor=8, seed=scale)
        t_j, _ = time_fn(lambda: pbahmani(g, eps=0.05), iters=3)
        t_n, _ = time_fn(lambda: pbahmani_np(g, eps=0.05), iters=3)
        t_c, _ = time_fn(lambda: charikar(g), iters=1)
        t_b, _ = time_fn(lambda: cbds_p(g), iters=3)
        row = (f"rmat_s{scale}", g.n_nodes, g.n_edges,
               round(t_j, 4), round(t_n, 4), round(t_c, 4), round(t_b, 4))
        rows.append(row)
        if csv:
            print(",".join(str(x) for x in row))
    return rows


def passes_vs_eps(csv=True):
    g = barabasi_albert(20000, 8, seed=1)
    if csv:
        print("eps,passes,density")
    out = []
    for eps in (0.0, 0.005, 0.05, 0.5, 1.0):
        rho, _, passes = pbahmani(g, eps=eps)
        out.append((eps, passes, round(rho, 3)))
        if csv:
            print(f"{eps},{passes},{rho:.3f}")
    return out


def main():
    runtime_vs_size()
    passes_vs_eps()
    peel_collective_scaling()


def peel_collective_scaling(csv=True):
    """Structural scaling of one distributed peel pass: per-device collective
    payload vs worker count (lowered HLO on fabricated devices; the paper's
    cores-axis replaced by the shard axis). Runs in a subprocess because the
    device count must be fixed before jax initializes; the subprocess is
    pinned to the CPU, whose fabricated devices are what it counts."""
    import os
    import subprocess
    import sys
    script = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=64"
import jax, jax.numpy as jnp, numpy as np
from repro.core.distributed import make_peel_pass, shard_edges
from repro.utils.mesh import make_mesh_auto
from repro.core.pbahmani import init_state
from repro.graphs.generators import rmat
from repro.launch.hlo_analysis import collective_stats

g = rmat(14, edge_factor=8, seed=1)
print("workers,coll_bytes_per_pass_per_device,coll_ops")
for w in (2, 4, 16, 64):
    mesh = make_mesh_auto((w,), ("data",))
    peel = make_peel_pass(mesh, g.n_nodes, 0.05)
    src, dst = shard_edges(g, mesh)
    state = init_state(src, dst, g.n_nodes, g.n_edges)
    lowered = jax.jit(peel).lower(state, src, dst)
    cs = collective_stats(lowered.compile().as_text())
    n_ops = sum(v["count"] for k, v in cs.items() if isinstance(v, dict))
    print(f"{w},{cs['total_bytes']},{n_ops}")
'''
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    if out.returncode != 0:
        print("# peel scaling failed:", out.stderr[-300:])
        return
    if csv:
        print(out.stdout.strip())


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
