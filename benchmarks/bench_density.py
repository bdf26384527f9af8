"""Paper Table 3 analog: Exact vs P-Bahmani(eps=0) vs CBDS-P densities.

The container is offline (no SNAP downloads), so the suite is synthetic
graphs with exactly solvable optima (exact Goldberg flow runs on all of
them) + the planted-dense family whose optimum is known by construction.
The table validates the paper's central claim: CBDS-P produces densities
strictly better than the 2-approximation class, usually matching exact.

Joins the benchmark-trajectory gate (ISSUE 5 satellite): every run writes
``BENCH_density.json`` whose headline metrics are the *quality ratios*
``pb_quality_min`` / ``cbds_quality_min`` = min over the suite of
(reported density / rho*) — deterministic seeded graphs, so the gate
catches an algorithmic quality regression, not wall-clock noise. The
``--smoke`` suite keeps the exact flow solver under CI budget.
"""
from __future__ import annotations

import os
import sys

if __name__ == "__main__":
    # direct invocation: put src/ and the repo root on the path (run.py
    # does this for the suite)
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(_root, "src"))
    sys.path.insert(0, _root)

import numpy as np

from benchmarks._artifacts import write_bench_json
from repro.core import cbds_p, exact_densest, pbahmani
from repro.graphs.generators import (
    barabasi_albert, erdos_renyi, planted_dense, rmat, small_named,
)


def suite():
    yield "triangle_plus_path", small_named("triangle_plus_path")
    yield "k4_plus_star", small_named("k4_plus_star")
    yield "two_cliques", small_named("two_cliques")
    yield "petersen", small_named("petersen")
    yield "er_1k_p01", erdos_renyi(1000, 0.01, seed=1)
    yield "er_2k_p02", erdos_renyi(2000, 0.02, seed=2)
    yield "ba_2k_m8", barabasi_albert(2000, 8, seed=3)
    yield "rmat_s12", rmat(12, edge_factor=8, seed=4)
    g, _, _ = planted_dense(3000, 60, seed=5)
    yield "planted_3k_60", g


def suite_smoke():
    """Small enough that the exact flow baseline stays in CI budget."""
    yield "triangle_plus_path", small_named("triangle_plus_path")
    yield "k4_plus_star", small_named("k4_plus_star")
    yield "two_cliques", small_named("two_cliques")
    yield "petersen", small_named("petersen")
    yield "er_300_p05", erdos_renyi(300, 0.05, seed=1)
    yield "ba_400_m6", barabasi_albert(400, 6, seed=3)
    g, _, _ = planted_dense(500, 25, seed=5)
    yield "planted_500_25", g


def run(csv=True, graphs=suite):
    rows = []
    header = "graph,|V|,|E|,exact,pbahmani_eps0,cbds_p,cbds_core,ratio_pb,ratio_cbds"
    if csv:
        print(header)
    for name, g in graphs():
        rho_star, _ = exact_densest(g) if g.n_nodes <= 5000 else (float("nan"), None)
        rho_pb, _, _ = pbahmani(g, eps=0.0)
        res = cbds_p(g)
        row = (name, g.n_nodes, g.n_edges, round(rho_star, 4),
               round(rho_pb, 4), round(res["density"], 4),
               round(res["core_density"], 4),
               round(rho_star / max(rho_pb, 1e-9), 4),
               round(rho_star / max(res["density"], 1e-9), 4))
        rows.append(row)
        if csv:
            print(",".join(str(x) for x in row))
    return rows


def _emit(rows, mode: str) -> None:
    """BENCH_density.json: quality ratios (density / rho*) for the gate."""
    with_exact = [r for r in rows if not np.isnan(r[3]) and r[3] > 0]
    metrics = {
        "pb_quality_min": min(r[4] / r[3] for r in with_exact),
        "cbds_quality_min": min(r[5] / r[3] for r in with_exact),
    }
    write_bench_json(
        "density", metrics,
        [dict(zip(("graph", "n_v", "n_e", "exact", "pbahmani", "cbds_p",
                   "cbds_core", "ratio_pb", "ratio_cbds"), r))
         for r in rows],
        mode=mode)


def main(smoke: bool = False):
    rows = run(graphs=suite_smoke if smoke else suite)
    # the paper's claim, checked across the whole suite:
    bad = [r for r in rows if not np.isnan(r[3]) and r[5] < r[3] / 2 - 1e-6]
    assert not bad, f"CBDS-P violated the 2-approx bound on {bad}"
    better = sum(1 for r in rows if r[5] >= r[4] - 1e-9)
    print(f"# CBDS-P >= P-Bahmani(0) density on {better}/{len(rows)} graphs")
    _emit(rows, "smoke" if smoke else "full")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if "--emit-metrics" in sys.argv:
        os.environ["BENCH_EMIT_METRICS"] = "1"
    main(smoke="--smoke" in sys.argv)
