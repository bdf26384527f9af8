"""Paper Table 2 analog: rho*(G)/rho~(G) for eps in {0.005, 0.05, 0.5},
plus pass counts (the O(log_{1+eps} n) trade the paper tabulates).

Joins the benchmark-trajectory gate (ISSUE 5 satellite): every run writes
``BENCH_epsilon.json`` with ``peel_quality_min`` = min over all (graph,
eps) cells of rho~/rho* — deterministic seeded graphs, so the gate trips
on an algorithmic quality regression. ``--smoke`` shrinks the suite to
keep the exact flow baseline inside CI budget.
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

from benchmarks._artifacts import write_bench_json
from repro.core import exact_densest, pbahmani
from repro.graphs.generators import barabasi_albert, erdos_renyi, planted_dense

EPS = (0.005, 0.05, 0.5)


def suite():
    yield "er_1k", erdos_renyi(1000, 0.015, seed=11)
    yield "er_3k", erdos_renyi(3000, 0.006, seed=12)
    yield "ba_3k", barabasi_albert(3000, 6, seed=13)
    g, _, _ = planted_dense(2000, 50, seed=14)
    yield "planted_2k", g


def suite_smoke():
    yield "er_400", erdos_renyi(400, 0.04, seed=11)
    yield "ba_400", barabasi_albert(400, 6, seed=13)
    g, _, _ = planted_dense(500, 25, seed=14)
    yield "planted_500", g


def run(csv=True, graphs=suite):
    if csv:
        head = "graph,|V|,|E|,exact," + ",".join(
            f"ratio_eps{e},passes_eps{e}" for e in EPS)
        print(head)
    rows = []
    quality_min = 1.0
    for name, g in graphs():
        rho_star, _ = exact_densest(g)
        cells = []
        for eps in EPS:
            rho, _, passes = pbahmani(g, eps=eps)
            assert rho >= rho_star / (2 + 2 * eps) - 1e-5, (name, eps)
            quality_min = min(quality_min, rho / max(rho_star, 1e-9))
            cells += [round(rho_star / rho, 4), passes]
        row = [name, g.n_nodes, g.n_edges, round(rho_star, 3)] + cells
        rows.append(row)
        if csv:
            print(",".join(str(x) for x in row))
    return rows, quality_min


def main(smoke: bool = False):
    rows, quality_min = run(graphs=suite_smoke if smoke else suite)
    head = ["graph", "n_v", "n_e", "exact"] + [
        x for e in EPS for x in (f"ratio_eps{e}", f"passes_eps{e}")]
    write_bench_json(
        "epsilon", {"peel_quality_min": quality_min},
        [dict(zip(head, r)) for r in rows],
        mode="smoke" if smoke else "full")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if "--emit-metrics" in sys.argv:
        os.environ["BENCH_EMIT_METRICS"] = "1"
    main(smoke="--smoke" in sys.argv)
