"""Candidate-pruning benchmark: pruned vs unpruned query latency (ISSUE 2).

Two identical ``DeltaEngine`` tenants (one with ``pruned=True``, one
without) ingest the same stream; after the churn window the warm query is
timed on both. The pruned engine answers from the compacted subproblem
(core/prune.py), the unpruned engine peels the full padded arrays — both
must return the *bit-identical* (density, mask, passes) triple, asserted
every run.

Axes (paper-style grid):
  graph family  — power_law (preferential attachment), uniform (ER),
                  planted (ER background + dense block)
  batch mix     — insert_heavy (10% deletes) vs churn (50% deletes)

Reported per cell: query latency both ways, speedup, steady-state compile
count (must be 0 — the pow-2 bucket contract), and the plan's candidate
fraction. The headline is the 4k-node power_law row: the trajectory sheds
~3/4 of the vertices in one pass, so almost all full-width lanes of the
unpruned peel are dead weight.
"""
from __future__ import annotations

import os
import sys

if __name__ == "__main__":
    # direct invocation (python benchmarks/bench_prune.py): put src/ on the
    # path before the package imports below (run.py does this for the suite)
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(_root, "src"))
    sys.path.insert(0, _root)

import numpy as np

from benchmarks._artifacts import write_bench_json
from repro.graphs.generators import barabasi_albert, erdos_renyi, planted_dense
from repro.stream.buffer import next_pow2
from repro.stream.delta import DeltaEngine
from repro.utils.timing import time_fn

FAMILIES = ("power_law", "uniform", "planted")
MIXES = {"insert_heavy": 0.1, "churn": 0.5}


def _family_edges(family: str, n_nodes: int, seed: int) -> np.ndarray:
    if family == "power_law":
        g = barabasi_albert(n_nodes, 8, seed=seed)
    elif family == "uniform":
        g = erdos_renyi(n_nodes, 16.0 / n_nodes, seed=seed)
    elif family == "planted":
        g, _, _ = planted_dense(n_nodes, max(n_nodes // 64, 16),
                                p_background=12.0 / n_nodes, seed=seed)
    else:
        raise ValueError(f"unknown family {family!r}")
    half = g.n_directed // 2
    return np.stack([g.src[:half], g.dst[:half]], axis=1).astype(np.int64)


def _churn_batches(rng, edges: set, n_nodes, n_batches, batch_size, del_frac,
                   skew_pool: np.ndarray):
    """(insert, delete) batches; inserts keep the family's degree skew by
    sampling one endpoint from the (degree-biased) edge-endpoint pool."""
    batches = []
    for _ in range(n_batches):
        k_ins = max(int(batch_size * (1.0 - del_frac)), 1)
        u = skew_pool[rng.integers(0, len(skew_pool), k_ins)]
        v = rng.integers(0, n_nodes, k_ins)
        ins = np.stack([u, v], axis=1)
        k_del = min(int(batch_size * del_frac), len(edges))
        if k_del:
            pool = np.asarray(sorted(edges))
            dels = pool[rng.choice(len(pool), k_del, replace=False)]
        else:
            dels = np.zeros((0, 2), np.int64)
        for a, b in dels:
            edges.discard((int(a), int(b)))
        for a, b in ins:
            a, b = int(a), int(b)
            if a != b:
                edges.add((min(a, b), max(a, b)))
        batches.append((ins, dels))
    return batches


def _bench_cell(family: str, mix: str, del_frac: float, n_nodes: int,
                batch_size: int, n_batches: int, seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    seed_edges = _family_edges(family, n_nodes, seed)
    capacity = next_pow2(12 * n_nodes)
    engines = {
        "pruned": DeltaEngine(n_nodes, capacity=capacity,
                              refresh_every=10**9, pruned=True),
        "unpruned": DeltaEngine(n_nodes, capacity=capacity,
                                refresh_every=10**9, pruned=False),
    }
    edges: set = set()
    for a, b in seed_edges:
        edges.add((min(int(a), int(b)), max(int(a), int(b))))
    skew_pool = seed_edges.reshape(-1)
    batches = _churn_batches(rng, edges, n_nodes, n_batches, batch_size,
                             del_frac, skew_pool)

    half = max(len(batches) // 2, 1)
    for eng in engines.values():
        eng.apply_updates(insert=seed_edges)
        eng.query()  # compiles the conservative first-shot plan
        eng.apply_updates(insert=batches[0][0], delete=batches[0][1])
        eng.query()
        # epoch refresh: the plan rebuilds from the observed handoff, so the
        # steady state runs in the adapted (tight) buckets
        eng.refresh()
        eng._cached_query = None
        eng.query()
    compiles_before = DeltaEngine.compile_count()

    # steady-state window — includes an epoch boundary: the second refresh
    # must re-derive the same buckets (bucket_reuses) and compile nothing
    for ins, dels in batches[1:half]:
        for eng in engines.values():
            eng.apply_updates(insert=ins, delete=dels)
    for eng in engines.values():
        eng.refresh()
    for ins, dels in batches[half:]:
        for eng in engines.values():
            eng.apply_updates(insert=ins, delete=dels)

    lat = {}
    results = {}
    for name, eng in engines.items():
        def timed_query(eng=eng):
            eng._cached_query = None  # defeat memoization: time the peel
            return eng.query()

        lat[name], results[name] = time_fn(timed_query, iters=5, warmup=1)
    steady_compiles = DeltaEngine.compile_count() - compiles_before

    qp, qu = results["pruned"], results["unpruned"]
    assert qp.density == qu.density, (qp.density, qu.density)
    assert np.array_equal(qp.mask, qu.mask)
    assert qp.passes == qu.passes, (qp.passes, qu.passes)
    assert qp.pruned, "pruned engine fell back on the measured query"

    m = engines["pruned"].metrics
    return {
        "family": family,
        "mix": mix,
        "n_edges": engines["pruned"].n_edges,
        "query_unpruned_ms": lat["unpruned"] * 1e3,
        "query_pruned_ms": lat["pruned"] * 1e3,
        "speedup": lat["unpruned"] / max(lat["pruned"], 1e-12),
        "steady_compiles": steady_compiles,
        "candidate_fraction": m.candidate_fraction,
        "bucket_v": m.prune_bucket_v,
        "bucket_e": m.prune_bucket_e,
        "density": qp.density,
    }


def run(n_nodes: int = 4096, batch_size: int = 512, n_batches: int = 12,
        families=FAMILIES, mixes=None, csv: bool = True) -> list[dict]:
    mixes = MIXES if mixes is None else mixes
    rows = []
    if csv:
        print("family,mix,n_edges,query_unpruned_ms,query_pruned_ms,"
              "speedup,steady_compiles,candidate_fraction,bucket_v,bucket_e")
    for family in families:
        for mix, del_frac in mixes.items():
            r = _bench_cell(family, mix, del_frac, n_nodes, batch_size,
                            n_batches)
            rows.append(r)
            if csv:
                print(f"{r['family']},{r['mix']},{r['n_edges']},"
                      f"{r['query_unpruned_ms']:.2f},"
                      f"{r['query_pruned_ms']:.2f},{r['speedup']:.1f}x,"
                      f"{r['steady_compiles']},"
                      f"{r['candidate_fraction']:.3f},"
                      f"{r['bucket_v']},{r['bucket_e']}")
    return rows


def main(smoke: bool = False, strict: bool = False) -> None:
    """Correctness (bit-identity, zero compiles) is always asserted;
    ``strict`` additionally enforces the >=3x power_law acceptance target,
    which is wall-clock- and machine-dependent (bench-suite convention:
    assert properties, report ratios)."""
    if smoke:
        rows = run(n_nodes=512, batch_size=128, n_batches=4,
                   mixes={"churn": 0.5})
        assert all(r["steady_compiles"] == 0 for r in rows), rows
        write_bench_json(
            "prune",
            {"speedup_max": max(r["speedup"] for r in rows),
             "steady_compiles": max(r["steady_compiles"] for r in rows)},
            rows, mode="smoke")
        print("# smoke ok: pruned == unpruned bit-identical, zero "
              "steady-state compiles")
        return
    rows = run()
    assert all(r["steady_compiles"] == 0 for r in rows), "hot path recompiled"
    write_bench_json(
        "prune",
        {"speedup_max": max(r["speedup"] for r in rows),
         "steady_compiles": max(r["steady_compiles"] for r in rows)},
        rows)
    pl = [r for r in rows if r["family"] == "power_law"]
    best = max(r["speedup"] for r in pl)
    print(f"# power_law query speedup {best:.1f}x at bit-identical results, "
          f"zero steady-state compiles")
    if best < 3.0:
        msg = f"acceptance target >=3x on power_law not met: {best:.1f}x"
        if strict:
            raise AssertionError(msg)
        print(f"# WARNING: {msg} (machine-dependent; rerun with --strict "
              f"to enforce)")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if "--emit-metrics" in sys.argv:
        os.environ["BENCH_EMIT_METRICS"] = "1"
    main(smoke="--smoke" in sys.argv, strict="--strict" in sys.argv)
