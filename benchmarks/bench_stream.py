"""Streaming subsystem benchmark: ingest throughput + query latency vs
from-scratch recompute.

Measurements over a synthetic evolving graph (churning ER background, the
fraud workload shape):

  ingest     — updates/sec through ``DeltaEngine.apply_updates``: one fused
               O(batch) device call (edge-slot scatter + signed degree
               histogram). No host re-pad, no rebuild, no recompile.
  baseline   — the static pipeline's cost to reflect the same batch:
               ``Graph.from_edges`` rebuild + cold ``pbahmani`` peel.
  query      — warm-peel latency from maintained state. Same density as the
               cold peel (oracle property, asserted); pays up to 2x pow-2
               padding slack in exchange for zero steady-state compiles.

The headline is the ingest column: the static path must pay the rebuild +
peel on every batch to stay current, the incremental path decouples ingest
(microseconds) from query (on demand).
"""
from __future__ import annotations

import os
import sys
import time

if __name__ == "__main__":
    # direct invocation (python benchmarks/bench_stream.py): put src/ on the
    # path before the package imports below (run.py does this for the suite)
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(_root, "src"))
    sys.path.insert(0, _root)

import jax
import numpy as np

from benchmarks._artifacts import write_bench_json
from repro.core import pbahmani
from repro.graphs.graph import Graph
from repro.stream.delta import DeltaEngine
from repro.utils.timing import time_fn


def _churn_batches(rng, n_nodes, n_batches, batch_size, edges):
    """Generate (insert, delete) batches: 80% inserts, 20% deletes."""
    batches = []
    for _ in range(n_batches):
        ins = rng.integers(0, n_nodes, (int(batch_size * 0.8), 2))
        if edges:
            pool = np.asarray(sorted(edges))
            take = rng.choice(len(pool), min(batch_size // 5, len(pool)),
                              replace=False)
            dels = pool[take]
        else:
            dels = np.zeros((0, 2), np.int64)
        # mirror EdgeBuffer.apply semantics: retract, then assert — an edge
        # both deleted and inserted in one batch nets to present
        for u, v in dels:
            edges.discard((int(u), int(v)))
        for u, v in ins:
            u, v = int(u), int(v)
            if u != v:
                edges.add((min(u, v), max(u, v)))
        batches.append((ins, dels))
    return batches


def run(n_nodes: int = 4096, batch_size: int = 512, n_batches: int = 30,
        csv: bool = True):
    rng = np.random.default_rng(0)
    from repro.stream.buffer import next_pow2

    # headroom for the seed (~8|V| edges) plus the whole churn window
    eng = DeltaEngine(n_nodes=n_nodes, capacity=next_pow2(12 * n_nodes),
                      refresh_every=10**9)
    edges: set = set()

    # seed graph
    seed = rng.integers(0, n_nodes, (8 * n_nodes, 2))
    eng.apply_updates(insert=seed)
    for u, v in seed:
        u, v = int(u), int(v)
        if u != v:
            edges.add((min(u, v), max(u, v)))
    eng.query()

    batches = _churn_batches(rng, n_nodes, n_batches, batch_size, edges)

    # warm up the churn-batch shape, then freeze the compile counter: the
    # measured window must be compile-free (the hot-path contract)
    eng.apply_updates(insert=batches[0][0], delete=batches[0][1])
    eng.query()
    compiles_before = DeltaEngine.compile_count()

    # -- ingest throughput --------------------------------------------------
    t0 = time.perf_counter()
    for ins, dels in batches[1:]:
        eng.apply_updates(insert=ins, delete=dels)
    # apply_updates only dispatches; charge the whole device backlog to the
    # ingest window (async dispatch must not hide the work)
    jax.block_until_ready((eng._src, eng._dst, eng._deg))
    ingest_s = time.perf_counter() - t0
    ups = (len(batches) - 1) * batch_size / ingest_s

    # -- warm query latency -------------------------------------------------
    def warm_query():
        eng._cached_query = None  # defeat memoization: time the peel itself
        return eng.query()

    q_s, q = time_fn(warm_query, iters=5, warmup=1)
    compiles_after = DeltaEngine.compile_count()

    # -- from-scratch baseline (rebuild + cold peel per batch) --------------
    pairs = np.asarray(sorted(edges), dtype=np.int64)

    def recompute():
        g = Graph.from_edges(pairs, n_nodes=n_nodes)
        return pbahmani(g)

    r_s, (rho_cold, _, _) = time_fn(recompute, iters=3, warmup=1)
    baseline_ups = batch_size / r_s

    assert abs(q.density - rho_cold) <= 1e-6 * max(rho_cold, 1.0), (
        f"incremental {q.density} != recompute {rho_cold}"
    )

    res = {
        "n_edges": eng.n_edges,
        "ingest_updates_per_s": ups,
        "baseline_updates_per_s": baseline_ups,
        "ingest_speedup": ups / max(baseline_ups, 1e-12),
        "query_ms": q_s * 1e3,
        "recompute_ms": r_s * 1e3,
        "steady_compiles": compiles_after - compiles_before,
        "density": q.density,
    }
    if csv:
        print("n_nodes,n_edges,ingest_ups,baseline_ups,ingest_speedup,"
              "query_ms,recompute_ms,steady_compiles")
        print(f"{n_nodes},{res['n_edges']},{ups:.0f},{baseline_ups:.0f},"
              f"{res['ingest_speedup']:.1f}x,{res['query_ms']:.2f},"
              f"{res['recompute_ms']:.2f},{res['steady_compiles']}")
    return res


def _record(res: dict, mode: str) -> None:
    write_bench_json(
        "stream",
        {"ingest_speedup": res["ingest_speedup"],
         "steady_compiles": res["steady_compiles"]},
        [res], mode=mode)


def main(smoke: bool = False, large: bool = False):
    if smoke:
        res = run(n_nodes=512, batch_size=128, n_batches=6)
        assert res["steady_compiles"] == 0, res
        _record(res, "smoke")
        print("# smoke ok: incremental == recompute, zero steady-state "
              "compiles")
        return
    if large:
        # ROADMAP P2 scale tier (scheduled CI): 16k-node evolving graph
        res = run(n_nodes=16384, batch_size=1024, n_batches=12)
        assert res["steady_compiles"] == 0, "hot path recompiled!"
        _record(res, "large")
        print(f"# large ok: ingest {res['ingest_speedup']:.1f}x the static "
              f"rebuild+peel path at 16k nodes")
        return
    res = run()
    assert res["steady_compiles"] == 0, "hot path recompiled!"
    _record(res, "full")
    print(f"# ingest {res['ingest_speedup']:.1f}x the static rebuild+peel "
          f"path at equal (exact) query density")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if "--emit-metrics" in sys.argv:
        os.environ["BENCH_EMIT_METRICS"] = "1"
    main(smoke="--smoke" in sys.argv, large="--large" in sys.argv)
