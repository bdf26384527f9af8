"""Sharded streaming benchmark: shard_map engine vs single-device (ISSUE 3).

Two identical ``DeltaEngine`` tenants ingest the same stream — one with
``sharded=True`` (edge slots partitioned over a mesh spanning every local
device, degree deltas and peel scalar state psum'd), one single-device.
Both must return the *bit-identical* (density, mask, passes) triple on
every query, asserted each cell: since all cross-shard reductions are
exact int32, sharding is free of numerical drift on any device count.

Run under ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the
``make bench-shard-smoke`` target does) to exercise a real multi-device
mesh on CPU; on a single device the mesh degenerates to one shard and the
comparison measures pure shard_map overhead.

Axes (same grid as bench_prune):
  graph family  — power_law (preferential attachment), uniform (ER),
                  planted (ER background + dense block)
  batch mix     — insert_heavy (10% deletes) vs churn (50% deletes)

Reported per cell: ingest updates/sec and query latency both ways, the
sharded/single ratios, steady-state compile count (must be 0 — the pow-2
bucket contract extends to the sharded executables), and the shard count.
On CPU meshes the sharded path pays collective overhead per pass, so the
ratios are a *cost* model here; the point of the benchmark is the parity
and compile assertions plus the scaling shape — on real multi-chip
hardware the same code is what lifts the one-chip memory cap.
"""
from __future__ import annotations

import os
import sys
import time

if __name__ == "__main__":
    # direct invocation (python benchmarks/bench_shard.py): put src/ on the
    # path before the package imports below (run.py does this for the suite)
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(_root, "src"))
    sys.path.insert(0, _root)

import jax
import numpy as np

from benchmarks._artifacts import write_bench_json
from benchmarks.bench_prune import FAMILIES, MIXES, _churn_batches, _family_edges
from repro.stream import FusedEngine, FusedPool
from repro.stream.buffer import next_pow2
from repro.stream.delta import DeltaEngine, default_stream_mesh
from repro.stream.fused import query_group
from repro.utils.timing import time_fn


def _bench_cell(family: str, mix: str, del_frac: float, n_nodes: int,
                batch_size: int, n_batches: int, mesh, seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    seed_edges = _family_edges(family, n_nodes, seed)
    capacity = next_pow2(12 * n_nodes)
    engines = {
        "sharded": DeltaEngine(n_nodes, capacity=capacity,
                               refresh_every=10**9, sharded=True, mesh=mesh),
        "single": DeltaEngine(n_nodes, capacity=capacity,
                              refresh_every=10**9),
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
        eng.query()
        eng.apply_updates(insert=batches[0][0], delete=batches[0][1])
        eng.query()
        # epoch refresh: plans rebuild from the observed handoff, so the
        # steady state runs in the adapted (tight) buckets on both paths
        eng.refresh()
        eng._cached_query = None
        eng.query()
    compiles_before = DeltaEngine.compile_count()

    # -- ingest throughput (steady window, includes an epoch boundary) ------
    ingest_s = {}
    for name, eng in engines.items():
        t0 = time.perf_counter()
        for ins, dels in batches[1:half]:
            eng.apply_updates(insert=ins, delete=dels)
        jax.block_until_ready((eng._src, eng._dst, eng._deg))
        ingest_s[name] = time.perf_counter() - t0
    for eng in engines.values():
        eng.refresh()
    for ins, dels in batches[half:]:
        for eng in engines.values():
            eng.apply_updates(insert=ins, delete=dels)

    # -- query latency ------------------------------------------------------
    lat, results = {}, {}
    for name, eng in engines.items():
        def timed_query(eng=eng):
            eng._cached_query = None  # defeat memoization: time the peel
            return eng.query()

        lat[name], results[name] = time_fn(timed_query, iters=5, warmup=1)
    steady_compiles = DeltaEngine.compile_count() - compiles_before

    qs, qu = results["sharded"], results["single"]
    assert qs.density == qu.density, (qs.density, qu.density)
    assert np.array_equal(qs.mask, qu.mask)
    assert qs.passes == qu.passes, (qs.passes, qu.passes)

    n_up = max(half - 1, 1) * batch_size
    return {
        "family": family,
        "mix": mix,
        "n_edges": engines["sharded"].n_edges,
        "n_shards": engines["sharded"].n_shards,
        "ingest_single_ups": n_up / max(ingest_s["single"], 1e-12),
        "ingest_sharded_ups": n_up / max(ingest_s["sharded"], 1e-12),
        "query_single_ms": lat["single"] * 1e3,
        "query_sharded_ms": lat["sharded"] * 1e3,
        "query_ratio": lat["sharded"] / max(lat["single"], 1e-12),
        "steady_compiles": steady_compiles,
        "density": qs.density,
    }


def _bench_fused_cell(n_tenants: int, n_nodes: int, capacity: int,
                      iters: int, mesh, seed: int = 0) -> dict:
    """Fused+sharded bucket (ISSUE 9): ``n_tenants`` sharded tenants share
    one vmap-inside-shard_map bucket stack, so a group flush issues one
    collective per pass for the whole bucket. Measured against (a) a solo
    single-device engine per tenant — ``query_ratio_worst``, the headline:
    the per-tenant amortized cost of sharding once the collective is
    amortized T ways — and (b) a solo *sharded* engine on the same stream —
    ``fused_sharded_speedup``, the win over pre-fusion sharding. Bit-exact
    per-tenant parity with both baselines is asserted, as is a compile-free
    measured window (engines run pruned=False, the bench_tenants
    convention: plan-bucket shapes are data-dependent and would blur the
    zero-recompile assertion)."""
    rng = np.random.default_rng(seed)
    pool = FusedPool()
    solo, fused = [], {}
    solo_sharded = DeltaEngine(n_nodes, capacity=capacity,
                               refresh_every=10**9, pruned=False,
                               sharded=True, mesh=mesh)
    for i in range(n_tenants):
        s = DeltaEngine(n_nodes, capacity=capacity, refresh_every=10**9,
                        pruned=False)
        f = FusedEngine(f"t{i}", pool, n_nodes, capacity=capacity,
                        refresh_every=10**9, pruned=False,
                        sharded=True, mesh=mesh)
        seed_edges = rng.integers(0, n_nodes, (3 * n_nodes, 2))
        s.apply_updates(insert=seed_edges)
        f.apply_updates(insert=seed_edges)
        if i == 0:
            solo_sharded.apply_updates(insert=seed_edges)
        s.query()
        solo.append(s)
        fused[f"t{i}"] = f
    solo_sharded.query()

    def flush():
        for f in fused.values():
            f._cached_query = None  # defeat memoization: time the peel
        return query_group(fused)

    def best_of(fn, reps=3):
        # min over repeated windows: the ratios feed regression gates, so
        # a single contended window must not fake a regression
        best, out = float("inf"), None
        for _ in range(reps):
            t, out = time_fn(fn, iters=iters, warmup=1)
            best = min(best, t)
        return best, out

    flush()  # warm the full group-flush shape
    compiles_before = DeltaEngine.compile_count()

    t_fused, results = best_of(flush)
    t_per_tenant = t_fused / n_tenants

    t_solo = []
    for s in solo:
        def timed_query(s=s):
            s._cached_query = None
            return s.query()

        t, _ = best_of(timed_query)
        t_solo.append(t)

    def timed_sharded():
        solo_sharded._cached_query = None
        return solo_sharded.query()

    t_sharded, q_sharded = best_of(timed_sharded)
    steady_compiles = DeltaEngine.compile_count() - compiles_before

    for i, s in enumerate(solo):
        q1, q2 = s.query(), results[f"t{i}"]
        assert q1.density == q2.density, (i, q1.density, q2.density)
        assert np.array_equal(q1.mask, q2.mask), i
        assert q1.passes == q2.passes, (i, q1.passes, q2.passes)
    assert q_sharded.density == results["t0"].density
    assert q_sharded.passes == results["t0"].passes

    return {
        "family": "fused_bucket",
        "mix": "static",
        "n_tenants": n_tenants,
        "n_edges": solo[0].n_edges,
        "n_shards": solo_sharded.n_shards,
        "query_single_ms": float(np.median(t_solo)) * 1e3,
        "query_solo_sharded_ms": t_sharded * 1e3,
        "query_fused_per_tenant_ms": t_per_tenant * 1e3,
        "query_ratio_worst": max(t_per_tenant / max(t, 1e-12)
                                 for t in t_solo),
        "fused_sharded_speedup": t_sharded / max(t_per_tenant, 1e-12),
        "steady_compiles": steady_compiles,
    }


def run(n_nodes: int = 4096, batch_size: int = 512, n_batches: int = 12,
        families=FAMILIES, mixes=None, csv: bool = True) -> list[dict]:
    mesh = default_stream_mesh()
    mixes = MIXES if mixes is None else mixes
    rows = []
    if csv:
        print("family,mix,n_edges,n_shards,ingest_single_ups,"
              "ingest_sharded_ups,query_single_ms,query_sharded_ms,"
              "query_ratio,steady_compiles")
    for family in families:
        for mix, del_frac in mixes.items():
            r = _bench_cell(family, mix, del_frac, n_nodes, batch_size,
                            n_batches, mesh)
            rows.append(r)
            if csv:
                print(f"{r['family']},{r['mix']},{r['n_edges']},"
                      f"{r['n_shards']},{r['ingest_single_ups']:.0f},"
                      f"{r['ingest_sharded_ups']:.0f},"
                      f"{r['query_single_ms']:.2f},"
                      f"{r['query_sharded_ms']:.2f},"
                      f"{r['query_ratio']:.2f}x,{r['steady_compiles']}")
    return rows


def _record(rows: list[dict], fcell: dict, mode: str) -> None:
    """One BENCH_shard.json for the solo grid + the fused bucket cell.
    ``query_ratio_worst`` is the ISSUE 9 headline (fused+sharded per-tenant
    latency / solo single-device latency, worst tenant — gated "lower" in
    check_regression); the pre-fusion solo-sharded ratio stays recorded as
    ``solo_query_ratio_worst`` for the trajectory."""
    write_bench_json(
        "shard",
        {"steady_compiles": max([r["steady_compiles"] for r in rows]
                                + [fcell["steady_compiles"]]),
         "n_shards": rows[0]["n_shards"],
         "solo_query_ratio_worst": max(r["query_ratio"] for r in rows),
         "query_ratio_worst": fcell["query_ratio_worst"],
         "fused_sharded_speedup": fcell["fused_sharded_speedup"]},
        rows + [fcell], mode=mode)


def main(smoke: bool = False, large: bool = False,
         strict: bool = False) -> None:
    """Parity (bit-identical triples) and zero steady-state compiles are
    always asserted; latency ratios are reported, not enforced (CPU meshes
    pay collective overhead the assertion must not depend on) — except the
    ISSUE 9 acceptance target ``query_ratio_worst <= 1.5`` at 8 tenants
    per bucket, enforced under ``--strict`` (bench-suite convention)."""
    mesh = default_stream_mesh()
    if smoke:
        rows = run(n_nodes=512, batch_size=128, n_batches=4,
                   mixes={"churn": 0.5})
        # the fused cell runs at 1024 nodes even in the smoke: below ~1k
        # nodes the flush is all fixed overhead and the ratio is noise
        fcell = _bench_fused_cell(8, n_nodes=1024, capacity=8192, iters=5,
                                  mesh=mesh)
        mode = "smoke"
    elif large:
        # ROADMAP P2 scale tier: 16k-node graphs, scheduled CI only
        rows = run(n_nodes=16384, batch_size=1024, n_batches=8,
                   families=("power_law", "uniform"), mixes={"churn": 0.5})
        fcell = _bench_fused_cell(8, n_nodes=16384, capacity=131072,
                                  iters=3, mesh=mesh)
        mode = "large"
    else:
        rows = run()
        fcell = _bench_fused_cell(8, n_nodes=1024, capacity=8192, iters=10,
                                  mesh=mesh)
        mode = "full"
    assert all(r["steady_compiles"] == 0 for r in rows), rows
    assert fcell["steady_compiles"] == 0, fcell
    _record(rows, fcell, mode)
    print(f"# {mode} ok: sharded == single-device bit-identical on "
          f"{rows[0]['n_shards']} shard(s), zero steady-state compiles; "
          f"fused+sharded per-tenant ratio {fcell['query_ratio_worst']:.2f}x "
          f"vs solo (solo-sharded {max(r['query_ratio'] for r in rows):.2f}x"
          f"), {fcell['fused_sharded_speedup']:.2f}x over solo-sharded at "
          f"{fcell['n_tenants']} tenants/bucket")
    if fcell["query_ratio_worst"] > 1.5:
        msg = (f"acceptance target query_ratio_worst <= 1.5 at 8 "
               f"tenants/bucket not met: {fcell['query_ratio_worst']:.2f}x")
        if strict:
            raise AssertionError(msg)
        print(f"# WARNING: {msg} (machine-dependent; rerun with --strict "
              f"to enforce)")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if "--emit-metrics" in sys.argv:
        os.environ["BENCH_EMIT_METRICS"] = "1"
    main(smoke="--smoke" in sys.argv, large="--large" in sys.argv,
         strict="--strict" in sys.argv)
