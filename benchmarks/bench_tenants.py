"""Fused multi-tenant benchmark: batched bucket peels vs sequential dispatch.

ISSUE 4 tentpole measurement. T small tenants share one capacity bucket;
the sequential baseline queries T unbatched ``DeltaEngine``s in a loop (one
program launch per tenant — the pre-fused service behavior), the fused path
answers all T through one ``query_group`` flush: a single vmapped peel per
bucket (dense GEMV passes under ``DENSE_NODE_CAP``), with per-tenant
early-exit masks. Every cell asserts, per tenant:

  * bit-identical (density, mask, passes) between fused and sequential —
    the exactness contract of stream/fused.py;
  * zero steady-state compiles across the measured window, INCLUDING a
    tenant evict/join (bucket membership is a row swap, not a compile).

Reported: aggregate queries/sec both ways and the fused speedup as tenant
count scales. The acceptance target is >=3x at 16 same-bucket tenants
(wall-clock-dependent: asserted under ``--strict``, reported otherwise —
the bench-suite convention). Fused ingest (one [T, B] scatter per bucket
via ``ingest_group``) is reported alongside.
"""
from __future__ import annotations

import os
import sys
import time

if __name__ == "__main__":
    # direct invocation (python benchmarks/bench_tenants.py): put src/ on
    # the path before the package imports below (run.py does this for the
    # suite)
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(_root, "src"))
    sys.path.insert(0, _root)

import jax
import numpy as np

from benchmarks._artifacts import write_bench_json
from repro.stream import DeltaEngine, FusedEngine, FusedPool
from repro.stream.fused import ingest_group, query_group

TENANT_COUNTS = (2, 4, 8, 16)
# engines run pruned=False: the fused win under measurement is the batched
# peel itself, and the candidate-pruned path's host-side prepare is
# per-tenant work either way. Plan-bucket shapes are also data-dependent
# (they compile on regrow in the unbatched engine too), which would blur
# the zero-recompile assertion this benchmark makes about tenant churn.


def _mixed_batch(rng, eng, n_nodes, batch_size):
    """Half inserts / half deletes sampled from the live edge set, so the
    graph churns at roughly constant |E| — tenants stay in their capacity
    bucket for the whole measured window (no mid-measure regrow)."""
    ins = rng.integers(0, n_nodes, (batch_size // 2, 2))
    pool = eng.buffer.live_pairs()
    k = min(batch_size // 2, len(pool))
    dels = pool[rng.choice(len(pool), k, replace=False)]
    return ins, dels


def _invalidate(engines):
    for eng in engines:
        eng._cached_query = None  # defeat memoization: time the peel


def _bench_cell(n_tenants: int, n_nodes: int, capacity: int,
                batch_size: int, iters: int, seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    pool = FusedPool()
    seq, fused = [], {}
    for i in range(n_tenants):
        s = DeltaEngine(n_nodes, capacity=capacity, refresh_every=10**9,
                        pruned=False)
        f = FusedEngine(f"t{i}", pool, n_nodes, capacity=capacity,
                        refresh_every=10**9, pruned=False)
        seed_edges = rng.integers(0, n_nodes, (3 * n_nodes, 2))
        s.apply_updates(insert=seed_edges)
        f.apply_updates(insert=seed_edges)
        ins, dels = _mixed_batch(rng, s, n_nodes, batch_size)
        ingest_group({f"t{i}": (ins, dels)}, {f"t{i}": f})
        s.apply_updates(insert=ins, delete=dels)
        s.query()
        f.query()  # warms the group-of-1 shape
        seq.append(s)
        fused[f"t{i}"] = f
    # warm the full group-flush and fused-ingest shapes, then freeze the
    # compile counter: the measured window (including tenant churn) must
    # be compile-free
    _invalidate(fused.values())
    query_group(fused)
    warm_upd = {name: _mixed_batch(rng, s, n_nodes, batch_size)
                for name, s in zip(fused, seq)}
    ingest_group(warm_upd, fused)
    for (ins, dels), s in zip(warm_upd.values(), seq):
        s.apply_updates(insert=ins, delete=dels)  # same batches: identical
    compiles_before = DeltaEngine.compile_count()

    # -- sequential dispatch: one program launch per tenant -----------------
    t0 = time.perf_counter()
    for _ in range(iters):
        for s in seq:
            s._cached_query = None
            s.query()
    t_seq = (time.perf_counter() - t0) / iters

    # -- fused: one batched flush for the whole bucket ----------------------
    t0 = time.perf_counter()
    for _ in range(iters):
        _invalidate(fused.values())
        query_group(fused)
    t_fused = (time.perf_counter() - t0) / iters

    # -- fused ingest: one [T, B] scatter vs T separate dispatches ----------
    # (apply_updates only dispatches; block on the device state so async
    # dispatch doesn't hide the work — same protocol as bench_stream)
    ingest_iters = max(iters // 2, 2)
    batch0 = next(iter(fused.values())).batch
    t_ingest_fused = t_ingest_seq = 0.0
    for _ in range(ingest_iters):
        # same batch content both ways, interleaved so the shared delete
        # pool (and hence every graph) stays in lockstep
        upd = {name: _mixed_batch(rng, s, n_nodes, batch_size)
               for name, s in zip(fused, seq)}
        t0 = time.perf_counter()
        ingest_group(upd, fused)
        jax.block_until_ready((batch0._src, batch0._deg))
        t_ingest_fused += time.perf_counter() - t0
        t0 = time.perf_counter()
        for (ins, dels), s in zip(upd.values(), seq):
            s.apply_updates(insert=ins, delete=dels)
        jax.block_until_ready([s._deg for s in seq])
        t_ingest_seq += time.perf_counter() - t0
    t_ingest_fused /= ingest_iters
    t_ingest_seq /= ingest_iters

    # -- tenant churn: evict + join must be a row swap, not a compile -------
    evicted = fused.pop("t0")
    evicted.release()
    re = FusedEngine("t0b", pool, n_nodes, capacity=capacity,
                     refresh_every=10**9, pruned=False)
    re.apply_updates(insert=rng.integers(0, n_nodes, (3 * n_nodes, 2)))
    fused["t0b"] = re
    _invalidate(fused.values())
    query_group(fused)
    fused.pop("t0b").release()
    fused["t0"] = evicted
    evicted._resync_device()

    # -- parity: bit-identical triples per tenant ---------------------------
    _invalidate(fused.values())
    results = query_group(fused)
    steady_compiles = DeltaEngine.compile_count() - compiles_before
    for i, s in enumerate(seq):
        q1, q2 = s.query(), results[f"t{i}"]
        assert q1.density == q2.density, (i, q1.density, q2.density)
        assert np.array_equal(q1.mask, q2.mask), i
        assert q1.passes == q2.passes, (i, q1.passes, q2.passes)

    batch = next(iter(fused.values())).batch
    return {
        "n_tenants": n_tenants,
        "n_nodes": n_nodes,
        "n_edges": seq[0].n_edges,
        "dense": batch.dense,
        "seq_qps": n_tenants / t_seq,
        "fused_qps": n_tenants / t_fused,
        "speedup": t_seq / max(t_fused, 1e-12),
        "ingest_speedup": t_ingest_seq / max(t_ingest_fused, 1e-12),
        "steady_compiles": steady_compiles,
    }


def run(n_nodes: int = 256, capacity: int = 2048, batch_size: int = 128,
        iters: int = 10, tenant_counts=TENANT_COUNTS,
        csv: bool = True) -> list[dict]:
    rows = []
    if csv:
        print("n_tenants,n_nodes,n_edges,dense,seq_qps,fused_qps,speedup,"
              "ingest_speedup,steady_compiles")
    for t in tenant_counts:
        r = _bench_cell(t, n_nodes, capacity, batch_size, iters)
        rows.append(r)
        if csv:
            print(f"{r['n_tenants']},{r['n_nodes']},{r['n_edges']},"
                  f"{int(r['dense'])},{r['seq_qps']:.0f},"
                  f"{r['fused_qps']:.0f},{r['speedup']:.2f}x,"
                  f"{r['ingest_speedup']:.2f}x,{r['steady_compiles']}")
    return rows


def main(smoke: bool = False, strict: bool = False,
         large: bool = False) -> None:
    """Parity (bit-identical triples), the evict/join row-swap contract and
    zero steady-state compiles are always asserted; ``strict``
    additionally enforces the >=3x acceptance target at 16 tenants, which
    is wall-clock- and machine-dependent (bench-suite convention: assert
    properties, report ratios)."""
    if smoke:
        rows = run(tenant_counts=(4, 16), iters=5)
        assert all(r["steady_compiles"] == 0 for r in rows), rows
        top = rows[-1]
        write_bench_json(
            "tenants",
            {"fused_speedup_16": top["speedup"],
             "fused_qps_16": top["fused_qps"],
             "steady_compiles": max(r["steady_compiles"] for r in rows)},
            rows, mode="smoke")
        print(f"# smoke ok: fused == sequential bit-identical, zero "
              f"steady-state compiles across evict/join, "
              f"{top['speedup']:.2f}x at 16 tenants")
        return
    if large:
        # ROADMAP P2 scale tier (scheduled CI): 16k-node tenants — above
        # DENSE_NODE_CAP, so this exercises the sparse vmapped peel at the
        # same metric names the regular baseline gates
        rows = run(n_nodes=16384, capacity=65536, batch_size=512, iters=3,
                   tenant_counts=(4, 16))
        assert all(r["steady_compiles"] == 0 for r in rows), rows
        top = rows[-1]
        write_bench_json(
            "tenants",
            {"fused_speedup_16": top["speedup"],
             "fused_qps_16": top["fused_qps"],
             "steady_compiles": max(r["steady_compiles"] for r in rows)},
            rows, mode="large")
        print(f"# large ok: fused == sequential bit-identical at 16k-node "
              f"tenants, {top['speedup']:.2f}x at 16 tenants")
        return
    rows = run()
    assert all(r["steady_compiles"] == 0 for r in rows), "hot path recompiled"
    top = [r for r in rows if r["n_tenants"] == 16][-1]
    write_bench_json(
        "tenants",
        {"fused_speedup_16": top["speedup"],
         "fused_qps_16": top["fused_qps"],
         "steady_compiles": max(r["steady_compiles"] for r in rows)},
        rows)
    print(f"# fused {top['speedup']:.2f}x aggregate query throughput at 16 "
          f"same-bucket tenants (bit-identical results, zero steady-state "
          f"compiles)")
    if top["speedup"] < 3.0:
        msg = f"acceptance target >=3x at 16 tenants not met: " \
              f"{top['speedup']:.2f}x"
        if strict:
            raise AssertionError(msg)
        print(f"# WARNING: {msg} (machine-dependent; rerun with --strict "
              f"to enforce)")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if "--emit-metrics" in sys.argv:
        os.environ["BENCH_EMIT_METRICS"] = "1"
    main(smoke="--smoke" in sys.argv, strict="--strict" in sys.argv,
         large="--large" in sys.argv)
