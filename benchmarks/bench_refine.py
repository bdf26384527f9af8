"""Near-optimal refinement benchmark: duality-gap closure + fused rounds.

ISSUE 5 tentpole measurement, in three parts:

  1. gap closure — ``refine()`` on the 4k benchmark families (uniform ER,
     power_law RMAT, planted) must reach a certified relative duality gap
     <= 1% (``TARGET_GAP``), with the per-round gap trajectory monotone
     nonincreasing (the running-min dual of certify.py) and ZERO
     steady-state recompiles across rounds — one executable per (shape,
     eps), reused every round. The classic preferential-attachment family
     is deliberately replaced by RMAT here: a BA graph's optimum is the
     *entire* min-degree-m graph, whose heavy-tailed loads balance at
     O(1/T) — a pathology of the generator, not of the workload the
     subsystem targets (reported in the module docstring, not gated).
  2. oracle verification — on <= 256-node instances of the same families
     the certificate sandwich density <= rho* <= dual is checked against
     the exact Goldberg-flow solver (certificate-only at 4k, where exact
     is the non-scaling baseline by design).
  3. fused refinement — 8 small same-bucket tenants refined through ONE
     batched round program per round (``_refine_flush``'s dense GEMV
     rounds) vs 8 sequential per-tenant round loops; results are
     bit-identical (asserted) and the acceptance target is >= 2x aggregate
     rounds/sec (wall-clock-dependent: asserted under ``--strict``,
     reported otherwise — the bench-suite convention).

Gated metrics (benchmarks/check_regression.py): ``certified_quality_min``
(min over families of density/dual = 1 - rel_gap, higher is better),
``fused_refine_speedup_8`` (higher), ``steady_compiles`` (zero).
"""
from __future__ import annotations

import os
import sys
import time

if __name__ == "__main__":
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(_root, "src"))
    sys.path.insert(0, _root)

import jax.numpy as jnp
import numpy as np

from benchmarks._artifacts import write_bench_json
from repro.graphs.generators import erdos_renyi, planted_dense, rmat
from repro.refine import oracle_check, refine
from repro.refine.loads import (
    _batched_dense_refine_round_jit, _refine_round_jit,
)
from repro.stream import DeltaEngine, FusedEngine, FusedPool
from repro.stream.fused import query_group

TARGET_GAP = 0.01  # the acceptance criterion: certified within 1% of rho*


def _family(name: str, n_nodes: int, seed: int):
    if name == "uniform":
        return erdos_renyi(n_nodes, 16.0 / n_nodes, seed=seed)
    if name == "power_law":
        return rmat(int(np.log2(n_nodes)), edge_factor=8, seed=seed)
    if name == "planted":
        return planted_dense(n_nodes, max(n_nodes // 50, 12), seed=seed)[0]
    raise ValueError(name)


FAMILIES = ("uniform", "power_law", "planted")


def _gap_cell(family: str, n_nodes: int, max_rounds: int,
              seed: int = 7) -> dict:
    g = _family(family, n_nodes, seed)
    # warm the round executable for this shape, then freeze the counter:
    # the measured refinement must be compile-free across ALL its rounds
    refine(g, target_gap=-1.0, max_rounds=1)
    compiles_before = DeltaEngine.compile_count()
    t0 = time.perf_counter()
    res = refine(g, target_gap=TARGET_GAP, max_rounds=max_rounds)
    dt = time.perf_counter() - t0
    steady = DeltaEngine.compile_count() - compiles_before
    gaps = [h.rel_gap for h in res.history]
    assert all(a >= b for a, b in zip(gaps, gaps[1:])), (
        "gap trajectory not monotone")  # running-min dual: by construction
    return {
        "family": family,
        "n_nodes": g.n_nodes,
        "n_edges": g.n_edges,
        "seed_density": res.seed_density,
        "density": res.density,
        "dual_bound": res.dual_bound,
        "rel_gap": res.rel_gap,
        "quality": 1.0 - res.rel_gap,  # certified density / dual bound
        "rounds": res.rounds,
        "rounds_per_s": res.rounds / max(dt, 1e-9),
        "converged": res.converged,
        "steady_compiles": steady,
    }


def _verify_cell(family: str, n_nodes: int, max_rounds: int,
                 seed: int = 7) -> dict:
    g = _family(family, n_nodes, seed)
    res = refine(g, target_gap=TARGET_GAP, max_rounds=max_rounds)
    rho_star = oracle_check(g, res.certificate)  # density <= rho* <= dual
    return {
        "family": family, "n_nodes": g.n_nodes, "rho_star": rho_star,
        "density": res.density, "dual_bound": res.dual_bound,
        "rel_gap": res.rel_gap,
    }


def _fused_cell(n_tenants: int, n_nodes: int, rounds: int,
                seed: int = 0) -> dict:
    """Aggregate refinement rounds/sec: one batched dense-round program for
    the whole bucket vs sequential per-tenant COO round loops (the
    unbatched engine's path) — same comparison shape as bench_tenants."""
    rng = np.random.default_rng(seed)
    pool = FusedPool()
    seq, fused = [], {}
    for i in range(n_tenants):
        e = rng.integers(0, n_nodes, (3 * n_nodes, 2))
        s = DeltaEngine(n_nodes, refresh_every=10**9, pruned=False)
        f = FusedEngine(f"t{i}", pool, n_nodes, refresh_every=10**9,
                        pruned=False)
        s.apply_updates(insert=e)
        f.apply_updates(insert=e)
        seq.append(s)
        fused[f"t{i}"] = f
    # warm every executable (seed peel + both round variants + flush
    # shapes), then freeze the compile counter over the measured window
    warm_seq = [s.query(refine=True, target_gap=-1.0, max_refine_rounds=1)
                for s in seq]
    del warm_seq
    query_group(fused, refine=True, target_gap=-1.0, max_refine_rounds=1)
    compiles_before = DeltaEngine.compile_count()

    # sequential: T per-tenant COO round loops off each engine's state
    nc = seq[0].node_capacity
    t0 = time.perf_counter()
    for s in seq:
        loads = jnp.zeros(nc, jnp.int32)
        bd = jnp.asarray(0.0, jnp.float32)
        be = jnp.asarray(0, jnp.int32)
        bv = jnp.asarray(0, jnp.int32)
        bm = jnp.zeros(nc, dtype=bool)
        ps = jnp.asarray(0, jnp.int32)
        ne = jnp.asarray(s.buffer.n_edges, jnp.int32)
        for _ in range(rounds):
            loads, bd, be, bv, bm, ps = _refine_round_jit(
                s._src, s._dst, s._deg, ne, loads, bd, be, bv, bm, ps,
                nc, s.eps)
        loads.block_until_ready()
    t_seq = time.perf_counter() - t0

    # fused: one batched dense round program per round for the whole bucket
    f0 = next(iter(fused.values()))
    batch = f0.batch
    lanes = jnp.asarray([fused[f"t{i}"]._lane for i in range(n_tenants)],
                        jnp.int32)
    from repro.stream.fused import _lane_gather_jit, _rows_gather_jit

    _, _, deg_g, _ = _lane_gather_jit(
        batch._src, batch._dst, batch._deg, batch._prev_mask, lanes)
    adj_g = _rows_gather_jit(batch._adj, lanes)
    ne_g = jnp.asarray([s.buffer.n_edges for s in seq], jnp.int32)
    t0 = time.perf_counter()
    loads = jnp.zeros((n_tenants, nc), jnp.int32)
    bd = jnp.zeros(n_tenants, jnp.float32)
    be = jnp.zeros(n_tenants, jnp.int32)
    bv = jnp.zeros(n_tenants, jnp.int32)
    bm = jnp.zeros((n_tenants, nc), dtype=bool)
    ps = jnp.zeros(n_tenants, jnp.int32)
    for _ in range(rounds):
        loads, bd, be, bv, bm, ps = _batched_dense_refine_round_jit(
            adj_g, deg_g, ne_g, loads, bd, be, bv, bm, ps, batch.eps)
    loads.block_until_ready()
    t_fused = time.perf_counter() - t0
    steady = DeltaEngine.compile_count() - compiles_before

    # engine-level parity: fixed-round group == fixed-round solo queries,
    # bit-identical certificates and masks (dense GEMV vs COO scatter)
    R = 6
    solo = [s.query(refine=True, target_gap=-1.0, max_refine_rounds=R)
            for s in seq]
    for eng in fused.values():
        eng._cached_refined = None
        eng._refine_cert = None
    group = query_group(fused, refine=True, target_gap=-1.0,
                        max_refine_rounds=R)
    for i, a in enumerate(solo):
        b = group[f"t{i}"]
        ca, cb = a.certificate, b.certificate
        assert (ca.best_ne, ca.best_nv, ca.dual_num, ca.dual_den) == \
               (cb.best_ne, cb.best_nv, cb.dual_num, cb.dual_den), (i, ca, cb)
        assert np.array_equal(a.mask, b.mask), i

    agg = n_tenants * rounds
    return {
        "n_tenants": n_tenants,
        "n_nodes": n_nodes,
        "rounds": rounds,
        "seq_rounds_per_s": agg / t_seq,
        "fused_rounds_per_s": agg / t_fused,
        "speedup": t_seq / max(t_fused, 1e-12),
        "steady_compiles": steady,
    }


def run(n_nodes: int = 4096, verify_nodes: int = 256, max_rounds: int = 400,
        fused_tenants: int = 8, fused_nodes: int = 256,
        fused_rounds: int = 24, csv: bool = True) -> tuple[list, dict]:
    rows = []
    if csv:
        print("family,n_nodes,n_edges,seed_density,density,dual_bound,"
              "rel_gap,rounds,rounds_per_s,steady_compiles")
    for fam in FAMILIES:
        r = _gap_cell(fam, n_nodes, max_rounds)
        rows.append(r)
        if csv:
            print(f"{r['family']},{r['n_nodes']},{r['n_edges']},"
                  f"{r['seed_density']:.4f},{r['density']:.4f},"
                  f"{r['dual_bound']:.4f},{r['rel_gap']:.5f},{r['rounds']},"
                  f"{r['rounds_per_s']:.1f},{r['steady_compiles']}")
    for fam in FAMILIES:
        v = _verify_cell(fam, verify_nodes, max_rounds)
        rows.append(v)
        if csv:
            print(f"# oracle {v['family']}@{v['n_nodes']}: "
                  f"rho*={v['rho_star']:.4f} in "
                  f"[{v['density']:.4f}, {v['dual_bound']:.4f}]")
    fcell = _fused_cell(fused_tenants, fused_nodes, fused_rounds)
    rows.append(fcell)
    if csv:
        print(f"# fused refinement: {fcell['speedup']:.2f}x aggregate "
              f"rounds/sec at {fused_tenants} tenants "
              f"({fcell['fused_rounds_per_s']:.0f} vs "
              f"{fcell['seq_rounds_per_s']:.0f})")
    metrics = {
        "certified_quality_min": min(
            r["quality"] for r in rows if "quality" in r),
        "fused_refine_speedup_8": fcell["speedup"],
        "steady_compiles": max(
            r["steady_compiles"] for r in rows if "steady_compiles" in r),
    }
    return rows, metrics


def main(smoke: bool = False, strict: bool = False) -> None:
    """Gap closure (<= 1% certified, monotone), the oracle sandwich, fused
    == solo bit-parity and zero steady-state compiles are always asserted;
    ``strict`` additionally enforces the >= 2x fused-rounds acceptance
    target, which is wall-clock-dependent (bench-suite convention)."""
    if smoke:
        rows, metrics = run(n_nodes=1024, verify_nodes=128, max_rounds=300,
                            fused_nodes=128, fused_rounds=12)
        mode = "smoke"
    else:
        rows, metrics = run()
        mode = "full"
    gap_rows = [r for r in rows if "quality" in r]
    assert all(r["converged"] for r in gap_rows), (
        f"certified gap did not reach {TARGET_GAP:.0%}: {gap_rows}")
    assert metrics["steady_compiles"] == 0, "refinement rounds recompiled"
    write_bench_json("refine", metrics, rows, mode=mode)
    print(f"# {mode} ok: certified <= {TARGET_GAP:.0%} gap on "
          f"{len(gap_rows)} families (quality_min="
          f"{metrics['certified_quality_min']:.4f}), fused "
          f"{metrics['fused_refine_speedup_8']:.2f}x, zero steady compiles")
    if metrics["fused_refine_speedup_8"] < 2.0:
        msg = (f"acceptance target >=2x fused rounds/sec not met: "
               f"{metrics['fused_refine_speedup_8']:.2f}x")
        if strict:
            raise AssertionError(msg)
        print(f"# WARNING: {msg} (machine-dependent; rerun with --strict)")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if "--emit-metrics" in sys.argv:
        os.environ["BENCH_EMIT_METRICS"] = "1"
    main(smoke="--smoke" in sys.argv, strict="--strict" in sys.argv)
