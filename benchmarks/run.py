"""Benchmark harness: one module per paper table/figure (DESIGN.md §6).

  bench_density  — paper Table 3 (exact vs P-Bahmani(0) vs CBDS-P)
  bench_epsilon  — paper Table 2 (rho*/rho~ by eps + pass counts)
  bench_scaling  — paper Figs 7-19 analog (runtime/pass scaling)
  bench_kernels  — Pallas segsum micro-validation + XLA path timing
  bench_roofline — three-term roofline from the dry-run artifact
  bench_stream   — streaming subsystem: ingest rate + query vs recompute
  bench_prune    — candidate pruning: pruned vs unpruned query latency
  bench_shard    — sharded streaming: shard_map engine vs single-device
  bench_tenants  — fused multi-tenant: batched peels vs sequential dispatch
  bench_refine   — near-optimal refinement: duality-gap closure + fused
                   batched rounds vs sequential per-tenant refinement
  bench_obs      — mesh-wide telemetry plane: worker processes -> collector
                   merge exactness, transport parity, scrape lint
"""
from __future__ import annotations

import time


def main() -> None:
    from benchmarks import (bench_density, bench_epsilon, bench_kernels,
                            bench_obs, bench_prune, bench_refine,
                            bench_roofline, bench_scaling, bench_shard,
                            bench_stream, bench_tenants)
    for name, fn in [
        ("bench_density (paper Table 3)", bench_density.main),
        ("bench_epsilon (paper Table 2)", bench_epsilon.main),
        ("bench_scaling (paper Figs 7-19)", bench_scaling.main),
        ("bench_kernels", bench_kernels.run),
        ("bench_roofline (single-pod)", bench_roofline.run),
        ("bench_stream (dynamic graphs)", bench_stream.main),
        ("bench_prune (candidate pruning)", bench_prune.main),
        ("bench_shard (sharded streaming)", bench_shard.main),
        ("bench_tenants (fused multi-tenant)", bench_tenants.main),
        ("bench_refine (near-optimal refinement)", bench_refine.main),
        ("bench_obs (mesh-wide telemetry plane)", bench_obs.main),
    ]:
        print(f"\n=== {name} ===")
        t0 = time.time()
        fn()
        print(f"# done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    import os
    import sys

    # direct invocation (python benchmarks/run.py) puts benchmarks/ on
    # sys.path, not the repo root / src the package imports need
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(_root, "src"))
    sys.path.insert(0, _root)
    if "--emit-metrics" in sys.argv:
        # every bench's write_bench_json also writes METRICS_<name>.json
        # (obs registry + recompile-audit snapshot) for the CI gate
        os.environ["BENCH_EMIT_METRICS"] = "1"
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
