"""Kernel-tier benchmark: sortedness win, roofline, parity (ISSUE 7).

Three questions, answered every run and recorded in BENCH_kernels.json:

  * does the maintained dst-sorted view pay? ``presorted_speedup`` is the
    ratio of grid-cell bodies the kernel's band-skip guard executes on
    unsorted vs sorted lanes — the exact quantity the scalar-prefetched
    band table controls (sorted: ~O(n_vb + n_eb) bodies; unsorted: the
    full O(n_vb * n_eb) grid). It is computed from the same band table the
    kernel prefetches, so it is deterministic per seed and machine-portable
    (CPU wall clock under interpret mode is dominated by per-cell block
    copies and too noisy to gate — it is still recorded in the rows as
    color).
  * where does the kernel sit against the scatter tier? The roofline pair
    ``mxu_us_per_edge`` (Pallas path) vs ``scatter_us_per_edge`` (the
    ``jax.ops.segment_sum`` XLA path) and their ratio
    ``roofline_ratio = scatter / mxu``. Under interpret mode the kernel is
    python-speed so the ratio is << 1; the gate tracks the *trajectory*
    (tolerance-banded against baseline.json), not an absolute target.
  * is the kernel hot path actually hot? A pre-sized ``DeltaEngine`` with
    ``kernel=True`` runs a same-shape churn window after warmup;
    ``steady_compiles`` must be exactly 0 (hard gate).

Bit-identity between the tiers (density, mask, passes — unpruned and
pruned) is asserted every run, smoke included.
"""
from __future__ import annotations

import os
import sys

if __name__ == "__main__":
    # direct invocation (python benchmarks/bench_kernels.py): put src/ on
    # the path before the package imports below (run.py does this for the
    # suite)
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(_root, "src"))
    sys.path.insert(0, _root)

import jax.numpy as jnp
import numpy as np

from benchmarks._artifacts import write_bench_json
from repro.core.pbahmani import pbahmani
from repro.core.prune import pbahmani_pruned
from repro.graphs.generators import barabasi_albert
from repro.kernels import ops
from repro.kernels.segsum import E_TILE, V_TILE, _round_up
from repro.stream.buffer import next_pow2
from repro.stream.delta import DeltaEngine
from repro.utils.timing import time_fn


def _peel_problem(n_nodes: int, seed: int = 0):
    """One peel-update call's inputs, in both lane orders. The unsorted
    variant feeds the raw symmetric COO straight to the kernel — legal
    (bands are recomputed from the data, results bit-identical) but every
    vertex band spans the whole edge range, so the band-skip guard never
    fires: exactly the slow path the maintained sorted views remove."""
    g = barabasi_albert(n_nodes, 4, seed=seed)
    rng = np.random.default_rng(seed + 1)
    failed = jnp.asarray(rng.random(g.n_nodes) < 0.3)
    src_s, dst_s = g.dst_sorted()
    return {
        "n_nodes": g.n_nodes,
        "n_lanes": g.src.size,
        "sorted": (jnp.asarray(src_s), jnp.asarray(dst_s)),
        "unsorted": (jnp.asarray(g.src), jnp.asarray(g.dst)),
        "failed": failed,
    }


def _executed_cells(seg_ids: np.ndarray, num_segments: int) -> int:
    """Grid-cell bodies the kernel executes for these seg ids: mirrors the
    band table segment_sum_sorted prefetches (min/max vertex block per edge
    tile, sentinel tail included)."""
    e = seg_ids.size
    e_pad = _round_up(max(e, 1), E_TILE)
    v_pad = _round_up(num_segments + 1, V_TILE)
    seg_p = np.full(e_pad, v_pad - 1, np.int64)
    seg_p[:e] = np.minimum(seg_ids.astype(np.int64), v_pad - 1)
    seg_p[seg_p >= num_segments] = v_pad - 1
    seg_2d = seg_p.reshape(-1, E_TILE)
    lo = seg_2d.min(axis=1) // V_TILE
    hi = seg_2d.max(axis=1) // V_TILE
    return int((hi - lo + 1).sum())


def _bench_sortedness(n_nodes: int, iters: int, seed: int = 0) -> dict:
    p = _peel_problem(n_nodes, seed)
    cells = {}
    times = {}
    outs = {}
    for order in ("sorted", "unsorted"):
        src, dst = p[order]
        cells[order] = _executed_cells(np.asarray(dst), p["n_nodes"])
        times[order], outs[order] = time_fn(
            lambda src=src, dst=dst: ops.peel_update(
                src, dst, p["failed"], n_nodes=p["n_nodes"]),
            iters=iters, warmup=1)
    # sortedness is a performance precondition only: identical counts
    np.testing.assert_array_equal(np.asarray(outs["sorted"]),
                                  np.asarray(outs["unsorted"]))
    n_eb = _round_up(p["n_lanes"], E_TILE) // E_TILE
    n_vb = _round_up(p["n_nodes"] + 1, V_TILE) // V_TILE
    return {
        "case": "sortedness",
        "n_nodes": n_nodes,
        "n_lanes": p["n_lanes"],
        "grid_cells": n_eb * n_vb,
        "cells_sorted": cells["sorted"],
        "cells_unsorted": cells["unsorted"],
        "presorted_speedup": cells["unsorted"] / max(cells["sorted"], 1),
        "sorted_us": times["sorted"] * 1e6,      # color only (interpret
        "unsorted_us": times["unsorted"] * 1e6,  # noise) — not gated
    }


def _bench_roofline(n_nodes: int, iters: int, seed: int = 0) -> dict:
    p = _peel_problem(n_nodes, seed)
    src, dst = p["sorted"]
    t_mxu, out_mxu = time_fn(
        lambda: ops.peel_update(src, dst, p["failed"], n_nodes=p["n_nodes"]),
        iters=iters, warmup=1)
    t_sc, out_sc = time_fn(
        lambda: ops.peel_update(src, dst, p["failed"], n_nodes=p["n_nodes"],
                                impl="xla"),
        iters=max(iters, 10), warmup=1)
    np.testing.assert_array_equal(np.asarray(out_mxu), np.asarray(out_sc))
    mxu_us = t_mxu * 1e6 / p["n_lanes"]
    sc_us = t_sc * 1e6 / p["n_lanes"]
    return {
        "case": "roofline",
        "n_nodes": n_nodes,
        "n_lanes": p["n_lanes"],
        "mxu_us_per_edge": mxu_us,
        "scatter_us_per_edge": sc_us,
        "roofline_ratio": sc_us / max(mxu_us, 1e-12),
    }


def _assert_parity(n_nodes: int, seed: int = 0) -> dict:
    g = barabasi_albert(n_nodes, 4, seed=seed)
    for peel in (pbahmani, pbahmani_pruned):
        d0, m0, p0 = peel(g, eps=0.1, kernel=False)
        d1, m1, p1 = peel(g, eps=0.1, kernel=True)
        assert (d0, p0) == (d1, p1), (peel.__name__, d0, d1, p0, p1)
        assert np.array_equal(np.asarray(m0), np.asarray(m1)), peel.__name__
    return {"case": "parity", "n_nodes": n_nodes, "density": d1,
            "passes": p1}


def _bench_steady_compiles(n_nodes: int, n_batches: int,
                           seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    eng = DeltaEngine(n_nodes, eps=0.1, capacity=next_pow2(16 * n_nodes),
                      refresh_every=10**9, kernel=True)
    assert eng.kernel, "kernel knob did not stick"
    # warmup: compile the batch shape + the warm peel once
    eng.apply_updates(insert=rng.integers(0, n_nodes, (48, 2)))
    eng.query()
    before = DeltaEngine.compile_count()
    for _ in range(n_batches):
        eng.apply_updates(insert=rng.integers(0, n_nodes, (48, 2)))
        eng._cached_query = None
        eng.query()
    return {
        "case": "steady",
        "n_nodes": n_nodes,
        "n_batches": n_batches,
        "steady_compiles": DeltaEngine.compile_count() - before,
    }


def run(n_nodes: int, iters: int, n_batches: int, csv: bool = True
        ) -> list[dict]:
    rows = [
        _bench_sortedness(n_nodes, iters),
        _bench_roofline(n_nodes, iters),
        _assert_parity(n_nodes),
        _bench_steady_compiles(n_nodes, n_batches),
    ]
    if csv:
        print("case,n_nodes,detail")
        for r in rows:
            detail = ",".join(f"{k}={v:.3f}" if isinstance(v, float)
                              else f"{k}={v}" for k, v in r.items()
                              if k not in ("case", "n_nodes"))
            print(f"{r['case']},{r['n_nodes']},{detail}")
    return rows


def _metrics(rows: list[dict]) -> dict:
    by = {r["case"]: r for r in rows}
    return {
        "presorted_speedup": by["sortedness"]["presorted_speedup"],
        "roofline_ratio": by["roofline"]["roofline_ratio"],
        "mxu_us_per_edge": by["roofline"]["mxu_us_per_edge"],
        "scatter_us_per_edge": by["roofline"]["scatter_us_per_edge"],
        "steady_compiles": by["steady"]["steady_compiles"],
    }


def main(smoke: bool = False) -> None:
    if smoke:
        rows = run(n_nodes=512, iters=2, n_batches=4)
        mode = "smoke"
    else:
        rows = run(n_nodes=2048, iters=3, n_batches=8)
        mode = "full"
    m = _metrics(rows)
    assert m["steady_compiles"] == 0, "kernel hot path recompiled"
    # deterministic grid-fraction win; the trajectory gate
    # (check_regression.py) additionally bands it against baseline.json
    assert m["presorted_speedup"] > 1.0, (
        f"sorted views did not shrink the grid: "
        f"{m['presorted_speedup']:.2f}x")
    write_bench_json("kernels", m, rows, mode=mode)
    print(f"# kernel tier: presorted_speedup {m['presorted_speedup']:.2f}x, "
          f"roofline {m['scatter_us_per_edge']:.3f} (scatter) vs "
          f"{m['mxu_us_per_edge']:.3f} (mxu) us/edge, zero steady-state "
          f"compiles, bit-identical tiers")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if "--emit-metrics" in sys.argv:
        os.environ["BENCH_EMIT_METRICS"] = "1"
    main(smoke="--smoke" in sys.argv)
