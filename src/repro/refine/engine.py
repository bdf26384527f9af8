"""Anytime near-optimal refinement: ``refine(graph, target_gap=...)``.

Seeds from any peel result (by default the eps-approximate ``pbahmani``
peel, pruned or not), then iterates weighted-peel rounds (loads.py) until
the exact-rational duality gap (certify.py) closes below ``target_gap`` or
``max_rounds`` is spent. Every round is one call into a single compiled
executable per (shape, eps) — a long refinement compiles once and stays on
the hot path (the zero-steady-state-recompile contract, gated in
benchmarks/bench_refine.py) — and yields a full certificate, so the caller
can stop anywhere with a sound sandwich rho_best <= rho* <= dual.

``refine_resident`` is the engine-facing core: it runs the same loop off
already-resident device arrays (the streaming engines' maintained
src/dst/deg state), which is how ``DeltaEngine.query(refine=True)`` serves
certified densities without an O(|E|) host rebuild.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np

from repro.graphs.graph import Graph
from repro.refine.certify import (
    GapCertificate, better_fraction, dual_fraction, make_certificate,
    max_fraction,
)
from repro.refine.loads import _refine_round_jit

# relative duality gap (gap / dual bound) at which refinement declares
# convergence: rel_gap <= g certifies rho_best >= (1 - g) * rho*(G)
DEFAULT_TARGET_GAP = 0.01


@dataclass(frozen=True)
class RoundRecord:
    """One row of the anytime trajectory (certificate after round t)."""

    round: int
    density: float
    dual_bound: float
    gap: float
    rel_gap: float
    passes: int  # cumulative peel passes including the seed peel's


@dataclass
class RefineResult:
    density: float            # best certified density (>= seed, exactly)
    mask: np.ndarray          # bool [n_nodes] achieving ``density``
    dual_bound: float         # running-min LP dual bound (>= rho*)
    gap: float
    rel_gap: float
    rounds: int
    passes: int               # cumulative passes (seed peel + all rounds)
    proved_optimal: bool      # density == rho*(G), proven in exact ints
    converged: bool           # rel_gap <= target_gap within max_rounds
    seed_density: float
    certificate: GapCertificate = None
    history: list = field(default_factory=list)


# repro: proof
def _seed_counts(mask: np.ndarray, u: np.ndarray, v: np.ndarray) -> tuple:
    """Exact integer (ne, nv) of the subgraph induced by ``mask`` from host
    endpoint arrays carrying one undirected entry per edge (no sentinels
    within range escape the appended always-False row)."""
    lv = np.zeros(mask.shape[0] + 1, dtype=bool)
    lv[: mask.shape[0]] = mask
    ne = int((lv[np.minimum(u, mask.shape[0])]
              & lv[np.minimum(v, mask.shape[0])]).sum())
    return ne, int(mask.sum())


def refine_resident(
    src, dst, deg, n_edges: int, n_nodes: int, eps: float,
    seed_ne: int, seed_nv: int, seed_mask: np.ndarray, seed_passes: int,
    target_gap: float, max_rounds: int, kernel: bool = False,
    mesh=None,
) -> tuple[GapCertificate, np.ndarray, int, int, list]:
    """Run refinement rounds off device-resident COO arrays.

    ``seed_mask`` is full-width (n_nodes); ``seed_ne/seed_nv`` its exact
    induced counts. Returns (certificate, best_mask_full, passes, rounds,
    history). The loop stops as soon as ``rel_gap <= target_gap`` — pass a
    negative target to run exactly ``max_rounds`` rounds (the deterministic
    fixed-budget mode benches and parity tests use). ``max_rounds`` is
    floored at 1: a certificate needs at least one load round for its dual
    side. ``kernel`` selects the Pallas segment-sum tier for the round's
    reductions (the caller supplies dst-sorted lanes for its band-skip
    envelope); certificates are bit-identical either way. ``mesh`` routes
    each round through the shard_map tier instead — ``src/dst`` are then
    the engine's resident mesh-sharded slot arrays (no re-upload), and the
    round integers are identical on any device count.
    """
    max_rounds = max(int(max_rounds), 1)
    if mesh is not None:
        from repro.refine.loads import _make_sharded_refine_round

        sharded_round = _make_sharded_refine_round(mesh, n_nodes, float(eps))

        def step(src, dst, deg, n_edges, loads, bd, be, bv, bm, ps):
            return sharded_round(src, dst, deg, n_edges, loads, bd, be, bv,
                                 bm, ps)
    else:
        def step(src, dst, deg, n_edges, loads, bd, be, bv, bm, ps):
            return _refine_round_jit(src, dst, deg, n_edges, loads, bd, be,
                                     bv, bm, ps, n_nodes, eps, kernel)
    loads = jnp.zeros(n_nodes, jnp.int32)
    seed_density = (np.float32(seed_ne) / np.float32(seed_nv)
                    if seed_nv > 0 else np.float32(0.0))
    best_density = jnp.asarray(seed_density, jnp.float32)
    best_ne = jnp.asarray(seed_ne, jnp.int32)
    best_nv = jnp.asarray(seed_nv, jnp.int32)
    best_mask = jnp.asarray(seed_mask, dtype=bool)
    passes = jnp.asarray(seed_passes, jnp.int32)
    n_edges = jnp.asarray(n_edges, jnp.int32)

    history: list[RoundRecord] = []
    dual_num = dual_den = None
    cert = None
    rounds = 0
    for t in range(1, int(max_rounds) + 1):
        (loads, best_density, best_ne, best_nv, best_mask,
         passes) = step(
            src, dst, deg, n_edges, loads, best_density, best_ne, best_nv,
            best_mask, passes)
        rounds = t
        # host guard: the device best-tracking compares f32 densities; fold
        # the seed back in exactly so refined >= seed always holds
        b_ne, b_nv = max_fraction((int(best_ne), int(best_nv)),
                                  (seed_ne, seed_nv))
        num, den = dual_fraction(np.asarray(loads), t)
        if dual_num is None or better_fraction(num, den, dual_num, dual_den):
            dual_num, dual_den = num, den
        cert = make_certificate(b_ne, b_nv, dual_num, dual_den)
        history.append(RoundRecord(
            round=t, density=cert.density, dual_bound=cert.dual_bound,
            gap=cert.gap, rel_gap=cert.rel_gap, passes=int(passes)))
        if cert.rel_gap <= target_gap:
            break

    if cert.best_ne == seed_ne and cert.best_nv == seed_nv:
        mask_full = np.asarray(seed_mask, dtype=bool).copy()
    else:
        mask_full = np.asarray(best_mask)
    return cert, mask_full, int(passes), rounds, history


def refine(
    graph: Graph,
    target_gap: float = DEFAULT_TARGET_GAP,
    max_rounds: int = 64,
    eps: float = 0.0,
    pruned: bool = False,
    seed: tuple[float, np.ndarray, int] | None = None,
    kernel: bool | None = None,
) -> RefineResult:
    """Refine a static graph's densest-subgraph estimate toward rho*(G).

    ``seed`` is an optional (density, mask, passes) triple from a previous
    peel; by default the eps-approximate ``pbahmani`` peel (``pruned=True``
    routes the seed through the candidate-pruned path). The result's
    ``density`` is certified within ``rel_gap`` of the optimum and is never
    below the seed's (exact-rational guard, not a float comparison).
    ``kernel`` selects the Pallas segment-sum tier (None = deploy default);
    kernel mode feeds ``graph.dst_sorted()`` lanes — same certificates.
    """
    from repro.core.dispatch import assert_exact_envelope

    kernel = bool(kernel)
    n = graph.n_nodes
    # refine_resident's kernel tier accumulates failed-neighbor counts in
    # f32 lanes — exact only below 2^24 (core/dispatch.py)
    assert_exact_envelope(graph.n_directed, n)
    if n == 0 or graph.n_edges == 0:
        cert = make_certificate(0, 0, 0, 1)
        return RefineResult(
            density=0.0, mask=np.zeros(n, dtype=bool), dual_bound=0.0,
            gap=0.0, rel_gap=0.0, rounds=0, passes=0, proved_optimal=True,
            converged=True, seed_density=0.0, certificate=cert, history=[])
    if seed is None:
        from repro.core.pbahmani import pbahmani

        seed = pbahmani(graph, eps=eps, pruned=pruned, kernel=kernel)
    seed_density, seed_mask, seed_passes = seed
    seed_mask = np.asarray(seed_mask, dtype=bool)
    half = graph.n_directed // 2
    seed_ne, seed_nv = _seed_counts(
        seed_mask, graph.src[:half], graph.dst[:half])

    if kernel:
        src_h, dst_h = graph.dst_sorted()
    else:
        src_h, dst_h = graph.src, graph.dst
    cert, mask_full, passes, rounds, history = refine_resident(
        jnp.asarray(src_h), jnp.asarray(dst_h),
        jnp.asarray(graph.degrees().astype(np.int32)),
        graph.n_edges, n, float(eps),
        seed_ne, seed_nv, seed_mask, int(seed_passes),
        float(target_gap), int(max_rounds), kernel,
    )
    return RefineResult(
        density=cert.density, mask=mask_full[:n], dual_bound=cert.dual_bound,
        gap=cert.gap, rel_gap=cert.rel_gap, rounds=rounds, passes=passes,
        proved_optimal=cert.proves_optimal,
        converged=cert.rel_gap <= target_gap,
        # exact f64 fraction (the f32 seed value can sit an ulp above it)
        seed_density=seed_ne / seed_nv if seed_nv else 0.0,
        certificate=cert, history=history)


__all__ = [
    "DEFAULT_TARGET_GAP",
    "RoundRecord",
    "RefineResult",
    "refine",
    "refine_resident",
]
