"""Candidate pruning (core/prune.py): the exactness-preservation invariant.

The load-bearing claim (ISSUE 2 acceptance): the pruned peel — host pass-0
simulation, host compaction into pow-2 buckets, device bucket peel with the
ladder — returns the *bit-identical* (density, mask, passes) triple of the
unpruned peel, for every bucket choice, on adversarial structure and random
streams alike. rho~ and the ceil(rho~)-core never gate correctness, but
their soundness (rho_lb <= rho*, S* inside the core) is asserted too.
"""
import numpy as np
import pytest

from _hyp import given, settings, st

from repro.core import exact_densest, pbahmani, pbahmani_np
from repro.core.prune import (
    BUCKET_SHRINK_HYSTERESIS, MIN_BUCKET_E, MIN_BUCKET_V, _plan_jit,
    build_plan, compact_candidates, hold_group_buckets, pbahmani_pruned,
    plan_for_graph,
)
from repro.graphs.generators import erdos_renyi, planted_dense, small_named
from repro.graphs.graph import Graph
from repro.stream.delta import DeltaEngine

import jax.numpy as jnp


def bit_identical(g, eps, plan=None):
    rho_u, mask_u, passes_u = pbahmani(g, eps=eps)
    rho_p, mask_p, passes_p = pbahmani_pruned(g, eps=eps, plan=plan)
    assert rho_p == rho_u, (rho_p, rho_u)
    assert np.array_equal(mask_p, mask_u)
    assert passes_p == passes_u, (passes_p, passes_u)


# ---------------------------------------------------------------------------
# adversarial structure
# ---------------------------------------------------------------------------
def _adversarial_graphs():
    k5a = [(i, j) for i in range(5) for j in range(i + 1, 5)]
    k5b = [(5 + i, 5 + j) for i in range(5) for j in range(i + 1, 5)]
    k4 = [(i, j) for i in range(4) for j in range(i + 1, 4)]
    cases = {
        # two equal-density components: the argmax set is tie-broken by the
        # trajectory (earliest best wins) — the classic mask-divergence trap
        "disjoint_equal_k5": Graph.from_edges(np.array(k5a + k5b)),
        # star: hub degree >> coreness, the case where degree-based and
        # core-based candidate sets disagree maximally
        "star": Graph.from_edges(np.array([[0, i] for i in range(1, 12)])),
        "empty": Graph.from_edges(np.zeros((0, 2), np.int64), n_nodes=0),
        "edgeless": Graph.from_edges(np.zeros((0, 2), np.int64), n_nodes=9),
        "single_edge": Graph.from_edges(np.array([[0, 1]]), n_nodes=6),
        # densest subgraph (K4, rho*=1.5) sits exactly at the ceil(rho~)-core
        # boundary: the attached cycle is 2-core but not part of S*
        "core_boundary_lollipop": Graph.from_edges(np.array(
            k4 + [(3, 4), (4, 5), (5, 6), (6, 3)])),
    }
    for name in ["triangle_plus_path", "k4_plus_star", "two_cliques",
                 "petersen"]:
        cases[name] = small_named(name)
    return cases


@pytest.mark.parametrize("name,graph", sorted(_adversarial_graphs().items()))
@pytest.mark.parametrize("eps", [0.0, 0.25])
def test_pruned_parity_adversarial(name, graph, eps):
    bit_identical(graph, eps)


@pytest.mark.parametrize("eps", [0.0, 0.25])
def test_pruned_parity_forced_tiny_buckets(eps):
    """Tiny buckets force mid-trajectory ladder handoffs and the in-flight
    regrow path; parity must hold for EVERY bucket choice."""
    g = erdos_renyi(150, 0.08, seed=3)
    tiny = build_plan(1.0, 1, g.n_nodes, g.n_edges, g.n_nodes,
                      g.src.shape[0], observed=(32, 128))
    assert tiny.bucket_v == MIN_BUCKET_V and tiny.bucket_e == MIN_BUCKET_E
    bit_identical(g, eps, plan=tiny)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([0.0, 0.1, 0.5]))
def test_pruned_parity_random(seed, eps):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(8, 140))
    g = erdos_renyi(n, float(rng.uniform(0.02, 0.35)), seed=seed)
    bit_identical(g, eps)


def test_pruned_parity_planted():
    g, _, _ = planted_dense(600, 30, seed=5)
    bit_identical(g, 0.0)
    bit_identical(g, 0.1)


# ---------------------------------------------------------------------------
# plan soundness: rho~ is a real lower bound, the core contains S*
# ---------------------------------------------------------------------------
@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10_000))
def test_plan_rho_lb_sound_and_core_contains_optimum(seed):
    g = erdos_renyi(70, 0.1, seed=seed)
    if g.n_edges == 0:
        return
    plan = plan_for_graph(g)
    rho_star, mask_star = exact_densest(g)
    assert plan.rho_lb <= rho_star + 1e-5
    # every vertex of a densest subgraph has induced degree >= rho* >=
    # rho~, hence coreness >= ceil(rho~): S* survives the candidate prune
    _, k, cand_mask, n_cand, _ = _plan_jit(
        jnp.asarray(g.src), jnp.asarray(g.dst),
        jnp.zeros(g.n_nodes, dtype=bool),
        jnp.asarray(g.n_edges, jnp.int32), g.n_nodes,
    )
    cand = np.asarray(cand_mask)
    assert int(n_cand) == int(cand.sum())
    assert not (mask_star & ~cand).any(), "optimum pruned away"
    assert plan.k == int(np.ceil(plan.rho_lb)) or plan.rho_lb == 0.0


def test_plan_buckets_pow2_and_caps():
    plan = build_plan(3.2, 4, 100, 400, node_width=4096, lane_width=131072)
    for b in plan.buckets:
        assert b & (b - 1) == 0, f"bucket {b} not a power of two"
    assert plan.bucket_e <= 131072 // 2
    grown = build_plan(3.2, 4, 100, 400, node_width=4096, lane_width=131072,
                       observed=(3000, 40000))
    assert grown.bucket_v == 4096 and grown.bucket_e == 65536
    tiny_graph = build_plan(0.0, 1, 0, 0, node_width=8, lane_width=256)
    assert not tiny_graph.enabled or tiny_graph.bucket_e < 256


# ---------------------------------------------------------------------------
# host compaction: remap correctness
# ---------------------------------------------------------------------------
def test_compact_candidates_remap():
    #   0-1-2 triangle, 2-3 pendant, 4 isolated, slot array with a hole
    u = np.array([0, 1, 0, 2, 5], dtype=np.int64)   # 5 == sentinel (hole)
    v = np.array([1, 2, 2, 3, 5], dtype=np.int64)
    live = np.array([True, True, True, False, False])  # prune 3 and 4
    perm, b_src, b_dst, lanes = compact_candidates(u, v, live, 4, 16)
    assert lanes == 6                      # triangle only, symmetric
    assert list(perm[:3]) == [0, 1, 2]
    pairs = set(zip(b_src[b_src < 4].tolist(), b_dst[b_dst < 4].tolist()))
    assert pairs == {(0, 1), (1, 0), (1, 2), (2, 1), (0, 2), (2, 0)}
    assert (b_src[lanes:] == 4).all() and (b_dst[lanes:] == 4).all()
    with pytest.raises(ValueError, match="does not fit"):
        compact_candidates(u, v, live, 4, 4)


# ---------------------------------------------------------------------------
# DeltaEngine integration: pruned == unpruned == cold oracle, query by query
# ---------------------------------------------------------------------------
def _stream(rng, n, n_batches, max_batch):
    edges: set = set()
    for _ in range(n_batches):
        ins = rng.integers(0, n, (int(rng.integers(1, max_batch)), 2))
        dels = None
        if edges and rng.random() < 0.6:
            pool = np.asarray(sorted(edges))
            dels = pool[rng.random(len(pool)) < 0.3]
            for a, b in dels:
                edges.discard((int(a), int(b)))
        for a, b in ins:
            a, b = int(a), int(b)
            if a != b:
                edges.add((min(a, b), max(a, b)))
        yield ins, dels, edges


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 10_000))
def test_engine_pruned_matches_unpruned_and_cold(seed):
    """ISSUE 2 acceptance: the pruned engine's query is bit-identical to the
    unpruned engine's and (to f32) to a cold pbahmani_np recompute — after
    any insert/delete sequence, across warm and epoch-refresh paths."""
    rng = np.random.default_rng(seed)
    n = 180
    ep = DeltaEngine(n_nodes=n, refresh_every=5, pruned=True)
    eu = DeltaEngine(n_nodes=n, refresh_every=5, pruned=False)
    for step, (ins, dels, edges) in enumerate(_stream(rng, n, 8, 50)):
        ep.apply_updates(insert=ins, delete=dels)
        eu.apply_updates(insert=ins, delete=dels)
        qp, qu = ep.query(), eu.query()
        assert qp.density == qu.density, f"step {step}"
        assert np.array_equal(qp.mask, qu.mask)
        assert qp.passes == qu.passes
        pairs = (np.asarray(sorted(edges), dtype=np.int64) if edges
                 else np.zeros((0, 2), np.int64))
        rho, mask, passes = pbahmani_np(Graph.from_edges(pairs, n_nodes=n))
        assert qp.density == pytest.approx(rho, rel=1e-6, abs=1e-9)
        assert np.array_equal(qp.mask, mask)
        assert qp.passes == passes


def test_engine_prune_metrics_and_bucket_reuse():
    rng = np.random.default_rng(9)
    eng = DeltaEngine(n_nodes=300, refresh_every=10**9, pruned=True)
    eng.apply_updates(insert=rng.integers(0, 300, (800, 2)))
    q = eng.query()
    assert q.pruned
    m = eng.metrics
    assert m.n_pruned_queries == 1 and m.n_plan_builds == 1
    assert 0.0 < m.candidate_fraction <= 1.0
    assert m.prune_bucket_v & (m.prune_bucket_v - 1) == 0
    # steady epochs re-derive the same buckets: reuse, not recompile churn
    eng.refresh()
    eng.refresh()
    assert eng.metrics.bucket_reuses >= 1
    assert eng.metrics.n_plan_builds >= 3


def test_engine_pruned_zero_recompiles_with_refresh():
    """A stationary stream — including epoch boundaries — compiles nothing
    new: the bucket executable and the plan analysis are shape-stable. (A
    *growing* graph legitimately re-tiers its buckets O(log growth) times,
    exactly like the edge buffer's capacity doubling.)"""
    rng = np.random.default_rng(11)
    eng = DeltaEngine(n_nodes=500, capacity=4096, refresh_every=10**9,
                      pruned=True)
    eng.apply_updates(insert=rng.integers(0, 500, (600, 2)))
    eng.query()
    eng.refresh()   # adapts buckets to the observed handoff
    # warm the churn-batch shape and the adapted bucket executable
    eng.apply_updates(insert=rng.integers(0, 500, (20, 2)),
                      delete=eng.buffer.live_pairs()[:20])
    eng.query()
    before = DeltaEngine.compile_count()
    for _ in range(10):
        ins = rng.integers(0, 500, (20, 2))
        dels = eng.buffer.live_pairs()[:20]  # stationary churn
        eng.apply_updates(insert=ins, delete=dels)
        eng.query()
    eng.refresh()
    assert DeltaEngine.compile_count() == before, "pruned hot path recompiled"


def test_engine_pruned_empty_and_tiny():
    eng = DeltaEngine(n_nodes=20, pruned=True)
    assert eng.query().density == 0.0
    eng.apply_updates(insert=np.array([[0, 1], [1, 2], [0, 2]]))
    assert eng.query().density == pytest.approx(1.0)
    eng.apply_updates(delete=np.array([[0, 1], [1, 2], [0, 2]]))
    q = eng.query()
    assert q.density == 0.0 and q.mask.sum() == 0


def test_service_reports_pruned_flag():
    from repro.stream import StreamService

    svc = StreamService()
    svc.create_tenant("t", n_nodes=128)
    svc.apply_updates("t", insert=np.array([[0, 1], [1, 2], [0, 2]]))
    d = svc.density("t")
    assert d.ok and "pruned" in d.value
    st_ = svc.stats("t")
    assert st_.ok and st_.value.pruned
    # opt-out reaches the engine through the service layer (PR-1 warm-mask
    # semantics stay available per tenant)
    svc.create_tenant("legacy", n_nodes=64, pruned=False)
    assert not svc.registry.get("legacy").pruned
    assert not svc.stats("legacy").value.pruned


H = BUCKET_SHRINK_HYSTERESIS


@pytest.mark.parametrize("held,shapes,expect", [
    (None, [(128, 512, 64, 256), (256, 256, 64, 256)], (256, 512, 64, 256)),
    ((256, 512, 64, 256), [(512, 256, 64, 256)], (512, 512, 64, 256)),
    ((256, 512, 64, 256), [(128, 256, 64, 256)], (256, 512, 64, 256)),
    ((256, 512, 64, 256), [(256 // H, 512, 64, 256)], (256 // H, 512, 64, 256)),
    ((256, 4096, 64, 512), [(128, 4096 // H, 64, 256)],
     (128, 4096 // H, 64, 256)),
], ids=["first", "grow", "hold", "shrink_v", "shrink_e"])
def test_hold_group_buckets(held, shapes, expect):
    """A group's held plan shape grows to its members' elementwise max at
    once and shrinks to it only past the plans' own hysteresis, on either
    axis (as ``maybe_shrink_plan``)."""
    assert hold_group_buckets(held, shapes) == expect


def test_engine_mid_epoch_bucket_shrink():
    """ISSUE 3 bugfix: plans used to only *regrow* buckets mid-epoch, so a
    contracting graph kept peeling inside peak-size buckets until the next
    refresh. An observation-sized plan now shrinks mid-epoch once the
    handoff fits BUCKET_SHRINK_HYSTERESIS-times-smaller buckets — at
    bit-identical results. First-shot (conservative) plans never shrink:
    that headroom is warmup slack, not contraction."""
    rng = np.random.default_rng(31)
    g, _, _ = planted_dense(1024, 48, seed=5)
    half = g.n_directed // 2
    seed_edges = np.stack([g.src[:half], g.dst[:half]], axis=1).astype(np.int64)
    eng = DeltaEngine(n_nodes=1024, capacity=8192, refresh_every=10**9)
    eng.apply_updates(insert=seed_edges)
    eng.query()
    # first-shot plan: tiny handoff slack is intentional, no shrink yet
    assert not eng._plan.from_observed
    assert eng.metrics.n_bucket_shrinks == 0
    eng.refresh()  # plan now sized from the observed handoff
    assert eng._plan.from_observed
    be_before = eng.metrics.prune_bucket_e

    # contract hard mid-epoch: drop ~95% of edges, keep the planted block
    pool = eng.buffer.live_pairs()
    dels = pool[rng.random(len(pool)) >= 0.05]
    for i in range(0, len(dels), 512):
        eng.apply_updates(delete=dels[i: i + 512])
    q = eng.query()
    assert q.pruned
    assert eng.metrics.n_bucket_shrinks >= 1
    assert eng.metrics.prune_bucket_e < be_before
    rho, mask, passes = pbahmani_np(eng.buffer.to_graph())
    assert q.density == pytest.approx(rho, rel=1e-6, abs=1e-9)
    assert np.array_equal(q.mask, mask[:1024]) and q.passes == passes

    # hysteresis: a stable graph never shrinks again on the next query
    shrinks = eng.metrics.n_bucket_shrinks
    eng._cached_query = None
    eng.query()
    assert eng.metrics.n_bucket_shrinks == shrinks
