"""Fused multi-tenant execution (ISSUE 4): batched == unbatched parity,
bucket-roster churn without recompiles, and the service front-end's
coalescing / error / edge paths.

The two load-bearing claims:
  * a FusedEngine's (density, mask, passes) triple is bit-identical to an
    unbatched DeltaEngine fed the same stream — for single queries, group
    flushes, epoch refreshes, the dense (GEMV) bucket representation and
    the sparse (scatter) one;
  * joining / evicting a tenant in a warm bucket is a lane row swap: the
    compile counter must not move.
"""
import numpy as np
import pytest

from _hyp import given, settings, st

from repro.core.pbahmani import pbahmani_np
from repro.core.prune import BUCKET_SHRINK_HYSTERESIS
from repro.stream import (
    DeltaEngine, FusedEngine, FusedPool, GraphRegistry, StreamService,
    ingest_group, query_group,
)
from repro.stream.fused import DENSE_NODE_CAP, MIN_LANES


def _churn(rng, n, edges):
    ins = rng.integers(0, n, (int(rng.integers(1, 50)), 2))
    dels = None
    if edges and rng.random() < 0.6:
        pool = np.asarray(sorted(edges))
        dels = pool[rng.random(len(pool)) < 0.3]
        for u, v in dels:
            edges.discard((int(u), int(v)))
    for u, v in ins:
        u, v = int(u), int(v)
        if u != v:
            edges.add((min(u, v), max(u, v)))
    return ins, dels


# ---------------------------------------------------------------------------
# bit-identity: fused == unbatched
# ---------------------------------------------------------------------------
@settings(max_examples=3, deadline=None)
@given(st.integers(0, 10_000))
def test_fused_matches_unbatched_stream(seed):
    """After any insert/delete sequence — including epoch refreshes — the
    fused engine's triple equals the unbatched engine's, both pruned and
    unpruned."""
    rng = np.random.default_rng(seed)
    n = 150
    pool = FusedPool()
    for pruned in (False, True):
        ref = DeltaEngine(n_nodes=n, refresh_every=4, pruned=pruned)
        fe = FusedEngine(f"t{pruned}", pool, n, refresh_every=4,
                         pruned=pruned)
        edges: set = set()
        for step in range(8):
            ins, dels = _churn(rng, n, edges)
            ref.apply_updates(insert=ins, delete=dels)
            fe.apply_updates(insert=ins, delete=dels)
            q1, q2 = ref.query(), fe.query()
            assert q1.density == q2.density, (pruned, step)
            assert np.array_equal(q1.mask, q2.mask), (pruned, step)
            assert q1.passes == q2.passes, (pruned, step)
            assert q1.warm_density == q2.warm_density, (pruned, step)
            assert q1.refreshed == q2.refreshed, (pruned, step)


def test_fused_group_query_parity_and_lane_growth():
    """A group flush answers every tenant bit-identically to its own
    unbatched twin; growing past MIN_LANES preserves resident lanes."""
    rng = np.random.default_rng(1)
    n = 120
    pool = FusedPool()
    refs, fused = [], {}
    for i in range(MIN_LANES + 2):  # forces one lane-stack growth
        r = DeltaEngine(n_nodes=n, refresh_every=10**9)
        f = FusedEngine(f"t{i}", pool, n, refresh_every=10**9)
        ins = rng.integers(0, n, (60 + 10 * i, 2))
        r.apply_updates(insert=ins)
        f.apply_updates(insert=ins)
        refs.append(r)
        fused[f"t{i}"] = f
    assert next(iter(fused.values())).batch.lanes > MIN_LANES
    results = query_group(fused)
    for i, r in enumerate(refs):
        q1, q2 = r.query(), results[f"t{i}"]
        assert q1.density == q2.density and q1.passes == q2.passes
        assert np.array_equal(q1.mask, q2.mask)
    # memoization: a second group flush returns the cached objects
    again = query_group(fused)
    assert all(again[k] is results[k] for k in fused)


def test_fused_sparse_bucket_parity():
    """Vertex spaces above DENSE_NODE_CAP use the scatter-based vmapped
    peel — same bit-identity contract."""
    rng = np.random.default_rng(2)
    n = DENSE_NODE_CAP + 10  # node capacity 1024 > DENSE_NODE_CAP
    pool = FusedPool()
    ref = DeltaEngine(n_nodes=n, refresh_every=10**9, pruned=False)
    fe = FusedEngine("big", pool, n, refresh_every=10**9, pruned=False)
    ins = rng.integers(0, n, (800, 2))
    ref.apply_updates(insert=ins)
    fe.apply_updates(insert=ins)
    assert not fe.batch.dense
    q1, q2 = ref.query(), fe.query()
    assert q1.density == q2.density and q1.passes == q2.passes
    assert np.array_equal(q1.mask, q2.mask)


def test_fused_sharded_bucket_parity_one_device():
    """ISSUE 9: fused+sharded tenants share a mesh-sharded bucket stack
    whose batched programs run vmap-inside-shard_map — on the in-process
    1-device mesh every tenant stays bit-identical to its solo twin, and
    cbds routes through the same sharded tier (the multi-device version of
    this oracle lives in tests/test_shard.py subprocesses). Stale members
    (``a`` every round, ``b`` every few) re-anchor inside the sharded
    bucket's flush beside the fresh ``c``, with their per-member sharded
    plan rebuilds."""
    rng = np.random.default_rng(9)
    n = 150
    reg = GraphRegistry(fused=True, sharded=True)
    names = ["a", "b", "c"]
    every = {"a": 1, "b": 3, "c": 32}
    solo, edge_sets = {}, {}
    for t in names:
        eng = reg.register(t, n_nodes=n, refresh_every=every[t])
        assert eng.sharded and eng.kind == "fused+sharded"
        solo[t] = DeltaEngine(n_nodes=n, refresh_every=every[t])
        edge_sets[t] = set()
    n_stale = 0
    for step in range(6):
        ups = {}
        for t in names:
            ins, dels = _churn(rng, n, edge_sets[t])
            ups[t] = (ins, dels)
            solo[t].apply_updates(insert=ins, delete=dels)
        ingest_group(ups, reg.engines())
        stale = {t for t in names if reg.get(t).stale}
        assert "a" in stale and "c" not in stale, step
        n_stale += len(stale)
        res = query_group(reg.engines())
        for t in names:
            qs = solo[t].query()
            assert res[t].density == qs.density, (step, t)
            assert res[t].passes == qs.passes, (step, t)
            assert np.array_equal(np.asarray(res[t].mask), qs.mask), (step, t)
            assert res[t].refreshed == qs.refreshed == (t in stale), (step, t)
    assert n_stale > 6  # b re-anchored too
    for t in names:
        assert (reg.get(t).metrics.n_refreshes
                == solo[t].metrics.n_refreshes), t
    for t in names:
        cf, cs = reg.get(t).cbds(), solo[t].cbds()
        assert cf["density"] == cs["density"] and cf["n_legit"] == cs["n_legit"]


def test_fused_capacity_migration_rebuckets():
    """A buffer regrow moves the tenant to the matching capacity bucket
    (evict + join) with exact results on the other side."""
    rng = np.random.default_rng(3)
    n = 100
    pool = FusedPool()
    fe = FusedEngine("grow", pool, n, capacity=256, refresh_every=10**9)
    fe.apply_updates(insert=rng.integers(0, n, (60, 2)))
    fe.query()
    first = fe.batch
    # overflow the 256-slot buffer: capacity doubles, bucket changes
    big = rng.integers(0, n, (2000, 2))
    fe.apply_updates(insert=big)
    assert fe.buffer.capacity > 256
    assert fe.batch is not first
    assert "grow" not in first.lane_of
    rho, mask, passes = pbahmani_np(fe.buffer.to_graph())
    q = fe.query()
    assert q.density == pytest.approx(rho, rel=1e-6, abs=1e-9)
    assert np.array_equal(q.mask, mask[:n]) and q.passes == passes


def test_fused_join_evict_zero_recompiles():
    """Tenant churn in a warm bucket is a row swap: evict one tenant, join
    another, ingest and run single + group queries — the compile counter
    must not move. (pruned=False: plan-bucket shapes are data-dependent
    and compile on regrow even in the unbatched engine.)"""
    rng = np.random.default_rng(4)
    n = 100
    pool = FusedPool()
    fused = {}
    for i in range(4):
        f = FusedEngine(f"t{i}", pool, n, refresh_every=10**9, pruned=False)
        f.apply_updates(insert=rng.integers(0, n, (48, 2)))
        f.query()
        fused[f"t{i}"] = f
    for f in fused.values():
        f._cached_query = None  # defeat memoization: warm the group shapes
    query_group(fused)
    ingest_group({k: (rng.integers(0, n, (20, 2)), None) for k in fused},
                 fused)
    before = DeltaEngine.compile_count()

    fused.pop("t1").release()
    nf = FusedEngine("t9", pool, n, refresh_every=10**9, pruned=False)
    nf.apply_updates(insert=rng.integers(0, n, (48, 2)))
    fused["t9"] = nf
    nf.query()
    for f in fused.values():
        f._cached_query = None
    query_group(fused)
    ingest_group({k: (rng.integers(0, n, (20, 2)), None) for k in fused},
                 fused)
    assert DeltaEngine.compile_count() == before, "join/evict recompiled"


def test_fused_ingest_group_parity():
    """One fused [T, B] scatter applies many tenants' batches with the
    same outcome as per-tenant dispatch."""
    rng = np.random.default_rng(5)
    n = 90
    pool = FusedPool()
    refs, fused, upd = [], {}, {}
    for i in range(3):
        r = DeltaEngine(n_nodes=n, refresh_every=10**9)
        f = FusedEngine(f"t{i}", pool, n, refresh_every=10**9)
        seedb = rng.integers(0, n, (40, 2))
        r.apply_updates(insert=seedb)
        f.apply_updates(insert=seedb)
        ins = rng.integers(0, n, (25, 2))
        dels = r.buffer.live_pairs()[:5]
        upd[f"t{i}"] = (ins, dels)
        refs.append(r)
        fused[f"t{i}"] = f
    stats = ingest_group(upd, fused)
    for i, r in enumerate(refs):
        s_ref = r.apply_updates(insert=upd[f"t{i}"][0],
                                delete=upd[f"t{i}"][1])
        assert stats[f"t{i}"].n_inserted == s_ref.n_inserted
        assert stats[f"t{i}"].n_deleted == s_ref.n_deleted
    results = query_group(fused)
    for i, r in enumerate(refs):
        assert results[f"t{i}"].density == r.query().density


def test_dense_ingest_is_one_dispatch(monkeypatch):
    """ISSUE 5 satellite: the dense-bucket ingest fuses the COO scatter and
    the adjacency scatter into ONE program — counted two ways: the batch's
    dispatch counter tracks its ingest counter 1:1, and monkeypatched jit
    entry points see exactly one launch per ingest."""
    from repro.stream import fused as fused_mod

    calls = []
    real_dense = fused_mod._batched_apply_dense_jit
    real_sparse = fused_mod._batched_apply_jit
    monkeypatch.setattr(
        fused_mod, "_batched_apply_dense_jit",
        lambda *a, **k: (calls.append("dense"), real_dense(*a, **k))[1])
    monkeypatch.setattr(
        fused_mod, "_batched_apply_jit",
        lambda *a, **k: (calls.append("sparse"), real_sparse(*a, **k))[1])

    rng = np.random.default_rng(9)
    n = 80
    pool = FusedPool()
    ref = DeltaEngine(n_nodes=n, refresh_every=10**9)
    eng = FusedEngine("t0", pool, n, refresh_every=10**9)
    seedb = rng.integers(0, n, (60, 2))
    ref.apply_updates(insert=seedb)
    eng.apply_updates(insert=seedb)
    assert eng.batch.dense  # 80 nodes -> dense (GEMV) bucket
    d0 = eng.batch.n_ingest_dispatches
    calls.clear()
    for _ in range(3):
        ins = rng.integers(0, n, (16, 2))
        ref.apply_updates(insert=ins)
        eng.apply_updates(insert=ins)
    assert calls == ["dense"] * 3  # one program per ingest, no second scatter
    assert eng.batch.n_ingest_dispatches == d0 + 3
    assert eng.batch.n_ingests == eng.batch.n_ingest_dispatches
    # and the fused program's state matches the unbatched engine exactly
    q_ref, q = ref.query(), eng.query()
    assert q.density == q_ref.density
    assert np.array_equal(q.mask, q_ref.mask)
    assert q.passes == q_ref.passes


def test_ingest_group_partial_failure_stays_consistent():
    """A failing tenant mid-ingest must not leave earlier tenants' device
    lanes stale: their host buffers already committed, so the staged rows
    must still dispatch (the code-review repro: density read 3.33 instead
    of 2.0 until an unrelated resync)."""
    svc = StreamService(fused=True)
    svc.create_tenant("good", n_nodes=20)
    svc.create_tenant("bad", n_nodes=10)
    svc.apply_updates("good", insert=np.array([[0, 1], [1, 2], [0, 2]]))
    svc.density("good")
    r = svc.ingest_many({
        "good": (np.array([[2, 3], [3, 4]]), None),
        "bad": (np.array([[0, 99]]), None),   # endpoint out of range
    })
    assert not r.ok and "out of range" in r.error
    # good's host buffer committed (5 edges) AND its lane received the row
    d = svc.density("good")
    rho, mask, passes = pbahmani_np(
        svc.registry.get("good").buffer.to_graph())
    assert d.ok and d.value["density"] == pytest.approx(rho)
    m = svc.membership("good")
    assert np.array_equal(m.value["mask"], mask[:20])


def test_flush_survives_engine_failure():
    """A tenant whose query raises at flush time must not orphan the other
    pending tickets — every ticket gets a response."""
    svc = StreamService(fused=True, coalesce_window_ms=1e9)
    svc.create_tenant("ok", n_nodes=20)
    svc.apply_updates("ok", insert=np.array([[0, 1], [1, 2], [0, 2]]))
    svc.create_tenant("boom", n_nodes=20)
    eng = svc.registry.get("boom")
    def explode():
        raise ValueError("engine exploded")
    # raises inside query_group (generation -1 forces a resync there) AND
    # inside the per-tenant fallback query
    eng._resync_device = explode
    t_ok = svc.submit_density("ok")
    t_boom = svc.submit_density("boom")
    assert svc.flush() == 2
    r_ok, r_boom = svc.poll(t_ok), svc.poll(t_boom)
    assert r_ok is not None and r_ok.ok
    assert r_ok.value["density"] == pytest.approx(1.0)
    assert r_boom is not None and not r_boom.ok
    assert "exploded" in r_boom.error


def test_flush_fallback_is_counted(monkeypatch):
    """When the batched program itself fails, the flush still answers every
    ticket per tenant, and ``flush_fallback_total`` counts the fallback."""
    import repro.stream.service as service_mod
    from repro.obs.trace import Tracer, set_tracer

    def broken_group(engines):
        raise RuntimeError("batched program failed")

    tr = Tracer(profiler_bridge=False)
    prev = set_tracer(tr)
    try:
        svc = StreamService(fused=True, coalesce_window_ms=1e9)
        for name in ("a", "b"):
            svc.create_tenant(name, n_nodes=20)
            svc.apply_updates(name, insert=np.array([[0, 1], [1, 2], [0, 2]]))
        monkeypatch.setattr(service_mod, "query_group", broken_group)
        tickets = [svc.submit_density("a"), svc.submit_density("b")]
        assert svc.flush() == 2
        for t in tickets:
            r = svc.poll(t)
            assert r.ok and r.value["density"] == pytest.approx(1.0)
        fallback = tr.registry.counter("flush_fallback_total", op="flush",
                                       tenant="-")
        assert fallback.value == 1
    finally:
        set_tracer(prev)


def test_group_helpers_accept_unbatched_engines():
    """query_group / ingest_group route plain DeltaEngines through their
    own paths, so mixed fused/unfused registries work (top_k, flush)."""
    plain = DeltaEngine(n_nodes=30, refresh_every=10**9)
    plain.apply_updates(insert=np.array([[0, 1], [1, 2], [0, 2]]))
    pool = FusedPool()
    fe = FusedEngine("f", pool, 30, refresh_every=10**9)
    fe.apply_updates(insert=np.array([[4, 5]]))
    res = query_group({"plain": plain, "f": fe})
    assert res["plain"].density == pytest.approx(1.0)
    assert res["f"].density == pytest.approx(0.5)
    stats = ingest_group({"plain": (np.array([[2, 3]]), None),
                          "f": (np.array([[5, 6]]), None)},
                         {"plain": plain, "f": fe})
    assert stats["plain"].n_inserted == 1 and stats["f"].n_inserted == 1


# ---------------------------------------------------------------------------
# fused-layer spans and transfer counters
# ---------------------------------------------------------------------------
def _clique_engines(pool, n, pruned_flags, rng):
    """Fused engines of one bucket, each a clique of its own size in
    random noise; ``pruned_flags`` picks each one's path."""
    engines = {}
    for i, pruned in enumerate(pruned_flags):
        eng = FusedEngine(f"t{i}", pool, n, refresh_every=10**9,
                          pruned=pruned)
        k = 10 + i
        clique = np.array([(a, b) for a in range(k) for b in range(a + 1, k)])
        eng.apply_updates(insert=np.concatenate(
            [clique, rng.integers(0, n, (400, 2))]))
        engines[eng.name] = eng
    return engines


def test_fused_flush_phase_spans_and_pass_counts(monkeypatch):
    """A flush of a mixed pruned and warm group opens its four phase
    spans under ``fused_flush``, which counts the members by path;
    ``member_passes`` is the sum of the answers' passes and
    ``lane_passes`` the padded group lanes x passes of each batched
    peel (counted here at the two batched entry points). The answers
    stay bit-identical to solo engines fed the same edges."""
    from repro.obs.trace import Tracer, set_tracer
    from repro.stream import fused as fused_mod

    peels = []  # (padded group lanes, passes) of each batched peel
    real_bucket = fused_mod._batched_bucket_peel_jit
    real_rows = fused_mod.TenantBatch.peel_rows

    def bucket_peel(*a, **k):
        out = real_bucket(*a, **k)
        passes = np.asarray(out[2])
        peels.append((passes.size, int(passes.max())))
        return out

    def peel_rows(self, lanes, n_edges):
        final, rho = real_rows(self, lanes, n_edges)
        passes = np.asarray(final.passes)
        peels.append((passes.size, int(passes.max())))
        return final, rho

    monkeypatch.setattr(fused_mod, "_batched_bucket_peel_jit", bucket_peel)
    monkeypatch.setattr(fused_mod.TenantBatch, "peel_rows", peel_rows)
    tr = Tracer(profiler_bridge=False)
    prev = set_tracer(tr)
    try:
        engines = _clique_engines(FusedPool(), 300,
                                  [True, True, True, False, False],
                                  np.random.default_rng(3))
        results = query_group(engines)
    finally:
        set_tracer(prev)
    ring = tr.ring()
    flush = next(r for r in ring if r.name == "fused_flush")
    children = {r.name for r in ring if r.parent_id == flush.span_id}
    assert {"fused_prepare", "fused_bucket_peel", "fused_warm_peel",
            "fused_mask_write"} <= children
    assert flush.attrs["members"] == 5
    assert flush.attrs["n_warm"] == 2 and flush.attrs["n_pruned"] == 3
    assert flush.attrs["n_fallback"] == 0
    assert sum(q.pruned for q in results.values()) == 3
    assert flush.attrs["member_passes"] == sum(q.passes
                                               for q in results.values())
    assert len(peels) == 2
    assert flush.attrs["lane_passes"] == sum(g * p for g, p in peels)
    assert flush.attrs["lane_passes"] >= flush.attrs["member_passes"]
    # the reads back and the uploads are counted on the phase spans
    by_name = {r.name: r for r in ring if r.parent_id == flush.span_id}
    assert by_name["fused_bucket_peel"].attrs["h2d_bytes"] > 0
    assert by_name["fused_bucket_peel"].attrs["d2h_bytes"] > 0
    assert by_name["fused_warm_peel"].attrs["d2h_bytes"] > 0
    assert by_name["fused_mask_write"].attrs["h2d_bytes"] > 0
    for name, eng in engines.items():
        ref = DeltaEngine(n_nodes=300, refresh_every=10**9,
                          pruned=eng.pruned)
        ref.apply_updates(insert=eng.buffer.live_pairs())
        q = ref.query()
        assert q.density == results[name].density, name
        assert q.passes == results[name].passes, name
        assert np.array_equal(q.mask, results[name].mask), name


def test_fused_flush_peels_in_the_high_water_shape(monkeypatch):
    """A bucket's pruned members share one batched bucket peel in the
    widest plan shape the bucket has met, so later flushes keep that
    executable whoever they ask, until the members' plans fit
    ``BUCKET_SHRINK_HYSTERESIS`` x smaller on an axis; peeled in a wider
    bucket than their own plans, the triples stay bit-identical to solo
    engines."""
    from repro.stream import fused as fused_mod

    shapes = []  # (padded group lanes, bucket_v, bucket_e, v2, e2)
    srcs = []
    real_bucket = fused_mod._batched_bucket_peel_jit

    def bucket_peel(*a, **k):
        shapes.append((a[0].shape[0],) + tuple(a[7:11]))
        srcs.append(np.asarray(a[0]))
        return real_bucket(*a, **k)

    monkeypatch.setattr(fused_mod, "_batched_bucket_peel_jit", bucket_peel)
    rng = np.random.default_rng(5)
    engines = _clique_engines(FusedPool(), 300, [True] * 3, rng)
    query_group(engines)
    assert len(shapes) == 1
    own = shapes[0][1:]
    batch = next(iter(engines.values())).batch
    assert batch.peel_buckets([own]) == own
    wide = (2 * own[0], 2 * own[1], own[2], own[3])
    assert batch.peel_buckets([wide, own]) == wide
    for eng in engines.values():
        eng.apply_updates(insert=rng.integers(0, 300, (40, 2)))
    results = query_group(dict(list(engines.items())[:2]))
    assert shapes[1:] == [(2,) + wide]
    # the members' rows are padded with the group's sentinel, not their own
    assert np.all((srcs[1] < own[0]) | (srcs[1] == wide[0]))
    assert all(q.pruned for q in results.values())
    for name, q in results.items():
        ref = DeltaEngine(n_nodes=300, refresh_every=10**9, pruned=True)
        ref.apply_updates(insert=engines[name].buffer.live_pairs())
        r = ref.query()
        assert (r.density, r.passes) == (q.density, q.passes), name
        assert np.array_equal(r.mask, q.mask), name
    # held while the plans are within the hysteresis, shrunk past it
    assert batch.peel_buckets([own]) == wide
    small = (wide[0] // BUCKET_SHRINK_HYSTERESIS,) + wide[1:]
    assert batch.peel_buckets([small]) == small


def test_fused_ingest_counts_its_rows():
    """``fused_ingest`` carries the stack width and the batch width, and
    counts as ``h2d_bytes`` exactly the ``[T, B]`` rows it uploads (six
    int32 arrays); the bucket's ``h2d_bytes_total`` counter grows by the
    same bytes. The service's ``ingest_many`` span counts the tenants and
    the edges applied."""
    from repro.obs.trace import Tracer, set_tracer

    tr = Tracer(profiler_bridge=False)
    prev = set_tracer(tr)
    try:
        svc = StreamService(fused=True)
        for name in ("a", "b", "c"):
            svc.create_tenant(name, n_nodes=90)
            svc.apply_updates(name, insert=np.array([[0, 1], [1, 2]]))
        label = "bucket:128x256"
        counter = tr.registry.counter("h2d_bytes_total", tenant=label,
                                      engine="fused")
        before = counter.value
        rng = np.random.default_rng(4)
        resp = svc.ingest_many({
            name: (rng.integers(0, 90, (70, 2)),
                   np.array([[0, 1]]) if name == "a" else None)
            for name in ("a", "b", "c")})
    finally:
        set_tracer(prev)
    assert resp.ok
    rec = next(r for r in tr.ring() if r.name == "fused_ingest")
    assert rec.labels["tenant"] == label
    lanes, b = rec.attrs["stack_lanes"], rec.attrs["batch_width"]
    assert rec.attrs["n_lanes"] == 3 and lanes == MIN_LANES and b == 128
    assert rec.attrs["h2d_bytes"] == 6 * lanes * b * 4
    assert counter.value - before == rec.attrs["h2d_bytes"]
    op = next(r for r in tr.ring() if r.name == "service"
              and r.labels.get("op") == "ingest_many")
    assert op.attrs["n_tenants"] == 3
    assert op.attrs["n_edges"] == sum(
        s.n_inserted + s.n_deleted for s in resp.value.values())
    assert op.attrs["n_edges"] > 0


# ---------------------------------------------------------------------------
# epoch refreshes inside the fused flush
# ---------------------------------------------------------------------------
def _assert_twin(q, r, label):
    """(density, mask, passes, refreshed) of a fused answer ``q`` equal its
    solo twin's ``r``."""
    assert q.density == r.density, label
    assert np.array_equal(np.asarray(q.mask), np.asarray(r.mask)), label
    assert q.passes == r.passes, label
    assert q.refreshed == r.refreshed, label


def _pairs(rng, lo, hi, k):
    e = rng.integers(lo, hi, (k, 2))
    return e[e[:, 0] != e[:, 1]]


# name: (n_nodes, refresh_every); "d_" tenants share a dense bucket
# (V = 256), "s_" tenants a scatter bucket (V = 1024), all at 512 slots
REANCHOR_SPEC = {
    "d_stale": (150, 1), "d_mid": (150, 3), "d_fresh": (150, 10**9),
    "d_clique": (150, 4),
    "s_stale": (600, 1), "s_mid": (600, 3), "s_fresh": (600, 10**9),
}
CLIQUE = np.array([(a, b) for a in range(24) for b in range(a + 1, 24)])


def _reanchor_round(rng, name, eng):
    """One update batch for ``name``. ``d_clique`` keeps a 24-clique and
    churns only leaf pairs: its pass-0 survivors (the clique) carry 552
    lanes, more than the 512 of the largest legal bucket, so every plan of
    it falls back to the full-width peel."""
    n = REANCHOR_SPEC[name][0]
    if eng.buffer.n_edges == 0:
        if name == "d_clique":
            return np.concatenate([CLIQUE, _pairs(rng, 24, n, 20)]), None
        return _pairs(rng, 0, n, 330), None
    live = eng.buffer.live_pairs()
    if name == "d_clique":
        leaves = live[live.min(axis=1) >= 24]
        return (_pairs(rng, 24, n, 5),
                leaves[rng.choice(len(leaves), 5, replace=False)])
    return (_pairs(rng, 0, n, 20),
            live[rng.choice(len(live), 10, replace=False)])


def test_stale_members_reanchor_inside_the_flush_bit_identically():
    """Epoch refreshes ride in their bucket's flush: over 8 rounds every
    bucket's flush mixes stale tenants with fresh ones, in a dense bucket,
    in a scatter bucket, and with a member whose plan falls back at every
    re-anchor; every answer's (density, mask, passes, refreshed) equals its
    solo twin's, the per-tenant refresh and plan counters agree, and after
    the first round the auditor sees no steady-state recompile."""
    from repro.obs.audit import AUDITOR

    rng = np.random.default_rng(18)
    pool = FusedPool()
    fused, solo = {}, {}
    for name, (n, every) in REANCHOR_SPEC.items():
        fused[name] = FusedEngine(name, pool, n, refresh_every=every)
        solo[name] = DeltaEngine(n_nodes=n, refresh_every=every)
    steady = None
    mixed = 0
    for step in range(8):
        ups = {name: _reanchor_round(rng, name, solo[name]) for name in fused}
        for name, (ins, dels) in ups.items():
            solo[name].apply_updates(insert=ins, delete=dels)
        ingest_group(ups, fused)
        stale = {name for name, eng in fused.items() if eng.stale}
        for side in ("d_", "s_"):
            group = {name for name in fused if name.startswith(side)}
            mixed += bool(group & stale) and bool(group - stale)
        res = query_group(fused)
        for name, eng in fused.items():
            _assert_twin(res[name], solo[name].query(), (step, name))
            assert res[name].refreshed == (name in stale)
            assert not eng.stale
        if step == 0:
            AUDITOR.sync()
            steady = AUDITOR.n_steady_recompiles
    assert mixed == 2 * 8  # both buckets, every round
    assert fused["d_clique"].batch is fused["d_stale"].batch
    assert fused["d_stale"].batch.dense
    assert not fused["s_stale"].batch.dense
    assert fused["d_clique"].metrics.n_prune_fallbacks >= 4
    for name, eng in fused.items():
        f, s = eng.metrics, solo[name].metrics
        assert f.n_refreshes == s.n_refreshes, name
        assert f.n_prune_fallbacks == s.n_prune_fallbacks, name
        assert f.n_plan_builds == s.n_plan_builds, name
        assert f.bucket_reuses == s.bucket_reuses, name
    assert fused["d_stale"].metrics.n_refreshes == 8
    assert fused["d_fresh"].metrics.n_refreshes == 0
    AUDITOR.sync()
    assert AUDITOR.n_steady_recompiles == steady, AUDITOR.snapshot(last=8)


def _ring_children(ring, parent):
    return [r for r in ring if r.parent_id == parent.span_id]


def test_stale_member_reanchors_under_the_flush_span():
    """A stale fused member opens no ``refresh`` span of its own: the
    bucket's ``fused_flush`` counts it in ``n_reanchored``, and its
    ``fused_reanchor`` child holds the member's buffer compaction."""
    from repro.obs.trace import Tracer, set_tracer

    rng = np.random.default_rng(7)
    pool = FusedPool()
    engines = {}
    for name, every in (("a", 1), ("b", 1), ("c", 10**9)):
        engines[name] = FusedEngine(name, pool, 300, refresh_every=every)
        engines[name].apply_updates(insert=np.concatenate(
            [CLIQUE[:100], _pairs(rng, 0, 300, 300)]))
    query_group(engines)  # a and b are stale from their first batch
    ingest_group({name: (_pairs(rng, 0, 300, 30), None)
                  for name in engines}, engines)
    assert {name for name, e in engines.items() if e.stale} == {"a", "b"}
    tr = Tracer(profiler_bridge=False)
    prev = set_tracer(tr)
    try:
        results = query_group(engines)
    finally:
        set_tracer(prev)
    ring = tr.ring()
    assert not [r for r in ring if r.name == "refresh"]
    flush = next(r for r in ring if r.name == "fused_flush")
    assert flush.attrs["members"] == 3 and flush.attrs["n_reanchored"] == 2
    kids = _ring_children(ring, flush)
    assert [k.name for k in kids][0] == "fused_reanchor"
    compactions = _ring_children(ring, kids[0])
    assert [c.name for c in compactions] == ["buffer_compact"] * 2
    assert [results[n].refreshed for n in "abc"] == [True, True, False]
    assert sum(e.metrics.n_refreshes for e in engines.values()) == 2 + 2
    for name, eng in engines.items():
        ref = DeltaEngine(n_nodes=300, refresh_every=10**9)
        ref.apply_updates(insert=eng.buffer.live_pairs())
        r = ref.query()
        assert (r.density, r.passes) == (results[name].density,
                                         results[name].passes), name
        assert np.array_equal(r.mask, results[name].mask), name


@pytest.mark.parametrize("pruned", [True, False])
def test_lone_refresh_is_a_flush_of_one(pruned):
    """``FusedEngine.refresh()`` re-anchors a fresh tenant as a flush of
    one, whatever its cache holds: one ``fused_flush`` with one member,
    re-anchored, no ``refresh`` span, and the solo engine's refresh triple
    and counters."""
    from repro.obs.trace import Tracer, set_tracer

    rng = np.random.default_rng(21)
    pool = FusedPool()
    eng = FusedEngine("lone", pool, 150, refresh_every=10**9, pruned=pruned)
    ref = DeltaEngine(n_nodes=150, refresh_every=10**9, pruned=pruned)
    for ins in (np.concatenate([CLIQUE[:60], _pairs(rng, 0, 150, 200)]),
                _pairs(rng, 0, 150, 40)):
        eng.apply_updates(insert=ins)
        ref.apply_updates(insert=ins)
        _assert_twin(eng.query(), ref.query(), "query")
    dels = ref.buffer.live_pairs()[:30]
    eng.apply_updates(delete=dels)
    ref.apply_updates(delete=dels)
    _assert_twin(eng.query(), ref.query(), "query")
    tr = Tracer(profiler_bridge=False)
    prev = set_tracer(tr)
    try:
        q = eng.refresh()
    finally:
        set_tracer(prev)
    r = ref.refresh()
    _assert_twin(q, r, "refresh")
    assert q.refreshed and q.pruned == r.pruned
    assert eng.query() is q and eng._staleness == 0.0
    assert eng.metrics.n_refreshes == ref.metrics.n_refreshes == 1
    assert eng.metrics.n_queries == ref.metrics.n_queries
    ring = tr.ring()
    assert not [x for x in ring if x.name == "refresh"]
    flush = [x for x in ring if x.name == "fused_flush"]
    assert len(flush) == 1
    assert flush[0].attrs["members"] == flush[0].attrs["n_reanchored"] == 1


def test_reanchor_shrink_migrates_the_lane_into_the_flush_bucket():
    """A re-anchor whose compaction shrinks the buffer moves the lane to
    the smaller bucket (evict + join) and is answered by that bucket's
    flush, with the solo engine's refresh triple."""
    rng = np.random.default_rng(8)
    pool = FusedPool()
    eng = FusedEngine("s", pool, 200, refresh_every=3)
    ref = DeltaEngine(n_nodes=200, refresh_every=3)
    big = _pairs(rng, 0, 200, 3000)
    eng.apply_updates(insert=big)
    ref.apply_updates(insert=big)
    _assert_twin(eng.query(), ref.query(), "grown")
    wide = eng.batch
    dels = ref.buffer.live_pairs()[100:]
    eng.apply_updates(delete=dels)
    ref.apply_updates(delete=dels)
    assert eng.stale and eng._reanchor_batch() is not wide
    _assert_twin(eng.query(), ref.query(), "shrunk")
    assert eng.batch is not wide and "s" not in wide.lane_of
    assert eng.batch.edge_capacity == eng.buffer.capacity < wide.edge_capacity
    assert eng.metrics.n_buffer_shrinks == ref.metrics.n_buffer_shrinks == 1


# ---------------------------------------------------------------------------
# registry roster
# ---------------------------------------------------------------------------
def test_registry_fused_roster_and_conflicts():
    reg = GraphRegistry(fused=True, max_tenants=2)
    a = reg.register("a", n_nodes=100)
    assert isinstance(a, FusedEngine)
    a.apply_updates(insert=np.array([[0, 1], [1, 2]]))
    a.query()
    st_ = reg.stats("a")
    assert st_.fused and st_.lane >= 0 and st_.batch_lanes >= MIN_LANES
    # conflicting fused flag on re-register raises
    with pytest.raises(ValueError, match="fused"):
        reg.register("a", n_nodes=100, fused=False)
    # fused + sharded composes (ISSUE 9): accepted, placed in a sharded
    # bucket stack, with the placement surfaced in the stats
    b = reg.register("b", n_nodes=100, sharded=True)
    assert isinstance(b, FusedEngine) and b.sharded
    assert b.kind == "fused+sharded"
    b.apply_updates(insert=np.array([[0, 1], [1, 2]]))
    b.query()
    st_b = reg.stats("b")
    assert st_b.fused and st_b.sharded and st_b.placement == "fused+sharded"
    assert st_b.lane >= 0
    reg.remove("b")
    # LRU eviction releases the lane back to the bucket
    batch = a.batch
    reg.register("c", n_nodes=100)
    reg.get("c")
    reg.register("d", n_nodes=100)  # evicts "a" (LRU)
    assert "a" not in reg and "a" not in batch.lane_of
    # remove() releases too
    d = reg.get("d")
    reg.remove("d")
    assert d.batch is None


# ---------------------------------------------------------------------------
# service: error/edge paths + coalescing
# ---------------------------------------------------------------------------
def test_service_unknown_tenant_paths():
    svc = StreamService(fused=True)
    for op in (lambda: svc.density("ghost"),
               lambda: svc.membership("ghost"),
               lambda: svc.apply_updates("ghost", insert=np.array([[0, 1]])),
               lambda: svc.stats("ghost"),
               lambda: svc.ingest_many({"ghost": (np.array([[0, 1]]), None)})):
        r = op()
        assert not r.ok and "ghost" in r.error
    assert svc.metrics.n_errors == 5


def test_service_empty_graph_density():
    svc = StreamService(fused=True)
    assert svc.create_tenant("empty", n_nodes=32).ok
    d = svc.density("empty")
    assert d.ok and d.value["density"] == 0.0
    m = svc.membership("empty")
    assert m.ok and m.value["n_members"] == 0


def test_service_top_k_exceeding_tenant_count():
    svc = StreamService(fused=True)
    svc.create_tenant("x", n_nodes=50)
    svc.create_tenant("y", n_nodes=50)
    svc.apply_updates("x", insert=np.array([[0, 1], [1, 2], [0, 2]]))
    svc.apply_updates("y", insert=np.array([[3, 4]]))
    top = svc.top_k_densest(k=99)
    assert top.ok and len(top.value) == 2  # all tenants, densest first
    assert top.value[0]["tenant"] == "x"


def test_service_coalescing_window_and_flush():
    svc = StreamService(fused=True, coalesce_window_ms=1e9)
    svc.create_tenant("a", n_nodes=40)
    svc.create_tenant("b", n_nodes=40)
    svc.apply_updates("a", insert=np.array([[0, 1], [1, 2], [0, 2]]))
    svc.apply_updates("b", insert=np.array([[4, 5]]))
    ta = svc.submit_density("a")
    tb = svc.submit_density("b")
    tg = svc.submit_density("ghost")  # unknown tenant: error at flush
    assert svc.poll(ta) is None      # window still open: pending
    assert svc.flush() == 3
    ra, rb, rg = svc.poll(ta), svc.poll(tb), svc.poll(tg)
    assert ra.ok and ra.value["density"] == pytest.approx(1.0)
    assert rb.ok and rb.value["density"] == pytest.approx(0.5)
    assert not rg.ok and "ghost" in rg.error
    assert svc.poll(ta) is None      # results pop once
    # window <= 0 degenerates to flush-per-submit
    svc0 = StreamService(fused=True)
    svc0.create_tenant("a", n_nodes=40)
    svc0.apply_updates("a", insert=np.array([[0, 1]]))
    t0 = svc0.submit_density("a")
    assert svc0.poll(t0).ok


def test_service_coalescing_flush_on_shutdown():
    svc = StreamService(fused=True, coalesce_window_ms=1e9)
    svc.create_tenant("a", n_nodes=40)
    svc.apply_updates("a", insert=np.array([[0, 1], [1, 2], [0, 2]]))
    t = svc.submit_density("a")
    assert svc.poll(t) is None
    assert svc.shutdown() == 1       # pending queries answered at shutdown
    r = svc.poll(t)
    assert r is not None and r.ok and r.value["density"] == pytest.approx(1.0)
    assert svc.shutdown() == 0       # idempotent
    with pytest.raises(RuntimeError):
        svc.submit_density("a")      # no new submissions after shutdown
