"""Streaming subsystem: buffer invariants, the incremental-vs-recompute
oracle, compile-count stability, registry LRU, service front-end, stream IO.

The two load-bearing claims (ISSUE 1 acceptance criteria):
  * after ANY sequence of insert/delete batches, the incremental engine's
    density equals a from-scratch ``pbahmani_np`` recompute on the
    materialized graph (exact trajectory, not an approximation);
  * repeated same-capacity update batches trigger ZERO recompilations after
    warmup (the shape-bucketing contract).
"""
import numpy as np
import pytest

from _hyp import given, settings, st

from repro.core.cbds import cbds_np
from repro.core.pbahmani import pbahmani_np
from repro.graphs.graph import Graph
from repro.graphs.io import load_edge_stream, save_edge_stream
from repro.stream import DeltaEngine, EdgeBuffer, GraphRegistry, StreamService
from repro.stream.buffer import next_pow2


def materialize(edges: set, n_nodes: int) -> Graph:
    pairs = (np.asarray(sorted(edges), dtype=np.int64) if edges
             else np.zeros((0, 2), np.int64))
    return Graph.from_edges(pairs, n_nodes=n_nodes)


def random_stream(rng, n_nodes, n_batches, max_batch):
    """Yield (insert, delete, mirror) where mirror is the running edge set."""
    edges: set = set()
    for _ in range(n_batches):
        ins = rng.integers(0, n_nodes, (int(rng.integers(1, max_batch)), 2))
        if edges and rng.random() < 0.7:
            pool = np.asarray(sorted(edges))
            take = rng.random(len(pool)) < 0.3
            dels = pool[take]
        else:
            dels = None
        if dels is not None:
            for u, v in dels:
                edges.discard((int(u), int(v)))
        for u, v in ins:
            u, v = int(u), int(v)
            if u != v:
                edges.add((min(u, v), max(u, v)))
        yield ins, dels, edges


# ---------------------------------------------------------------------------
# EdgeBuffer
# ---------------------------------------------------------------------------
def test_buffer_insert_delete_dedup():
    buf = EdgeBuffer(n_nodes=10)
    ins, ins_slots, dele, del_slots = buf.apply(
        insert=np.array([[0, 1], [1, 0], [2, 3], [4, 4]]))
    assert buf.n_edges == 2                   # dup orientation + self-loop
    assert ins.shape == (2, 2) and ins_slots.shape == (2,)
    assert (0, 1) in buf and (1, 0) in buf and (4, 4) not in buf
    ins2, _, dele2, _ = buf.apply(insert=np.array([[0, 1]]),
                                  delete=np.array([[3, 2], [5, 6]]))
    assert ins2.shape[0] == 0                 # already present
    assert dele2.shape[0] == 1                # (5,6) absent, dropped
    assert buf.n_edges == 1


def test_buffer_device_view_sentinel_and_symmetry():
    buf = EdgeBuffer(n_nodes=10)
    buf.apply(insert=np.array([[0, 1], [2, 3]]))
    src, dst = buf.device_view()
    assert src.shape == (2 * buf.capacity,)
    valid = src < buf.sentinel
    assert valid.sum() == 2 * buf.n_edges     # symmetric pairs
    assert (dst[~valid] == buf.sentinel).all()
    pairs = set(zip(src[valid].tolist(), dst[valid].tolist()))
    assert (0, 1) in pairs and (1, 0) in pairs


def test_buffer_pow2_growth_and_generation():
    buf = EdgeBuffer(n_nodes=100, capacity=256)
    gen0 = buf.generation
    rng = np.random.default_rng(0)
    # overfill: 100-node simple graph holds at most 4950 edges
    buf.apply(insert=rng.integers(0, 100, (4000, 2)))
    assert buf.capacity == next_pow2(buf.capacity)  # stayed a power of two
    assert buf.capacity >= buf.n_edges
    assert buf.generation > gen0
    g = buf.to_graph()
    assert g.n_edges == buf.n_edges


def test_buffer_compact_preserves_graph():
    buf = EdgeBuffer(n_nodes=50)
    rng = np.random.default_rng(1)
    buf.apply(insert=rng.integers(0, 50, (200, 2)))
    pool = np.asarray(sorted(buf._slot))[::3]
    buf.apply(delete=pool)
    before = sorted(buf._slot)
    buf.epoch_compact()
    assert sorted(buf._slot) == before
    src, _ = buf.device_view()
    # compaction is hole-free: the valid prefix is dense
    assert (src[: buf.n_edges] < buf.sentinel).all()
    assert (src[buf.n_edges : buf.capacity] == buf.sentinel).all()


def test_buffer_rejects_out_of_range():
    buf = EdgeBuffer(n_nodes=10)
    with pytest.raises(ValueError):
        buf.apply(insert=np.array([[0, 10]]))


def test_buffer_epoch_shrink_with_hysteresis():
    """ISSUE 3 bugfix: capacity used to only ever grow. An epoch compact
    with shrink=True halves down to pow-2 with 2x headroom — but only below
    SHRINK_FRACTION occupancy, so stable graphs never thrash."""
    from repro.stream.buffer import MIN_CAPACITY, SHRINK_FRACTION

    buf = EdgeBuffer(n_nodes=100, capacity=1024, compact_threshold=None)
    rng = np.random.default_rng(2)
    buf.apply(insert=rng.integers(0, 100, (400, 2)))
    n_mid = buf.n_edges
    assert buf.capacity == 1024
    # above the hysteresis floor: no shrink
    assert n_mid > 1024 * SHRINK_FRACTION
    assert buf.shrink_target() is None
    assert not buf.epoch_compact(shrink=True)
    assert buf.capacity == 1024

    # contract far below the floor: shrink to next_pow2(2*live)
    pool = np.asarray(sorted(buf._slot))
    buf.apply(delete=pool[60:])
    assert buf.n_edges == 60
    before = buf.to_graph()
    gen0 = buf.generation
    assert buf.epoch_compact(shrink=True)
    assert buf.capacity == max(next_pow2(120), MIN_CAPACITY) == 256
    assert buf.generation > gen0
    after = buf.to_graph()
    assert before.n_edges == after.n_edges
    assert np.array_equal(before.src, after.src)
    # post-shrink occupancy <= 50%: the next regrow needs the graph to double
    assert buf.n_edges <= buf.capacity // 2
    # and the buffer still works: inserts land in the shrunken slot space
    buf.apply(insert=np.array([[0, 99]]))
    assert (0, 99) in buf


def test_buffer_tombstone_autocompact():
    """ISSUE 3 bugfix: delete-heavy streams fragment the slot space with no
    compaction threshold. When un-recycled holes exceed compact_threshold
    the buffer compacts mid-stream and bumps generation (so engines resync
    and executables re-bucket)."""
    buf = EdgeBuffer(n_nodes=100, capacity=256, compact_threshold=0.3)
    rng = np.random.default_rng(3)
    buf.apply(insert=rng.integers(0, 100, (250, 2)))
    n0 = buf.n_edges
    gen0 = buf.generation
    pool = np.asarray(sorted(buf._slot))
    buf.apply(delete=pool[: n0 - 50])  # way past 0.3 * 256 holes
    assert buf.generation > gen0                 # compaction happened
    assert buf.tombstone_fraction == 0.0         # holes cleared
    src, _ = buf.device_view()
    assert (src[: buf.n_edges] < buf.sentinel).all()   # dense prefix
    assert (src[buf.n_edges: buf.capacity] == buf.sentinel).all()

    # holes below the threshold leave the layout alone (O(batch) contract)
    buf2 = EdgeBuffer(n_nodes=100, capacity=256, compact_threshold=0.5)
    buf2.apply(insert=rng.integers(0, 100, (100, 2)))
    gen1 = buf2.generation
    pool2 = np.asarray(sorted(buf2._slot))
    buf2.apply(delete=pool2[:20])
    assert buf2.generation == gen1
    assert buf2.tombstone_fraction > 0.0

    # threshold=None disables mid-stream compaction entirely
    buf3 = EdgeBuffer(n_nodes=100, capacity=256, compact_threshold=None)
    buf3.apply(insert=rng.integers(0, 100, (250, 2)))
    gen3 = buf3.generation
    buf3.apply(delete=np.asarray(sorted(buf3._slot)))
    assert buf3.generation == gen3


def test_buffer_hole_reuse_keeps_fragmentation_low():
    """Freed slots recycle before fresh ones, so churn (delete+insert in
    one batch) leaves no tombstones behind."""
    buf = EdgeBuffer(n_nodes=100, capacity=256)
    buf.apply(insert=np.array([[0, 1], [1, 2], [2, 3]]))
    buf.apply(delete=np.array([[0, 1]]), insert=np.array([[4, 5]]))
    assert buf.tombstone_fraction == 0.0
    assert buf.n_edges == 3


class _PlainSlots:
    """The slot layout spelled out one pair at a time: deletes first, in
    batch order; inserts deduplicated in (u, v) order, each taking the
    newest hole, else the lowest never-used slot; a compaction lays the
    live pairs out sorted from slot 0."""

    def __init__(self, n, capacity):
        self.n, self.capacity = n, capacity
        self.slot, self.holes = {}, []
        self.fresh = list(range(capacity - 1, -1, -1))

    def apply(self, insert, delete):
        dels, ins = [], []
        for u, v in (delete if delete is not None else []):
            key = (min(int(u), int(v)), max(int(u), int(v)))
            if key[0] != key[1] and key in self.slot:
                s = self.slot.pop(key)
                self.holes.append(s)
                dels.append((key, s))
        keys = sorted({(min(int(u), int(v)), max(int(u), int(v)))
                       for u, v in (insert if insert is not None else [])
                       if u != v})
        new = [k for k in keys if k not in self.slot]
        if len(self.slot) + len(new) > self.capacity:
            grown = max(next_pow2(len(self.slot) + len(new)),
                        2 * self.capacity)
            self.fresh = list(range(grown - 1, self.capacity - 1, -1)) + \
                self.fresh
            self.capacity = grown
        for k in new:
            s = self.holes.pop() if self.holes else self.fresh.pop()
            self.slot[k] = s
            ins.append((k, s))
        return ins, dels

    def compact(self):
        self.slot = {k: i for i, k in enumerate(sorted(self.slot))}
        self.fresh = list(range(self.capacity - 1, len(self.slot) - 1, -1))
        self.holes = []

    def arrays(self):
        u = np.full(self.capacity, self.n, np.int32)
        v = np.full(self.capacity, self.n, np.int32)
        for (a, b), s in self.slot.items():
            u[s], v[s] = a, b
        return u, v


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_buffer_slot_layout_matches_plain_model(seed):
    """The batch-at-once buffer hands out exactly the slots of the
    one-pair-at-a-time model, through growth, churn and compaction: the
    engine patches device lanes by these slot numbers."""
    rng = np.random.default_rng(seed)
    n = 40
    buf = EdgeBuffer(n_nodes=n, capacity=256, compact_threshold=None)
    model = _PlainSlots(n, 256)
    for step in range(30):
        ins = rng.integers(0, n, (int(rng.integers(0, 200)), 2))
        live = sorted(model.slot)
        dels = None
        if live and rng.random() < 0.8:
            pick = rng.integers(0, len(live), int(rng.integers(1, 60)))
            dels = np.concatenate([np.asarray(live)[pick][:, ::-1],
                                   rng.integers(0, n, (4, 2))])
        got = buf.apply(insert=ins, delete=dels)
        want_ins, want_del = model.apply(ins, dels)
        assert [tuple(p) for p in got[0].tolist()] == [k for k, _ in want_ins]
        assert got[1].tolist() == [s for _, s in want_ins]
        assert [tuple(p) for p in got[2].tolist()] == [k for k, _ in want_del]
        assert got[3].tolist() == [s for _, s in want_del]
        if step % 7 == 6:
            buf.epoch_compact()
            model.compact()
        u, v = buf.host_view()
        mu, mv = model.arrays()
        assert buf.capacity == model.capacity, step
        assert np.array_equal(u, mu) and np.array_equal(v, mv), step
        assert buf.n_edges == len(model.slot)


# ---------------------------------------------------------------------------
# DeltaEngine: the incremental == from-scratch oracle
# ---------------------------------------------------------------------------
@settings(max_examples=5, deadline=None)
@given(st.integers(0, 10_000))
def test_engine_matches_cold_recompute(seed):
    """Acceptance criterion: after any randomized insert/delete sequence the
    engine's density/mask/passes equal pbahmani_np on the materialized
    graph. refresh_every=4 exercises warm AND epoch-refresh paths."""
    rng = np.random.default_rng(seed)
    n = 200
    eng = DeltaEngine(n_nodes=n, refresh_every=4)
    for step, (ins, dels, edges) in enumerate(
            random_stream(rng, n, n_batches=10, max_batch=60)):
        eng.apply_updates(insert=ins, delete=dels)
        assert eng.n_edges == len(edges)
        q = eng.query()
        rho, mask, passes = pbahmani_np(materialize(edges, n))
        assert q.density == pytest.approx(rho, rel=1e-6, abs=1e-9), (
            f"step {step} refreshed={q.refreshed}")
        assert q.passes == passes
        assert np.array_equal(q.mask, mask)
        assert q.warm_density >= q.density - 1e-9


def test_engine_maintained_degrees_exact():
    """Incrementally-maintained degrees == recomputed degrees (the property
    that makes the warm peel bit-identical to a cold start)."""
    rng = np.random.default_rng(3)
    n = 150
    eng = DeltaEngine(n_nodes=n, refresh_every=10**9)
    for ins, dels, edges in random_stream(rng, n, n_batches=8, max_batch=50):
        eng.apply_updates(insert=ins, delete=dels)
        g = materialize(edges, n)
        expect = np.zeros(eng.node_capacity, np.int32)
        expect[:n] = g.degrees()
        assert np.array_equal(np.asarray(eng._deg), expect)


def test_engine_empty_and_deletion_to_empty():
    eng = DeltaEngine(n_nodes=20)
    assert eng.query().density == 0.0
    eng.apply_updates(insert=np.array([[0, 1], [1, 2], [0, 2]]))
    assert eng.query().density == pytest.approx(1.0)
    eng.apply_updates(delete=np.array([[0, 1], [1, 2], [0, 2]]))
    q = eng.query()
    assert q.density == 0.0 and q.mask.sum() == 0


def test_engine_cbds_matches_np():
    rng = np.random.default_rng(5)
    n = 120
    eng = DeltaEngine(n_nodes=n)
    edges = None
    for ins, dels, edges in random_stream(rng, n, n_batches=5, max_batch=80):
        eng.apply_updates(insert=ins, delete=dels)
    res = eng.cbds()
    ref = cbds_np(materialize(edges, n))
    assert res["density"] == pytest.approx(ref["density"], rel=1e-5)


def test_engine_zero_recompiles_after_warmup():
    """Acceptance criterion: repeated same-capacity update batches hit the
    jit caches — DeltaEngine.compile_count() must not move."""
    rng = np.random.default_rng(7)
    eng = DeltaEngine(n_nodes=500, capacity=4096, refresh_every=10**9)
    # warmup: compile the batch shape + the warm peel once
    eng.apply_updates(insert=rng.integers(0, 500, (48, 2)))
    eng.query()
    before = DeltaEngine.compile_count()
    for _ in range(12):
        ins = rng.integers(0, 500, (30, 2))
        dels = np.asarray(sorted(eng.buffer._slot))[:10]
        eng.apply_updates(insert=ins, delete=dels)
        eng.query()
    assert DeltaEngine.compile_count() == before, "hot path recompiled"


def test_engine_query_memoized_until_update():
    eng = DeltaEngine(n_nodes=30)
    eng.apply_updates(insert=np.array([[0, 1], [1, 2], [0, 2]]))
    q1 = eng.query()
    assert eng.query() is q1          # unchanged graph: cached result
    assert eng.metrics.n_queries == 1  # cache hits do no work
    eng.apply_updates(insert=np.array([[2, 3]]))
    q2 = eng.query()
    assert q2 is not q1               # updates invalidate the cache


def test_staleness_weighted_by_deleted_fraction():
    """ROADMAP follow-up: delete-dominated streams age the epoch faster
    (tombstone holes are what the compaction cleans up), while insert-only
    streams keep the historical one-per-batch cadence exactly."""
    # insert-only: refresh lands on the refresh_every-th batch, as before
    eng = DeltaEngine(n_nodes=50, refresh_every=4)
    for i in range(3):
        eng.apply_updates(insert=np.array([[i, i + 1]]))
        assert not eng.stale
    eng.apply_updates(insert=np.array([[10, 11]]))
    assert eng.stale
    q = eng.query()
    assert q.refreshed and not eng.stale

    # delete-dominated: an all-delete batch weighs 1 + DELETE_STALENESS_WEIGHT
    from repro.stream.delta import DELETE_STALENESS_WEIGHT

    eng2 = DeltaEngine(n_nodes=50, refresh_every=4)
    eng2.apply_updates(insert=np.array([[i, i + 1] for i in range(8)]))
    assert not eng2.stale
    eng2.apply_updates(delete=np.array([[0, 1], [1, 2]]))
    assert eng2._staleness == pytest.approx(2.0 + DELETE_STALENESS_WEIGHT)
    assert eng2.stale  # 2 batches instead of 4
    assert eng2.query().refreshed

    # no-op deletes (absent edges) are dropped: weight stays the insert-only 1
    eng3 = DeltaEngine(n_nodes=50, refresh_every=4)
    eng3.apply_updates(insert=np.array([[0, 1]]))
    eng3.apply_updates(delete=np.array([[30, 31]]))
    assert eng3._staleness == pytest.approx(2.0)
    # mixed batch: weight interpolates by the deleted-edge fraction
    eng3.apply_updates(insert=np.array([[2, 3], [3, 4], [4, 5]]),
                      delete=np.array([[0, 1]]))
    assert eng3._staleness == pytest.approx(
        3.0 + DELETE_STALENESS_WEIGHT * 0.25)


def test_engine_grow_shrink_grow_roundtrip():
    """ISSUE 3 acceptance: a grow -> shrink -> grow cycle returns correct
    results at every step, and revisited capacities are jit-cache hits —
    zero recompiles once every steady-state shape has been seen."""
    rng = np.random.default_rng(19)
    n = 256
    eng = DeltaEngine(n_nodes=n, capacity=256, refresh_every=10**9,
                      pruned=False)
    edges: set = set()

    def feed(k):
        """Insert k fresh edges in batches of <=48 (one padded batch shape)."""
        added = 0
        while added < k:
            ins = rng.integers(0, n, (48, 2))
            for u, v in ins:
                u, v = int(u), int(v)
                if u != v:
                    edges.add((min(u, v), max(u, v)))
            eng.apply_updates(insert=ins)
            added += 48

    def drop_to(k):
        pool = np.asarray(sorted(edges))
        dels = pool[k:]
        for u, v in dels:
            edges.discard((int(u), int(v)))
        for i in range(0, len(dels), 48):
            eng.apply_updates(delete=dels[i: i + 48])

    def check():
        q = eng.query()
        rho, mask, passes = pbahmani_np(materialize(edges, n))
        assert q.density == pytest.approx(rho, rel=1e-6, abs=1e-9)
        assert np.array_equal(q.mask, mask) and q.passes == passes

    # grow phase: visit capacities 256 -> 512 -> 1024, warming the query
    # AND refresh executables at each
    for target in (200, 400, 800):
        feed(target - len(edges))
        check()
        eng.refresh()
        check()
    assert eng.buffer.capacity == 1024
    caps_seen = DeltaEngine.compile_count()

    # shrink: contract to 120 live edges; the refresh compacts + halves
    drop_to(120)
    check()                      # pre-shrink query at peak capacity
    q = eng.refresh()            # epoch refresh triggers the shrink
    assert eng.buffer.capacity == 256, eng.buffer.capacity
    assert eng.metrics.n_buffer_shrinks == 1
    rho, mask, passes = pbahmani_np(materialize(edges, n))
    assert q.density == pytest.approx(rho, rel=1e-6, abs=1e-9)
    assert np.array_equal(q.mask, mask) and q.passes == passes
    check()

    # regrow through the same capacities: every shape is a cache hit
    feed(700 - len(edges))
    check()
    eng.refresh()
    check()
    assert eng.buffer.capacity == 1024
    assert DeltaEngine.compile_count() == caps_seen, (
        "revisited capacities recompiled")


def test_engine_delete_heavy_capacity_bound():
    """ISSUE 3 acceptance: a delete-heavy stream shrinking a tenant from
    2^16 to 2^10 live edges must end with buffer capacity <= 4x live size,
    with query results unchanged."""
    rng = np.random.default_rng(23)
    n = 4096
    pairs = rng.integers(0, n, (90_000, 2)).astype(np.int64)
    u = np.minimum(pairs[:, 0], pairs[:, 1])
    v = np.maximum(pairs[:, 0], pairs[:, 1])
    keep = u != v
    pairs = np.unique(np.stack([u[keep], v[keep]], axis=1), axis=0)
    assert pairs.shape[0] >= 2**16
    pairs = pairs[: 2**16]

    eng = DeltaEngine(n_nodes=n, refresh_every=10**9)
    eng.apply_updates(insert=pairs)
    assert eng.n_edges == 2**16
    assert eng.buffer.capacity == 2**16

    # delete down to 2^10 live edges (chunked: one padded batch shape)
    dels = pairs[2**10:]
    for i in range(0, len(dels), 8192):
        eng.apply_updates(delete=dels[i: i + 8192])
    assert eng.n_edges == 2**10
    q_before = eng.query()

    q_after = eng.refresh()      # epoch refresh compacts + shrinks
    live = eng.n_edges
    assert eng.buffer.capacity <= 4 * live, (eng.buffer.capacity, live)
    assert eng.metrics.n_buffer_shrinks >= 1
    # query results unchanged by the shrink
    assert q_after.density == q_before.density
    assert np.array_equal(q_after.mask, q_before.mask)
    assert q_after.passes == q_before.passes
    rho, mask, passes = pbahmani_np(eng.buffer.to_graph())
    assert q_after.density == pytest.approx(rho, rel=1e-6, abs=1e-9)
    assert np.array_equal(q_after.mask, mask[:n]) and q_after.passes == passes


def test_engine_tombstone_autocompact_resyncs():
    """A delete-only stream that crosses the tombstone threshold forces a
    mid-stream compaction; the engine detects the generation bump, resyncs
    device state whole, and queries stay exact."""
    rng = np.random.default_rng(29)
    n = 128
    eng = DeltaEngine(n_nodes=n, capacity=256, refresh_every=10**9)
    # ~235 distinct edges: stays within the 256-slot capacity, so the 0.5
    # threshold is 128 holes — crossed by the delete chunks below
    ins = rng.integers(0, n, (240, 2))
    eng.apply_updates(insert=ins)
    edges = set(eng.buffer._slot)
    n0 = len(edges)
    assert eng.buffer.capacity == 256
    pool = np.asarray(sorted(edges))
    dels = pool[: n0 - 40]
    saw_compact = False
    for i in range(0, len(dels), 50):
        chunk = dels[i: i + 50]
        st_ = eng.apply_updates(delete=chunk)
        for u, v in chunk:
            edges.discard((int(u), int(v)))
        saw_compact = saw_compact or st_.regrew
        q = eng.query()
        rho, mask, passes = pbahmani_np(materialize(edges, n))
        assert q.density == pytest.approx(rho, rel=1e-6, abs=1e-9)
        assert np.array_equal(q.mask, mask) and q.passes == passes
    assert saw_compact, "tombstone threshold never fired"
    assert eng.buffer.tombstone_fraction <= 0.5


def test_engine_epoch_refresh_resyncs():
    rng = np.random.default_rng(11)
    n = 100
    eng = DeltaEngine(n_nodes=n, refresh_every=3)
    edges = None
    for i, (ins, dels, edges) in enumerate(
            random_stream(rng, n, n_batches=7, max_batch=40)):
        eng.apply_updates(insert=ins, delete=dels)
    assert eng.stale
    q = eng.query()
    assert q.refreshed
    assert not eng.stale
    rho, _, _ = pbahmani_np(materialize(edges, n))
    assert q.density == pytest.approx(rho, rel=1e-6, abs=1e-9)
    assert eng.metrics.n_refreshes == 1


# ---------------------------------------------------------------------------
# GraphRegistry
# ---------------------------------------------------------------------------
def test_registry_register_get_lru_eviction():
    reg = GraphRegistry(max_tenants=2)
    reg.register("a", n_nodes=100)
    reg.register("b", n_nodes=200)
    reg.get("a")                      # touch: b becomes LRU
    reg.register("c", n_nodes=300)    # evicts b
    assert "a" in reg and "c" in reg and "b" not in reg
    assert reg.evictions == 1
    with pytest.raises(KeyError):
        reg.get("b")


def test_registry_reregister_conflict():
    reg = GraphRegistry()
    reg.register("t", n_nodes=100)
    assert reg.register("t", n_nodes=100) is reg.get("t")  # idempotent
    with pytest.raises(ValueError, match="already registered"):
        reg.register("t", n_nodes=5000)
    svc = StreamService()
    svc.create_tenant("t", n_nodes=100)
    r = svc.create_tenant("t", n_nodes=5000)
    assert not r.ok and "already registered" in r.error


def test_registry_bucketing_shares_executables():
    """Tenants bucketed to the same (node, edge, batch) capacities add zero
    compiled executables — the point of pow-2 normalization."""
    rng = np.random.default_rng(13)
    reg = GraphRegistry(max_tenants=8)
    a = reg.register("a", n_nodes=500, capacity=2048)
    a.apply_updates(insert=rng.integers(0, 500, (40, 2)))
    a.query()
    before = DeltaEngine.compile_count()
    for name, n in (("b", 400), ("c", 300), ("d", 257)):
        e = reg.register(name, n_nodes=n, capacity=2048)  # all bucket to 512
        assert e.node_capacity == 512
        e.apply_updates(insert=rng.integers(0, n, (40, 2)))
        e.query()
    assert DeltaEngine.compile_count() == before


def test_registry_stats():
    reg = GraphRegistry()
    eng = reg.register("t", n_nodes=100)
    eng.apply_updates(insert=np.array([[0, 1], [1, 2]]))
    eng.query()
    st_ = reg.stats("t")
    assert st_.n_edges == 2 and st_.n_update_batches == 1
    assert st_.n_queries == 1 and st_.node_capacity == 128


# ---------------------------------------------------------------------------
# StreamService
# ---------------------------------------------------------------------------
def test_service_end_to_end():
    svc = StreamService(max_tenants=4)
    assert svc.create_tenant("us", n_nodes=100).ok
    assert svc.create_tenant("eu", n_nodes=100).ok
    # a triangle in us, a single edge in eu
    assert svc.apply_updates("us", insert=np.array([[0, 1], [1, 2], [0, 2]])).ok
    assert svc.apply_updates("eu", insert=np.array([[5, 6]])).ok
    d = svc.density("us")
    assert d.ok and d.value["density"] == pytest.approx(1.0)
    m = svc.membership("us")
    assert m.ok and m.value["n_members"] == 3
    top = svc.top_k_densest(k=1)
    assert top.ok and top.value[0]["tenant"] == "us"
    s = svc.stats()
    assert s.ok and len(s.value) == 2
    assert svc.metrics.n_requests >= 7 and svc.metrics.n_errors == 0


def test_service_structured_errors():
    svc = StreamService()
    r = svc.density("nope")
    assert not r.ok and "nope" in r.error and r.latency_ms >= 0
    svc.create_tenant("t", n_nodes=10)
    r2 = svc.apply_updates("t", insert=np.array([[0, 99]]))
    assert not r2.ok and "out of range" in r2.error
    assert svc.metrics.n_errors == 2


# ---------------------------------------------------------------------------
# edge-stream IO
# ---------------------------------------------------------------------------
def test_edge_stream_roundtrip(tmp_path):
    rng = np.random.default_rng(17)
    n = 60
    events, edges = [], set()
    for _ in range(300):
        u, v = int(rng.integers(0, n)), int(rng.integers(0, n))
        if u == v:
            continue
        key = (min(u, v), max(u, v))
        if key in edges and rng.random() < 0.4:
            events.append(("-", u, v))
            edges.discard(key)
        else:
            events.append(("+", u, v))
            edges.add(key)
    path = str(tmp_path / "stream.txt")
    save_edge_stream(events, path)

    eng = DeltaEngine(n_nodes=n)
    for ins, dels in load_edge_stream(path, batch_size=64):
        eng.apply_updates(insert=ins, delete=dels)
    assert eng.n_edges == len(edges)
    rho, _, _ = pbahmani_np(materialize(edges, n))
    assert eng.query().density == pytest.approx(rho, rel=1e-6, abs=1e-9)


def test_edge_stream_intra_batch_net(tmp_path):
    path = str(tmp_path / "s.txt")
    save_edge_stream([("+", 0, 1), ("-", 0, 1), ("-", 2, 3), ("+", 2, 3)],
                     path)
    batches = list(load_edge_stream(path, batch_size=100))
    assert len(batches) == 1
    ins, dels = batches[0]
    assert [tuple(e) for e in ins.tolist()] == [(2, 3)]   # last op wins
    assert [tuple(e) for e in dels.tolist()] == [(0, 1)]


def test_edge_stream_bare_rows_are_inserts(tmp_path):
    path = tmp_path / "snap.txt"
    path.write_text("# comment\n0 1\n1 2\n+ 2 3\n")
    (ins, dels), = load_edge_stream(str(path))
    assert ins.shape[0] == 3 and dels.shape[0] == 0
