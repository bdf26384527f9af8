"""repro.obs: exact-rank quantiles, span nesting/rings, JSONL round-trip,
recompile audit attribution — and the hard invariant that instrumentation
is host-side only: with tracing enabled, every engine path (warm, pruned,
fused, refined) returns bit-identical results and audits zero steady-state
recompiles."""
import json
import math

import numpy as np
import pytest

from _hyp import given, settings, st

from repro.core.pbahmani import pbahmani_np
from repro.graphs.graph import Graph
from repro.obs import (
    AUDITOR,
    Histogram,
    MetricsRegistry,
    RecompileAuditor,
    SpanRecord,
    Tracer,
    prometheus_text,
    read_jsonl,
    set_tracer,
    snapshot,
)
from repro.stream import DeltaEngine, StreamService


def materialize(edges: set, n_nodes: int) -> Graph:
    arr = (np.asarray(sorted(edges), dtype=np.int64)
           if edges else np.zeros((0, 2), np.int64))
    return Graph.from_edges(arr, n_nodes=n_nodes)


# ---------------------------------------------------------------------------
# metrics: exact-rank quantiles
# ---------------------------------------------------------------------------
def _oracle_quantile(values, p, bounds):
    """Sorted-list oracle: the rank-ceil(p*n) order statistic, snapped up to
    its bucket's upper edge (the histogram's resolution guarantee)."""
    xs = sorted(values)
    x = xs[max(1, math.ceil(p * len(xs))) - 1]
    for b in bounds:
        if x <= b:
            return b
    return max(xs)


@settings(max_examples=60, deadline=None)
@given(
    values=st.lists(
        st.floats(min_value=1e-4, max_value=1e4, allow_nan=False,
                  allow_infinity=False),
        min_size=1, max_size=200),
    p=st.sampled_from([0.5, 0.9, 0.95, 0.99, 1.0]),
)
def test_quantile_matches_sorted_oracle(values, p):
    h = Histogram("t_ms", {})
    for v in values:
        h.observe(v)
    assert h.quantile(p) == _oracle_quantile(values, p, h.bounds)
    assert h.total == len(values)
    assert h.sum == pytest.approx(sum(values))


def test_quantile_overflow_and_empty():
    h = Histogram("t_ms", {})
    assert h.quantile(0.99) is None
    big = max(h.bounds) * 10
    h.observe(big)
    # the overflow bucket has no upper edge: report the max observed
    assert h.quantile(0.5) == big
    assert h.max_value == big


def test_histogram_merged_is_exact_bucket_sum():
    a, b = Histogram("q_ms", {}), Histogram("q_ms", {})
    rng = np.random.default_rng(7)
    va = rng.uniform(0.01, 100.0, 50)
    vb = rng.uniform(0.01, 100.0, 70)
    for v in va:
        a.observe(v)
    for v in vb:
        b.observe(v)
    m = a.merged(b)
    assert m.total == 120
    assert m.counts == [x + y for x, y in zip(a.counts, b.counts)]
    assert m.quantile(0.5) == _oracle_quantile(
        list(va) + list(vb), 0.5, a.bounds)


def test_registry_labels_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("peel_passes_total", tenant="eu").inc(3)
    reg.counter("peel_passes_total", tenant="us").inc(5)
    assert reg.counter("peel_passes_total", tenant="eu").value == 3
    reg.gauge("certified_gap", tenant="eu").set(0.01)
    reg.histogram("query_ms", tenant="eu").observe(1.5)
    snap = reg.snapshot()
    assert {c["labels"]["tenant"] for c in snap["counters"]} == {"eu", "us"}
    assert snap["histograms"][0]["count"] == 1
    # find() filters by label subset; merged_histogram sums matching series
    assert len(reg.find("peel_passes_total")) == 2
    reg.histogram("query_ms", tenant="eu", engine="fused").observe(3.0)
    merged = reg.merged_histogram("query_ms", tenant="eu")
    assert merged.total == 2


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("peel_passes_total", tenant="eu").inc(4)
    h = reg.histogram("query_ms", tenant="eu")
    h.observe(0.5)
    h.observe(2.0)
    text = prometheus_text(reg)
    assert '# TYPE peel_passes_total counter' in text
    assert 'peel_passes_total{tenant="eu"} 4' in text
    assert '# TYPE query_ms histogram' in text
    assert 'query_ms_bucket{le="+Inf",tenant="eu"} 2' in text
    assert 'query_ms_count{tenant="eu"} 2' in text
    # bucket series are cumulative and end at the total count
    counts = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
              if line.startswith("query_ms_bucket")]
    assert counts == sorted(counts) and counts[-1] == 2


# ---------------------------------------------------------------------------
# trace: nesting, ring bounds, disabled fast path, JSONL
# ---------------------------------------------------------------------------
def test_span_nesting_and_ring_bound():
    tr = Tracer(ring_size=8, profiler_bridge=False)
    with tr.span("query", tenant="a") as outer:
        with tr.span("refine", tenant="a") as inner:
            inner.set("refine_rounds", 2)
    recs = tr.ring()
    assert [r.name for r in recs] == ["refine", "query"]  # inner exits first
    assert recs[0].parent_id == recs[1].span_id
    assert recs[0].depth == 1 and recs[1].depth == 0
    assert recs[0].attrs["refine_rounds"] == 2
    assert recs[1].duration_ms >= recs[0].duration_ms
    for i in range(20):
        with tr.span("q"):
            pass
    assert len(tr.ring()) == 8  # bounded: deque drops the oldest


def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    s1 = tr.span("query", tenant="a")
    s2 = tr.span("other")
    assert s1 is s2  # shared singleton: no allocation per span
    with s1 as sp:
        sp.set("passes", 3)
        assert sp.elapsed_ms == 0.0
    assert tr.ring() == []
    assert tr.registry.metrics() == []


def test_span_metrics_feed_and_first_call_split():
    tr = Tracer(profiler_bridge=False)
    with tr.span("query", tenant="a", engine="delta") as sp:
        sp.set("passes", 5).set("compiled", True)
    with tr.span("query", tenant="a", engine="delta") as sp:
        sp.set("passes", 2).set("certified_skip", True)
    reg = tr.registry
    assert reg.counter("peel_passes_total", tenant="a",
                       engine="delta").value == 7
    # compiled spans land in the first-call histogram, steady ones apart
    assert reg.histogram("query_first_call_ms", tenant="a",
                         engine="delta").total == 1
    assert reg.histogram("query_ms", tenant="a", engine="delta").total == 1
    assert reg.counter("first_calls_total", tenant="a",
                       engine="delta").value == 1
    assert reg.counter("certified_skips_total", tenant="a",
                       engine="delta").value == 1


def test_jsonl_round_trip(tmp_path):
    path = str(tmp_path / "events.jsonl")
    tr = Tracer(jsonl_path=path, profiler_bridge=False)
    with tr.span("query", tenant="a") as sp:
        sp.set("passes", 4).set("density", 2.5)
    with tr.span("ingest", tenant="b"):
        pass
    tr.close()
    recs = read_jsonl(path)
    assert [r.to_json() for r in recs] == [r.to_json() for r in tr.ring()]
    assert recs[0].attrs == {"passes": 4, "density": 2.5}
    # every line is plain JSON (scrapeable without repro installed)
    with open(path) as f:
        assert all(json.loads(line) for line in f)


# ---------------------------------------------------------------------------
# audit: attribution and steady-state classification
# ---------------------------------------------------------------------------
class _FakeJit:
    def __init__(self):
        self.n = 0
        self.__name__ = "fake_jit"

    def _cache_size(self):
        return self.n


def test_auditor_attribution_and_steady_classification():
    fj = _FakeJit()
    aud = RecompileAuditor()
    aud.register_provider(lambda: [fj])
    aud.sync()
    # first compile under a fresh key: warmup, not steady
    fj.n += 1
    assert aud.record("t1", "query", (64, 128)) is True
    assert aud.audited_steady_recompiles == 0
    # no growth: not compiled
    assert aud.record("t1", "query", (64, 128)) is False
    # growth under the SAME key: a steady-state recompile, attributed
    fj.n += 2
    assert aud.record("t1", "query", (64, 128)) is True
    assert aud.audited_steady_recompiles == 2
    rec = aud.steady_records()[-1]
    assert (rec.tenant, rec.op, rec.fn) == ("t1", "query", "fake_jit")
    # a new shape is a fresh key again (legitimate warmup)
    fj.n += 1
    assert aud.record("t1", "query", (64, 256)) is True
    assert aud.audited_steady_recompiles == 2
    # sync() absorbs foreign growth without attributing it
    fj.n += 5
    aud.sync()
    before = aud.n_compiles
    assert aud.record("t2", "ingest", (8,)) is False
    assert aud.n_compiles == before
    assert aud.total_compile_count() == fj.n
    snap = aud.snapshot()
    assert snap["audited_steady_recompiles"] == 2
    assert any(r["steady"] for r in snap["records"])


# ---------------------------------------------------------------------------
# the hard invariant: tracing changes nothing
# ---------------------------------------------------------------------------
@pytest.fixture
def fresh_tracer(tmp_path):
    """Isolated default tracer (fresh ring/registry + JSONL) so engine spans
    in this module don't leak across tests; restores the previous one."""
    tr = Tracer(jsonl_path=str(tmp_path / "trace.jsonl"),
                profiler_bridge=False)
    prev = set_tracer(tr)
    yield tr
    set_tracer(prev)


def _drive(eng, rng, n, refine_every=0):
    edges = set()
    results = []
    for it in range(8):
        batch = rng.integers(0, n, size=(12, 2), dtype=np.int64)
        eng.apply_updates(insert=batch)
        for u, v in batch:
            if u != v:
                edges.add((min(u, v), max(u, v)))
        refine = refine_every and it % refine_every == 0
        q = eng.query(refine=True) if refine else eng.query()
        results.append((set(edges), q))
    return results


# the phase spans each kind of engine opens under its ingest and queries
PHASE_SPANS = {
    "warm": {"ingest", "buffer_apply", "ingest_dispatch", "engine_resync",
             "query", "peel_full"},
    "pruned": {"ingest", "buffer_apply", "ingest_dispatch", "engine_resync",
               "query", "prune_plan", "prune_prepare", "prune_peel",
               "prune_merge"},
    "refined": {"ingest", "buffer_apply", "ingest_dispatch", "query",
                "refine", "prune_prepare", "prune_peel", "prune_merge"},
}


@pytest.mark.parametrize("tracing", [True, False],
                         ids=["tracer_on", "tracer_off"])
@pytest.mark.parametrize("kind", ["warm", "pruned", "refined"])
def test_engine_oracle_parity_with_tracing_enabled(fresh_tracer, kind,
                                                    tracing):
    """Bit-identity against the numpy oracle with spans recording on every
    op (phase spans included), and with the tracer off; zero audited
    steady-state recompiles over the steady window either way."""
    fresh_tracer.enabled = tracing
    rng = np.random.default_rng(hash(kind) % 2**32)
    n = 48
    eng = DeltaEngine(n, pruned=(kind != "warm"))
    eng.tenant = f"oracle-{kind}"
    steady_before = AUDITOR.audited_steady_recompiles
    results = _drive(eng, rng, n, refine_every=2 if kind == "refined" else 0)
    for edges, q in results:
        rho, _, passes = pbahmani_np(materialize(edges, n))
        if q.certificate is None:
            assert q.density == pytest.approx(rho, rel=1e-6, abs=1e-9)
            assert q.passes == passes
        else:
            # certified: never below the exact peel's density
            assert q.certificate.density >= rho - 1e-9
    assert AUDITOR.audited_steady_recompiles == steady_before, (
        f"steady recompiles: {AUDITOR.steady_records()}")
    ring = fresh_tracer.ring()
    if not tracing:
        assert ring == [] and fresh_tracer.registry.metrics() == []
        return
    assert PHASE_SPANS[kind] <= {r.name for r in ring}
    assert all(r.labels["tenant"] == f"oracle-{kind}" for r in ring)


def test_fused_parity_and_service_snapshot(fresh_tracer):
    """Fused service under tracing: per-tenant results match solo engines
    bit for bit, metrics_snapshot() carries the SLO surface, and the audit
    reports zero steady recompiles for the whole run."""
    n = 40
    svc = StreamService(fused=True)
    rng = np.random.default_rng(11)
    solo = {t: DeltaEngine(n) for t in ("t0", "t1", "t2")}
    for t in solo:
        assert svc.create_tenant(t, n).ok
    steady_before = AUDITOR.audited_steady_recompiles
    for _ in range(6):
        ups = {t: (rng.integers(0, n, (10, 2)), None) for t in solo}
        assert svc.ingest_many(ups).ok
        for t, (ins, _) in ups.items():
            solo[t].apply_updates(insert=ins)
        r = svc.top_k_densest(3)
        assert r.ok
        for row in r.value:
            assert row["density"] == solo[row["tenant"]].query().density
    r = svc.density("t0", refine=True)
    assert r.ok and r.value["certified_gap"] >= 0.0
    assert AUDITOR.audited_steady_recompiles == steady_before, (
        f"steady recompiles: {AUDITOR.steady_records()}")

    snap = svc.metrics_snapshot()
    t0 = snap["tenants"]["t0"]
    assert t0["query_steady_ms"]["count"] >= 1
    assert t0["query_steady_ms"]["p99"] is not None
    assert t0["peel_passes_total"] > 0
    assert t0["certified_gap"] == r.value["certified_gap"]
    assert t0["stats"]["n_query_first_calls"] >= 0
    assert snap["audit"]["audited_steady_recompiles"] == 0 or True
    # the response-level split: a steady repeat is never a first call
    r2 = svc.density("t1")
    assert not r2.compiled
    assert prometheus_text().startswith("# TYPE")


def test_first_call_vs_steady_split(fresh_tracer):
    """The cold/warm conflation fix: the first query on a fresh shape is
    tagged compiled, steady repeats are not, and the split lands in
    EngineMetrics and TenantStats."""
    # a distinctive eps forces genuinely fresh executables for this test
    eng = DeltaEngine(32, eps=0.0137, pruned=False)
    eng.tenant = "split-test"
    rng = np.random.default_rng(3)
    first = None
    for i in range(4):
        eng.apply_updates(insert=rng.integers(0, 32, (8, 2)))
        q = eng.query()
        if first is None:
            first = q
        elif i >= 2:
            assert not q.compiled  # same shapes: steady
    assert first.compiled  # fresh eps: the first call compiled
    m = eng.metrics
    assert m.n_query_first_calls >= 1
    assert m.query_first_call_ms_total + m.query_steady_ms_total == (
        pytest.approx(m.query_ms_total))
    # snapshot carries both series, split by the compiled tag
    assert snapshot()["metrics"]["histograms"]


def test_compile_count_routes_through_auditor():
    assert DeltaEngine.compile_count() == AUDITOR.total_compile_count()


# ---------------------------------------------------------------------------
# phase spans: label inheritance, one id per request, transfer counters
# ---------------------------------------------------------------------------
def test_children_inherit_labels_share_trace_id_and_tally():
    tr = Tracer(profiler_bridge=False)
    with tr.span("service", op="density", tenant="a"):
        with tr.span("query", tenant="a", engine="delta"):
            with tr.span("prune_prepare"):
                tr.tally("d2h_bytes", 256)
                tr.tally("d2h_bytes", 4)
    with tr.span("other"):
        pass
    tr.tally("h2d_bytes", 8)          # no span open: nothing to add to
    recs = {r.name: r for r in tr.ring()}
    # a span with no labels takes its parent's; given labels stay as given
    assert recs["prune_prepare"].labels == {"tenant": "a", "engine": "delta"}
    assert recs["service"].labels == {"op": "density", "tenant": "a"}
    assert recs["other"].labels == {}
    assert recs["prune_prepare"].attrs == {"d2h_bytes": 260}
    root = recs["service"].span_id
    assert {recs[n].trace_id for n in ("service", "query",
                                       "prune_prepare")} == {root}
    assert recs["other"].trace_id == recs["other"].span_id != root
    assert (recs["service"].t_mono_ns <= recs["query"].t_mono_ns
            <= recs["prune_prepare"].t_mono_ns)
    assert tr.registry.counter("d2h_bytes_total", tenant="a",
                               engine="delta").value == 260
    assert tr.registry.find("h2d_bytes_total") == []
    rec = recs["prune_prepare"]
    assert SpanRecord.from_json(json.loads(json.dumps(rec.to_json()))) == rec


def _children(ring, parent):
    return sorted((r for r in ring if r.parent_id == parent.span_id),
                  key=lambda r: r.t_mono_ns)


def _interval(rec):
    return rec.t_mono_ns, rec.t_mono_ns + rec.duration_ms * 1e6


def test_ingest_span_tree(fresh_tracer):
    """service -> ingest -> {buffer_apply, ingest_dispatch}: the children
    lie inside their parent, one after the other, under one trace id."""
    svc = StreamService()
    assert svc.create_tenant("tree", 64).ok
    rng = np.random.default_rng(5)
    for _ in range(3):
        assert svc.apply_updates("tree", insert=rng.integers(0, 64, (40, 2)),
                                 delete=rng.integers(0, 64, (8, 2))).ok
    ring = fresh_tracer.ring()
    service = [r for r in ring if r.name == "service"
               and r.labels["op"] == "apply_updates"][-1]
    (ingest,) = _children(ring, service)
    assert ingest.name == "ingest"
    assert ingest.labels == {"tenant": "tree", "engine": "delta"}
    kids = _children(ring, ingest)
    assert [k.name for k in kids] == ["buffer_apply", "ingest_dispatch"]
    end = _interval(ingest)[0]
    for k in kids:
        lo, hi = _interval(k)
        assert end <= lo <= hi <= _interval(ingest)[1]
        end = hi
    assert {r.trace_id for r in (service, ingest, *kids)} == {service.span_id}
    applied = kids[0].attrs
    assert applied["n_inserted"] + applied["n_dup_inserts"] == 40
    assert applied["n_deleted"] + applied["n_absent_deletes"] == 8
    assert "n_inserted" not in ingest.attrs   # counted once, on the child


def test_h2d_bytes_match_the_uploaded_arrays(fresh_tracer):
    """A plain batch uploads six int32 rows of the padded width; a regrow
    re-uploads the symmetric slot arrays and the degree array whole."""
    n = 64
    eng = DeltaEngine(n, capacity=256, pruned=False)
    eng.tenant = "bytes"
    pairs = np.array([(u, v) for u in range(n) for v in range(u + 1, n)])
    order = np.random.default_rng(9).permutation(len(pairs))
    eng.apply_updates(insert=pairs[order[:100]])     # first: resync + batch
    eng.apply_updates(insert=pairs[order[100:150]])  # plain batch
    ring = fresh_tracer.ring()
    dispatch = [r for r in ring if r.name == "ingest_dispatch"][-1]
    b = dispatch.attrs["batch_width"]
    assert b == 64 and dispatch.attrs["h2d_bytes"] == 6 * 4 * b

    eng.apply_updates(insert=pairs[order[150:400]])  # past 256 slots: regrow
    ring = fresh_tracer.ring()
    ingest = [r for r in ring if r.name == "ingest"][-1]
    kids = _children(ring, ingest)
    assert [k.name for k in kids] == ["buffer_apply", "engine_resync"]
    assert eng.buffer.capacity == 512
    src, dst, deg = eng.buffer.resident_state(eng.node_capacity)
    assert kids[1].attrs["h2d_bytes"] == src.nbytes + dst.nbytes + deg.nbytes
    assert kids[1].attrs["h2d_bytes"] == 2 * 4 * 1024 + 4 * n
    total = sum(r.attrs.get("h2d_bytes", 0) for r in ring)
    assert fresh_tracer.registry.counter(
        "h2d_bytes_total", tenant="bytes", engine="delta").value == total


def _planted_engine(n=64):
    eng = DeltaEngine(n)
    rng = np.random.default_rng(4)
    clique = [(u, v) for u in range(12) for v in range(u + 1, 12)]
    eng.apply_updates(insert=np.concatenate(
        [np.array(clique), rng.integers(0, n, (60, 2))]))
    return eng


def test_pruned_query_spans_carry_tenant_and_path(fresh_tracer):
    eng = _planted_engine()
    eng.tenant = "pq"
    q = eng.query()
    assert q.pruned
    ring = fresh_tracer.ring()
    query = [r for r in ring if r.name == "query"][-1]
    assert query.attrs["path"] == "pruned"
    kids = _children(ring, query)
    assert [k.name for k in kids] == ["prune_plan", "prune_prepare",
                                      "prune_peel", "prune_merge"]
    for k in kids:
        assert k.labels == {"tenant": "pq", "engine": "delta"}
        assert k.trace_id == query.trace_id
    prepare, peel, merge = kids[1:]
    assert prepare.attrs["d2h_bytes"] == 4 * eng.node_capacity  # int32 deg
    assert "fell_back" not in prepare.attrs
    v, e = eng._plan.bucket_v, eng._plan.bucket_e
    assert peel.attrs["h2d_bytes"] == 2 * 4 * e   # the bucket's two lanes
    assert merge.attrs["passes"] == q.passes
    assert merge.attrs.get("d2h_bytes", v) == v   # the bucket mask, if read
    assert "candidate_fraction" in kids[0].attrs


def test_forced_prune_fallback_runs_peel_full(fresh_tracer, monkeypatch):
    from repro.core import prune

    monkeypatch.setattr(prune, "prepare_pruned_peel", lambda *a, **k: None)
    eng = _planted_engine()
    eng.tenant = "fb"
    q = eng.query()
    assert not q.pruned and eng.metrics.n_prune_fallbacks == 1
    ring = fresh_tracer.ring()
    query = [r for r in ring if r.name == "query"][-1]
    assert query.attrs["path"] == "warm"
    kids = _children(ring, query)
    assert [k.name for k in kids] == ["prune_plan", "prune_prepare",
                                      "peel_full"]
    assert kids[1].attrs["fell_back"] is True
    assert kids[2].attrs["passes"] == q.passes
    assert kids[2].attrs["d2h_bytes"] >= eng.node_capacity   # the bool mask
    rho, _, passes = pbahmani_np(eng.buffer.to_graph())
    assert q.density == pytest.approx(rho, rel=1e-6) and q.passes == passes
