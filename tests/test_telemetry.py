"""Mesh-wide telemetry plane (ISSUE 10): cross-process collector merge
exactness (vs a pooled oracle, property-tested), both transports, the
scrape endpoint under the strict exposition lint, label-escaping
round-trips, JSONL sink rotation, multi-window burn-rate alerts on a fake
clock — and the hard invariant that a live scrape
server plus collector push cannot perturb engine results or compile
caches (oracle parity with the whole plane up)."""
import json
import math
import urllib.error
import urllib.request

import numpy as np
import pytest

from _hyp import given, settings, st

from repro.core.pbahmani import pbahmani_np
from repro.graphs.graph import Graph
from repro.obs import (
    AUDITOR,
    BurnRatePolicy,
    Collector,
    CollectorServer,
    Histogram,
    MetricsRegistry,
    SloMonitor,
    Tracer,
    burn_exceeds,
    escape_label_value,
    parse_prometheus_text,
    prometheus_text,
    push_snapshot,
    serve_metrics,
    set_tracer,
    unescape_label_value,
    write_spool,
)
from repro.stream import StreamService

ADVERSARIAL_NAMES = (
    'acme "eu"', "bank\\prod", "multi\nline", 'tricky\\"mix\\n',
    "plain", "trailing\\",
)


@pytest.fixture
def fresh_tracer(tmp_path):
    """Isolated default tracer (fresh ring/registry + JSONL) so spans in
    this module don't leak across tests; restores the previous one."""
    tr = Tracer(jsonl_path=str(tmp_path / "trace.jsonl"),
                profiler_bridge=False)
    prev = set_tracer(tr)
    yield tr
    set_tracer(prev)


def _hist_from(values, name="query_ms", **labels):
    h = Histogram(name, labels)
    for v in values:
        h.observe(v)
    return h


def _oracle_quantile(values, p, bounds):
    """Sorted-list oracle: the rank-ceil(p*n) order statistic snapped up
    to its bucket's upper edge (same contract as tests/test_obs.py)."""
    xs = sorted(values)
    x = xs[max(1, math.ceil(p * len(xs))) - 1]
    for b in bounds:
        if x <= b:
            return b
    return max(xs)


# ---------------------------------------------------------------------------
# merge identity: the property the whole fleet aggregation rests on
# ---------------------------------------------------------------------------
@settings(max_examples=40, deadline=None)
@given(
    a=st.lists(st.floats(min_value=1e-4, max_value=1e4, allow_nan=False,
                         allow_infinity=False), min_size=0, max_size=80),
    b=st.lists(st.floats(min_value=1e-4, max_value=1e4, allow_nan=False,
                         allow_infinity=False), min_size=0, max_size=80),
)
def test_merge_commutes_and_adds_exactly(a, b):
    ha, hb = _hist_from(a), _hist_from(b)
    ab, ba = ha.merged(hb), hb.merged(ha)
    assert ab.counts == ba.counts == [x + y for x, y in
                                      zip(ha.counts, hb.counts)]
    assert ab.total == ba.total == len(a) + len(b)
    assert ab.quantiles() == ba.quantiles()
    assert ab.max_value == ba.max_value


@settings(max_examples=40, deadline=None)
@given(
    workers=st.lists(
        st.lists(st.floats(min_value=1e-4, max_value=1e4, allow_nan=False,
                           allow_infinity=False), min_size=0, max_size=60),
        min_size=3, max_size=5),
)
def test_merge_associates_and_matches_pooled_oracle(workers):
    """>=3 simulated workers: any merge-tree shape gives the same bucket
    counts, and the fleet quantile equals the sorted-list oracle over the
    pooled observations (exactly, not approximately)."""
    hs = [_hist_from(vs) for vs in workers]
    left = hs[0]
    for h in hs[1:]:
        left = left.merged(h)          # ((a+b)+c)+...
    right = hs[-1]
    for h in reversed(hs[:-1]):
        right = h.merged(right)        # a+(b+(c+...))
    assert left.counts == right.counts
    assert left.total == right.total
    assert left.quantiles() == right.quantiles()
    pooled = [v for vs in workers for v in vs]
    if pooled:
        for p in (0.5, 0.95, 0.99):
            assert left.quantile(p) == _oracle_quantile(pooled, p,
                                                        left.bounds)
    else:
        assert left.quantile(0.5) is None


def test_merge_rejects_different_bounds():
    h1 = Histogram("q", {}, bounds=(1.0, 2.0))
    h2 = Histogram("q", {}, bounds=(1.0, 4.0))
    with pytest.raises(ValueError):
        h1.merged(h2)


def test_histogram_dict_round_trip_is_lossless():
    h = _hist_from([0.01, 5.0, 123.0, 1e6], tenant='we"ird\\')
    back = Histogram.from_dict(
        json.loads(json.dumps(h.to_dict())))
    assert back.counts == h.counts and back.total == h.total
    assert back.bounds == h.bounds and back.labels == h.labels
    assert back.quantiles() == h.quantiles()


# ---------------------------------------------------------------------------
# collector: 3 worker registries vs one pooled registry, bit for bit
# ---------------------------------------------------------------------------
def _worker_registry(seed, tenant="checkout"):
    rng = np.random.default_rng(seed)
    reg = MetricsRegistry()
    for v in rng.uniform(0.01, 500.0, 40):
        reg.histogram("query_ms", tenant=tenant).observe(float(v))
    reg.counter("peel_passes_total", tenant=tenant).inc(int(seed) + 1)
    g = reg.gauge("certified_gap", tenant=tenant)
    g.set(0.001 * seed)
    g.updated_at = 100.0 + seed       # deterministic last-writer ordering
    return reg


def test_collector_matches_pooled_registry_bit_identically():
    col, pooled = Collector(), MetricsRegistry()
    for seed in (1, 2, 3):
        reg = _worker_registry(seed)
        col.ingest(f"w{seed}", {"metrics": reg.snapshot()})
        rng = np.random.default_rng(seed)
        for v in rng.uniform(0.01, 500.0, 40):   # same draws, one registry
            pooled.histogram("query_ms", tenant="checkout").observe(float(v))
    fleet = col.fleet_histogram("query_ms", tenant="checkout")
    one = pooled.merged_histogram("query_ms", tenant="checkout")
    assert fleet.counts == one.counts and fleet.total == one.total == 120
    for p in (0.5, 0.95, 0.99):
        assert fleet.quantile(p) == one.quantile(p)
    # per-worker series stay distinct in the registry view
    reg = col.as_registry()
    assert {m.labels["worker"] for m in reg.find("query_ms")} == \
        {"w1", "w2", "w3"}


def test_fleet_snapshot_sums_counters_and_picks_freshest_gauge():
    col = Collector()
    for seed in (1, 2, 3):
        col.ingest(f"w{seed}",
                   {"metrics": _worker_registry(seed).snapshot(),
                    "audit": {"compile_count_total": seed,
                              "audited_steady_recompiles": 0},
                    "tenants": {"checkout": {"ok": seed}}})
    fleet = col.fleet_snapshot()
    assert fleet["n_workers"] == 3 and fleet["workers"] == ["w1", "w2", "w3"]
    counters = {(c["name"], c["labels"]["tenant"]): c["value"]
                for c in fleet["fleet"]["counters"]}
    assert counters[("peel_passes_total", "checkout")] == 2 + 3 + 4
    gauges = {g["name"]: g for g in fleet["fleet"]["gauges"]}
    # last writer wins by updated_at: w3 wrote last (updated_at=103)
    assert gauges["certified_gap"]["value"] == pytest.approx(0.003)
    assert fleet["audit"]["compile_count_total"] == 6
    assert set(fleet["tenants"]) == {"w1/checkout", "w2/checkout",
                                     "w3/checkout"}
    # re-ingest supersedes: same worker, new snapshot replaces the old one
    col.ingest("w1", {"metrics": MetricsRegistry().snapshot()})
    assert col.fleet_snapshot()["audit"]["compile_count_total"] == 5


def test_collector_rejects_malformed_snapshot():
    with pytest.raises(ValueError):
        Collector().ingest("w0", {"not-metrics": {}})


# ---------------------------------------------------------------------------
# transports: file spool + socket push
# ---------------------------------------------------------------------------
def test_spool_round_trip_skips_torn_files(tmp_path):
    spool = str(tmp_path / "spool")
    snap = {"metrics": _worker_registry(4).snapshot()}
    path = write_spool(spool, "w4", snap)
    assert path.endswith("w4.json")
    (tmp_path / "spool" / "torn.json").write_text('{"worker": "oops", ')
    (tmp_path / "spool" / "notes.txt").write_text("not a snapshot")
    col = Collector()
    assert col.scan_spool(spool) == 1
    assert col.workers() == ["w4"]
    fleet = col.fleet_histogram("query_ms", tenant="checkout")
    assert fleet.total == 40


def test_push_transport_round_trip_and_rejects():
    server = CollectorServer()
    try:
        snap = {"metrics": _worker_registry(5).snapshot()}
        assert push_snapshot(server.address, "w5", snap)
        assert server.collector.workers() == ["w5"]
        assert server.collector.fleet_histogram(
            "query_ms", tenant="checkout").total == 40
        # malformed push is counted, never kills the listener
        import socket
        with socket.create_connection(server.address, timeout=5) as sock:
            sock.sendall(b"this is not json")
            sock.shutdown(socket.SHUT_WR)
            assert sock.recv(64).startswith(b"error")
        assert server.n_rejected == 1
        assert push_snapshot(server.address, "w6", snap)  # still alive
    finally:
        server.close()
    # collector gone: push degrades to False, never raises
    assert push_snapshot(server.address, "w7", snap) is False


# ---------------------------------------------------------------------------
# label escaping: adversarial names must round-trip the exposition format
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ADVERSARIAL_NAMES)
def test_escape_round_trip(name):
    assert unescape_label_value(escape_label_value(name)) == name


def test_prometheus_text_with_adversarial_labels_lints_and_round_trips():
    reg = MetricsRegistry()
    for name in ADVERSARIAL_NAMES:
        reg.counter("peel_passes_total", tenant=name).inc(2)
        reg.histogram("query_ms", tenant=name).observe(1.5)
    text = prometheus_text(reg)
    samples = parse_prometheus_text(text)   # strict: raises on malformed
    recovered = {lab["tenant"] for _, lab, _ in samples if "tenant" in lab}
    assert set(ADVERSARIAL_NAMES) <= recovered
    counts = {lab["tenant"]: v for n, lab, v in samples
              if n == "peel_passes_total"}
    assert all(counts[name] == 2 for name in ADVERSARIAL_NAMES)


def test_parse_prometheus_text_rejects_malformed():
    for bad in ('query_ms{tenant="eu} 1',          # unterminated value
                'query_ms{tenant=eu} 1',           # unquoted value
                "1bad_name 2",                     # bad metric name
                'query_ms{tenant="eu"} not-a-number',
                "# TYPE query_ms wibble"):
        with pytest.raises(ValueError):
            parse_prometheus_text(bad)


# ---------------------------------------------------------------------------
# scrape endpoint
# ---------------------------------------------------------------------------
def _get(url, timeout=5):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.headers["Content-Type"], resp.read()


def test_scrape_server_serves_registry_and_shuts_down_cleanly():
    reg = MetricsRegistry()
    reg.counter("peel_passes_total", tenant='acme "eu"').inc(7)
    reg.histogram("query_ms", tenant='acme "eu"').observe(2.0)
    server = serve_metrics(registry=reg)
    url = server.url
    status, ctype, body = _get(f"{url}/metrics")
    assert status == 200 and ctype.startswith("text/plain")
    samples = parse_prometheus_text(body.decode())
    assert ('peel_passes_total', {'tenant': 'acme "eu"'}, 7.0) in samples

    status, ctype, body = _get(f"{url}/snapshot")
    assert status == 200 and ctype == "application/json"
    snap = json.loads(body)
    assert snap["metrics"]["counters"][0]["value"] == 7

    status, _, body = _get(f"{url}/slo")
    slo = json.loads(body)
    assert 'acme "eu"' in slo["policies"]["query_latency"]["tenants"]
    assert slo["paging"] == []

    assert _get(f"{url}/healthz")[2] == b"ok\n"
    with pytest.raises(urllib.error.HTTPError):
        _get(f"{url}/nope")

    server.close()
    with pytest.raises(urllib.error.URLError):
        _get(f"{url}/healthz", timeout=2)


def test_scrape_server_over_collector_serves_fleet_view():
    col = Collector()
    for seed in (1, 2):
        col.ingest(f"w{seed}", {"metrics": _worker_registry(seed).snapshot()})
    server = serve_metrics(collector=col)
    try:
        _, _, body = _get(f"{server.url}/metrics")
        samples = parse_prometheus_text(body.decode())
        workers = {lab["worker"] for _, lab, _ in samples if "worker" in lab}
        assert workers == {"w1", "w2"}
        _, _, body = _get(f"{server.url}/snapshot")
        assert json.loads(body)["n_workers"] == 2
    finally:
        server.close()


# ---------------------------------------------------------------------------
# tracer JSONL sink rotation
# ---------------------------------------------------------------------------
def _spam_spans(tr, n):
    for i in range(n):
        with tr.span("query", tenant="rot") as sp:
            sp.attrs["i"] = i


def test_jsonl_rotation_bounds_disk(tmp_path):
    path = tmp_path / "t.jsonl"
    tr = Tracer(jsonl_path=str(path), profiler_bridge=False,
                jsonl_max_bytes=2048, jsonl_backups=2)
    _spam_spans(tr, 400)
    tr.close()
    assert path.exists() and (tmp_path / "t.jsonl.1").exists()
    assert (tmp_path / "t.jsonl.2").exists()
    assert not (tmp_path / "t.jsonl.3").exists()   # oldest dropped
    # each file is bounded by the cap plus at most one record's overshoot
    for p in (path, tmp_path / "t.jsonl.1", tmp_path / "t.jsonl.2"):
        assert p.stat().st_size <= 2048 + 512
        for line in p.read_text().splitlines():
            json.loads(line)                       # rotation never tears


def test_jsonl_rotation_zero_backups_truncates(tmp_path):
    path = tmp_path / "t.jsonl"
    tr = Tracer(jsonl_path=str(path), profiler_bridge=False,
                jsonl_max_bytes=1024, jsonl_backups=0)
    _spam_spans(tr, 300)
    tr.close()
    assert path.stat().st_size <= 1024 + 512
    assert not (tmp_path / "t.jsonl.1").exists()
    assert len(tr.ring()) == 300                   # the ring is unaffected


def test_jsonl_uncapped_never_rotates(tmp_path):
    path = tmp_path / "t.jsonl"
    tr = Tracer(jsonl_path=str(path), profiler_bridge=False)
    _spam_spans(tr, 50)
    tr.close()
    assert len(path.read_text().splitlines()) == 50
    assert not (tmp_path / "t.jsonl.1").exists()


# ---------------------------------------------------------------------------
# SLO burn-rate alerts on a fake clock
# ---------------------------------------------------------------------------
def test_burn_exceeds_integer_predicate():
    # 99/100 SLO, 14.4x budget: alert iff bad/total > 0.144
    assert burn_exceeds(15, 100, 99, 100, 144, 10)
    assert not burn_exceeds(14, 100, 99, 100, 144, 10)
    assert not burn_exceeds(0, 0, 99, 100, 144, 10)   # empty window
    assert not burn_exceeds(0, 100, 99, 100, 144, 10)
    # exact boundary is NOT an alert (strict inequality)
    assert not burn_exceeds(144, 1000, 99, 100, 144, 10)
    assert burn_exceeds(145, 1000, 99, 100, 144, 10)


def _slo_rig(threshold_ms=1.0):
    reg = MetricsRegistry()
    now = [0.0]
    pol = BurnRatePolicy(name="lat", threshold_ms=threshold_ms,
                         fast_windows_s=(5.0, 60.0),
                         slow_windows_s=(30.0, 120.0))
    mon = SloMonitor(registry_fn=lambda: reg, policies=(pol,),
                     clock=lambda: now[0])
    hist = reg.histogram("query_ms", tenant="eu")
    return reg, now, mon, hist


def test_slo_pages_only_when_both_fast_windows_burn():
    _, now, mon, hist = _slo_rig()
    mon.sample()                       # t=0 baseline: nothing observed yet
    ev = mon.evaluate()
    assert ev["policies"]["lat"]["tenants"]["eu"]["page"] is False  # no data
    # t=1: a burst of 100 bad observations (way over the 1ms threshold)
    now[0] = 1.0
    for _ in range(100):
        hist.observe(50.0)
    mon.sample()
    ev = mon.evaluate()
    view = ev["policies"]["lat"]["tenants"]["eu"]
    assert view["page"] and ev["paging"] == ["lat/eu"]  # both windows burn
    assert view["ticket"]
    # good-only traffic for 50s: the fast-short window drains, the
    # fast-long window still holds the burst -> old smoke does not page
    for t in range(2, 52):
        now[0] = float(t)
        hist.observe(0.1)
        mon.sample()
    now[0] = 55.0
    ev = mon.evaluate()
    view = ev["policies"]["lat"]["tenants"]["eu"]
    fast_short, fast_long = view["fast"]
    assert not fast_short["alerting"] and fast_short["window_complete"]
    assert fast_long["alerting"]       # burst still inside the 60s window
    assert not view["page"] and ev["paging"] == []


def test_slo_healthy_traffic_never_alerts():
    _, now, mon, hist = _slo_rig()
    for t in range(0, 40, 2):
        now[0] = float(t)
        for _ in range(5):
            hist.observe(0.2)          # all under the 1ms threshold
        mon.sample()
    ev = mon.evaluate()
    view = ev["policies"]["lat"]["tenants"]["eu"]
    assert not view["page"] and not view["ticket"]
    assert all(not w["alerting"] for w in view["fast"] + view["slow"])
    assert all(w["burn"] == 0.0 for w in view["fast"] if w["total"])


def test_slo_threshold_snaps_down_to_bucket_grid():
    pol = BurnRatePolicy(threshold_ms=10.0)    # edges ...8.192, 16.384...
    h = _hist_from([8.0, 9.0])
    # 9.0 lands in the 16.384 bucket (> 8.192 edge): gated as bad even
    # though it is under the nominal 10ms — the conservative direction
    assert pol.good_count(h) == 1


def test_slo_partial_window_is_flagged_not_silent():
    _, now, mon, hist = _slo_rig()
    now[0] = 1.0
    hist.observe(50.0)
    mon.sample()
    now[0] = 2.0
    hist.observe(50.0)
    mon.sample()
    ev = mon.evaluate()
    view = ev["policies"]["lat"]["tenants"]["eu"]
    # history (1s) is shorter than every window: degraded to since-first,
    # reported incomplete, but still alerting on the real bad data
    assert all(not w["window_complete"] for w in view["fast"])
    assert view["page"]


def test_slo_policy_validation():
    with pytest.raises(ValueError):
        BurnRatePolicy(slo_num=100, slo_den=100)
    with pytest.raises(ValueError):
        SloMonitor(policies=(BurnRatePolicy(), BurnRatePolicy()))


def test_gap_freshness_stale_and_missing():
    reg = MetricsRegistry()
    mon = SloMonitor(registry_fn=lambda: reg, gap_freshness_s=600.0,
                     clock=lambda: 1000.0)
    g = reg.gauge("certified_gap", tenant="eu")
    g.set(0.004)
    g.updated_at = 100.0               # last certificate 900s ago
    never = reg.gauge("certified_gap", tenant="us")  # never set()
    assert never.updated_at == 0.0
    fresh = mon.evaluate()["freshness"]
    assert fresh["eu"]["stale"] and fresh["eu"]["age_s"] == 900.0
    assert not fresh["us"]["stale"] and fresh["us"]["age_s"] is None
    g.updated_at = 900.0               # certificate 100s ago: healthy
    assert not mon.evaluate()["freshness"]["eu"]["stale"]


# ---------------------------------------------------------------------------
# the hard invariant: a live telemetry plane changes nothing
# ---------------------------------------------------------------------------
def materialize(edges: set, n_nodes: int) -> Graph:
    arr = (np.asarray(sorted(edges), dtype=np.int64)
           if edges else np.zeros((0, 2), np.int64))
    return Graph.from_edges(arr, n_nodes=n_nodes)


def test_engine_oracle_parity_with_live_scrape_and_push(fresh_tracer):
    """Bit-identity against the numpy oracle with the FULL plane running:
    a scrape server being polled every step AND per-step snapshot pushes
    to a collector — zero audited steady recompiles, because everything
    in repro.obs is host-side by construction."""
    n = 48
    svc = StreamService(max_tenants=4, refresh_every=10**9, worker="wtest")
    svc.create_tenant("par", n_nodes=n)
    server = svc.serve_metrics(port=0)
    csrv = CollectorServer()
    rng = np.random.default_rng(23)
    edges: set = set()
    steady_before = AUDITOR.audited_steady_recompiles
    try:
        for _ in range(6):
            batch = rng.integers(0, n, size=(12, 2), dtype=np.int64)
            svc.apply_updates("par", insert=batch)
            for u, v in batch:
                if u != v:
                    edges.add((min(u, v), max(u, v)))
            r = svc.density("par")
            rho, _, passes = pbahmani_np(materialize(edges, n))
            assert r.value["density"] == pytest.approx(rho, rel=1e-6,
                                                       abs=1e-9)
            assert r.value["passes"] == passes
            # the plane is live DURING the measured window
            _, _, body = _get(f"{server.url}/metrics")
            parse_prometheus_text(body.decode())
            assert svc.push_snapshot(csrv.address)
        assert AUDITOR.audited_steady_recompiles == steady_before, (
            f"steady recompiles: {AUDITOR.steady_records()}")
        fleet = csrv.collector.fleet_snapshot()
        assert fleet["workers"] == ["wtest"]
        # relative, not absolute: other tests in the session may have
        # deliberately classified steady recompiles on the global AUDITOR
        assert fleet["audit"]["audited_steady_recompiles"] == steady_before
        assert "wtest/par" in fleet["tenants"]
        assert csrv.collector.fleet_histogram(
            "query_ms", tenant="par").total >= 1
    finally:
        svc.shutdown()                 # also closes the scrape endpoint
        csrv.close()
    with pytest.raises(urllib.error.URLError):
        _get(f"{server.url}/healthz", timeout=2)


def test_service_spool_and_launch_endpoint(fresh_tracer, tmp_path):
    """The serve-path wiring: spool_snapshot writes a collector-readable
    file, and launch.serve.serve_metrics_endpoint is scrape-able with no
    arguments (process-default registry)."""
    from repro.launch.serve import serve_metrics_endpoint

    svc = StreamService(max_tenants=2, refresh_every=10**9, worker="wsp")
    svc.create_tenant("sp", n_nodes=32)
    svc.apply_updates("sp", insert=np.asarray([[0, 1], [1, 2]]))
    svc.density("sp")
    path = svc.spool_snapshot(str(tmp_path / "spool"))
    col = Collector()
    assert col.scan_spool(str(tmp_path / "spool")) == 1
    assert col.workers() == ["wsp"] and path.endswith("wsp.json")

    server = serve_metrics_endpoint()
    try:
        _, _, body = _get(f"{server.url}/metrics")
        parse_prometheus_text(body.decode())
    finally:
        server.close()
    svc.shutdown()
