"""Streaming fraud-ring detection over an evolving transaction graph.

Two regional payment graphs stream transaction batches into the
multi-tenant StreamService. Midway, a fraud ring (dense block of colluding
accounts) starts forming in one region. An operator loop watches the
cross-tenant density leaderboard; when a tenant's density spikes it pulls
the membership mask and recovers the ring — no rebuilds, no recompiles,
exact densities (the incremental engine equals a from-scratch recompute).

  PYTHONPATH=src python examples/streaming_fraud.py

With ``--serve-metrics`` the operator loop runs against the live scrape
endpoint instead of in-process dicts (mesh-wide telemetry plane,
ISSUE 10): the service binds an HTTP port, and each step the loop GETs
``/slo`` — multi-window burn-rate alerts computed from the exact latency
bucket counts — alongside the density alarm. A deliberately impossible
latency objective pages within the demo's tiny windows (proving the
fast+slow window logic end-to-end over HTTP) while the realistic
objective stays green; ``/metrics`` is linted as genuine Prometheus
exposition text at the end.
"""
import json
import sys
import urllib.request

sys.path.insert(0, "src")

import numpy as np

from repro.stream import DeltaEngine, StreamService

N_ACCOUNTS = 2000
RING = 40           # colluding accounts
STEPS = 24
RING_STARTS = 10    # ring begins wiring up at this step


def organic_batch(rng, size=300):
    """Sparse background commerce: random account pairs."""
    return rng.integers(0, N_ACCOUNTS, (size, 2))


def ring_batch(rng, ring_ids, size=60):
    """The ring densifies: random pairs *within* the colluding block."""
    idx = rng.integers(0, len(ring_ids), (size, 2))
    return np.stack([ring_ids[idx[:, 0]], ring_ids[idx[:, 1]]], axis=1)


def scrape_json(url: str):
    with urllib.request.urlopen(url, timeout=5) as resp:
        return json.load(resp)


def main():
    rng = np.random.default_rng(7)
    svc = StreamService(max_tenants=8, refresh_every=50)
    for region in ("payments-us", "payments-eu"):
        svc.create_tenant(region, n_nodes=N_ACCOUNTS, capacity=1 << 14)

    server = None
    slo_pages: set[str] = set()
    if "--serve-metrics" in sys.argv:
        # mesh-wide telemetry plane: the operator loop reads the live
        # scrape endpoint instead of in-process dicts. Two objectives on
        # the same exact latency buckets: an impossible one (threshold
        # below the smallest bucket edge, so every query is "bad") that
        # must page within the demo's sub-second windows, and a generous
        # 4s one that must stay green — paging the first but not the
        # second proves the multi-window burn-rate math end-to-end over
        # HTTP, not just which side of a constant the latency landed on.
        from repro.obs import BurnRatePolicy, SloMonitor

        demo_windows = dict(fast_windows_s=(0.25, 1.0),
                            slow_windows_s=(0.5, 2.0))
        monitor = SloMonitor(policies=(
            BurnRatePolicy(name="latency_impossible", threshold_ms=0.0005,
                           **demo_windows),
            BurnRatePolicy(name="latency_headroom", threshold_ms=8192.0,
                           **demo_windows),
        ))
        server = svc.serve_metrics(port=0, slo=monitor)
        print(f"scrape endpoint live at {server.url} "
              f"(/metrics /snapshot /slo)")

    ring_ids = rng.choice(N_ACCOUNTS, RING, replace=False)
    history: dict[str, list[float]] = {}
    alerts: list[tuple[int, str, float]] = []
    alerted: set[str] = set()

    for step in range(STEPS):
        for region in ("payments-us", "payments-eu"):
            svc.apply_updates(region, insert=organic_batch(rng))
            # old transactions age out of the sliding window
            eng = svc.registry.get(region)
            if eng.n_edges > 4000:
                stale_edges = eng.buffer.live_pairs()[:250]
                svc.apply_updates(region, delete=stale_edges)
        if step >= RING_STARTS:
            svc.apply_updates("payments-eu", insert=ring_batch(rng, ring_ids))

        board = svc.top_k_densest(k=2).value
        for row in board:
            hist = history.setdefault(row["tenant"], [])
            # alarm: density doubled vs the trailing window (organic churn
            # drifts slowly; a forming ring doubles in a couple of steps)
            if (len(hist) >= 4 and row["tenant"] not in alerted
                    and row["density"] > 2.0 * hist[-4]):
                alerts.append((step, row["tenant"], row["density"]))
                alerted.add(row["tenant"])
            hist.append(row["density"])
        top = board[0]
        if server is not None:
            # scraping IS the sampling cadence: each GET appends one
            # cumulative (good, total) integer pair per (policy, tenant)
            slo_pages.update(scrape_json(f"{server.url}/slo")["paging"])
        print(f"step {step:2d}  top={top['tenant']:12s} "
              f"rho={top['density']:6.3f}  "
              f"{'<-- ALERT' if alerts and alerts[-1][0] == step else ''}")

    assert alerts, "fraud ring never tripped the density alarm"
    step0, region, rho = alerts[0]
    print(f"\nalert: {region} density {rho:.2f} at step {step0} "
          f"(ring started at {RING_STARTS})")

    # pull membership and score the ring recovery
    resp = svc.membership(region)
    flagged = np.where(resp.value["mask"])[0]
    hits = len(set(flagged.tolist()) & set(ring_ids.tolist()))
    recall = hits / RING
    precision = hits / max(len(flagged), 1)
    print(f"membership: {len(flagged)} accounts flagged, "
          f"ring recall={100*recall:.0f}% precision={100*precision:.0f}%")

    st = svc.stats(region).value
    print(f"{region}: {st.n_update_batches} batches, {st.n_queries} queries, "
          f"{st.n_refreshes} epoch refreshes, "
          f"{DeltaEngine.compile_count()} executables compiled total")
    assert recall >= 0.9, "ring recovery failed"

    if server is not None:
        from repro.obs import parse_prometheus_text

        paged = {p.split("/", 1)[0] for p in slo_pages}
        assert "latency_impossible" in paged, \
            f"impossible objective never paged: {sorted(slo_pages)}"
        assert "latency_headroom" not in paged, \
            f"headroom objective paged: {sorted(slo_pages)}"
        samples = parse_prometheus_text(
            urllib.request.urlopen(f"{server.url}/metrics",
                                   timeout=5).read().decode())
        health = scrape_json(f"{server.url}/snapshot")
        assert health["audit"]["audited_steady_recompiles"] == 0
        print(f"slo: impossible objective paged on "
              f"{sorted(p.split('/', 1)[1] for p in slo_pages)}, "
              f"8s headroom objective stayed green; "
              f"/metrics lint ok ({len(samples)} samples)")
        svc.shutdown()

    if "--emit-metrics" in sys.argv:
        # `make metrics-demo` path: dump the run's metric registry in
        # Prometheus exposition format plus the per-tenant SLO snapshot
        from repro.obs import prometheus_text

        snap = svc.metrics_snapshot()
        audit = snap["audit"]
        print("\n# --- observability ---")
        for name, t in snap["tenants"].items():
            q = t["query_steady_ms"]
            print(f"# {name}: steady query p50={q['p50']}ms "
                  f"p99={q['p99']}ms (n={q['count']}), "
                  f"peel passes={t['peel_passes_total']}")
        print(f"# audit: {audit['compile_count_total']} executables, "
              f"{audit['audited_steady_recompiles']} steady recompiles\n")
        print(prometheus_text(), end="")
        assert audit["audited_steady_recompiles"] == 0


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
