"""Mesh-wide telemetry benchmark: real worker processes -> one collector.

ISSUE 10 tentpole measurement. The parent launches N *separate Python
processes* (``--worker`` self-invocations), each running a small
StreamService workload with its own process-local metrics registry — the
honest multi-worker topology, not threads sharing one registry. Every
worker ships its snapshot over BOTH transports (atomic file spool + TCP
push to a live ``CollectorServer``); the parent then asserts the
exactness contracts the telemetry plane is built on:

  * **merge exactness** — for every tenant, the collector's fleet
    histogram (bucket counts AND p50/p95/p99) is bit-identical to a
    pooled oracle built by merging the per-worker histograms by hand, in
    forward and reversed worker order (commutativity is load-bearing:
    ingest order across workers must not change a reported quantile);
  * **transport parity** — the spool-fed collector and the push-fed
    collector produce identical fleet aggregates (histograms, counters,
    audit), so which transport a deployment picks is operational, not
    semantic;
  * **scrape lint** — ``/metrics`` over the fleet collector parses under
    the strict exposition-format parser, and ``/slo`` + ``/snapshot``
    are well-formed;
  * **zero steady recompiles** — summed across the whole fleet.

All four are deterministic pass/fail counts gated at zero by
``check_regression.py`` (no machine-dependent baseline). The merged
fleet snapshot is written to ``FLEET_snapshot.json`` (uploaded as a CI
artifact next to the BENCH/METRICS trajectory files).

The workers run on the CPU (``JAX_PLATFORMS=cpu`` in their environment):
what is measured here is the collector's merge, not the chip, and a chip
belongs to one process at a time. The parent never imports jax.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import urllib.request

if __name__ == "__main__":
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(_root, "src"))
    sys.path.insert(0, _root)

from benchmarks._artifacts import write_bench_json

N_WORKERS = 3
# one tenant name shared by every worker (the cross-worker merge case the
# fleet SLO needs) plus one tenant unique per worker
SHARED_TENANT = "checkout"


# ---------------------------------------------------------------------------
# worker mode: one real process, one registry, two transports out
# ---------------------------------------------------------------------------
def run_worker(worker: str, spool_dir: str, push_addr: str | None) -> None:
    import numpy as np

    from repro.obs.collector import push_snapshot, write_spool
    from repro.stream import StreamService

    rng = np.random.default_rng(abs(hash(worker)) % (1 << 31))
    svc = StreamService(max_tenants=4, refresh_every=10**9, worker=worker)
    for tenant in (SHARED_TENANT, f"search-{worker}"):
        svc.create_tenant(tenant, n_nodes=128, capacity=1 << 10)
        for _ in range(4):
            svc.apply_updates(tenant, insert=rng.integers(0, 128, (200, 2)))
            svc.density(tenant)

    snap = svc.metrics_snapshot()  # ship the SAME snapshot both ways
    write_spool(spool_dir, worker, snap)
    if push_addr:
        host, port = push_addr.rsplit(":", 1)
        ok = push_snapshot((host, int(port)), worker, snap)
        if not ok:
            raise SystemExit(f"{worker}: push to {push_addr} failed")
    print(f"# {worker}: spooled + pushed "
          f"({len(snap['metrics']['histograms'])} histogram series)")


# ---------------------------------------------------------------------------
# parent mode: launch the fleet, then hold it to the exactness contracts
# ---------------------------------------------------------------------------
def _merged(parts):
    out = parts[0]
    for h in parts[1:]:
        out = out.merged(h)
    return out


def _check_merge_exact(collector, spool_dir: str) -> tuple[int, list[dict]]:
    """Fleet histogram vs hand-pooled per-worker oracle, both orders."""
    from repro.obs.metrics import Histogram

    per_tenant: dict[str, list] = {}
    for fname in sorted(os.listdir(spool_dir)):
        if not fname.endswith(".json"):
            continue
        with open(os.path.join(spool_dir, fname)) as f:
            snap = json.load(f)["snapshot"]
        for h in snap["metrics"]["histograms"]:
            if h["name"] == "query_ms":
                tenant = h["labels"].get("tenant", "-")
                per_tenant.setdefault(tenant, []).append(
                    Histogram.from_dict(h))
    assert len(per_tenant[SHARED_TENANT]) >= 2, \
        "shared tenant must span multiple workers to exercise the merge"

    mismatches, rows = 0, []
    for tenant, parts in sorted(per_tenant.items()):
        fleet = collector.fleet_histogram("query_ms", tenant=tenant)
        fwd, rev = _merged(parts), _merged(list(reversed(parts)))
        ok = (fleet is not None
              and fleet.counts == fwd.counts == rev.counts
              and fleet.total == fwd.total
              and fleet.quantiles() == fwd.quantiles() == rev.quantiles())
        mismatches += 0 if ok else 1
        rows.append({"tenant": tenant, "n_workers": len(parts),
                     "count": fwd.total, "exact": ok,
                     **(fleet.quantiles() if fleet else {})})
    return mismatches, rows


def _check_transport_parity(spool_col, push_col) -> int:
    """Spool-fed and push-fed collectors must agree on the fleet view
    (ingest timestamps aside — those are transport-local by nature)."""
    mismatches = 0
    a, b = spool_col.fleet_snapshot(), push_col.fleet_snapshot()
    for section in ("fleet", "audit", "workers", "n_workers"):
        if json.dumps(a[section], sort_keys=True, default=str) != \
                json.dumps(b[section], sort_keys=True, default=str):
            mismatches += 1
            print(f"# transport mismatch in {section!r}")
    return mismatches


def _check_scrape(collector) -> tuple[int, int]:
    """Serve the fleet collector on a real port; lint what comes back."""
    from repro.obs.export import parse_prometheus_text
    from repro.obs.scrape import serve_metrics

    errors, n_samples = 0, 0
    server = serve_metrics(collector=collector)
    try:
        with urllib.request.urlopen(f"{server.url}/metrics",
                                    timeout=5) as resp:
            n_samples = len(parse_prometheus_text(resp.read().decode()))
        with urllib.request.urlopen(f"{server.url}/slo", timeout=5) as resp:
            slo = json.load(resp)
        if "policies" not in slo or "paging" not in slo:
            errors += 1
        with urllib.request.urlopen(f"{server.url}/snapshot",
                                    timeout=5) as resp:
            if json.load(resp)["n_workers"] != N_WORKERS:
                errors += 1
    except (OSError, ValueError) as e:
        print(f"# scrape lint error: {e}")
        errors += 1
    finally:
        server.close()
    return errors, n_samples


def run(n_workers: int = N_WORKERS) -> dict:
    from repro.obs.collector import Collector, CollectorServer

    server = CollectorServer()
    host, port = server.address
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root, env.get("PYTHONPATH", "")])
    env["JAX_PLATFORMS"] = "cpu"
    try:
        with tempfile.TemporaryDirectory(prefix="obs-spool-") as spool:
            procs = [subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 "--worker", f"w{i}", "--spool", spool,
                 "--push", f"{host}:{port}"],
                env=env, cwd=root) for i in range(n_workers)]
            rcs = [p.wait(timeout=600) for p in procs]
            assert rcs == [0] * n_workers, f"worker exit codes: {rcs}"

            spool_col = Collector()
            n_spooled = spool_col.scan_spool(spool)
            assert n_spooled == n_workers, (n_spooled, n_workers)
            assert server.collector.workers() == spool_col.workers()
            assert server.n_rejected == 0

            merge_mismatches, rows = _check_merge_exact(spool_col, spool)
            transport_mismatches = _check_transport_parity(
                spool_col, server.collector)
            scrape_errors, n_samples = _check_scrape(spool_col)
            fleet = spool_col.fleet_snapshot()
    finally:
        server.close()

    out_dir = os.environ.get("BENCH_ARTIFACT_DIR", ".")
    fleet_path = os.path.join(out_dir, "FLEET_snapshot.json")
    with open(fleet_path, "w") as f:
        json.dump(fleet, f, indent=2, sort_keys=True, default=str)
    print(f"# wrote {fleet_path}")

    return {
        "rows": rows,
        "metrics": {
            "n_workers": n_workers,
            "merge_mismatches": merge_mismatches,
            "transport_mismatches": transport_mismatches,
            "scrape_lint_errors": scrape_errors,
            "steady_compiles": fleet["audit"]["audited_steady_recompiles"],
            # ungated trajectory numbers
            "fleet_query_count": sum(r["count"] for r in rows),
            "scrape_samples": n_samples,
        },
    }


def main(smoke: bool = False) -> None:
    res = run()
    m = res["metrics"]
    for row in res["rows"]:
        print(f"# tenant {row['tenant']:12s} workers={row['n_workers']} "
              f"count={row['count']:3d} p50={row.get('p50')} "
              f"p99={row.get('p99')} exact={row['exact']}")
    write_bench_json("obs", m, res["rows"],
                     mode="smoke" if smoke else "full")
    failures = (m["merge_mismatches"] + m["transport_mismatches"]
                + m["scrape_lint_errors"] + m["steady_compiles"])
    assert failures == 0, m
    print(f"# {'smoke ' if smoke else ''}ok: {m['n_workers']} worker "
          f"processes, fleet quantiles bit-identical to the pooled oracle "
          f"both merge orders, spool == push, /metrics lint clean "
          f"({m['scrape_samples']} samples), zero steady recompiles")


if __name__ == "__main__":
    if "--worker" in sys.argv:
        from repro.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
        args = sys.argv[1:]
        run_worker(args[args.index("--worker") + 1],
                   args[args.index("--spool") + 1],
                   (args[args.index("--push") + 1]
                    if "--push" in args else None))
        sys.exit(0)
    if "--emit-metrics" in sys.argv:
        os.environ["BENCH_EMIT_METRICS"] = "1"
    main(smoke="--smoke" in sys.argv)
