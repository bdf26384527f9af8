"""Scrape-endpoint smoke: a live worker served over HTTP, end to end.

The CI check for the telemetry plane's front door: start a real
StreamService with adversarially named tenants (quotes, backslashes — the
label-escaping regression class), bind ``serve_metrics`` on a free port,
then hold the endpoint to its contract over actual HTTP:

  * ``/metrics`` parses under the strict exposition-format parser and the
    adversarial tenant names round-trip through the escaping;
  * ``/slo`` is well-formed burn-rate JSON covering every tenant;
  * ``/snapshot`` reports ``audited_steady_recompiles == 0`` with the
    server up (serving scrapes is host-side only — it must not perturb
    the engines);
  * ``shutdown()`` closes the port (a follow-up connection is refused).

Exit code is the gate; no BENCH artifact (nothing here is a trajectory
number).
"""
from __future__ import annotations

import json
import os
import sys
import urllib.error
import urllib.request

if __name__ == "__main__":
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(_root, "src"))
    sys.path.insert(0, _root)

# tenant names chosen to break naive exposition rendering
TENANTS = ('acme "eu"', "bank\\prod", "plain")


def main() -> None:
    import numpy as np

    from repro.obs.export import parse_prometheus_text
    from repro.stream import StreamService

    rng = np.random.default_rng(0)
    svc = StreamService(max_tenants=4, refresh_every=10**9, worker="smoke")
    for tenant in TENANTS:
        svc.create_tenant(tenant, n_nodes=64, capacity=1 << 9)
        for _ in range(3):
            svc.apply_updates(tenant, insert=rng.integers(0, 64, (100, 2)))
            svc.density(tenant)

    server = svc.serve_metrics(port=0)
    url = server.url
    print(f"# serving {url}")

    with urllib.request.urlopen(f"{url}/metrics", timeout=5) as resp:
        ctype = resp.headers["Content-Type"]
        samples = parse_prometheus_text(resp.read().decode())
    assert ctype.startswith("text/plain"), ctype
    seen = {lab["tenant"] for _, lab, _ in samples if "tenant" in lab}
    missing = set(TENANTS) - seen
    assert not missing, f"tenants lost in label escaping: {missing}"

    with urllib.request.urlopen(f"{url}/slo", timeout=5) as resp:
        slo = json.load(resp)
    pol = slo["policies"]["query_latency"]
    assert set(TENANTS) <= set(pol["tenants"]), sorted(pol["tenants"])
    for view in pol["tenants"].values():
        assert len(view["fast"]) == 2 and len(view["slow"]) == 2

    with urllib.request.urlopen(f"{url}/snapshot", timeout=5) as resp:
        snap = json.load(resp)
    assert snap["audit"]["audited_steady_recompiles"] == 0
    assert snap["worker"] == "smoke"

    with urllib.request.urlopen(f"{url}/healthz", timeout=5) as resp:
        assert resp.read() == b"ok\n"

    svc.shutdown()  # must close the scrape endpoint too
    try:
        urllib.request.urlopen(f"{url}/healthz", timeout=2)
        raise AssertionError("endpoint still serving after shutdown()")
    except urllib.error.URLError:
        pass

    print(f"# scrape smoke ok: {len(samples)} samples linted, "
          f"{len(TENANTS)} adversarial tenant names round-tripped, "
          f"SLO well-formed, zero steady recompiles, clean shutdown")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
