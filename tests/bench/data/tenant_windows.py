"""Traffic: many tenants, each a count-based sliding window of Kronecker
edges, driven through the service's grouped paths.

Tenant ``t<i>`` of scale ``s`` (config ``tenant_scales[i]``) holds the last
``edge_factor`` x 2^s arrivals of its own seeded Kronecker stream, with the
bookkeeping of ``sliding_window.py`` (an edge is live while some arrival of
it is in the window). Its edge capacity is that arrival count rounded up
to a power of two, so the live set never grows or shrinks it and a tenant
keeps its fused bucket. The windows are filled during set-up. A round of
events is then:

* one ``ingest_many`` that brings every tenant ``batch_edges`` arrivals
  and expires as many;
* one ``density_many`` over a seeded subset of ``group_min`` or more
  tenants;
* ``singles`` single ``update`` events and one single ``density``, each
  on a seeded tenant.

Config keys: ``tenant_scales``, ``edge_factor``, ``initiator``, ``service``.
Mix keys: ``batch_edges``, ``group_min``, ``singles``, ``max_rounds`` (the
window's supply), ``warmup_rounds`` (sent before the window),
``check_sample``.
"""
from __future__ import annotations

import numpy as np

from bench.sampling import kronecker_keys, pairs


class _Window:
    """One tenant's arrival log and live-edge multiset."""

    def __init__(self, rng, scale: int, edge_factor: int, n_batches: int,
                 batch: int, initiator):
        self.n = 1 << scale
        self.window = edge_factor * self.n
        self.capacity = 1 << (self.window - 1).bit_length()
        self.batch = batch
        a, b, c = initiator
        arrivals = kronecker_keys(rng, scale, self.window + n_batches * batch,
                                  a, b, c)
        keys, ids = np.unique(arrivals, return_inverse=True)
        self.keys, self.ids = keys, ids.astype(np.int32)
        self.counts = np.bincount(self.ids[: self.window],
                                  minlength=keys.size).astype(np.int32)
        self.applied = 0

    def fill(self) -> list:
        chunks = np.array_split(self.ids[: self.window],
                                max(1, self.window // self.batch))
        return [pairs(self.keys[np.unique(ch)], self.n) for ch in chunks]

    def next_batch(self) -> tuple:
        """(insert, delete) pairs of the next batch of arrivals."""
        j, b, w = self.applied, self.batch, self.window
        new = self.ids[w + j * b: w + (j + 1) * b]
        old = self.ids[j * b: (j + 1) * b]
        self.applied += 1
        touched, inv = np.unique(np.concatenate([new, old]),
                                 return_inverse=True)
        sign = np.concatenate([np.ones(b), -np.ones(b)])
        delta = np.bincount(inv, weights=sign, minlength=touched.size)
        before = self.counts[touched] > 0
        self.counts[touched] += delta.astype(np.int32)
        after = self.counts[touched] > 0
        return (pairs(self.keys[touched[after & ~before]], self.n),
                pairs(self.keys[touched[before & ~after]], self.n))

    def live(self, applied: int) -> np.ndarray:
        lo = applied * self.batch
        return self.keys[np.unique(self.ids[lo: lo + self.window])]


class Traffic:
    def __init__(self, config: dict, mix: dict, seed: int):
        rng = np.random.default_rng(seed)
        scales = [int(s) for s in config["tenant_scales"]]
        self.names = [f"t{i}" for i in range(len(scales))]
        self.service = dict(config["service"])
        self.check_sample = int(mix["check_sample"])
        batch = int(mix["batch_edges"])
        group_min = int(mix["group_min"])
        singles = int(mix["singles"])

        # the rounds' shape first (who gets a batch, who is asked), then
        # each tenant's stream long enough for the batches it gets
        def rounds(n):
            out = []
            for _ in range(n):
                size = int(rng.integers(group_min, len(scales) + 1))
                asked = rng.choice(len(scales), size=size, replace=False)
                solo = rng.integers(0, len(scales), size=singles + 1)
                out.append((asked.tolist(), solo.tolist()))
            return out

        warm = rounds(int(mix["warmup_rounds"]))
        supply = rounds(int(mix["max_rounds"]))
        n_batches = np.zeros(len(scales), np.int64)
        for _, solo in warm + supply:
            n_batches += 1
            np.add.at(n_batches, solo[:-1], 1)
        self.windows = [
            _Window(rng, s, int(config["edge_factor"]), int(k), batch,
                    config["initiator"])
            for s, k in zip(scales, n_batches)]
        # applied[v][i]: tenant i's batches after v update events
        self.applied = [np.zeros(len(scales), np.int64)]

        def events(plan):
            out = []
            for asked, solo in plan:
                out.append(("ingest_many", None, {
                    t: w.next_batch()
                    for t, w in zip(self.names, self.windows)}))
                self._log()
                out.append(("density_many", None,
                            [self.names[i] for i in asked]))
                for i in solo[:-1]:
                    out.append(("update", self.names[i],
                                self.windows[i].next_batch()))
                    self._log()
                out.append(("density", self.names[solo[-1]], None))
            return out

        self.warm_events = events(warm)
        self.events = events(supply)

    def _log(self):
        self.applied.append(np.array([w.applied for w in self.windows]))

    def setup(self, make_service):
        """The service with every tenant's window filled; returns it."""
        svc = make_service(**self.service)
        for t, w in zip(self.names, self.windows):
            _ok(svc.create_tenant(t, n_nodes=w.n, capacity=w.capacity))
            for ins in w.fill():
                _ok(svc.apply_updates(t, insert=ins))
        return svc

    def live(self, version: int, tenant: str):
        """(n_nodes, distinct live keys) of ``tenant`` after ``version``
        update events past the fill, rebuilt from the arrival log alone."""
        i = self.names.index(tenant)
        w = self.windows[i]
        return w.n, w.live(int(self.applied[version][i]))


def _ok(resp):
    if not resp.ok:
        raise RuntimeError(f"set-up {resp.op} failed: {resp.error}")
    return resp.value
