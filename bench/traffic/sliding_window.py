"""Traffic: one tenant over a count-based sliding window of Kronecker edges.

The stream is a seeded sequence of i.i.d. Kronecker edge arrivals. The
window holds the last ``edge_factor`` x 2^``scale`` arrivals as a multiset:
an edge is live while some arrival of it is inside the window, so a batch
inserts the edges whose first live copy arrives and deletes the edges whose
last live copy expires (the sliding-window bookkeeping of
``chip_smoke.py``, made count-based). The window is filled during set-up;
each update event then brings ``batch_edges`` new arrivals and expires as
many old ones, and one ``density`` query follows every ``queries_every``
batches.

Mix keys: ``batch_edges``, ``queries_every``, ``max_batches`` (the supply
of batches for the window, sent as fast as accepted), ``warmup_batches``
(sent before the window), ``check_sample``.
"""
from __future__ import annotations

import numpy as np

from bench.sampling import kronecker_keys, pairs

TENANT = "stream"


class Traffic:
    def __init__(self, config: dict, mix: dict, seed: int):
        rng = np.random.default_rng(seed)
        self.n = 1 << int(config["scale"])
        self.window = int(config["edge_factor"]) * self.n
        self.capacity = int(config["edge_capacity"])
        self.service = dict(config["service"])
        self.batch = int(mix["batch_edges"])
        every = int(mix["queries_every"])

        n_batches = int(mix["max_batches"])
        n_events = n_batches + n_batches // every
        kinds = ["density" if (i + 1) % (every + 1) == 0 else "update"
                 for i in range(n_events)]
        n_warm = int(mix["warmup_batches"])
        warm_kinds = ["density" if (i + 1) % (every + 1) == 0 else "update"
                      for i in range(n_warm + n_warm // every)]
        n_post = warm_kinds.count("update") + kinds.count("update")

        a, b, c = config["initiator"]
        arrivals = kronecker_keys(rng, int(config["scale"]),
                                  self.window + n_post * self.batch, a, b, c)
        # compact ids make the multiset bookkeeping a bincount; keys of
        # scale <= 16 sort faster as 32-bit words
        if self.n * self.n <= 1 << 32:
            arrivals = arrivals.astype(np.uint32)
        keys, ids = np.unique(arrivals, return_inverse=True)
        self.keys = keys.astype(np.int64)
        self.ids = ids.astype(np.int32)
        self.fill = [pairs(self.keys[np.unique(ch)], self.n)
                     for ch in np.split(self.ids[: self.window],
                                        self.window // self.batch)]
        counts = np.bincount(self.ids[: self.window],
                             minlength=self.keys.size).astype(np.int32)
        batches = iter(range(n_post))
        sign = np.concatenate([np.ones(self.batch), -np.ones(self.batch)])

        def make(kind_list):
            out = []
            for kind in kind_list:
                if kind == "density":
                    out.append(("density", TENANT, None))
                    continue
                j = next(batches)
                new = self.ids[self.window + j * self.batch:
                               self.window + (j + 1) * self.batch]
                old = self.ids[j * self.batch:(j + 1) * self.batch]
                touched, inv = np.unique(np.concatenate([new, old]),
                                         return_inverse=True)
                delta = np.bincount(inv, weights=sign,
                                    minlength=touched.size)
                before = counts[touched] > 0
                counts[touched] += delta.astype(np.int32)
                after = counts[touched] > 0
                ins = pairs(self.keys[touched[after & ~before]], self.n)
                dels = pairs(self.keys[touched[before & ~after]], self.n)
                out.append(("update", TENANT, (ins, dels)))
            return out

        self.warm_events = make(warm_kinds)
        self.events = make(kinds)
        self.check_sample = int(mix["check_sample"])

    def setup(self, make_service):
        """The service with the window filled; returns it."""
        svc = make_service(**self.service)
        _ok(svc.create_tenant(TENANT, n_nodes=self.n, capacity=self.capacity))
        for ins in self.fill:
            _ok(svc.apply_updates(TENANT, insert=ins))
        return svc

    def live(self, version: int, tenant: str):
        """(n_nodes, distinct live keys) after ``version`` update events
        past the fill, rebuilt from the arrival log alone."""
        lo = version * self.batch
        window = self.ids[lo: lo + self.window]
        return self.n, self.keys[np.unique(window)]


def _ok(resp):
    if not resp.ok:
        raise RuntimeError(f"set-up {resp.op} failed: {resp.error}")
    return resp.value
