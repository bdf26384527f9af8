"""Traffic: many tenants of rank-size Zipf scales, each a count-based
sliding window of Kronecker edges, asked for their densest subgraph with
YCSB's scrambled Zipfian request distribution.

Tenant ``tNN`` of scale ``s`` (config ``tenant_scales[NN]``, in size-rank
order) holds the last ``edge_factor`` x 2^s arrivals of its own seeded
Kronecker stream, with the bookkeeping of ``sliding_window.py``: an edge is
live while some arrival of it is in the window. Its edge capacity is that
arrival count rounded up to a power of two, so the live set never grows
or shrinks it and the tenant keeps its fused bucket. Set-up fills every
window with one batch per tenant. A pane is 1/``pane_divisor`` of the
tenant's window. A tick is then:

* one ``ingest_many`` that brings every tenant one pane of arrivals and
  expires as many;
* one ``density_many`` of ``group_requests`` requests, each drawn from the
  scrambled Zipfian; repeats stay tickets of their own;
* one ``update`` that brings a drawn tenant an extra pane (its stream runs
  ahead);
* one ``density`` on a drawn tenant.

The request distribution is YCSB's ``zipfian`` (constant
``zipf_constant``) over the tenants: popularity rank r (from 1) has weight
1 / r^theta. The scramble places the ranks by a fixed FNV-1a hash of the
tenant index (YCSB's ``ScrambledZipfianGenerator`` hashes the same way),
not drawn from the seed, so every seed has the same hot tenants and
popularity is independent of size.

The warm-up is deterministic in shape: ``warm_rounds`` sweeps in which
each tick's ``density_many`` asks, in every bucket (tenants of one scale
share one), all its tenants, then half the power-of-two cover of the
largest bucket, and so on down to 1, with a lone update and a lone
density in every bucket, so each bucket meets every power-of-two flush
it can meet, after its first flush has set its batched peel shape, and
its tenants age towards a refresh; then ``warm_ticks`` ticks of the
window's own traffic.

Config keys: ``tenant_scales``, ``edge_factor``, ``initiator``,
``zipf_constant``, ``service``. Mix keys: ``pane_divisor``,
``group_requests``, ``max_ticks`` (the window's supply), ``warm_rounds``,
``warm_ticks``, ``check_sample``.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from bench.sampling import kronecker_keys, pairs

FNV_OFFSET = 0xCBF29CE484222325
FNV_PRIME = 0x100000001B3


def fnv1a64(value: int) -> int:
    """FNV-1a over the eight little-endian bytes of ``value``."""
    h = FNV_OFFSET
    for i in range(8):
        h ^= (value >> (8 * i)) & 0xFF
        h = (h * FNV_PRIME) & 0xFFFFFFFFFFFFFFFF
    return h


def draw(rng, order: np.ndarray, probs: np.ndarray, size: int) -> np.ndarray:
    """``size`` tenant indices drawn independently, rank r with
    probability ``probs[r]``; repeats are kept."""
    return order[rng.choice(order.size, size=size, p=probs)]


def popularity(n: int, theta: float) -> tuple[np.ndarray, np.ndarray]:
    """(tenant index of each popularity rank, probability of each rank):
    Zipf weights 1 / r^theta, ranks placed by the FNV-1a hash of the
    tenant index."""
    order = np.asarray(sorted(range(n), key=fnv1a64), np.int64)
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** float(theta)
    return order, w / w.sum()


class _Window:
    """One tenant's arrival log and the live-edge changes of every pane.

    After j panes the window holds arrivals ``[j * pane, j * pane +
    window)``, so the arrival at position x keeps its edge live for
    j in ``[ceil((x - window + 1) / pane), x // pane]``. An edge is live
    over the union of its arrivals' ranges: pane j - 1 inserts it where a
    union range starts at j >= 1, and pane j deletes it where one ends at
    j. All panes come from one sort of the log."""

    def __init__(self, rng, scale: int, edge_factor: int, n_panes: int,
                 pane: int, initiator):
        self.n = 1 << scale
        self.window = w = edge_factor * self.n
        self.capacity = 1 << (w - 1).bit_length()
        self.pane = p = pane
        a, b, c = initiator
        arrivals = kronecker_keys(rng, scale, w + n_panes * p, a, b, c)
        # x: arrival positions by (key, position); ids: each arrival's key
        bits = int(arrivals.size - 1).bit_length()
        if 2 * scale + bits > 62:
            raise ValueError(f"scale {scale} with {arrivals.size} arrivals "
                             "overflows the (key, position) sort key")
        both = np.sort((arrivals << bits) | np.arange(arrivals.size))
        x, srt = both & ((1 << bits) - 1), both >> bits
        first = np.ones(x.size, bool)
        first[1:] = srt[1:] != srt[:-1]
        self.keys = srt[first]
        rank = (np.cumsum(first) - 1).astype(np.int32)
        self.ids = np.empty(x.size, np.int32)
        self.ids[x] = rank
        lo = np.maximum(0, -((w - 1 - x) // p))
        hi = np.minimum(x // p, n_panes)
        last = np.ones(x.size, bool)
        last[:-1] = first[1:]
        gap = lo[1:] > hi[:-1] + 1
        start = first.copy()
        start[1:] |= gap
        end = last.copy()
        end[:-1] |= gap
        self._ins = self._by_pane(lo[start] - 1, rank[start], n_panes)
        self._del = self._by_pane(hi[end], rank[end], n_panes)
        self.applied = 0

    def _by_pane(self, pane, rank, n_panes) -> tuple:
        """(pairs in pane order, then key order; each pane's offsets),
        dropping the changes outside panes 0 .. n_panes - 1."""
        keep = (pane >= 0) & (pane < n_panes)
        both = np.sort((pane[keep] << 32) | rank[keep])
        offsets = np.searchsorted(both >> 32, np.arange(n_panes + 1))
        return self._pairs(both & 0xFFFFFFFF), offsets

    def fill(self) -> np.ndarray:
        return self._pairs(np.unique(self.ids[: self.window]))

    def _pairs(self, ids) -> np.ndarray:
        return pairs(self.keys[ids], self.n).astype(np.int32)

    def next_pane(self) -> tuple:
        """(insert, delete) pairs of the next pane of arrivals."""
        j = self.applied
        self.applied += 1
        return tuple(prs[off[j]: off[j + 1]]
                     for prs, off in (self._ins, self._del))

    def live(self, applied: int) -> np.ndarray:
        lo = applied * self.pane
        return self.keys[np.unique(self.ids[lo: lo + self.window])]


class Traffic:
    def __init__(self, config: dict, mix: dict, seed: int):
        t0 = time.perf_counter()
        rng = np.random.default_rng(seed)
        scales = [int(s) for s in config["tenant_scales"]]
        n_t = len(scales)
        self.names = [f"t{i:02d}" for i in range(n_t)]
        self.service = dict(config["service"])
        self.check_sample = int(mix["check_sample"])
        self.order, self.probs = popularity(n_t,
                                            float(config["zipf_constant"]))
        divisor = int(mix["pane_divisor"])
        k = int(mix["group_requests"])

        # the events' shape first: who is asked, who gets the extra pane;
        # then each tenant's stream long enough for the panes it gets
        buckets: dict = {}
        for i, s in enumerate(scales):
            buckets.setdefault(s, []).append(i)
        # group sizes from the pow-2 cover of the largest bucket down to 1:
        # the first ask of the first sweep reaches every tenant, so each
        # bucket's batched peel shape is set before any smaller group runs
        cover = 1 << (max(len(b) for b in buckets.values()) - 1).bit_length()
        sizes = [cover >> j for j in range(cover.bit_length())]
        sweep = []
        for r in range(int(mix["warm_rounds"])):
            for g in sizes:
                asked = [b[(r * g + m) % len(b)] for b in buckets.values()
                         for m in range(min(g, len(b)))]
                sweep.append((asked, [b[r % len(b)] for b in buckets.values()],
                              [b[(r + 1) % len(b)] for b in buckets.values()]))

        def ticks(n):
            return [tuple(draw(rng, self.order, self.probs, size).tolist()
                          for size in (k, 1, 1))
                    for _ in range(n)]

        warm = sweep + ticks(int(mix["warm_ticks"]))
        supply = ticks(int(mix["max_ticks"]))
        n_panes = np.zeros(n_t, np.int64)
        for _, extra, _ in warm + supply:
            n_panes += 1
            np.add.at(n_panes, extra, 1)
        self.windows = [
            _Window(rng, s, int(config["edge_factor"]), int(p),
                    max(1, (int(config["edge_factor"]) << s) // divisor),
                    config["initiator"])
            for s, p in zip(scales, n_panes)]
        # applied[v][i]: tenant i's panes after v update events
        self.applied = [np.zeros(n_t, np.int64)]
        self.warm_events = self._events(warm)
        self.events = self._events(supply)
        self.gen_s = time.perf_counter() - t0
        print(f"tenant_zipf: {n_t} tenants, {len(warm)} warm and "
              f"{len(supply)} supply ticks, "
              f"{sum(w.ids.size for w in self.windows)} arrivals, "
              f"generated in {self.gen_s:.3f} s", file=sys.stderr, flush=True)

    def _events(self, plan) -> list:
        out = []
        for asked, extra, solo in plan:
            out.append(("ingest_many", None, {
                t: w.next_pane() for t, w in zip(self.names, self.windows)}))
            self._log()
            out.append(("density_many", None,
                        [self.names[i] for i in asked]))
            for i in extra:
                out.append(("update", self.names[i],
                            self.windows[i].next_pane()))
                self._log()
            for i in solo:
                out.append(("density", self.names[i], None))
        return out

    def _log(self):
        self.applied.append(np.array([w.applied for w in self.windows]))

    def setup(self, make_service):
        """The service with every tenant's window filled; returns it."""
        svc = make_service(**self.service)
        for t, w in zip(self.names, self.windows):
            _ok(svc.create_tenant(t, n_nodes=w.n, capacity=w.capacity))
            _ok(svc.apply_updates(t, insert=w.fill()))
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
