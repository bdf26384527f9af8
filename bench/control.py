#!/usr/bin/env python3
"""The control: the plain reference put in the service's place, computed
one precision below what the configuration states (densities rounded to
bfloat16 instead of float32). A cell's comparison must find it not
correct; ``tests/bench`` holds the same at a size a test run can hold.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

prints each seed's compared numbers. It drives the cell's own traffic, at
the cell's own sizes and load, through the same harness as ``run.py``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from typing import Any  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclass
class Response:
    ok: bool
    op: str
    value: Any = None
    error: str | None = None


@dataclass
class Stats:
    n_inserted: int
    n_deleted: int


class ControlService:
    """The service's API, answered by the low-precision reference over its
    own live edge sets."""

    def __init__(self, **_settings):
        self.n = {}
        self.live = {}
        self.answers = {}
        self.pending = []
        self.results = {}
        self.tickets = 0

    def create_tenant(self, tenant, n_nodes, capacity=0, **_kw):
        self.n[tenant] = int(n_nodes)
        self.live[tenant] = set()
        return Response(True, "create_tenant")

    def _keys(self, tenant, edges):
        if edges is None:
            return set()
        e = np.asarray(edges, np.int64).reshape(-1, 2)
        lo, hi = e.min(axis=1), e.max(axis=1)
        keep = lo != hi
        return set((lo[keep] * self.n[tenant] + hi[keep]).tolist())

    def apply_updates(self, tenant, insert=None, delete=None):
        live = self.live[tenant]
        dels = self._keys(tenant, delete) & live
        live -= dels
        ins = self._keys(tenant, insert) - live
        live |= ins
        self.answers.pop(tenant, None)
        return Response(True, "apply_updates", Stats(len(ins), len(dels)))

    def ingest_many(self, updates):
        return Response(True, "ingest_many", {
            t: self.apply_updates(t, insert=ins, delete=dels).value
            for t, (ins, dels) in updates.items()})

    def _answer(self, tenant):
        from bench.reference.peel import peel

        if tenant not in self.answers:
            keys = np.fromiter(sorted(self.live[tenant]), np.int64)
            self.answers[tenant] = peel(self.n[tenant], keys, control=True)
        return self.answers[tenant]

    def density(self, tenant):
        d, _, passes = self._answer(tenant)
        return Response(True, "density", {
            "density": float(d), "passes": passes, "refreshed": False,
            "pruned": False})

    def submit_density(self, tenant):
        self.pending.append((self.tickets, tenant))
        self.tickets += 1
        return self.tickets - 1

    def flush(self):
        pending, self.pending = self.pending, []
        for ticket, tenant in pending:
            self.results[ticket] = self.density(tenant)
        return len(pending)

    def poll(self, ticket):
        return self.results.pop(ticket, None)

    def membership(self, tenant):
        return Response(True, "membership", {"mask": self._answer(tenant)[1]})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import run

    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    from bench.harness import run_cell

    t_start = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        result, lines = run_cell(run.BENCH, manifest, args.workload, seed,
                                 args.seconds, False, t_start,
                                 make_service=ControlService)
        print(f"control {args.workload} seed={seed} "
              f"correct={result['correct']} " + json.dumps(
                  {k: v["value"] for k, v in result["checks"].items()}),
              flush=True)
        for line in lines[:2]:
            print("control   " + line, flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
