#!/usr/bin/env python3
"""Chip smoke test: drive the densest-subgraph serving path once on a TPU.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py                # one chip: phases A, B and C
    python chip_smoke.py --four-chips   # four chips: the sharded phase only

Phases (data is generated from ``--seed``):

  0. device: JAX must find a TPU; there is no CPU fallback.
  A. static Graph500 deployment: RMAT scale 18, edge factor 16
     (A/B/C = 0.57/0.19/0.19). Cold ``pbahmani`` unpruned and pruned, then
     ``refine(target_gap=0.01)``. Peels must be bit-identical to
     ``pbahmani_np``; the refine certificate must equal a numpy replay of
     its rounds, and the certificate sandwich is checked against the exact
     flow solver on a graph small enough for it.
  B. stream and tenants through ``StreamService``: one solo pruned tenant
     over a sliding window of power-law edge inserts, and one fused service
     with 16 tenants of heavy-tailed sizes, two of them dense-bucket
     near-cliques whose degrees exceed 256. Every response must be ``ok``,
     every answer must match ``pbahmani_np``, flushes must take the batched
     path, and the steady state must compile nothing.
  C. kernel tier: ``kernel=True`` on an RMAT scale-16 static peel and on a
     pruned streaming tenant must be bit-identical to ``kernel=False``, and
     the programs must contain the compiled Pallas kernel.

With ``--four-chips`` only the sharded phase runs: a sharded streaming
tenant and a fused+sharded bucket of four tenants, each compared with the
solo single-device engine and ``pbahmani_np``.

Lines before the last are smoke output (phase wall time, compile seconds,
persistent-cache hits, peak device bytes), not metrics. The last line is one
JSON object naming the device. Any failed check exits nonzero before it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from contextlib import contextmanager

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

RMAT_SCALE = 18          # largest scale under the 2^24-lane exactness envelope
EDGE_FACTOR = 16
KERNEL_SCALE = 16        # kernel tier: segsum temporaries grow 512 B per lane
STREAM_NODES = 1 << 16
SHARDED_STREAM_NODES = 1 << 18
WINDOW_EDGES = 1 << 17
N_WINDOWS = 8
LIVE_WINDOWS = 4
TARGET_GAP = 0.01
# a scale-18 refine round costs seconds on the scatter tier and RMAT needs
# 50-70 rounds to a 1% gap; the smoke runs a fixed budget of rounds and
# reports whether the target was reached
REFINE_ROUNDS = 16
# heavy-tailed fused tenant sizes; the two 400-node near-cliques sit in the
# dense (V <= 512) bucket with degrees above 256
TENANT_SIZES = (4096, 2048, 1024, 1024, 512, 400, 400, 256, 256, 192, 128,
                128, 96, 64, 64, 64)
NEAR_CLIQUE_P = 0.9
ORACLE_NODES = 300       # the exact flow solver is pure Python


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# device, compile accounting
# ---------------------------------------------------------------------------
def tpu_device() -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; JAX found {devs[0].platform}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileClock:
    """Sums JAX's backend-compile durations (persistent-cache hits
    included, which take far less) and counts persistent-cache hits."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


@contextmanager
def phase(name: str, clock: CompileClock):
    import jax

    t0, c0, h0 = time.perf_counter(), clock.seconds, clock.cache_hits
    yield
    stats = jax.devices()[0].memory_stats() or {}
    print(f"smoke phase {name}: wall_s={time.perf_counter() - t0} "
          f"compile_s={clock.seconds - c0} "
          f"cache_hits={clock.cache_hits - h0} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}", flush=True)


@contextmanager
def timed(label: str):
    """Print the wall time of one step, so a run cut short still shows
    where its time went."""
    t0 = time.perf_counter()
    yield
    print(f"smoke step {label}: wall_s={time.perf_counter() - t0}",
          flush=True)


# ---------------------------------------------------------------------------
# references
# ---------------------------------------------------------------------------
def graph_of(keys: np.ndarray, n_nodes: int):
    """Graph of the undirected edges encoded as ``u * n_nodes + v``."""
    from repro.graphs.graph import Graph

    keys = np.asarray(keys, np.int64)
    return Graph.from_edges(
        np.stack([keys // n_nodes, keys % n_nodes], axis=1), n_nodes=n_nodes)


def check_triple(what: str, got, ref, n_nodes: int | None = None) -> None:
    """(density, mask, passes) bit-identical to the reference peel; the
    device computes density in float32."""
    d, mask, passes = got
    rd, rmask, rpasses = ref
    mask, rmask = np.asarray(mask, bool), np.asarray(rmask, bool)
    if n_nodes is not None:
        check(not mask[n_nodes:].any() and not rmask[n_nodes:].any(),
              f"{what}: members beyond n_nodes")
        mask, rmask = mask[:n_nodes], rmask[:n_nodes]
    check(np.float32(d) == np.float32(rd),
          f"{what}: density {d!r} != reference {rd!r}")
    check(int(passes) == int(rpasses),
          f"{what}: passes {passes} != reference {rpasses}")
    check(np.array_equal(mask, rmask), f"{what}: mask differs")


def replay_refine(graph, seed_mask: np.ndarray, rounds: int):
    """The certificate ``refine`` must produce after ``rounds`` rounds,
    recomputed with the numpy round oracle and the exact dual fraction."""
    from repro.refine.certify import (
        better_fraction, dual_fraction, make_certificate, max_fraction,
        refine_round_np,
    )

    half = graph.n_directed // 2
    lv = np.append(seed_mask, False)
    u = np.minimum(graph.src[:half], graph.n_nodes)
    v = np.minimum(graph.dst[:half], graph.n_nodes)
    seed_ne, seed_nv = int((lv[u] & lv[v]).sum()), int(seed_mask.sum())
    best = (np.float32(seed_ne) / np.float32(max(seed_nv, 1)), seed_ne,
            seed_nv, seed_mask)
    deg = graph.degrees()
    loads = np.zeros(graph.n_nodes, np.int64)
    dual = None
    for t in range(1, rounds + 1):
        loads, best, _ = refine_round_np(graph.src, graph.dst, deg,
                                         graph.n_edges, loads, best, 0.0)
        num, den = dual_fraction(loads, t)
        if dual is None or better_fraction(num, den, *dual):
            dual = (num, den)
    b_ne, b_nv = max_fraction((best[1], best[2]), (seed_ne, seed_nv))
    return make_certificate(b_ne, b_nv, *dual)


def induced_counts(graph, mask: np.ndarray) -> tuple[int, int]:
    s, d = graph.src[: graph.n_directed], graph.dst[: graph.n_directed]
    return int((mask[s] & mask[d]).sum()) // 2, int(mask.sum())


def power_law_window(rng, n_nodes: int, n_edges: int) -> np.ndarray:
    """``n_edges`` seeded inserts whose endpoints follow a Zipf-like
    popularity (weight (rank+1)^-0.8), as undirected keys ``u*n+v``, u<v."""
    p = (np.arange(n_nodes) + 1.0) ** -0.8
    ends = rng.choice(n_nodes, size=(n_edges, 2), p=p / p.sum())
    ends = ends[ends[:, 0] != ends[:, 1]]
    lo, hi = ends.min(axis=1), ends.max(axis=1)
    return np.unique(lo.astype(np.int64) * n_nodes + hi)


def keys_to_pairs(keys: np.ndarray, n_nodes: int) -> np.ndarray:
    return np.stack([keys // n_nodes, keys % n_nodes], axis=1)


def sliding_windows(rng, n_nodes: int):
    """Yield (insert_keys, delete_keys, live_keys) per window: once
    ``LIVE_WINDOWS`` windows are live, each new window expires the oldest
    (its edges not re-sent by a live window are deleted)."""
    windows: list[np.ndarray] = []
    for _ in range(N_WINDOWS):
        new = power_law_window(rng, n_nodes, WINDOW_EDGES)
        windows.append(new)
        dels = np.zeros(0, np.int64)
        if len(windows) > LIVE_WINDOWS:
            old = windows.pop(0)
            dels = np.setdiff1d(old, np.concatenate(windows))
        yield new, dels, np.unique(np.concatenate(windows))


def ok(resp, what: str):
    check(resp.ok, f"{what}: {resp.error}")
    return resp.value


# ---------------------------------------------------------------------------
# phase A: static Graph500
# ---------------------------------------------------------------------------
def phase_static(seed: int) -> None:
    from repro.core import pbahmani, pbahmani_np
    from repro.graphs.generators import planted_dense, rmat
    from repro.refine import refine
    from repro.refine.certify import oracle_check

    g = rmat(RMAT_SCALE, edge_factor=EDGE_FACTOR, seed=seed)
    print(f"smoke rmat scale={RMAT_SCALE} n_nodes={g.n_nodes} "
          f"n_edges={g.n_edges} lanes={g.src.shape[0]}", flush=True)
    ref = pbahmani_np(g)
    for pruned in (False, True):
        with timed(f"A pbahmani pruned={pruned}"):
            got = pbahmani(g, pruned=pruned)
        check_triple(f"pbahmani(pruned={pruned})", got, ref)

    with timed("A refine"):
        res = refine(g, target_gap=TARGET_GAP, max_rounds=REFINE_ROUNDS)
    with timed("A refine replay (host)"):
        replay = replay_refine(g, ref[1], res.rounds)
    check(res.certificate == replay,
          "refine certificate differs from the numpy replay")
    check(induced_counts(g, res.mask) == (res.certificate.best_ne,
                                          res.certificate.best_nv),
          "refine mask does not induce the certified counts")
    check(res.density >= ref[0], "refine fell below its seed")
    print(f"smoke refine rounds={res.rounds} converged={res.converged} "
          f"density={res.density} dual_bound={res.dual_bound} "
          f"rel_gap={res.rel_gap}", flush=True)

    with timed("A oracle_check"):
        small, _, _ = planted_dense(ORACLE_NODES, 40, seed=seed)
        oracle_check(small, refine(small, target_gap=TARGET_GAP).certificate)


# ---------------------------------------------------------------------------
# phase B: stream and tenants
# ---------------------------------------------------------------------------
def tenant_edges(rng, n: int, near_clique: bool) -> np.ndarray:
    if near_clique:
        iu = np.triu_indices(n, k=1)
        keep = rng.random(iu[0].shape[0]) < NEAR_CLIQUE_P
        return iu[0][keep].astype(np.int64) * n + iu[1][keep]
    return power_law_window(rng, n, 8 * n)


def churn(rng, keys: np.ndarray, n: int, k: int = 16):
    """Delete ``k`` live edges and insert ``k`` fresh pairs."""
    dels = rng.choice(keys, size=min(k, keys.size), replace=False)
    ins = power_law_window(rng, n, k)
    live = np.union1d(np.setdiff1d(keys, dels), ins)
    return ins, dels, live


def phase_stream(seed: int) -> None:
    from repro.core import pbahmani_np
    from repro.obs.trace import get_tracer
    from repro.stream import StreamService
    from repro.stream.buffer import next_pow2
    from repro.stream.fused import DENSE_NODE_CAP

    rng = np.random.default_rng(seed + 1)
    n = STREAM_NODES
    svc = StreamService()
    ok(svc.create_tenant("stream", n_nodes=n,
                         capacity=LIVE_WINDOWS * WINDOW_EDGES), "create")
    for w, (ins, dels, live) in enumerate(sliding_windows(rng, n)):
        with timed(f"B window {w}"):
            ok(svc.apply_updates("stream", insert=keys_to_pairs(ins, n),
                                 delete=keys_to_pairs(dels, n)), "ingest")
            val = ok(svc.density("stream"), "density")
        if w in (0, N_WINDOWS - 1):
            mask = ok(svc.membership("stream"), "membership")["mask"]
            check_triple(f"stream window {w}",
                         (val["density"], mask, val["passes"]),
                         pbahmani_np(graph_of(live, n)), n_nodes=n)

    tsvc = StreamService(fused=True, coalesce_window_ms=1e9)
    live = {}
    for i, size in enumerate(TENANT_SIZES):
        name = f"t{i:02d}"
        live[name] = tenant_edges(rng, size, near_clique=size == 400)
        ok(tsvc.create_tenant(name, n_nodes=size,
                              capacity=next_pow2(2 * live[name].size)),
           "create")
    ok(tsvc.ingest_many({t: (keys_to_pairs(k, TENANT_SIZES[i]), None)
                         for i, (t, k) in enumerate(live.items())}),
       "ingest_many")
    dense_hot = [t for i, t in enumerate(live)
                 if TENANT_SIZES[i] <= DENSE_NODE_CAP
                 and graph_of(live[t], TENANT_SIZES[i]).degrees().max() > 256]
    check(len(dense_hot) >= 2, f"dense tenants above degree 256: {dense_hot}")

    for sweep in range(3):  # sweep 0 warms up, 1 warms the churn batch
        if sweep:
            updates = {}
            for i, t in enumerate(live):
                ins, dels, live[t] = churn(rng, live[t], TENANT_SIZES[i])
                updates[t] = (keys_to_pairs(ins, TENANT_SIZES[i]),
                              keys_to_pairs(dels, TENANT_SIZES[i]))
            ok(tsvc.ingest_many(updates), "ingest_many")
        refs = {t: pbahmani_np(graph_of(k, TENANT_SIZES[i]))
                for i, (t, k) in enumerate(live.items())}
        board = ok(tsvc.top_k_densest(k=len(live)), "top_k_densest")
        check(len(board) == len(live), "top_k lost tenants")
        for row in board:
            ref = refs[row["tenant"]][0]
            check(np.float32(row["density"]) == np.float32(ref),
                  f"top_k {row['tenant']} sweep {sweep}: {row['density']!r} "
                  f"!= reference {ref!r}")
        tickets = {t: tsvc.submit_density(t) for t in live}
        check(tsvc.flush() == len(live), "flush answered too few")
        for i, (t, ticket) in enumerate(tickets.items()):
            val = ok(tsvc.poll(ticket), f"flush {t}")
            mask = ok(tsvc.membership(t), "membership")["mask"]
            check_triple(f"tenant {t} sweep {sweep}",
                         (val["density"], mask, val["passes"]), refs[t],
                         n_nodes=TENANT_SIZES[i])

    fallbacks = get_tracer().registry.counter(
        "flush_fallback_total", op="flush", tenant="-").value
    check(fallbacks == 0, f"{fallbacks} flushes fell back to per-tenant")
    for s in (svc, tsvc):
        steady = s.metrics_snapshot()["audit"]["audited_steady_recompiles"]
        check(steady == 0, f"{steady} steady-state recompiles")
    print(f"smoke tenants={len(live)} dense_above_256={len(dense_hot)} "
          f"flush_fallbacks={fallbacks}", flush=True)


# ---------------------------------------------------------------------------
# phase C: kernel tier
# ---------------------------------------------------------------------------
def has_kernel(fn, *args) -> bool:
    return "tpu_custom_call" in fn.lower(*args).as_text()


def phase_kernel(seed: int) -> None:
    import jax.numpy as jnp

    from repro.core import pbahmani, pbahmani_np
    from repro.core.pbahmani import _pbahmani_jit
    from repro.core.prune import _bucket_peel_jit
    from repro.graphs.generators import rmat
    from repro.stream import StreamService

    g = rmat(KERNEL_SCALE, edge_factor=EDGE_FACTOR, seed=seed)
    ref = pbahmani_np(g)
    for pruned in (False, True):
        got = pbahmani(g, pruned=pruned, kernel=True)
        check_triple(f"kernel pbahmani(pruned={pruned})", got,
                     pbahmani(g, pruned=pruned, kernel=False))
        check_triple(f"kernel pbahmani(pruned={pruned}) vs numpy", got, ref)
    src, dst = (jnp.asarray(a) for a in g.dst_sorted())
    check(has_kernel(_pbahmani_jit, src, dst, g.n_nodes,
                     jnp.asarray(g.n_edges, jnp.int32), 0.0, True),
          "static kernel peel has no compiled Pallas kernel")

    rng = np.random.default_rng(seed + 2)
    n = 1 << 12
    svc = StreamService()
    for name, kernel in (("scatter", False), ("kernel", True)):
        ok(svc.create_tenant(name, n_nodes=n, kernel=kernel,
                             capacity=1 << 15), "create")
    for w in range(3):
        ins = keys_to_pairs(power_law_window(rng, n, 1 << 13), n)
        triples = []
        for name in ("scatter", "kernel"):
            ok(svc.apply_updates(name, insert=ins), "ingest")
            val = ok(svc.density(name), "density")
            check(val["pruned"], f"{name} query skipped the pruned path")
            mask = ok(svc.membership(name), "membership")["mask"]
            triples.append((val["density"], mask, val["passes"]))
        check_triple(f"kernel tenant window {w}", triples[1], triples[0])
    eng = svc.registry.get("kernel")
    plan = eng._plan
    lanes = jnp.zeros(plan.bucket_e, jnp.int32)
    one = jnp.asarray(1, jnp.int32)
    check(has_kernel(_bucket_peel_jit, lanes, lanes, one, one,
                     jnp.asarray(0.0, jnp.float32), one, eng.eps,
                     *plan.buckets, True),
          "pruned kernel bucket peel has no compiled Pallas kernel")


# ---------------------------------------------------------------------------
# four chips: sharded and fused+sharded tenants
# ---------------------------------------------------------------------------
def shard_devices(arr) -> int:
    return len({s.device for s in arr.addressable_shards})


def phase_sharded(seed: int, n_devices: int) -> None:
    from repro.core import pbahmani_np
    from repro.stream import StreamService

    rng = np.random.default_rng(seed + 3)
    n = SHARDED_STREAM_NODES
    svc = StreamService()
    cap = LIVE_WINDOWS * WINDOW_EDGES
    ok(svc.create_tenant("sharded", n_nodes=n, sharded=True, capacity=cap),
       "create")
    ok(svc.create_tenant("solo", n_nodes=n, capacity=cap), "create")
    for w, (ins, dels, live) in enumerate(sliding_windows(rng, n)):
        triples = []
        for name in ("solo", "sharded"):
            with timed(f"{name} window {w}"):
                ok(svc.apply_updates(name, insert=keys_to_pairs(ins, n),
                                     delete=keys_to_pairs(dels, n)), "ingest")
                val = ok(svc.density(name), "density")
            mask = ok(svc.membership(name), "membership")["mask"]
            triples.append((val["density"], mask, val["passes"]))
        check_triple(f"sharded window {w}", triples[1], triples[0])
        if w in (0, N_WINDOWS - 1):
            check_triple(f"sharded window {w} vs numpy", triples[1],
                         pbahmani_np(graph_of(live, n)), n_nodes=n)
    eng = svc.registry.get("sharded")
    check(shard_devices(eng._src) == n_devices,
          f"sharded tenant spans {shard_devices(eng._src)} devices")

    fsvc = StreamService(fused=True, sharded=True, coalesce_window_ms=1e9)
    size = 1 << 12
    live = {}
    for i in range(4):
        live[f"f{i}"] = power_law_window(rng, size, 8 * size)
        for name, kw in ((f"f{i}", {}), (f"s{i}", {"fused": False,
                                                    "sharded": False})):
            ok(fsvc.create_tenant(name, n_nodes=size, capacity=1 << 16, **kw),
               "create")
            ok(fsvc.apply_updates(name, insert=keys_to_pairs(live[f"f{i}"],
                                                             size)), "ingest")
    tickets = {t: fsvc.submit_density(t) for t in live}
    fsvc.flush()
    for t, ticket in tickets.items():
        val = ok(fsvc.poll(ticket), f"flush {t}")
        got = (val["density"], ok(fsvc.membership(t), "membership")["mask"],
               val["passes"])
        solo = "s" + t[1:]
        sval = ok(fsvc.density(solo), "density")
        check_triple(f"fused+sharded {t} vs solo", got,
                     (sval["density"],
                      ok(fsvc.membership(solo), "membership")["mask"],
                      sval["passes"]))
        check_triple(f"fused+sharded {t} vs numpy", got,
                     pbahmani_np(graph_of(live[t], size)), n_nodes=size)
    batch = fsvc.registry.get("f0").batch
    check(batch.sharded and shard_devices(batch._src) == n_devices,
          "fused+sharded stack is not spread over the mesh")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded phase, on four chips")
    args = ap.parse_args(argv)

    device = tpu_device()
    print(f"smoke device kind={device['kind']} count={device['count']}",
          flush=True)
    from repro.utils.compile_cache import enable_compile_cache

    print(f"smoke compile_cache={enable_compile_cache()}", flush=True)
    clock = CompileClock()
    try:
        if args.four_chips:
            check(device["count"] == 4,
                  f"--four-chips needs 4 chips, found {device['count']}")
            with phase("sharded", clock):
                phase_sharded(args.seed, device["count"])
        else:
            with phase("A", clock):
                phase_static(args.seed)
            with phase("B", clock):
                phase_stream(args.seed)
            with phase("C", clock):
                phase_kernel(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
