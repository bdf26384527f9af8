"""Sharded streaming engine (ISSUE 3): the sharded==single-device parity
oracle, zero-recompile stability of the sharded executables, and the
registry/service opt-in wiring.

The load-bearing claim: because every cross-shard reduction in the sharded
engine (update histograms, peel degree deltas, scalar density state) is an
exact int32 psum, ``DeltaEngine(sharded=True)`` returns the *bit-identical*
(density, mask, passes) triple of the single-device engine — on a 1-device
mesh (asserted in-process below) and on forced multi-device CPU meshes
(asserted in subprocesses, density additionally fp32-checked against the
numpy oracle, per the acceptance criteria). Those subprocesses are pinned
to the CPU (``JAX_PLATFORMS=cpu``): they test fabricated meshes, not the
chip.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.pbahmani import pbahmani_np
from repro.graphs.graph import Graph
from repro.stream import DeltaEngine, GraphRegistry, StreamService
from repro.utils.mesh import make_mesh_auto

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_multidev(script: str, devices: int) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def materialize(edges: set, n_nodes: int) -> Graph:
    pairs = (np.asarray(sorted(edges), dtype=np.int64) if edges
             else np.zeros((0, 2), np.int64))
    return Graph.from_edges(pairs, n_nodes=n_nodes)


def stream_steps(rng, n_nodes, n_batches, max_batch):
    edges: set = set()
    for step in range(n_batches):
        ins = rng.integers(0, n_nodes, (int(rng.integers(1, max_batch)), 2))
        dels = None
        if edges and step % 2:
            pool = np.asarray(sorted(edges))
            dels = pool[rng.random(len(pool)) < 0.3]
            for u, v in dels:
                edges.discard((int(u), int(v)))
        for u, v in ins:
            u, v = int(u), int(v)
            if u != v:
                edges.add((min(u, v), max(u, v)))
        yield ins, dels, edges


# ---------------------------------------------------------------------------
# 1-device mesh, in-process: bit-identity is exact
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("pruned", [True, False])
def test_sharded_bit_identical_on_one_device_mesh(pruned):
    """Acceptance criterion: on a 1-device mesh, DeltaEngine(sharded=True)
    returns bit-identical (density, mask, passes) to the single-device
    engine — across warm, pruned AND epoch-refresh query paths."""
    rng = np.random.default_rng(42)
    n = 200
    mesh = make_mesh_auto((1,), ("shard",))
    sh = DeltaEngine(n_nodes=n, refresh_every=4, pruned=pruned,
                     sharded=True, mesh=mesh)
    single = DeltaEngine(n_nodes=n, refresh_every=4, pruned=pruned)
    assert sh.n_shards == 1
    for step, (ins, dels, edges) in enumerate(
            stream_steps(rng, n, n_batches=8, max_batch=50)):
        sh.apply_updates(insert=ins, delete=dels)
        single.apply_updates(insert=ins, delete=dels)
        qs, qu = sh.query(), single.query()
        assert qs.density == qu.density, (step, qs.density, qu.density)
        assert np.array_equal(qs.mask, qu.mask), step
        assert qs.passes == qu.passes, step
        assert qs.refreshed == qu.refreshed, step
        rho, _, passes = pbahmani_np(materialize(edges, n))
        assert qs.density == pytest.approx(rho, rel=1e-6, abs=1e-9)
        assert qs.passes == passes


def test_sharded_zero_recompiles_after_warmup():
    """The pow-2 shape contract extends to the sharded executables: after
    one warm update+query cycle, repeated same-capacity batches must not
    move DeltaEngine.compile_count() (which includes SHARDED_JITS)."""
    rng = np.random.default_rng(7)
    eng = DeltaEngine(n_nodes=500, capacity=4096, refresh_every=10**9,
                      sharded=True)
    eng.apply_updates(insert=rng.integers(0, 500, (48, 2)))
    eng.query()
    before = DeltaEngine.compile_count()
    for _ in range(10):
        ins = rng.integers(0, 500, (30, 2))
        dels = eng.buffer.live_pairs()[:10]
        eng.apply_updates(insert=ins, delete=dels)
        eng.query()
    assert DeltaEngine.compile_count() == before, "sharded hot path recompiled"


def test_sharded_engine_validation():
    with pytest.raises(ValueError, match="power-of-two"):
        DeltaEngine(n_nodes=50, sharded=True,
                    mesh=_FakeMesh())  # non-pow-2 device count


class _FakeMesh:
    """Minimal stand-in exposing a 3-device shape (mesh construction with a
    fabricated device count needs a subprocess; validation does not)."""
    shape = {"shard": 3}
    axis_names = ("shard",)


def test_sharded_cbds_matches_np():
    """CBDS on a sharded tenant == oracle. The peel inside cbds() runs
    through the shard_map tier (ISSUE 9 bugfix: it used to re-upload the
    state to a single device), so this doubles as a routing check."""
    from repro.core.cbds import cbds_np

    rng = np.random.default_rng(11)
    n = 100
    eng = DeltaEngine(n_nodes=n, sharded=True)
    edges = None
    for ins, dels, edges in stream_steps(rng, n, n_batches=4, max_batch=60):
        eng.apply_updates(insert=ins, delete=dels)
    res = eng.cbds()
    ref = cbds_np(materialize(edges, n))
    assert res["density"] == pytest.approx(ref["density"], rel=1e-5)


def test_registry_and_service_sharded_opt_in():
    reg = GraphRegistry(max_tenants=4)
    a = reg.register("plain", n_nodes=64)
    b = reg.register("sharded", n_nodes=64, sharded=True)
    assert not a.sharded and a.n_shards == 1
    assert b.sharded and b.n_shards >= 1
    st = reg.stats("sharded")
    assert st.sharded and st.n_shards == b.n_shards
    # re-registering with a conflicting sharded flag raises, like n_nodes/eps
    assert reg.register("sharded", n_nodes=64, sharded=True) is b
    with pytest.raises(ValueError, match="sharded"):
        reg.register("plain", n_nodes=64, sharded=True)

    svc = StreamService(max_tenants=4)
    r = svc.create_tenant("t", n_nodes=64, sharded=True)
    assert r.ok and r.value["n_shards"] >= 1
    svc.apply_updates("t", insert=np.array([[0, 1], [1, 2], [0, 2]]))
    d = svc.density("t")
    assert d.ok and d.value["density"] == pytest.approx(1.0)
    st = svc.stats("t")
    assert st.ok and st.value.sharded


# ---------------------------------------------------------------------------
# forced multi-device CPU meshes (subprocess, like tests/test_distributed.py)
# ---------------------------------------------------------------------------
MULTIDEV_SCRIPT = """
import numpy as np, jax
from repro.stream.delta import DeltaEngine
from repro.core.pbahmani import pbahmani_np
from repro.graphs.graph import Graph
from repro.utils.mesh import make_mesh_auto

n_dev = len(jax.devices())
assert n_dev == %d, n_dev
mesh = make_mesh_auto((n_dev,), ("shard",))
rng = np.random.default_rng(3)
n = 300
engines = {
    "sharded_pruned": DeltaEngine(n_nodes=n, refresh_every=4,
                                  sharded=True, mesh=mesh),
    "sharded_plain": DeltaEngine(n_nodes=n, refresh_every=4, pruned=False,
                                 sharded=True, mesh=mesh),
    "single": DeltaEngine(n_nodes=n, refresh_every=4),
}
edges = set()
for step in range(8):
    ins = rng.integers(0, n, (60, 2))
    dels = None
    if edges and step %% 2:
        pool = np.asarray(sorted(edges))
        dels = pool[rng.random(len(pool)) < 0.3]
        for u, v in dels:
            edges.discard((int(u), int(v)))
    for u, v in ins:
        u, v = int(u), int(v)
        if u != v:
            edges.add((min(u, v), max(u, v)))
    qs = {}
    for name, e in engines.items():
        e.apply_updates(insert=ins, delete=dels)
        qs[name] = e.query()
    pairs = (np.asarray(sorted(edges), dtype=np.int64) if edges
             else np.zeros((0, 2), np.int64))
    rho, mask, passes = pbahmani_np(Graph.from_edges(pairs, n_nodes=n))
    ref = qs["single"]
    # density must match the oracle to fp32 tolerance (acceptance), and the
    # sharded triples are in fact bit-identical to the single-device engine
    assert abs(ref.density - rho) <= 1e-6 * max(rho, 1.0)
    for name, q in qs.items():
        assert q.density == ref.density, (step, name, q.density, ref.density)
        assert np.array_equal(q.mask, ref.mask), (step, name)
        assert q.passes == ref.passes == passes, (step, name)

# steady state compiles nothing new on the multi-device mesh either:
# fixed batch shapes at fixed capacity, one warm cycle, then flat
eng = DeltaEngine(n_nodes=n, capacity=4096, refresh_every=10**9,
                  sharded=True, mesh=mesh)
eng.apply_updates(insert=rng.integers(0, n, (48, 2)))
eng.query()
before = DeltaEngine.compile_count()
for _ in range(6):
    ins = rng.integers(0, n, (30, 2))
    dels = eng.buffer.live_pairs()[:10]
    eng.apply_updates(insert=ins, delete=dels)
    eng.query()
assert DeltaEngine.compile_count() == before, "multi-device path recompiled"
print("OK")
"""


@pytest.mark.parametrize("devices", [2, 4])
def test_sharded_parity_multidevice(devices):
    """Acceptance criterion: on forced 2- and 4-device CPU meshes the
    sharded engine's densities match the cold recompute to fp32 tolerance
    (they are in fact bit-identical to the single-device engine)."""
    out = run_multidev(MULTIDEV_SCRIPT % devices, devices=devices)
    assert "OK" in out


# ---------------------------------------------------------------------------
# fused + sharded (ISSUE 9): vmap-inside-shard_map tenant bucket stacks
# ---------------------------------------------------------------------------
FUSED_MULTIDEV_SCRIPT = """
import numpy as np, jax
from repro.stream.registry import GraphRegistry
from repro.stream.delta import DeltaEngine
from repro.stream.fused import FusedEngine, ingest_group, query_group
from repro.obs.audit import AUDITOR

n_dev = len(jax.devices())
assert n_dev == %d, n_dev
N = 96
reg = GraphRegistry(fused=True, sharded=True)
names = ["a", "b", "c", "d"]
solo = {t: DeltaEngine(n_nodes=N) for t in names}
for t in names:
    eng = reg.register(t, n_nodes=N)
    assert isinstance(eng, FusedEngine) and eng.sharded, t
    assert eng.kind == "fused+sharded" and eng.n_shards == n_dev


def step_ups(step, roster):
    ups = {}
    for i, t in enumerate(roster):
        r = np.random.default_rng(100 + 7 * step + i)
        e = r.integers(0, N, size=(40, 2))
        e = e[e[:, 0] != e[:, 1]]
        dele = None
        if step >= 3:  # from step 3 on, delete ALL of the previous insert
            prev = np.random.default_rng(
                100 + 7 * (step - 1) + i).integers(0, N, size=(40, 2))
            dele = prev[prev[:, 0] != prev[:, 1]]
        ups[t] = (e, dele)
    return ups


# bit-identity: every tenant of the sharded bucket stack vs its own solo
# single-device engine, across ingest churn including deletes
for step in range(8):
    ups = step_ups(step, names)
    ingest_group(ups, reg.engines())
    for t in names:
        solo[t].apply_updates(insert=ups[t][0], delete=ups[t][1])
    res = query_group(reg.engines())
    for t in names:
        qs = solo[t].query()
        assert res[t].density == qs.density, (step, t)
        assert res[t].passes == qs.passes, (step, t)
        assert np.array_equal(np.asarray(res[t].mask),
                              np.asarray(qs.mask)), (step, t)

# cbds and fixed-round refinement route through the same sharded batched
# tier and stay bit-identical to the solo engines
for t in ["a", "b", "c"]:
    cf, cs = reg.get(t).cbds(), solo[t].cbds()
    assert cf["density"] == cs["density"], t
    assert cf["n_legit"] == cs["n_legit"], t
rf = query_group({t: reg.get(t) for t in ["a", "b", "c"]},
                 refine=True, target_gap=-1.0, max_refine_rounds=4)
for t in ["a", "b", "c"]:
    rs = solo[t].query(refine=True, target_gap=-1.0, max_refine_rounds=4)
    assert rf[t].density == rs.density, t
    assert rf[t].certificate.rel_gap == rs.certificate.rel_gap, t

# steady state on the live mesh: stationary churn must not trip the
# recompile auditor (a NEW plan-bucket shape may compile once — a
# first-call event, not a steady-state recompile)
for step in range(8, 14):
    ups = step_ups(step, names)
    ingest_group(ups, reg.engines())
    query_group(reg.engines())
AUDITOR.sync()
assert AUDITOR.n_steady_recompiles == 0, AUDITOR.snapshot(last=20)

# join/evict churn: swapping a same-shape tenant into the warm bucket is a
# lane-row swap, not a compile event — ingest+query over the full roster
# (the warmed 4-lane group shape) stays strictly flat
reg.remove("d")
reg.register("e", n_nodes=N)
c0 = DeltaEngine.compile_count()
ups = step_ups(1, ["a", "b", "c", "e"])
ingest_group(ups, reg.engines())
query_group(reg.engines())
c1 = DeltaEngine.compile_count()
assert c1 == c0, (c0, c1)
print("OK fused+sharded")
"""


@pytest.mark.parametrize("devices", [2, 4])
def test_fused_sharded_parity_multidevice(devices):
    """ISSUE 9 acceptance: fused+sharded tenants (vmap-inside-shard_map
    bucket stacks) return per-tenant results bit-identical to the solo
    single-device engine on forced multi-device meshes, with zero audited
    steady-state recompiles and compile-free join/evict on the live mesh."""
    out = run_multidev(FUSED_MULTIDEV_SCRIPT % devices, devices=devices)
    assert "OK fused+sharded" in out
