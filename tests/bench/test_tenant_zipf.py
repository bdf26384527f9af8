"""The many-tenant cell ``tenants-zipf`` at a size a CPU test run holds.

``tiny-zipf`` is the cell's own generator (``bench/traffic/tenant_zipf.py``)
over 8 fused tenants of rank-size scales 10 down to 7 at edge factor 4,
added to a copy of ``bench/`` as new files only, beside the tiny cells of
``test_chip_bench``: its answers, grouped ones included, equal the plain
reference, the control and the grouped faults come out not correct, the
generator repeats for a seed, its popularity ranks hold for every seed,
its draws follow the Zipf weights, ``live()`` matches the events'
incremental log, and a traced run reports the fused layer's readers. The
readers themselves are checked on hand-made records, and read nothing
from a ring without the fused layer's attributes.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import time
from types import SimpleNamespace

import numpy as np
import pytest

# first: it puts the checkout's root on the path for ``bench``
from test_chip_bench import (  # noqa: F401
    BENCH, GROUPED_FAULTS, ROOT, SINGLE_FAULTS, _faulty)

from bench import harness  # noqa: E402
from bench.control import ControlService  # noqa: E402
from bench.harness import Context, Update  # noqa: E402
from bench.reference.peel import peel  # noqa: E402
from repro.obs.trace import SpanRecord  # noqa: E402

CELL = "tiny-zipf"
# the real cell's config and mix, cut to 8 tenants (one bucket each of
# scales 10 and 9, dense and scatter sides of V = 512, and two buckets of
# three) with short panes; every answer of the window is checked
CONFIG = dict(tenant_scales=[10, 9, 8, 8, 8, 7, 7, 7], edge_factor=4)
MIX = dict(pane_divisor=32, group_requests=4, max_ticks=400, warm_rounds=2,
           warm_ticks=1, check_sample=10000)
FUSED_READERS = ("fused_ingest_us_per_edge", "fused_flush_us_per_answer",
                 "fused_pass_waste_pct", "device_idle_pct.fused_prepare")


def _real(kind: str, name: str) -> dict:
    return harness.load_json(os.path.join(BENCH, kind, name + ".json"))


def _generator():
    return harness.load_module(
        os.path.join(BENCH, "traffic", "tenant_zipf.py"), "tenant_zipf")


def _tiny(**mix) -> tuple[dict, dict]:
    config = dict(_real("configs", "tenants-64"), name=CELL, **CONFIG)
    return config, dict(_real("workloads", "tenants-zipf"), **dict(MIX, **mix))


@pytest.fixture(scope="module")
def zipf_bench(tmp_path_factory):
    """A copy of ``bench/`` with ``tiny-zipf`` added as new files only, and
    a manifest that lists it beside the real cells; the fused readers'
    lists gain it, so a traced run reports them."""
    bench = os.path.join(tmp_path_factory.mktemp("zipf_copy"), "bench")
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    config, mix = _tiny()
    with open(os.path.join(bench, "configs", CELL + ".json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "workloads", CELL + ".json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["workloads"].append({"name": CELL, "config": CELL,
                                  "traffic": CELL, "chips": 1,
                                  "why": "tiny CPU cell"})
    for m in manifest["per_layer"]:
        if m["name"] in FUSED_READERS:
            m["workloads"].append(CELL)
    return bench, manifest


def run_zipf(zipf_bench, make_service=None, seconds=1.0, seed=2**34 + 17,
             trace=False):
    bench, manifest = zipf_bench
    return harness.run_cell(bench, manifest, CELL, seed, seconds, trace,
                            time.perf_counter(), make_service=make_service)


@pytest.mark.parametrize("cell", [CELL])
def test_dummy_cell_added_as_data_runs_correct(zipf_bench, cell):
    """Every answer of the window, grouped ones included, equals the plain
    reference exactly; every ``ingest_many`` goes over all 8 lanes."""
    result, lines = run_zipf(zipf_bench)
    assert result["correct"], (result["checks"], lines)
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    queries = int(re.search(r"queries=(\d+)", lines[1]).group(1))
    checked = int(re.search(r"checked=(\d+)", lines[1]).group(1))
    assert checked == queries >= 1
    groups = json.loads(next(line for line in lines
                             if line.startswith("bench groups "))[13:])
    assert groups["ingest_many"]["events"] >= 1
    assert groups["ingest_many"]["mean"] == groups["ingest_many"]["max"] == 8
    assert groups["density_many"]["max"] == MIX["group_requests"]
    assert groups["checked_in_groups"] >= 1
    assert result["metrics"]["edges_per_s"]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0


@pytest.mark.parametrize("cell", [CELL])
def test_control_is_not_correct(zipf_bench, cell):
    result, _ = run_zipf(zipf_bench, make_service=ControlService,
                         seconds=0.2)
    assert not result["correct"]
    assert result["checks"]["wrong_density"]["value"] > 0


FAULT_CASES = [(CELL, f) for f in SINGLE_FAULTS + GROUPED_FAULTS]


@pytest.mark.parametrize("cell,fault", FAULT_CASES,
                         ids=[f"{c}-{f}" for c, f in FAULT_CASES])
def test_fault_in_timed_path_is_not_correct(zipf_bench, cell, fault):
    result, _ = run_zipf(zipf_bench, make_service=_faulty(fault))
    assert not result["correct"], result["checks"]


def test_traced_run_reports_the_fused_readers(zipf_bench):
    """The fused layer's program readers read the window's spans; the
    device idle share has no device plane to read on the CPU."""
    result, _ = run_zipf(zipf_bench, trace=True)
    assert result["correct"], result["checks"]
    metrics = result["metrics"]
    assert metrics["fused_ingest_us_per_edge"]["value"] > 0
    assert metrics["fused_flush_us_per_answer"]["value"] > 0
    assert 0.0 <= metrics["fused_pass_waste_pct"]["value"] < 100.0
    assert "device_idle_pct.fused_prepare" not in metrics


@pytest.mark.parametrize("traffic,config", [("tenants-zipf", "tenants-64")])
def test_generators_deterministic_for_a_seed(traffic, config):
    """The real cell's generator at a small size: same code, fewer
    tenants and ticks."""
    config, mix = _real("configs", config), _real("workloads", traffic)
    config.update(CONFIG)
    mix.update(MIX, max_ticks=6)
    gen = _generator()

    def events(seed):
        t = gen.Traffic(config, mix, seed)
        return [(e[0], e[1], repr(e[2])) for e in t.warm_events + t.events]

    a, b, c = events(2**40 + 1), events(2**40 + 1), events(2**40 + 2)
    assert a == b
    assert a != c


def test_popularity_ranks_are_the_same_for_every_seed():
    """The scramble is a fixed hash of the tenant index, not drawn from
    the seed: two seeds give the same ranks, and the hottest tenants are
    not the largest ones by construction."""
    config, mix = _tiny(max_ticks=2)
    gen = _generator()
    a = gen.Traffic(config, mix, 2**41 + 3)
    b = gen.Traffic(config, mix, 7)
    assert np.array_equal(a.order, b.order)
    assert np.array_equal(a.probs, b.probs)
    order, _ = gen.popularity(64, 0.99)
    assert sorted(order.tolist()) == list(range(64))
    assert order.tolist() != list(range(64))
    for value in (0, 1, 63, 2**40 + 7):
        assert gen.fnv1a64(value) == _fnv1a64(value.to_bytes(8, "little"))


def _fnv1a64(data: bytes) -> int:
    h = 0xCBF29CE484222325
    for byte in data:
        h = ((h ^ byte) * 0x100000001B3) % 2**64
    return h


def test_fnv1a64_reference_vector():
    assert _fnv1a64(b"a") == 0xAF63DC4C8601EC8C  # the published vector


def test_zipf_draws_follow_the_weights():
    """10^5 draws of the cell's picker: each rank's share is within 5
    standard deviations of its Zipf (0.99) weight, and the hottest rank
    takes about a fifth of the requests over 64 tenants."""
    gen = _generator()
    order, probs = gen.popularity(64, 0.99)
    n = 100_000
    drawn = gen.draw(np.random.default_rng(2**42 + 9), order, probs, n)
    counts = np.bincount(drawn, minlength=64)[order] / n
    tol = 5 * np.sqrt(probs * (1 - probs) / n)
    assert np.all(np.abs(counts - probs) <= tol), np.abs(counts - probs) / tol
    assert 0.19 < probs[0] < 0.22
    assert np.all(np.diff(probs) < 0)


def test_live_matches_the_incremental_log():
    """``live(version, tenant)`` equals the live edge set built by applying
    the events' own inserts and deletes, one update event at a time, to
    the fill: every delete is of a live edge, every insert of an absent
    one."""
    config, mix = _tiny(max_ticks=4)
    gen = _generator()
    t = gen.Traffic(config, mix, 2**36 + 5)
    live = {}
    for name, w in zip(t.names, t.windows):
        p = w.fill().astype(np.int64)
        live[name] = set((p[:, 0] * w.n + p[:, 1]).tolist())

    def check(version):
        for name in t.names:
            n, keys = t.live(version, name)
            assert set(keys.tolist()) == live[name], (version, name)

    def apply(name, ins, dels):
        n = t.windows[t.names.index(name)].n
        d = set((dels[:, 0].astype(np.int64) * n + dels[:, 1]).tolist())
        i = set((ins[:, 0].astype(np.int64) * n + ins[:, 1]).tolist())
        assert d <= live[name] and not i & live[name]
        live[name] = (live[name] - d) | i

    version = 0
    check(version)
    for op, tenant, payload in t.warm_events + t.events:
        if op == "ingest_many":
            for name, (ins, dels) in payload.items():
                apply(name, ins, dels)
        elif op == "update":
            apply(tenant, *payload)
        else:
            continue
        version += 1
        check(version)
    assert version == len(t.applied) - 1
    # and the reference reads it: a tenant's answer is the plain peel
    n, keys = t.live(version, t.names[0])
    d, mask, passes = peel(n, keys)
    assert d > 0 and mask.shape == (n,) and passes >= 1


# -- the readers on hand-made records ----------------------------------------
def reader(name):
    return harness.load_module(os.path.join(BENCH, "metrics", name + ".py"),
                               "test_metric_" + name.replace(".", "_"))


def _rec(i, name, ms, parent=None, labels=None, **attrs):
    return SpanRecord(span_id=i, parent_id=parent,
                      depth=int(parent is not None), name=name,
                      labels=labels or {}, t_start=0.0, duration_ms=ms,
                      attrs=attrs, trace_id=i if parent is None else parent,
                      t_mono_ns=i)


def _ctx(records, trace=None):
    return Context(setup_s=1.0, window=(0.0, 1.0), queries=[],
                   updates=[Update(1000, True)], spans=records, trace=trace)


def test_fused_readers_on_a_hand_made_ring():
    many = {"op": "ingest_many", "tenant": "-"}
    ring = [
        _rec(1, "service", 30.0, labels=many, n_tenants=64, n_edges=2000),
        _rec(2, "fused_ingest", 5.0, parent=1, n_lanes=32),
        _rec(3, "service", 10.0, labels=many, n_tenants=64, n_edges=500),
        _rec(4, "service", 99.0, labels={"op": "flush", "tenant": "-"}),
        _rec(5, "fused_flush", 8.0, members=4, lane_passes=40,
             member_passes=30),
        _rec(6, "fused_prepare", 2.0, parent=5),
        _rec(7, "fused_flush", 4.0, members=2, lane_passes=20,
             member_passes=15),
    ]
    got = {n: reader(n).read(_ctx(ring)) for n in FUSED_READERS}
    # 40 ms over 2,500 edges; the flush's service span is not ingest
    assert got["fused_ingest_us_per_edge"] == pytest.approx(16.0)
    # 12 ms over 6 answers
    assert got["fused_flush_us_per_answer"] == pytest.approx(2000.0)
    # 45 of 60 lane-passes were the members' own
    assert got["fused_pass_waste_pct"] == pytest.approx(25.0)
    assert got["device_idle_pct.fused_prepare"] is None  # no trace
    trace = SimpleNamespace(n_devices=1, window_s=1.0, window_ns=(0.0, 1e9),
                            annotations=[("obs:fused_prepare", 0.0, 1e7)],
                            idle_by_host={"obs:fused_prepare": 1.5e8})
    assert reader("device_idle_pct.fused_prepare").read(
        _ctx(ring, trace)) == pytest.approx(15.0)


def test_fused_readers_read_nothing_from_an_older_ring():
    """A program without the fused layer's attributes (the service's
    ``ingest_many`` span without ``n_edges``, flushes without the pass
    counts) or without any fused span leaves the new metrics out, and no
    reader raises. A flush span that counts its ``members`` is read."""
    many = {"op": "ingest_many", "tenant": "-"}
    older = [_rec(1, "service", 30.0, labels=many),
             _rec(2, "fused_ingest", 5.0, parent=1, n_lanes=32),
             _rec(3, "fused_flush", 8.0, members=4),
             _rec(4, "ingest", 3.0)]
    trace = SimpleNamespace(n_devices=1, window_s=1.0, window_ns=(0.0, 1e9),
                            annotations=[("obs:fused_flush", 0.0, 1e7)],
                            idle_by_host={"obs:fused_flush": 5e8})
    got = {n: reader(n).read(_ctx(older, trace)) for n in FUSED_READERS}
    assert got.pop("fused_flush_us_per_answer") == pytest.approx(2000.0)
    assert got == dict.fromkeys(got)
    solo = [_rec(1, "ingest", 10.0), _rec(2, "query", 9.0, path="warm")]
    assert all(reader(n).read(_ctx(solo, trace)) is None
               for n in FUSED_READERS)
