"""Tests of the chip benchmark under ``bench/``, on the CPU at tiny sizes.

They drive the harness the way ``bench/run.py`` does, minus the look for a
TPU: tiny cells added as new files only (one of them many fused tenants
behind the grouped ops, with a generator of its own), the control that
must come out not correct, the faults a timed path can have, the trace
reduction on a recorded trace, the generators' determinism, and the
manifest's contract.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness, xtrace  # noqa: E402
from bench.control import ControlService  # noqa: E402
from bench.reference.peel import peel  # noqa: E402

TINY = {
    "tiny-panes": (
        {"name": "tiny-panes", "reference": "peel", "chips": 1, "scale": 8,
         "initiator": [0.57, 0.19, 0.19], "edge_factor": 2,
         "edge_capacity": 512,
         "service": {"pruned": True, "refresh_every": 32}},
        {"generator": "sliding_window", "batch_edges": 128,
         "queries_every": 2, "max_batches": 4000, "warmup_batches": 2,
         "check_sample": 4}),
    "tiny-queries": (
        {"name": "tiny-queries", "reference": "peel", "chips": 1,
         "scale": 9, "initiator": [0.57, 0.19, 0.19], "edge_factor": 4,
         "edge_capacity": 2048,
         "service": {"pruned": True, "refresh_every": 8}},
        {"generator": "sliding_window", "batch_edges": 64,
         "queries_every": 1, "max_batches": 4000, "warmup_batches": 4,
         "check_sample": 8}),
    # fused tenants in four buckets (two hold two tenants each), queried
    # in groups that only the harness's own flush answers; every answer
    # of the window is checked
    "tiny-tenants": (
        {"name": "tiny-tenants", "reference": "peel", "chips": 1,
         "tenant_scales": [6, 6, 7, 8, 8, 9], "edge_factor": 4,
         "initiator": [0.57, 0.19, 0.19],
         "service": {"fused": True, "pruned": True, "refresh_every": 8,
                     "coalesce_window_ms": 3.6e6}},
        {"generator": "tenant_windows", "batch_edges": 16, "group_min": 2,
         "singles": 1, "max_rounds": 1000, "warmup_rounds": 2,
         "check_sample": 10000}),
}
# a generator that ``bench/traffic/`` lacks, added to the copy as a file
GENERATORS = {"tenant_windows": os.path.join(DATA, "tenant_windows.py")}
# the cells whose spans the per-layer readers read
LAYERED = ("tiny-panes", "tiny-queries")


@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    """A copy of ``bench/`` with the tiny cells added as new files only,
    and a manifest that lists them beside the real cells; no file of the
    copy is edited, and no end-to-end metric's entry either."""
    root = tmp_path_factory.mktemp("bench_copy")
    bench = os.path.join(root, "bench")
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for name, path in GENERATORS.items():
        shutil.copy(path, os.path.join(bench, "traffic", name + ".py"))
    for name, (config, mix) in TINY.items():
        with open(os.path.join(bench, "configs", name + ".json"), "w") as f:
            json.dump(config, f)
        with open(os.path.join(bench, "workloads", name + ".json"), "w") as f:
            json.dump(mix, f)
        manifest["workloads"].append(
            {"name": name, "config": name, "traffic": name, "chips": 1,
             "why": "tiny CPU cell"})
        for m in manifest["per_layer"]:
            if name in LAYERED:
                m["workloads"].append(name)
    return bench, manifest


def run_tiny(tiny_bench, cell, make_service=None, seconds=1.0, seed=2**33 + 5,
             trace=False):
    bench, manifest = tiny_bench
    return harness.run_cell(bench, manifest, cell, seed, seconds, trace,
                            time.perf_counter(), make_service=make_service)


@pytest.mark.parametrize("cell", sorted(TINY))
def test_dummy_cell_added_as_data_runs_correct(tiny_bench, cell):
    result, lines = run_tiny(tiny_bench, cell)
    assert result["correct"], (result["checks"], lines)
    assert result["failed"] == 0
    assert result["attempted"] > 0
    assert list(result)[-1] == "checks"
    checked = int(re.search(r"checked=(\d+)", lines[1]).group(1))
    assert checked >= 1
    assert result["metrics"]["edges_per_s"]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0


def test_tenants_cell_drives_the_grouped_paths(tiny_bench):
    """The many-tenant cell goes through ``ingest_many`` and
    ``density_many``: its checked answers include grouped ones, and the
    window's spans show fused scatters over two lanes or more and
    flushes of two tickets or more."""
    from repro.obs.trace import get_tracer

    bench, manifest = tiny_bench
    t_start = time.perf_counter()
    result, lines = harness.run_cell(bench, manifest, "tiny-tenants",
                                     2**35 + 11, 1.0, False, t_start)
    assert result["correct"], (result["checks"], lines)
    assert result["failed"] == 0 and result["attempted"] > 0
    assert {"edges_per_s", "setup_s"} <= set(result["metrics"])
    groups = json.loads(next(line for line in lines
                             if line.startswith("bench groups "))[13:])
    assert groups["ingest_many"]["events"] > 0
    assert groups["density_many"]["max"] >= 2
    assert groups["checked_in_groups"] >= 1
    setup_s = float(re.search(r"setup_s=(\S+)", lines[0]).group(1))
    window_s = float(re.search(r"window_s=(\S+)", lines[0]).group(1))
    t0 = (t_start + setup_s) * 1e9
    spans = [r for r in get_tracer().ring()
             if t0 <= r.t_mono_ns <= t0 + window_s * 1e9]
    assert any(r.name == "fused_ingest" and r.attrs.get("n_lanes", 0) >= 2
               for r in spans)
    assert any(r.name == "service" and r.labels.get("op") == "flush"
               and r.attrs.get("n_flushed", 0) >= 2 for r in spans)


@pytest.mark.parametrize("cell", sorted(TINY))
def test_control_is_not_correct(tiny_bench, cell):
    """The reference computed with bfloat16 densities in the service's
    place must fail the comparison."""
    # the control is quick: a short window keeps the loop inside its
    # events
    result, _ = run_tiny(tiny_bench, cell, make_service=ControlService,
                         seconds=0.2)
    assert not result["correct"]
    assert result["checks"]["wrong_density"]["value"] > 0


def _faulty(kind):
    from repro.stream import StreamService

    class Faulty(StreamService):
        """StreamService with one fault in its timed path. The faults of
        the single-tenant ops are armed once the set-up has filled the
        state (at the first query); those of the grouped ops once the
        window has begun (at the first mask read, which the harness
        makes only there)."""

        armed = False
        in_window = False

        def apply_updates(self, tenant, insert=None, delete=None):
            if self.armed and kind == "state_unchanged":
                return super().apply_updates(tenant)
            if self.armed and kind == "half_batch":
                insert = None if insert is None else insert[: len(insert) // 2]
                delete = None if delete is None else delete[: len(delete) // 2]
            return super().apply_updates(tenant, insert=insert, delete=delete)

        def density(self, tenant, **kw):
            self.armed = True
            return self._alter(super().density(tenant, **kw))

        def _alter(self, resp):
            if kind == "altered_answer" and resp is not None and resp.ok:
                d = np.float32(resp.value["density"])
                resp.value["density"] = float(np.nextafter(d, np.inf))
            return resp

        def membership(self, tenant, **kw):
            self.in_window = True
            return super().membership(tenant, **kw)

        def ingest_many(self, updates):
            if self.in_window and kind == "dropped_tenant_batch":
                updates = dict(list(updates.items())[1:])
            return super().ingest_many(updates)

        def flush(self):
            tickets = [ticket for ticket, _, _ in self._pending]
            n = super().flush()
            if self.in_window and len(tickets) >= 2:
                a, b = tickets[:2]
                if kind == "swapped_answer":
                    self._results[a], self._results[b] = (
                        self._results[b], self._results[a])
                if kind == "unanswered_ticket":
                    self._results.pop(a)
            return n

    return Faulty


SINGLE_FAULTS = ["state_unchanged", "half_batch", "altered_answer"]
GROUPED_FAULTS = ["swapped_answer", "unanswered_ticket",
                  "dropped_tenant_batch"]
FAULT_CASES = ([(cell, f) for cell in sorted(TINY) for f in SINGLE_FAULTS]
               + [("tiny-tenants", f) for f in GROUPED_FAULTS])


@pytest.mark.parametrize("cell,fault", FAULT_CASES,
                         ids=[f"{c}-{f}" for c, f in FAULT_CASES])
def test_fault_in_timed_path_is_not_correct(tiny_bench, cell, fault):
    result, _ = run_tiny(tiny_bench, cell, make_service=_faulty(fault))
    assert not result["correct"], result["checks"]


def test_traced_run_reads_the_trace(tiny_bench):
    """A traced run reduces its profiler trace: the device line carries the
    traced window, the breakdown is there, and the ingest span reader
    finds its spans. On the CPU no device plane exists, so the idle share
    has nothing to read and is left out, never reported as 0 or 100."""
    result, lines = run_tiny(tiny_bench, "tiny-panes", trace=True)
    assert result["correct"], result["checks"]
    dev = result["device"]
    assert dev["window_s"] > 0 and dev["busy_s"] == 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert result["metrics"]["ingest_us_per_edge"]["value"] > 0
    assert "device_idle_pct.ingest" not in result["metrics"]
    assert any(line.startswith("bench trace_read_s=") for line in lines)


def test_reference_matches_program_oracle():
    """The copied reference agrees with the program's own numpy oracle."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core import pbahmani_np
    from repro.graphs.graph import Graph

    from bench.sampling import kronecker_keys

    rng = np.random.default_rng(3)
    for n, m in ((256, 3000), (1024, 9000)):
        keys = np.unique(kronecker_keys(rng, int(np.log2(n)), m,
                                        0.57, 0.19, 0.19))
        d, mask, passes = peel(n, keys)
        g = Graph.from_edges(np.stack([keys // n, keys % n], axis=1),
                             n_nodes=n)
        rd, rmask, rpasses = pbahmani_np(g)
        assert np.float32(rd) == d and passes == rpasses
        assert np.array_equal(mask, rmask)


@pytest.mark.parametrize("traffic,config", [
    ("window-ingest", "window-s16")])
def test_generators_deterministic_for_a_seed(traffic, config):
    config = harness.load_json(os.path.join(BENCH, "configs",
                                            config + ".json"))
    mix = harness.load_json(os.path.join(BENCH, "workloads",
                                         traffic + ".json"))
    # the real cell's generator at a small size: same code, fewer events
    config.update(scale=10, edge_factor=4, edge_capacity=4096)
    mix.update(batch_edges=256, max_batches=8, warmup_batches=4)
    gen = harness.load_module(os.path.join(BENCH, "traffic",
                                           mix["generator"] + ".py"), "g")

    def events(seed):
        t = gen.Traffic(config, mix, seed)
        return [(e[0], e[1], repr(e[2])) for e in t.warm_events + t.events]

    a, b, c = events(2**40 + 1), events(2**40 + 1), events(2**40 + 2)
    assert a == b
    assert a != c


def test_run_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", "window-ingest", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "window-ingest", "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


# -- trace reduction --------------------------------------------------------
def _plane(name, lines):
    return {"name": name,
            "lines": [{"name": n, "events": ev} for n, ev in lines.items()]}


def test_trace_summary_on_a_hand_made_trace():
    planes = [
        _plane("/host:CPU", {"python": [
            ("bench:window", 0.0, 1000.0),
            ("obs:service", 120.0, 480.0),
            ("obs:query", 150.0, 400.0),
            ("obs:ingest", 700.0, 100.0)]}),
        _plane("/device:TPU:0", {
            "XLA Modules": [("jit__bucket_peel_jit(1)", 200.0, 100.0),
                            ("jit__apply_batch_jit(2)", 720.0, 50.0)],
            "XLA Ops": [("while", 200.0, 60.0), ("fusion", 250.0, 50.0),
                        ("scatter", 720.0, 50.0)]}),
    ]
    s = xtrace.summarize(planes)
    assert s.window_ns == (0.0, 1000.0)
    assert s.busy_ns == 150.0
    assert s.module_ns(lambda n: "bucket_peel" in n) == (1, 100.0)
    # gaps [0, 200) and [770, 1000) have no annotation over their
    # midpoints; [300, 720) falls in the query, the innermost annotation
    assert s.idle_by_host == {"host": 430.0, "obs:query": 420.0}
    # the query annotation [150, 550) has the device busy for 100
    assert s.host_only_ns({"obs:query"}) == 300.0
    b = xtrace.breakdown(s)
    assert b["device_ops"][0] == ["while", 60.0 * 1e-9]


def test_trace_summary_on_a_recorded_trace():
    """An excerpt of a profiler trace the harness recorded in a traced run
    of the tiny window cell on the CPU (no chip trace was recorded yet):
    the window and the program's annotations are found, the CPU plane is
    no device, so the whole window is idle and attributed to what the
    host was doing, and the query annotations count as host-only time."""
    with open(os.path.join(DATA, "trace_excerpt.json")) as f:
        planes = json.load(f)
    s = xtrace.summarize(planes)
    assert s.n_devices == 0 and s.busy_ns == 0
    window = s.window_ns[1] - s.window_ns[0]
    assert window > 0
    assert abs(sum(s.idle_by_host.values()) - window) < 1e-6 * window
    names = {a[0] for a in s.annotations}
    assert {"obs:query", "obs:ingest"} <= names
    host = s.host_only_ns({"obs:query", "obs:refresh"})
    assert 0 < host < window
    assert s.module_ns(lambda n: True) == (0, 0.0)


# -- the manifest's contract ------------------------------------------------
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_manifest_follows_the_contract():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    m = json.loads(raw)
    assert len(raw.encode()) <= 64 * 1024
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51
    for p in m["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    configs = {c["name"]: c for c in m["configs"]}
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and os.path.isfile(
            os.path.join(ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
        for text in (c["source"], c["why"]):
            assert 1 <= len(text) <= 200 and "\n" not in text
    cells = set()
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in configs
        assert (w["config"], w["traffic"]) not in cells
        cells.add((w["config"], w["traffic"]))
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(BENCH, "workloads",
                                           w["traffic"] + ".json"))
    names = [w["name"] for w in m["workloads"]]
    assert len(set(names)) == len(names)
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for e in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(e["name"]) and UNIT.match(e["unit"])
        assert e["better"] in ("lower", "higher")
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           e["name"] + ".py"))
        assert set(e.get("workloads", names)) <= set(names)
    for e in m["end_to_end"]:
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25
    for e in m["per_layer"]:
        assert e["moves"] in e2e
        moved = e2e[e["moves"]].get("workloads", names)
        assert set(e.get("workloads", names)) <= set(moved)
        assert e["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for w in names:
        assert len(_reporting(m, w)) >= 2
        assert any(w in e.get("workloads", names) for e in m["per_layer"])


def _reporting(manifest: dict, cell: str) -> list:
    """The end-to-end metrics that ``cell`` reports."""
    names = [w["name"] for w in manifest["workloads"]]
    return [e for e in manifest["end_to_end"]
            if cell in e.get("workloads", names)]


def test_an_added_cell_reports_two_end_to_end_metrics():
    """A cell appended to the manifest, with no metric's entry touched,
    reports two end-to-end metrics or more: a later deployment adds its
    cell without editing what is there."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    m["workloads"].append(dict(m["workloads"][0], name="added-cell",
                               traffic="added-traffic"))
    assert len(_reporting(m, "added-cell")) >= 2
