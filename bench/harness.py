"""One run of one benchmark cell: set-up, measured window, metrics, check.

Everything that belongs to one configuration, traffic mix or metric is a
file found by its name in ``BENCHMARK.json``:

* ``configs/<config>.json``: the deployment (sizes, service settings);
* ``workloads/<traffic>.json``: the traffic mix, naming its generator;
* ``traffic/<generator>.py``: a ``Traffic`` class that builds the events
  from the seed, sets the service up, and replays the live edge set of
  any answered query for the reference;
* ``metrics/<metric>.py``: a ``read(ctx)`` returning the metric's value,
  or ``None`` where the run has nothing for it to read;
* ``reference/<reference>.py``: the configuration's plain reference.

The harness drives only ``repro.stream.StreamService``, in a closed loop:
each event is sent as soon as the previous one returned, until the window
closes; the event running at the close completes.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import tempfile
import time
from dataclasses import dataclass, field

import numpy as np

RING = 1 << 21      # span ring: holds every span of a window


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Query:
    idx: int            # position among the window's events
    tenant: str
    answered: float | None = None
    version: int = -1   # update events applied before the answer
    ok: bool = False
    density: float | None = None
    passes: int | None = None
    mask: np.ndarray | None = None
    path: str = ""


@dataclass
class Update:
    edges: int
    ok: bool


@dataclass
class Context:
    """What a metric reader sees."""

    setup_s: float
    window: tuple           # (start, end) perf_counter of the window
    queries: list           # Query records of the window's queries
    updates: list           # Update records of the window's updates
    spans: list             # program SpanRecords that began in the window
    trace: object = None    # xtrace.Summary (traced runs)
    peaks: dict = field(default_factory=dict)


class Driver:
    """Executes events against the service and records them."""

    def __init__(self, svc, sample: set):
        self.svc = svc
        self.sample = sample
        self.version = 0
        self.queries: list[Query] = []
        self.updates: list[Update] = []

    def run(self, idx, op, tenant, payload):
        if op == "update":
            resp = self.svc.apply_updates(tenant, insert=payload[0],
                                          delete=payload[1])
            self.version += 1
            edges = (resp.value.n_inserted + resp.value.n_deleted
                     if resp.ok else 0)
            self.updates.append(Update(edges, resp.ok))
        elif op == "density":
            q = Query(idx=idx, tenant=tenant)
            self.queries.append(q)
            resp = self.svc.density(tenant)
            self._answer(q, resp, time.perf_counter())
        else:
            raise ValueError(f"unknown event op {op!r}")

    def _answer(self, q: Query, resp, now: float):
        q.answered = now
        q.version = self.version
        q.ok = resp is not None and resp.ok
        if not q.ok:
            return
        q.density = resp.value["density"]
        q.passes = resp.value["passes"]
        q.path = ("refresh" if resp.value["refreshed"] else
                  "pruned" if resp.value["pruned"] else "warm")
        if q.idx in self.sample:
            m = self.svc.membership(q.tenant)
            q.mask = np.asarray(m.value["mask"], bool) if m.ok else None


def _compile_counter():
    """Counts executables compiled or loaded from the persistent cache,
    with the names of the functions, from JAX's monitoring events."""
    import jax

    seen: list = []

    def on_duration(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(kw.get("fun_name", "?"))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return seen


def _window(driver: Driver, events, t0: float, seconds: float):
    """Each event as soon as the previous one returned, until the window
    closes; the event running at the close completes."""
    i = 0
    while i < len(events) and time.perf_counter() - t0 < seconds:
        driver.run(i, *events[i])
        i += 1
    if i == len(events):
        raise RuntimeError("closed loop ran out of events before the window "
                           "closed: raise the mix's max_batches")
    return i


def _start_profiler(trace_dir: str):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # keep host annotations, not every call
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def _draw(rng, items, k: int) -> set:
    if not items:
        return set()
    return set(rng.choice(items, size=min(k, len(items)),
                          replace=False).tolist())


def check_answers(gen, ref_mod, queries, sample: set):
    """Compare the sampled answers with the plain reference peel of the
    live edge set each was answered on. Returns the numbers compared."""
    wrong = {"wrong_density": 0, "wrong_mask": 0, "wrong_passes": 0,
             "unanswered": 0}
    checked = 0
    picked = sorted((q for q in queries if q.idx in sample),
                    key=lambda q: q.version)
    for q in picked:
        if not q.ok or q.answered is None:
            wrong["unanswered"] += 1
            continue
        n, keys = gen.live(q.version, q.tenant)
        rd, rmask, rpasses = ref_mod.peel(n, keys)
        checked += 1
        if np.float32(q.density) != np.float32(rd):
            wrong["wrong_density"] += 1
        if int(q.passes) != int(rpasses):
            wrong["wrong_passes"] += 1
        mask = q.mask
        if (mask is None or mask.shape[0] < n or mask[n:].any()
                or not np.array_equal(mask[:n], rmask)):
            wrong["wrong_mask"] += 1
    return wrong, checked


def run_cell(bench_dir: str, manifest: dict, cell_name: str, seed: int,
             seconds: float, trace: bool, t_start: float,
             make_service=None, device: dict | None = None
             ) -> tuple[dict, list[str]]:
    """Run ``cell_name`` once; returns (result line, lines to print first).

    ``make_service`` replaces ``StreamService``: the control and the fault
    tests put their own service in its place."""
    cell = next(w for w in manifest["workloads"] if w["name"] == cell_name)
    config = load_json(os.path.join(bench_dir, "configs",
                                    cell["config"] + ".json"))
    mix = load_json(os.path.join(bench_dir, "workloads",
                                 cell["traffic"] + ".json"))
    gen_mod = load_module(os.path.join(bench_dir, "traffic",
                                       mix["generator"] + ".py"),
                          "bench_traffic_" + mix["generator"])
    ref_mod = load_module(os.path.join(bench_dir, "reference",
                                       config["reference"] + ".py"),
                          "bench_reference_" + config["reference"])
    peaks = load_json(os.path.join(bench_dir, "peaks.json"))
    if device is not None and device["kind"] not in peaks:
        raise SystemExit(f"no peaks for device kind {device['kind']!r} "
                         "in peaks.json")

    from repro.obs.trace import Tracer, set_tracer

    set_tracer(Tracer(ring_size=RING))
    if make_service is None:
        from repro.stream import StreamService as make_service

    gen = gen_mod.Traffic(config, mix, seed)
    # the loop runs an unknown prefix of its events, so every answer keeps
    # its mask and the sample is drawn, from the seed, among those that ran
    rng = np.random.default_rng([seed, 1])
    sample = {i for i, e in enumerate(gen.events) if e[0] == "density"}

    svc = gen.setup(make_service)
    driver = Driver(svc, set())
    # warm-up: the mix's own traffic, so the window meets the shapes (batch
    # widths, prune buckets, refresh) compiled; every warm update must
    # land, since the generator's log assumes them
    for i, ev in enumerate(gen.warm_events):
        driver.run(i, *ev)
    bad = [r for r in driver.queries + driver.updates if not r.ok]
    if bad:
        raise RuntimeError(f"{len(bad)} warm-up requests failed")
    driver.queries, driver.updates, driver.sample = [], [], sample

    compiles = _compile_counter()
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        _start_profiler(trace_dir)
    from jax.profiler import TraceAnnotation

    n_before = len(compiles)
    with TraceAnnotation("bench:window"):
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        n_run = _window(driver, gen.events, t0, seconds)
        t1 = time.perf_counter()
    in_window = compiles[n_before:]
    if trace:
        import jax

        jax.profiler.stop_trace()
    mem = _memory_peak()

    from repro.obs.trace import get_tracer

    epoch0 = time.time() - (time.perf_counter() - t0)
    spans = [r for r in get_tracer().ring()
             if epoch0 <= r.t_start <= epoch0 + (t1 - t0)]
    summary = None
    if trace:
        from bench import xtrace

        t_read = time.perf_counter()
        summary = xtrace.summarize(xtrace.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        t_read = time.perf_counter() - t_read

    ctx = Context(setup_s=setup_s, window=(t0, t1), queries=driver.queries,
                  updates=driver.updates, spans=spans, trace=summary,
                  peaks=peaks.get(device["kind"], {}) if device else {})
    wanted = [m for m in manifest["per_layer" if trace else "end_to_end"]
              if cell_name in m.get("workloads", [cell_name])]
    metrics = {}
    for m in wanted:
        reader = load_module(os.path.join(bench_dir, "metrics",
                                          m["name"] + ".py"),
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    # free the program's state before the reference runs
    svc = driver.svc = None
    gc.collect()
    failed = (sum(not u.ok for u in driver.updates)
              + sum(not q.ok for q in driver.queries))
    sample = _draw(rng, [q.idx for q in driver.queries], gen.check_sample)
    t_check = time.perf_counter()
    wrong, checked = check_answers(gen, ref_mod, driver.queries, sample)
    t_check = time.perf_counter() - t_check
    wrong["too_few_checked"] = int(checked < 1)
    correct = all(v == 0 for v in wrong.values())

    lines = [
        f"bench cell={cell_name} seed={seed} seconds={seconds} "
        f"trace={int(trace)} setup_s={setup_s} window_s={t1 - t0}",
        f"bench events run={n_run} queries={len(driver.queries)} "
        f"updates={len(driver.updates)} checked_of={len(sample)} "
        f"checked={checked} check_s={t_check}",
        "bench paths " + json.dumps(_count(q.path for q in driver.queries)),
        f"bench compiles_in_window={len(in_window)} "
        + json.dumps(_count(in_window)),
    ]
    result = {
        "correct": bool(correct),
        "attempted": int(n_run),
        "failed": int(failed),
        "metrics": metrics,
        "device": dict(device or {}, memory_peak_bytes=mem),
    }
    if summary is not None:
        lines.append(f"bench trace_read_s={t_read}")
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        from bench import xtrace

        result["breakdown"] = xtrace.breakdown(summary)
    result["checks"] = {k: {"value": v, "limit": 0} for k, v in wrong.items()}
    return result, lines


def _count(items) -> dict:
    out: dict = {}
    for x in items:
        out[x] = out.get(x, 0) + 1
    return out


def _memory_peak() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks or [0]))
