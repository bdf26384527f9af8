"""One run of one benchmark cell: set-up, measured window, metrics, check.

Everything that belongs to one configuration, traffic mix or metric is a
file found by its name in ``BENCHMARK.json``:

* ``configs/<config>.json``: the deployment (sizes, service settings);
* ``workloads/<traffic>.json``: the traffic mix, naming its generator;
* ``traffic/<generator>.py``: the generator (contract below);
* ``metrics/<metric>.py``: a ``read(ctx)`` returning the metric's value,
  or ``None`` where the run has nothing for it to read;
* ``reference/<reference>.py``: the configuration's plain reference.

A generator module holds a class ``Traffic(config, mix, seed)`` with:

* ``events``, ``warm_events``: lists of ``(op, tenant, payload)`` events,
  the window's supply and the set-up's warm-up;
* ``setup(make_service)``: builds the service, fills it, returns it;
* ``live(version, tenant)``: ``(n_nodes, distinct live keys u * n + v)``
  of ``tenant`` after ``version`` update events, warm-up included,
  rebuilt from the generator's own log;
* ``check_sample``: how many of the window's answers to compare.

The harness drives only ``repro.stream.StreamService``'s public API, in a
closed loop: each event is sent as soon as the previous one returned,
until the window closes; the event running at the close completes. The
ops:

* ``("update", tenant, (insert, delete))``: ``apply_updates``;
* ``("ingest_many", None, {tenant: (insert, delete), ...})``:
  ``ingest_many``, one update event for the whole group;
* ``("density", tenant, None)``: ``density``, one answer;
* ``("density_many", None, [tenant, ...])``: ``submit_density`` for each
  tenant, then the harness's own ``flush``, then ``poll`` for each
  ticket: one answer per ticket, in submission order. Only that explicit
  flush groups the tickets where the configuration sets a
  ``coalesce_window_ms`` longer than a group takes to submit; at 0 the
  service flushes each ticket as it is submitted.

``version`` counts update events, single or grouped: every answer is
compared with the live edge set after that many of them.
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
    pos: int = 0        # position among its event's answers
    group: int = 1      # answers its event gave
    answered: float | None = None
    version: int = -1   # update events applied before the answer
    ok: bool = False
    density: float | None = None
    passes: int | None = None
    mask: np.ndarray | None = None
    path: str = ""

    @property
    def key(self) -> tuple:
        return self.idx, self.pos


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
    """Executes events against the service and records them; with
    ``masks`` on, every answer's member mask is read (the check may pick
    any of them)."""

    def __init__(self, svc, masks: bool):
        self.svc = svc
        self.masks = masks
        self.version = 0
        self.queries: list[Query] = []
        self.updates: list[Update] = []
        self.groups: dict = {"ingest_many": [], "density_many": []}

    def run(self, idx, op, tenant, payload):
        if op == "update":
            resp = self.svc.apply_updates(tenant, insert=payload[0],
                                          delete=payload[1])
            self._updated(resp, [resp.value] if resp.ok else [])
        elif op == "ingest_many":
            resp = self.svc.ingest_many(payload)
            self.groups[op].append(len(payload))
            self._updated(resp, resp.value.values() if resp.ok else [])
        elif op == "density":
            q = Query(idx=idx, tenant=tenant)
            self.queries.append(q)
            resp = self.svc.density(tenant)
            self._answer(q, resp, time.perf_counter())
        elif op == "density_many":
            tickets = [self.svc.submit_density(t) for t in payload]
            self.svc.flush()
            now = time.perf_counter()
            self.groups[op].append(len(payload))
            for pos, (t, ticket) in enumerate(zip(payload, tickets)):
                q = Query(idx=idx, tenant=t, pos=pos, group=len(payload))
                self.queries.append(q)
                self._answer(q, self.svc.poll(ticket), now)
        else:
            raise ValueError(f"unknown event op {op!r}")

    def _updated(self, resp, stats):
        self.version += 1
        edges = sum(s.n_inserted + s.n_deleted for s in stats)
        self.updates.append(Update(edges, resp.ok))

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
        if self.masks:
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


def _draw(rng, items: list, k: int) -> set:
    if not items:
        return set()
    picks = rng.choice(len(items), size=min(k, len(items)), replace=False)
    return {items[i] for i in picks.tolist()}


def check_answers(gen, ref_mod, queries, sample: set):
    """Compare the sampled answers with the plain reference peel of the
    live edge set each was answered on. Returns the numbers compared."""
    wrong = {"wrong_density": 0, "wrong_mask": 0, "wrong_passes": 0,
             "unanswered": 0}
    checked = 0
    picked = sorted((q for q in queries if q.key in sample),
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

    svc = gen.setup(make_service)
    driver = Driver(svc, masks=False)
    # warm-up: the mix's own traffic, so the window meets the shapes (batch
    # widths, prune buckets, refresh) compiled; every warm update must
    # land, since the generator's log assumes them
    for i, ev in enumerate(gen.warm_events):
        driver.run(i, *ev)
    bad = [r for r in driver.queries + driver.updates if not r.ok]
    if bad:
        raise RuntimeError(f"{len(bad)} warm-up requests failed")
    driver.queries, driver.updates, driver.masks = [], [], True
    driver.groups = {op: [] for op in driver.groups}

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
    sample = _draw(rng, [q.key for q in driver.queries], gen.check_sample)
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
        "bench groups " + json.dumps(_groups(driver, sample)),
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


def _groups(driver: Driver, sample: set) -> dict:
    """The window's grouped events of each op (how many, mean and largest
    group), and how many checked answers came from a group of two or
    more."""
    out = {op: {"events": len(sizes),
                "mean": sum(sizes) / len(sizes) if sizes else 0.0,
                "max": max(sizes, default=0)}
           for op, sizes in driver.groups.items()}
    out["checked_in_groups"] = sum(
        q.key in sample and q.ok and q.group >= 2 for q in driver.queries)
    return out


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
