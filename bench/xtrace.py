"""Reduce a JAX profiler trace to the benchmark's device numbers.

The profiler writes an ``.xplane.pb``; :func:`load` turns it into plain
data (planes -> lines -> ``(name, start_ns, duration_ns)`` events) and
:func:`summarize` reduces that to:

* the traced window: the host annotation ``bench:window`` the harness
  opens around its measured window;
* device busy time: the union of the intervals in which an operation ran
  on a device (the ``XLA Ops`` line of each ``/device:`` plane), inside the
  window, averaged over the devices that ran anything;
* device time per program (``XLA Modules`` line) and per operation;
* idle gaps: the window minus the busy union, each attributed to the
  innermost ``obs:*`` host annotation (the program's spans) covering its
  midpoint, or to ``host`` when none does;
* host-only time of chosen annotations: the part of their union during
  which no device was busy.

Everything past :func:`load` works on plain lists, so a test can feed it a
recorded excerpt.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

WINDOW = "bench:window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(trace_dir: str) -> list[dict]:
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one xplane file, found {files}")
    data = ProfileData.from_file(files[0])
    return [{"name": p.name,
             "lines": [{"name": ln.name,
                        "events": [(e.name, float(e.start_ns),
                                    float(e.duration_ns)) for e in ln.events]}
                       for ln in p.lines]}
            for p in data.planes]


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def intersect_len(a, b) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


@dataclass
class Summary:
    window_ns: tuple[float, float]
    n_devices: int
    busy_ns: float                      # per device, averaged
    busy: list = field(default_factory=list)   # union over all devices
    modules: dict = field(default_factory=dict)  # name -> [count, ns]
    ops: dict = field(default_factory=dict)      # name -> ns
    idle_by_host: dict = field(default_factory=dict)  # annotation -> ns
    annotations: list = field(default_factory=list)   # (name, s, e)

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    def module_ns(self, match) -> tuple[int, float]:
        """(executions, device ns) of the programs whose name ``match``
        accepts."""
        n = t = 0.0
        for name, (c, ns) in self.modules.items():
            if match(name):
                n += c
                t += ns
        return int(n), t

    def host_only_ns(self, names) -> float:
        """Time inside the union of the named annotations with no device
        busy."""
        spans = union((s, e) for n, s, e in self.annotations if n in names)
        spans = clip(spans, *self.window_ns)
        return length(spans) - intersect_len(spans, self.busy)


def summarize(planes: list[dict]) -> Summary:
    host_events = []
    devices = []
    for p in planes:
        if p["name"].startswith("/device:"):
            lines = {ln["name"]: ln["events"] for ln in p["lines"]}
            if lines.get(OPS_LINE) or lines.get(MODULES_LINE):
                devices.append(lines)
        elif p["name"].startswith("/host:"):
            for ln in p["lines"]:
                host_events.extend(ln["events"])
    windows = [(s, s + d) for n, s, d in host_events if n == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW} annotation, "
                           f"found {len(windows)}")
    lo, hi = windows[0]
    annotations = sorted((n, s, s + d) for n, s, d in host_events
                         if n.startswith("obs:"))
    annotations.sort(key=lambda a: (a[1], -a[2]))

    busy_total = 0.0
    all_busy = []
    modules: dict = {}
    ops: dict = {}
    for lines in devices:
        op_events = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        busy = clip(union((s, s + d) for _, s, d in op_events), lo, hi)
        busy_total += length(busy)
        all_busy.extend(busy)
        for name, s, d in lines.get(MODULES_LINE, []):
            if lo <= s < hi:
                m = modules.setdefault(name, [0, 0.0])
                m[0] += 1
                m[1] += d
        for name, s, d in lines.get(OPS_LINE, []):
            if lo <= s < hi:
                ops[name] = ops.get(name, 0.0) + d
    busy = union(all_busy)

    # idle gaps, attributed by a sweep over properly nested annotations
    gaps = []
    t = lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    idle: dict = {}
    stack: list = []
    k = 0
    for gs, ge in gaps:
        mid = 0.5 * (gs + ge)
        while k < len(annotations) and annotations[k][1] <= mid:
            while stack and stack[-1][2] < annotations[k][1]:
                stack.pop()
            stack.append(annotations[k])
            k += 1
        while stack and stack[-1][2] < mid:
            stack.pop()
        label = stack[-1][0] if stack else "host"
        idle[label] = idle.get(label, 0.0) + (ge - gs)
    n_dev = max(len(devices), 1)
    return Summary(window_ns=(lo, hi), n_devices=len(devices),
                   busy_ns=busy_total / n_dev, busy=busy, modules=modules,
                   ops=ops, idle_by_host=idle, annotations=annotations)


def breakdown(summary: Summary, top: int = 10) -> dict:
    ops = sorted(summary.ops.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(summary.idle_by_host.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, ns * 1e-9] for n, ns in ops],
            "idle_gaps": [[n, ns * 1e-9] for n, ns in idle]}
