"""Readers of the program's phase spans (``bench/metrics``), on the CPU.

A traced run of the tiny window cell of ``test_chip_bench`` reports every
reader of the program's spans and counters; the device-trace reader has no
device plane to read on the CPU and is left out. The ring's records join
the profiler trace through the ``bench:window`` anchor. On a ring without
the phase spans, as an older program writes, the new readers read nothing.
"""
from __future__ import annotations

import os
from types import SimpleNamespace

import pytest

# first: it puts the checkout's root on the path for ``bench``
from test_chip_bench import BENCH, run_tiny, tiny_bench  # noqa: F401

from bench import harness, spans  # noqa: E402
from bench.harness import Context, Update  # noqa: E402
from repro.obs.trace import SpanRecord  # noqa: E402

PROGRAM_READERS = ("ingest_buffer_us_per_edge", "ingest_dispatch_us_per_edge",
                   "buffer_compact_ms", "h2d_bytes_per_edge",
                   "pruned_query_pct")
NEW_READERS = PROGRAM_READERS + ("device_idle_pct.buffer_apply",)


def reader(name):
    return harness.load_module(os.path.join(BENCH, "metrics", name + ".py"),
                               "test_metric_" + name.replace(".", "_"))


@pytest.fixture(scope="module")
def traced(tiny_bench):  # noqa: F811
    """One traced run of ``tiny-panes``: its result line and the context
    the readers saw."""
    seen = []
    make = harness.Context

    def recording(**kw):
        seen.append(make(**kw))
        return seen[-1]

    harness.Context = recording
    try:
        result, _ = run_tiny(tiny_bench, "tiny-panes", trace=True)
    finally:
        harness.Context = make
    return result, seen[-1]


def test_traced_run_reports_the_program_readers(traced):
    result, _ = traced
    assert result["correct"], result["checks"]
    metrics = result["metrics"]
    for name in PROGRAM_READERS:
        assert metrics[name]["value"] > 0, name
    # the parts of ingest are inside the whole
    parts = (metrics["ingest_buffer_us_per_edge"]["value"]
             + metrics["ingest_dispatch_us_per_edge"]["value"])
    assert parts <= metrics["ingest_us_per_edge"]["value"]
    assert metrics["pruned_query_pct"]["value"] <= 100.0
    # no device plane on the CPU: left out, as the ingest idle share is
    assert "device_idle_pct.buffer_apply" not in metrics
    assert "device_idle_pct.ingest" not in metrics


def test_ring_spans_join_their_trace_annotations(traced):
    """Each ring span that began in the window, mapped by ``t_mono_ns``
    through the ``bench:window`` anchor, starts within 2 ms of a profiler
    annotation of the same name."""
    _, ctx = traced
    starts: dict = {}
    for name, s, _e in ctx.trace.annotations:
        starts.setdefault(name, []).append(s)
    lo, hi = ctx.trace.window_ns
    joined = 0
    for rec in ctx.spans:
        at = spans.to_trace_ns(ctx, rec.t_mono_ns)
        if not lo <= at <= hi:
            continue
        near = min(abs(s - at) for s in starts["obs:" + rec.name])
        assert near < 2e6, (rec.name, near)
        joined += 1
    names = {r.name for r in ctx.spans}
    assert joined > 0 and {"buffer_apply", "ingest_dispatch",
                           "prune_prepare"} <= names


def _rec(i, name, ms, parent=None, **attrs):
    return SpanRecord(span_id=i, parent_id=parent, depth=int(parent is not None),
                      name=name, labels={}, t_start=0.0, duration_ms=ms,
                      attrs=attrs, trace_id=i if parent is None else parent,
                      t_mono_ns=i)


def _ctx(records, edges=1000, trace=None):
    return Context(setup_s=1.0, window=(0.0, 1.0), queries=[],
                   updates=[Update(edges, True)], spans=records, trace=trace)


def test_readers_on_a_hand_made_ring():
    ring = [
        _rec(1, "ingest", 10.0),
        _rec(2, "buffer_apply", 6.0, parent=1),
        _rec(3, "buffer_compact", 2.0, parent=2),
        _rec(4, "ingest_dispatch", 1.0, parent=1, h2d_bytes=6000),
        _rec(5, "ingest", 20.0),
        _rec(6, "buffer_apply", 4.0, parent=5),
        _rec(7, "engine_resync", 3.0, parent=5, h2d_bytes=24000),
        _rec(8, "refresh", 30.0, path="pruned"),
        _rec(9, "buffer_compact", 8.0, parent=8),
        _rec(10, "engine_resync", 5.0, parent=8, h2d_bytes=10000),
        _rec(11, "query", 9.0, path="warm"),
        _rec(12, "query", 9.0, path="pruned", h2d_bytes=0),
    ]
    got = {n: reader(n).read(_ctx(ring)) for n in PROGRAM_READERS}
    # 6 + 4 ms of buffer_apply less the 2 ms compaction under it, 1000 edges
    assert got["ingest_buffer_us_per_edge"] == pytest.approx(8.0)
    # dispatch 1 ms plus the resync under an ingest; not the refresh's
    assert got["ingest_dispatch_us_per_edge"] == pytest.approx(4.0)
    assert got["buffer_compact_ms"] == pytest.approx(5.0)
    assert got["h2d_bytes_per_edge"] == pytest.approx(40.0)
    assert got["pruned_query_pct"] == pytest.approx(200.0 / 3)


def test_readers_read_nothing_from_an_older_ring():
    """An older program writes the ``ingest`` and answer spans only: the
    new readers leave their metrics out, and the answers' paths are still
    counted."""
    ring = [_rec(1, "ingest", 10.0), _rec(2, "query", 9.0, path="pruned"),
            _rec(3, "refresh", 30.0, path="warm")]
    trace = SimpleNamespace(n_devices=1, window_s=1.0, window_ns=(0.0, 1e9),
                            annotations=[("obs:ingest", 0.0, 1e7)],
                            idle_by_host={"obs:ingest": 5e8})
    ctx = _ctx(ring, trace=trace)
    got = {n: reader(n).read(ctx) for n in NEW_READERS}
    assert got.pop("pruned_query_pct") == pytest.approx(50.0)
    assert got == dict.fromkeys(got)
    # with the annotation there, the idle share under it is read
    trace.annotations.append(("obs:buffer_apply", 0.0, 1e6))
    trace.idle_by_host["obs:buffer_apply"] = 2.5e8
    assert reader("device_idle_pct.buffer_apply").read(ctx) == 25.0
