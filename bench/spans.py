"""Shared arithmetic of the readers of the program's spans.

``ctx.spans`` holds the ring records that began in the window. A program
that lacks a span or an attribute leaves its reader nothing to read, and
the reader returns ``None``: the metric is then left out of the line.
"""
from __future__ import annotations


def edges_applied(ctx) -> int:
    """Effective inserts plus expiries the service reported in the window."""
    return sum(u.edges for u in ctx.updates if u.ok)


def us_per_edge(ctx, ms: float):
    edges = edges_applied(ctx)
    return ms * 1e3 / edges if edges and ms > 0 else None


def named(ctx, *names) -> list:
    return [s for s in ctx.spans if s.name in names]


def to_trace_ns(ctx, t_mono_ns: int):
    """A record's ``t_mono_ns`` (``perf_counter_ns`` at enter) on the
    profiler trace's timebase. The anchor is the ``bench:window``
    annotation: the harness opens it and reads ``ctx.window[0]`` on the
    same clock at once, so both mark the start of the window."""
    if ctx.trace is None:
        return None
    return ctx.trace.window_ns[0] + (t_mono_ns - ctx.window[0] * 1e9)
