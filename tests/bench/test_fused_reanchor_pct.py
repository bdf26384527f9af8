"""The ``fused_reanchor_pct`` reader: the share of the flushed answers that
were epoch refreshes re-anchored inside their bucket's flush. Checked on
hand-made records, on a ring of a program whose flushes do not count
re-anchors (it reads nothing there), and in a traced run of the tiny
many-tenant cell (``tiny-zipf``), where it reads the flushes' own
counts.
"""
from __future__ import annotations

import json
import os
import shutil
import time
from types import SimpleNamespace

import pytest

# first: it puts the checkout's root on the path for ``bench``
from test_tenant_zipf import (  # noqa: F401
    BENCH, CELL, ROOT, _ctx, _rec, _tiny, reader)

from bench import harness  # noqa: E402

NAME = "fused_reanchor_pct"


def test_reads_the_reanchored_share_of_the_flushed_answers():
    ring = [
        _rec(1, "service", 99.0, labels={"op": "flush", "tenant": "-"}),
        _rec(2, "fused_flush", 8.0, parent=1, members=4, n_reanchored=1),
        _rec(3, "fused_reanchor", 1.0, parent=2),
        _rec(4, "fused_flush", 4.0, parent=1, members=2, n_reanchored=0),
        _rec(5, "fused_flush", 6.0, members=2, n_reanchored=2),
        _rec(6, "refresh", 5.0, path="pruned"),
    ]
    # 3 of 8 flushed answers re-anchored; a solo refresh is not flushed
    assert reader(NAME).read(_ctx(ring)) == pytest.approx(37.5)
    quiet = [_rec(1, "fused_flush", 8.0, members=4, n_reanchored=0)]
    assert reader(NAME).read(_ctx(quiet)) == 0.0


def test_reads_nothing_where_flushes_do_not_count_reanchors():
    """A program that refreshes fused tenants one at a time (flushes
    without ``n_reanchored``, ``refresh`` spans beside them) or runs no
    flush leaves the metric out, and the reader does not raise."""
    trace = SimpleNamespace(n_devices=1, window_s=1.0, window_ns=(0.0, 1e9),
                            annotations=[], idle_by_host={})
    older = [_rec(1, "fused_flush", 8.0, members=4, n_warm=1, n_pruned=3),
             _rec(2, "refresh", 5.0, path="pruned")]
    assert reader(NAME).read(_ctx(older, trace)) is None
    solo = [_rec(1, "ingest", 10.0), _rec(2, "query", 9.0, path="warm")]
    assert reader(NAME).read(_ctx(solo)) is None
    assert reader(NAME).read(_ctx([])) is None


@pytest.fixture(scope="module")
def reanchor_bench(tmp_path_factory):
    """A copy of ``bench/`` with ``tiny-zipf`` added as new files only; the
    reader's list gains it, so a traced run reports it. The tiny tenants
    refresh every 4 batches, so the window's flushes re-anchor."""
    bench = os.path.join(tmp_path_factory.mktemp("reanchor_copy"), "bench")
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    config, mix = _tiny()
    config["service"] = dict(config["service"], refresh_every=4)
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
        if m["name"] == NAME:
            m["workloads"].append(CELL)
    return bench, manifest


def test_traced_run_reports_the_reanchored_share(reanchor_bench):
    bench, manifest = reanchor_bench
    result, lines = harness.run_cell(bench, manifest, CELL, 2**35 + 18, 1.0,
                                     True, time.perf_counter())
    assert result["correct"], (result["checks"], lines)
    assert 0.0 < result["metrics"][NAME]["value"] <= 100.0
