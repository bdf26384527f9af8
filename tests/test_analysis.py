"""Tests for the invariant linter (repro.analysis).

One known-good + one known-bad fixture snippet per rule ID, pragma
round-trips, reporter/exit-code contracts, and the meta-test: the repo's
own tree lints clean (0 findings) — the same gate CI's
``make lint-invariants`` enforces.
"""
from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis import run_analysis
from repro.analysis.cli import main as cli_main
from repro.analysis.framework import load_module
from repro.analysis.pragmas import parse_pragmas
from repro.analysis.report import to_json
from repro.analysis.rules import ALL_RULES, RULE_CATALOG, rules_by_id
from repro.analysis.rules.audit import AuditCoverageRule

REPO = Path(__file__).resolve().parents[1]
SRC = REPO / "src" / "repro"


def _static_rules(ids=None):
    """Rule set with RPR201 in pure-static mode (no runtime import) so
    fixture modules don't need the live providers snapshot."""
    rules = []
    for cls in ALL_RULES:
        if ids and cls.rule_id not in ids:
            continue
        rules.append(cls(dynamic=False) if cls is AuditCoverageRule
                     else cls())
    return rules


def lint_snippet(tmp_path, code: str, ids=None):
    path = tmp_path / "snippet.py"
    path.write_text(textwrap.dedent(code))
    return run_analysis([path], rules=_static_rules(ids))


def rule_ids(result):
    return sorted({f.rule for f in result.findings})


# ---------------------------------------------------------------------------
# fixtures per rule: (rule id, known-bad snippet, known-good snippet)
# ---------------------------------------------------------------------------
FIXTURES = [
    ("RPR101", """
        import jax
        @jax.jit
        def f(x):
            y = x + 1
            return float(y)
        """, """
        import jax, jax.numpy as jnp
        @jax.jit
        def f(x):
            return jnp.float32(x + 1)
        """),
    ("RPR102", """
        import jax
        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
        """, """
        import jax, jax.numpy as jnp
        @jax.jit
        def f(x):
            if x.ndim == 1:          # static shape branch: fine
                x = x[:, None]
            return jnp.where(x > 0, x, -x)
        """),
    ("RPR103", """
        import jax
        @jax.jit
        def f(x):
            s = x + 1
            return {s: 1}
        """, """
        import jax
        @jax.jit
        def f(x):
            return {x.ndim: x}       # ndim is static under tracing
        """),
    ("RPR104", """
        import jax
        def caller(fn, x):
            step = jax.jit(fn)
            return step(x)
        """, """
        import jax
        from functools import lru_cache
        @lru_cache(maxsize=None)
        def make_step(n):
            return jax.jit(lambda x: x * n)
        """),
    ("RPR201", """
        import jax

        def helper(x):
            @jax.jit
            def run(y):
                return y + 1
            return run(x)
        """, """
        import jax
        MY_JITS = []

        def helper_factory():
            @jax.jit
            def run(y):
                return y + 1
            MY_JITS.append(run)
            return run
        """),
    ("RPR301", """
        # repro: proof
        def certify(ne, nv):
            return ne >= nv * 2.0
        """, """
        # repro: proof
        def certify(ne, nv):
            return ne >= nv * 2
        """),
    ("RPR302", """
        # repro: proof
        def density(ne, nv):
            return ne / nv
        """, """
        # repro: proof
        def denser(a_ne, a_nv, b_ne, b_nv):
            return a_ne * b_nv > b_ne * a_nv
        """),
    ("RPR303", """
        import jax.numpy as jnp
        # repro: proof
        def acc(x):
            return x.astype(jnp.float32)
        """, """
        import jax.numpy as jnp
        # repro: proof
        def acc(x):
            return x.astype(jnp.int32)
        """),
    ("RPR304", """
        from repro.core.dispatch import peel_delta

        def round_step(fail, dst, n, kernel):
            return peel_delta(fail, dst, n, kernel)
        """, """
        from repro.core.dispatch import assert_exact_envelope, peel_delta

        def round_step(fail, dst, n, kernel):
            assert_exact_envelope(n)
            return peel_delta(fail, dst, n, kernel)
        """),
    ("RPR401", """
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map

        def make(mesh, axes):
            def body(src_l):
                local = jnp.sum(src_l)
                return local
            return shard_map(body, mesh=mesh, in_specs=(P(axes),),
                             out_specs=P())
        """, """
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map

        def make(mesh, axes):
            def body(src_l):
                local = jnp.sum(src_l)
                return jax.lax.psum(local, axes)
            return shard_map(body, mesh=mesh, in_specs=(P(axes),),
                             out_specs=P())
        """),
    ("RPR402", """
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map

        def make(mesh):
            def body(src_l):
                return jax.lax.psum(jnp.sum(src_l), "workers")
            return shard_map(body, mesh=mesh, in_specs=(P("edges"),),
                             out_specs=P())
        """, """
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map

        def make(mesh):
            def body(src_l):
                return jax.lax.psum(jnp.sum(src_l), "edges")
            return shard_map(body, mesh=mesh, in_specs=(P("edges"),),
                             out_specs=P())
        """),
    ("RPR501", """
        class Pool:
            def __init__(self):
                self.batches = {}

            def batch_for(self, node_capacity, edge_capacity, eps,
                          kernel=False, mesh=None):
                key = (int(node_capacity), int(edge_capacity), float(eps),
                       bool(kernel))  # mesh missing: sharded tenants alias
                if key not in self.batches:
                    self.batches[key] = object()
                return self.batches[key]
        """, """
        class Pool:
            def __init__(self):
                self.batches = {}

            def batch_for(self, node_capacity, edge_capacity, eps,
                          kernel=False, mesh=None):
                key = (int(node_capacity), int(edge_capacity), float(eps),
                       bool(kernel), mesh)
                if key not in self.batches:
                    self.batches[key] = object()
                return self.batches[key]
        """),
]


@pytest.mark.parametrize("rule_id,bad,good",
                         FIXTURES, ids=[f[0] for f in FIXTURES])
def test_rule_fires_on_bad_fixture(tmp_path, rule_id, bad, good):
    result = lint_snippet(tmp_path, bad)
    assert rule_id in rule_ids(result), (
        f"{rule_id} did not fire on its known-bad fixture; "
        f"got {rule_ids(result)}")


@pytest.mark.parametrize("rule_id,bad,good",
                         FIXTURES, ids=[f[0] for f in FIXTURES])
def test_rule_silent_on_good_fixture(tmp_path, rule_id, bad, good):
    result = lint_snippet(tmp_path, good)
    assert rule_id not in rule_ids(result), (
        f"{rule_id} fired on its known-good fixture: "
        f"{[f.message for f in result.findings if f.rule == rule_id]}")


def test_rule_filter_restricts_findings(tmp_path):
    bad_everything = FIXTURES[0][1]  # RPR101 bad snippet
    result = lint_snippet(tmp_path, bad_everything, ids={"RPR302"})
    assert result.findings == []


# ---------------------------------------------------------------------------
# pragmas / suppressions
# ---------------------------------------------------------------------------
def test_pragma_suppression_round_trip(tmp_path):
    bad = """
        # repro: proof
        def density(ne, nv):
            return ne / nv  # repro: allow RPR302 -- reporting convenience
        """
    result = lint_snippet(tmp_path, bad)
    assert "RPR302" not in rule_ids(result)
    assert len(result.suppressed) == 1
    finding, reason = result.suppressed[0]
    assert finding.rule == "RPR302"
    assert reason == "reporting convenience"


def test_standalone_suppression_covers_next_line(tmp_path):
    bad = """
        # repro: proof
        def density(ne, nv):
            # repro: allow RPR302 -- reporting convenience
            return ne / nv
        """
    result = lint_snippet(tmp_path, bad)
    assert "RPR302" not in rule_ids(result)
    assert len(result.suppressed) == 1


def test_suppression_does_not_leak_to_other_lines(tmp_path):
    bad = """
        # repro: proof
        def density(ne, nv):
            x = ne / nv  # repro: allow RPR302 -- here only
            return ne / nv
        """
    result = lint_snippet(tmp_path, bad)
    assert "RPR302" in rule_ids(result)          # second line still flagged
    assert len(result.suppressed) == 1


def test_malformed_pragmas_are_rpr001(tmp_path):
    bad = """
        # repro: allow -- no rule ids
        # repro: allow RPR302
        # repro: unaudited
        # repro: frobnicate
        x = 1
        """
    result = lint_snippet(tmp_path, bad)
    assert [f.rule for f in result.findings] == ["RPR001"] * 4


def test_rpr001_is_not_suppressible(tmp_path):
    bad = """
        # repro: frobnicate  # repro: allow RPR001 -- nice try
        x = 1
        """
    result = lint_snippet(tmp_path, bad)
    assert "RPR001" in rule_ids(result)


def test_pragma_text_inside_strings_is_ignored():
    idx = parse_pragmas(['DOC = "use # repro: allow RPR301 to suppress"',
                         "x = 1  # repro: proof"])
    assert idx.malformed == []
    assert idx.proof_lines == {2}


def test_unaudited_pragma_requires_reason():
    idx = parse_pragmas(["# repro: unaudited -- demo path, not audited"])
    assert idx.unaudited == {1: "demo path, not audited"}
    idx2 = parse_pragmas(["# repro: unaudited"])
    assert idx2.unaudited == {} and len(idx2.malformed) == 1


def test_unaudited_silences_rpr201(tmp_path):
    bad = """
        import jax

        def helper(x):
            # repro: unaudited -- fixture
            @jax.jit
            def run(y):
                return y + 1
            return run(x)
        """
    result = lint_snippet(tmp_path, bad, ids={"RPR201"})
    assert result.findings == []


# ---------------------------------------------------------------------------
# CLI / reporters
# ---------------------------------------------------------------------------
def test_cli_exit_codes_and_json(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("# repro: proof\ndef f(a, b):\n    return a / b\n")
    good = tmp_path / "good.py"
    good.write_text("def f(a, b):\n    return a // b\n")

    assert cli_main(["--static", str(good)]) == 0
    capsys.readouterr()
    assert cli_main(["--static", "--json", str(bad)]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["counts"] == {"RPR302": 1}
    assert payload["findings"][0]["rule"] == "RPR302"
    assert payload["findings"][0]["line"] == 3

    assert cli_main(["--static", str(tmp_path / "missing.py")]) == 2
    capsys.readouterr()
    assert cli_main(["--static", "--rules", "RPR999", str(good)]) == 2
    capsys.readouterr()
    assert cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in RULE_CATALOG:
        assert rid in out


def test_json_report_includes_suppression_reasons(tmp_path):
    path = tmp_path / "s.py"
    path.write_text("# repro: proof\ndef f(a, b):\n"
                    "    return a / b  # repro: allow RPR302 -- why not\n")
    result = run_analysis([path], rules=_static_rules())
    payload = json.loads(to_json(result))
    assert payload["findings"] == []
    assert payload["suppressed"][0]["reason"] == "why not"


def test_catalog_is_consistent():
    ids = [cls.rule_id for cls in ALL_RULES]
    assert len(ids) == len(set(ids))
    assert set(RULE_CATALOG) == set(ids) | {"RPR001"}
    assert all(r.rule_id in RULE_CATALOG for r in rules_by_id())
    assert [r.rule_id for r in rules_by_id(["RPR301"])] == ["RPR301"]


def test_syntax_error_reports_rpr001(tmp_path):
    path = tmp_path / "broken.py"
    path.write_text("def f(:\n")
    result = run_analysis([path], rules=_static_rules())
    assert [f.rule for f in result.findings] == ["RPR001"]


# ---------------------------------------------------------------------------
# the repo's own tree
# ---------------------------------------------------------------------------
def test_repo_tree_lints_clean():
    """The CI gate: src/repro has 0 findings under the full catalog (with
    the dynamic RPR201 providers snapshot), and every suppression that
    fired carries a reason."""
    result = run_analysis([SRC], root=REPO)
    assert result.findings == [], "\n".join(
        f"{f.path}:{f.line}: {f.rule} {f.message}" for f in result.findings)
    assert all(reason for _f, reason in result.suppressed)


def test_providers_snapshot_matches_static_discovery():
    """providers_snapshot() (the runtime source of truth for RPR201) names
    the stream provider and yields the delta entry points the static
    walker sees at module level."""
    import repro.stream.delta  # noqa: F401 — registers the provider
    from repro.obs.audit import AUDITOR

    snap = AUDITOR.providers_snapshot()
    assert "stream" in snap
    entries = set(snap["stream"])
    mod = load_module(SRC / "stream" / "delta.py")
    assert mod.module == "repro.stream.delta"
    assert "repro.stream.delta._apply_batch_jit" in entries
    assert "repro.stream.delta._apply_batch_sorted_jit" in entries


def test_repro_lint_entry_point_runs():
    """`python -m repro.analysis` (the repro-lint console script target)
    exits 0 on a clean file."""
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--static", "--json",
         str(SRC / "analysis" / "pragmas.py")],
        capture_output=True, text=True,
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin",
             "HOME": "/tmp"},
    )
    assert proc.returncode == 0, proc.stderr
    assert json.loads(proc.stdout)["findings"] == []
