"""Checker framework: module loading, jit-context discovery, rule driver.

The linter is a set of small :class:`Rule` subclasses over a shared
per-module view (:class:`ModuleInfo`: path, dotted name, AST, source
lines, parsed pragmas) plus shared discovery passes that the rule
families reuse:

  * :func:`find_jit_contexts` — every function the tracer will run:
    ``@jax.jit`` / ``@partial(jax.jit, static_argnames=...)`` decorated
    defs, ``name = jax.jit(fn_or_lambda, ...)`` wrappings, and bodies
    handed to ``shard_map``. Each context knows
    its traced parameter names (params minus ``static_argnames``).
  * :func:`find_shard_map_calls` — shard_map call sites with their
    resolved body function and the axis tokens used in ``P(...)`` specs
    (the RPR4xx rules key on which params are actually sharded).
  * :func:`tainted_names` — a flow-insensitive closure of local names
    derived from a seed set (traced params, sharded inputs); the cheap
    stand-in for dataflow that keeps every rule ~50 lines.

Rules yield :class:`Finding`s; the :class:`Analyzer` filters them
through the pragma suppressions (recording which suppression fired, so
reports can show reviewed reasons) and turns malformed pragmas into
RPR001 findings of their own.
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field, replace
from pathlib import Path
from typing import Iterable, Iterator

from repro.analysis.pragmas import PragmaIndex, parse_pragmas

# rule family anchors (catalog lives in rules/__init__.py)
FRAMEWORK_RULE = "RPR001"


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str          # repo-relative where possible
    line: int
    message: str
    context: str = ""  # enclosing function / scope, for the human report

    def sort_key(self):
        return (self.path, self.line, self.rule)

    def to_json(self) -> dict:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "message": self.message, "context": self.context}


@dataclass
class ModuleInfo:
    path: Path
    module: str              # dotted module name, e.g. "repro.stream.delta"
    source: str
    lines: list[str]
    tree: ast.Module
    pragmas: PragmaIndex

    def rel(self, root: Path | None = None) -> str:
        try:
            return str(self.path.relative_to(root)) if root else str(self.path)
        except ValueError:
            return str(self.path)


def dotted_module_name(path: Path) -> str:
    """Best-effort dotted name: everything under the nearest ``src`` or
    site-packages-style root; falls back to the stem."""
    parts = list(path.with_suffix("").parts)
    for anchor in ("src",):
        if anchor in parts:
            parts = parts[parts.index(anchor) + 1:]
            break
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts) if parts else path.stem


def load_module(path: Path) -> ModuleInfo:
    source = Path(path).read_text()
    lines = source.splitlines()
    tree = ast.parse(source, filename=str(path))
    return ModuleInfo(path=Path(path), module=dotted_module_name(Path(path)),
                      source=source, lines=lines, tree=tree,
                      pragmas=parse_pragmas(lines))


# ---------------------------------------------------------------------------
# AST helpers shared by the rule families
# ---------------------------------------------------------------------------
def dotted(node: ast.AST) -> str:
    """'jax.lax.psum' for Attribute/Name chains; '' for anything else."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = dotted(node.value)
        return f"{base}.{node.attr}" if base else node.attr
    return ""


def names_in(node: ast.AST) -> set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


# attribute reads that are static under tracing: `x.ndim == 1` branches on
# the (compile-time) shape, not the traced value
STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding", "aval"}


def dynamic_names(node: ast.AST) -> set[str]:
    """Like :func:`names_in` but skips subtrees under a static attribute
    read (``x.shape``/``x.ndim``/``x.dtype``...): branching or hashing on
    those is trace-safe, so they must not propagate taint."""
    out: set[str] = set()

    def walk(n: ast.AST):
        if isinstance(n, ast.Attribute) and n.attr in STATIC_ATTRS:
            return
        if isinstance(n, ast.Name):
            out.add(n.id)
        for child in ast.iter_child_nodes(n):
            walk(child)

    walk(node)
    return out


def is_jax_jit(node: ast.AST) -> bool:
    return dotted(node) in ("jax.jit", "jit")


def _static_argnames_from_call(call: ast.Call) -> tuple[str, ...]:
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            vals = []
            if isinstance(kw.value, (ast.Tuple, ast.List)):
                elts = kw.value.elts
            else:
                elts = [kw.value]
            for e in elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, str):
                    vals.append(e.value)
            return tuple(vals)
    return ()


def jit_decorator_info(dec: ast.AST) -> tuple[bool, tuple[str, ...]]:
    """(is_jit_decorator, static_argnames) for one decorator node."""
    if is_jax_jit(dec):
        return True, ()
    if isinstance(dec, ast.Call):
        fn = dotted(dec.func)
        if fn in ("jax.jit",):
            return True, _static_argnames_from_call(dec)
        if fn in ("partial", "functools.partial") and dec.args \
                and is_jax_jit(dec.args[0]):
            return True, _static_argnames_from_call(dec)
    return False, ()


def param_names(fn: ast.FunctionDef | ast.AsyncFunctionDef | ast.Lambda
                ) -> list[str]:
    a = fn.args
    return [p.arg for p in (a.posonlyargs + a.args + a.kwonlyargs)]


@dataclass
class JitContext:
    """One function the jax tracer runs: where, how, and what is traced."""

    node: ast.AST                     # FunctionDef or Lambda
    name: str
    lineno: int
    kind: str                         # decorated | wrapped | shard_map_body
    static_argnames: tuple[str, ...]
    enclosing: tuple[str, ...]        # names of enclosing function defs
    module_level: bool                # defined at module scope

    @property
    def traced_params(self) -> set[str]:
        return set(param_names(self.node)) - set(self.static_argnames)

    def def_lines(self) -> set[int]:
        """Lines a pragma governing this def may sit on: the def line, the
        line above it, and any decorator lines."""
        out = {self.lineno, self.lineno - 1}
        for dec in getattr(self.node, "decorator_list", []):
            out.add(dec.lineno)
            out.add(dec.lineno - 1)
        return out


class _ScopeWalker(ast.NodeVisitor):
    """Collects (node, enclosing-def-name-chain) for every function def."""

    def __init__(self):
        self.stack: list[str] = []
        self.defs: list[tuple[ast.AST, tuple[str, ...]]] = []

    def visit_FunctionDef(self, node):
        self.defs.append((node, tuple(self.stack)))
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef


def iter_function_defs(tree: ast.Module
                       ) -> list[tuple[ast.FunctionDef, tuple[str, ...]]]:
    w = _ScopeWalker()
    w.visit(tree)
    return w.defs


def _resolve_local_def(scope_body: list[ast.stmt], name: str
                       ) -> ast.FunctionDef | None:
    for stmt in scope_body:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and stmt.name == name:
            return stmt
    return None


def find_jit_contexts(mod: ModuleInfo) -> list[JitContext]:
    contexts: list[JitContext] = []
    seen: set[int] = set()

    def add(node, name, kind, static_argnames, enclosing):
        if id(node) in seen:
            return
        seen.add(id(node))
        contexts.append(JitContext(
            node=node, name=name, lineno=node.lineno, kind=kind,
            static_argnames=tuple(static_argnames), enclosing=enclosing,
            module_level=not enclosing))

    # decorated defs
    for fn, enclosing in iter_function_defs(mod.tree):
        for dec in fn.decorator_list:
            is_jit, statics = jit_decorator_info(dec)
            if is_jit:
                add(fn, fn.name, "decorated", statics, enclosing)
                break

    # name = jax.jit(fn_or_lambda, ...) wrappings
    class _Wrap(ast.NodeVisitor):
        def __init__(self):
            self.stack: list[ast.AST] = [mod.tree]
            self.names: list[str] = []

        def _scan_call(self, call: ast.Call, target_name: str):
            if not (isinstance(call, ast.Call) and is_jax_jit(call.func)
                    and call.args):
                return
            statics = _static_argnames_from_call(call)
            inner = call.args[0]
            enclosing = tuple(self.names)
            if isinstance(inner, ast.Lambda):
                add(inner, target_name, "wrapped", statics, enclosing)
            elif isinstance(inner, ast.Name):
                target = _resolve_local_def(
                    getattr(self.stack[-1], "body", []), inner.id)
                if target is not None:
                    add(target, inner.id, "wrapped", statics, enclosing)

        def visit_Assign(self, node):
            if isinstance(node.value, ast.Call) and node.targets \
                    and isinstance(node.targets[0], ast.Name):
                self._scan_call(node.value, node.targets[0].id)
            self.generic_visit(node)

        def visit_FunctionDef(self, node):
            self.stack.append(node)
            self.names.append(node.name)
            self.generic_visit(node)
            self.names.pop()
            self.stack.pop()

        visit_AsyncFunctionDef = visit_FunctionDef

    _Wrap().visit(mod.tree)

    # shard_map bodies
    for call_info in find_shard_map_calls(mod):
        body = call_info.body
        if body is not None and id(body) not in seen:
            add(body, call_info.body_name, "shard_map_body", (),
                call_info.enclosing)
    return contexts


# ---------------------------------------------------------------------------
# shard_map call sites (shared by context discovery and the RPR4xx rules)
# ---------------------------------------------------------------------------
SHARD_MAP_NAMES = ("shard_map", "jax.shard_map", "shmap")


@dataclass
class ShardMapCall:
    call: ast.Call
    body: ast.AST | None             # resolved FunctionDef or Lambda
    body_name: str
    enclosing: tuple[str, ...]
    in_specs: ast.AST | None
    out_specs: ast.AST | None

    def spec_axis_tokens(self, specs: ast.AST | None) -> set[str]:
        """Axis tokens appearing inside ``P(...)`` constructors of a specs
        expression: variable names and string literals. These are the only
        things a collective inside the body may legally reduce over."""
        tokens: set[str] = set()
        if specs is None:
            return tokens
        for node in ast.walk(specs):
            if isinstance(node, ast.Call) \
                    and dotted(node.func) in ("P", "PartitionSpec",
                                              "jax.sharding.PartitionSpec"):
                for arg in node.args:
                    for sub in ast.walk(arg):
                        if isinstance(sub, ast.Name):
                            tokens.add(sub.id)
                        elif isinstance(sub, ast.Constant) \
                                and isinstance(sub.value, str):
                            tokens.add(sub.value)
        return tokens

    def sharded_param_indices(self) -> set[int]:
        """Positions in in_specs whose P(...) carries at least one axis —
        the body params that receive per-shard (not replicated) blocks."""
        out: set[int] = set()
        if isinstance(self.in_specs, (ast.Tuple, ast.List)):
            elts = self.in_specs.elts
        elif self.in_specs is not None:
            elts = [self.in_specs]
        else:
            return out
        for i, e in enumerate(elts):
            if self.spec_axis_tokens(e):
                out.add(i)
        return out


def find_shard_map_calls(mod: ModuleInfo) -> list[ShardMapCall]:
    calls: list[ShardMapCall] = []

    class _V(ast.NodeVisitor):
        def __init__(self):
            self.stack: list[ast.AST] = [mod.tree]
            self.names: list[str] = []

        def visit_Call(self, node: ast.Call):
            if dotted(node.func) in SHARD_MAP_NAMES and node.args:
                body_arg = node.args[0]
                body, body_name = None, "<lambda>"
                if isinstance(body_arg, ast.Lambda):
                    body = body_arg
                elif isinstance(body_arg, ast.Name):
                    body_name = body_arg.id
                    for scope in reversed(self.stack):
                        body = _resolve_local_def(
                            getattr(scope, "body", []), body_arg.id)
                        if body is not None:
                            break
                kwargs = {kw.arg: kw.value for kw in node.keywords}
                calls.append(ShardMapCall(
                    call=node, body=body, body_name=body_name,
                    enclosing=tuple(self.names),
                    in_specs=kwargs.get("in_specs"),
                    out_specs=kwargs.get("out_specs")))
            self.generic_visit(node)

        def visit_FunctionDef(self, node):
            self.stack.append(node)
            self.names.append(node.name)
            self.generic_visit(node)
            self.names.pop()
            self.stack.pop()

        visit_AsyncFunctionDef = visit_FunctionDef

    _V().visit(mod.tree)
    return calls


def tainted_names(fn: ast.AST, seeds: set[str]) -> set[str]:
    """Names (transitively) assigned from expressions referencing ``seeds``
    inside ``fn`` — flow-insensitive, iterated to a fixpoint so later
    passes catch assignments that textually precede their sources."""
    tainted = set(seeds)
    body = getattr(fn, "body", [])
    if isinstance(fn, ast.Lambda):
        return tainted
    assigns: list[tuple[set[str], set[str]]] = []  # (targets, sources)
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign):
            targets = set()
            for t in node.targets:
                targets |= names_in(t)
            assigns.append((targets, dynamic_names(node.value)))
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)) \
                and node.value is not None:
            assigns.append((names_in(node.target), dynamic_names(node.value)))
    del body
    changed = True
    while changed:
        changed = False
        for targets, sources in assigns:
            if sources & tainted and not targets <= tainted:
                tainted |= targets
                changed = True
    return tainted


# ---------------------------------------------------------------------------
# rule base + driver
# ---------------------------------------------------------------------------
class Rule:
    """One checker. Subclasses set ``rule_id``/``title`` and implement
    ``check_module``; project-wide rules (RPR2xx) implement
    ``check_project`` over every module at once and set
    ``project_level = True``."""

    rule_id: str = "RPR000"
    title: str = ""
    project_level: bool = False

    def check_module(self, mod: ModuleInfo) -> Iterator[Finding]:
        return iter(())

    def check_project(self, mods: list[ModuleInfo]) -> Iterator[Finding]:
        return iter(())


@dataclass
class AnalysisResult:
    findings: list[Finding]
    suppressed: list[tuple[Finding, str]]   # (finding, reason)
    files: int

    @property
    def counts(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for f in self.findings:
            out[f.rule] = out.get(f.rule, 0) + 1
        return dict(sorted(out.items()))


class Analyzer:
    def __init__(self, rules: Iterable[Rule], root: Path | None = None):
        self.rules = list(rules)
        self.root = root

    def _collect_paths(self, paths: Iterable[Path]) -> list[Path]:
        out: list[Path] = []
        for p in paths:
            p = Path(p)
            if p.is_dir():
                out.extend(sorted(p.rglob("*.py")))
            elif p.suffix == ".py":
                out.append(p)
        return out

    def run(self, paths: Iterable[Path]) -> AnalysisResult:
        files = self._collect_paths(paths)
        mods: list[ModuleInfo] = []
        raw: list[Finding] = []
        for path in files:
            try:
                mod = load_module(path)
            except SyntaxError as e:
                raw.append(Finding(
                    rule=FRAMEWORK_RULE, path=str(path),
                    line=e.lineno or 0, message=f"syntax error: {e.msg}"))
                continue
            mods.append(mod)
            for line, msg in mod.pragmas.malformed:
                raw.append(Finding(rule=FRAMEWORK_RULE, path=mod.rel(),
                                   line=line,
                                   message=f"malformed pragma: {msg}"))
            for rule in self.rules:
                if not rule.project_level:
                    raw.extend(rule.check_module(mod))
        for rule in self.rules:
            if rule.project_level:
                raw.extend(rule.check_project(mods))

        # rules key findings on mod.rel() (no root); match suppressions on
        # that same key, then relativize for display
        by_path = {mod.rel(): mod for mod in mods}
        rel_path = {mod.rel(): mod.rel(self.root) for mod in mods}
        findings: list[Finding] = []
        suppressed: list[tuple[Finding, str]] = []
        for f in raw:
            mod = by_path.get(f.path)
            sup = mod.pragmas.is_suppressed(f.rule, f.line) if mod else None
            if f.path in rel_path and rel_path[f.path] != f.path:
                f = replace(f, path=rel_path[f.path])
            if sup is not None and f.rule != FRAMEWORK_RULE:
                suppressed.append((f, sup.reason))
            else:
                findings.append(f)
        findings.sort(key=Finding.sort_key)
        return AnalysisResult(findings=findings, suppressed=suppressed,
                              files=len(files))


def run_analysis(paths: Iterable[Path], rules: Iterable[Rule] | None = None,
                 root: Path | None = None) -> AnalysisResult:
    """One-call API: lint ``paths`` with ``rules`` (default: the full
    catalog) and return the filtered result."""
    if rules is None:
        from repro.analysis.rules import ALL_RULES
        rules = [cls() for cls in ALL_RULES]
    return Analyzer(rules, root=root).run(paths)


__all__ = [
    "Analyzer", "AnalysisResult", "Finding", "JitContext", "ModuleInfo",
    "Rule", "ShardMapCall", "dotted", "dotted_module_name",
    "find_jit_contexts", "find_shard_map_calls", "iter_function_defs",
    "jit_decorator_info", "load_module", "names_in", "dynamic_names",
    "param_names", "run_analysis", "tainted_names", "STATIC_ATTRS",
]
