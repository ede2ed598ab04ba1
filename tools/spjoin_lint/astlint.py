"""Scope machinery + driver of the AST layer.

The interesting part is hot-scope detection. Rather than hand-listing every
jitted function (which rots on the first refactor), the linter finds traced
scopes STRUCTURALLY:

  * decorated: ``@jax.jit`` / ``@functools.partial(jax.jit, ...)``
  * passed to a tracer: ``jax.jit(f, ...)``, ``jax.shard_map(f, ...)``,
    ``jax.vmap(f)``, ``jax.lax.scan(f, ...)``, ``jax.lax.switch(i, [f, g])``,
    ``pl.pallas_call(f, ...)`` — including module-level aliases like
    ``_tile_verify = jax.jit(verify_tile, static_argnames=...)``
  * nested inside a traced function (closures trace with their parent)
  * CALLED from a traced function in the same module (intra-module call
    graph, iterated to a fixpoint) — helpers like ``apply_dedup`` or
    ``_map_assign`` are traced because their callers are.

``static_argnames`` are read off the jit call/decorator so that
``float(delta)`` on a static argument is not a sync. What structure cannot
see (factory-returned closures invoked through a variable, and the "stream"
tier, which is a design decision) comes from ``config.EXTRA_TRACED`` /
``config.STREAM_SCOPES``.
"""
from __future__ import annotations

import ast
import dataclasses
import pathlib

from spjoin_lint import config


@dataclasses.dataclass
class Violation:
    file: str
    line: int
    rule: str
    message: str
    waived: bool = False

    def format(self) -> str:
        tag = " (waived)" if self.waived else ""
        return f"{self.file}:{self.line}: [{self.rule}] {self.message}{tag}"


@dataclasses.dataclass
class FuncInfo:
    node: ast.AST  # FunctionDef | AsyncFunctionDef
    qualname: str
    parent: "FuncInfo | None"
    tier: str | None = None  # "traced" | "stream" | None
    exempt: bool = False
    static_args: frozenset = frozenset()
    children: dict = dataclasses.field(default_factory=dict)  # name -> FuncInfo


def _root_name(node: ast.AST) -> str | None:
    """Leftmost Name of a dotted attribute chain (``jax.lax.scan`` -> jax)."""
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _attr_tail(node: ast.AST) -> str | None:
    return node.attr if isinstance(node, ast.Attribute) else (
        node.id if isinstance(node, ast.Name) else None
    )


def _static_argnames_from_call(call: ast.Call) -> frozenset:
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                return frozenset({v.value})
            if isinstance(v, (ast.Tuple, ast.List)):
                return frozenset(
                    e.value
                    for e in v.elts
                    if isinstance(e, ast.Constant) and isinstance(e.value, str)
                )
    return frozenset()


def _is_jit_expr(node: ast.AST) -> bool:
    """``jax.jit`` or bare ``jit`` as an expression."""
    return _attr_tail(node) == "jit"


# Call-taking tracer APIs: attr name -> index/extractor of the traced callee.
_TRACER_FIRST_ARG = {"shard_map", "jit", "vmap", "pmap", "scan", "pallas_call",
                     "checkpoint", "remat", "custom_vjp", "grad", "value_and_grad"}


class ModuleIndex:
    """Per-file scope index: functions, tiers, static argnames."""

    def __init__(self, tree: ast.Module, relpath: str):
        self.tree = tree
        self.relpath = relpath
        self.functions: dict[str, FuncInfo] = {}
        self._by_node: dict[int, FuncInfo] = {}
        self.module_scope: dict[str, FuncInfo] = {}
        self._build(tree)
        self._detect_seeds(tree)
        self._apply_config()
        self._propagate_calls()
        self._apply_config()  # config tiers win over propagation

    # -- construction ------------------------------------------------------

    def _build(self, tree: ast.Module) -> None:
        def visit(node: ast.AST, parent: FuncInfo | None, prefix: str) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    qual = f"{prefix}{child.name}"
                    fi = FuncInfo(node=child, qualname=qual, parent=parent)
                    self.functions[qual] = fi
                    self._by_node[id(child)] = fi
                    if parent is None:
                        self.module_scope[child.name] = fi
                    else:
                        parent.children[child.name] = fi
                    visit(child, fi, qual + ".")
                elif isinstance(child, ast.ClassDef):
                    visit(child, parent, f"{prefix}{child.name}.")
                else:
                    visit(child, parent, prefix)

        visit(tree, None, "")

    def func_of(self, node: ast.AST) -> FuncInfo | None:
        return self._by_node.get(id(node))

    # -- seed detection ----------------------------------------------------

    def _mark_traced(self, fi: FuncInfo, statics: frozenset = frozenset()) -> None:
        stack = [fi]
        while stack:
            f = stack.pop()
            if f.tier is None:
                f.tier = "traced"
            stack.extend(f.children.values())
        if statics:
            fi.static_args = fi.static_args | statics

    def _resolve(self, name: str, scope: FuncInfo | None) -> FuncInfo | None:
        """Resolve a bare function name from a scope, innermost first."""
        s = scope
        while s is not None:
            if name in s.children:
                return s.children[name]
            s = s.parent
        return self.module_scope.get(name)

    def _detect_seeds(self, tree: ast.Module) -> None:
        # Decorators.
        for fi in self.functions.values():
            for dec in getattr(fi.node, "decorator_list", []):
                if _is_jit_expr(dec):
                    self._mark_traced(fi)
                elif isinstance(dec, ast.Call):
                    if _is_jit_expr(dec.func):
                        self._mark_traced(fi, _static_argnames_from_call(dec))
                    elif (
                        _attr_tail(dec.func) == "partial"
                        and dec.args
                        and _is_jit_expr(dec.args[0])
                    ):
                        self._mark_traced(fi, _static_argnames_from_call(dec))

        # Call sites: jax.jit(f, ...), shard_map(f, ...), vmap/scan/switch...
        scope_stack: list[FuncInfo] = []

        index = self

        class SeedVisitor(ast.NodeVisitor):
            def visit_FunctionDef(self, node):  # noqa: N802
                scope_stack.append(index._by_node[id(node)])
                self.generic_visit(node)
                scope_stack.pop()

            visit_AsyncFunctionDef = visit_FunctionDef  # noqa: N815

            def visit_Call(self, node):  # noqa: N802
                tail = _attr_tail(node.func)
                scope = scope_stack[-1] if scope_stack else None
                if tail in _TRACER_FIRST_ARG and node.args:
                    arg = node.args[0]
                    if isinstance(arg, ast.Name):
                        fi = index._resolve(arg.id, scope)
                        if fi is not None:
                            statics = (
                                _static_argnames_from_call(node)
                                if tail == "jit"
                                else frozenset()
                            )
                            index._mark_traced(fi, statics)
                elif tail == "switch" and len(node.args) >= 2:
                    branches = node.args[1]
                    if isinstance(branches, (ast.List, ast.Tuple)):
                        for e in branches.elts:
                            if isinstance(e, ast.Name):
                                fi = index._resolve(e.id, scope)
                                if fi is not None:
                                    index._mark_traced(fi)
                self.generic_visit(node)

        SeedVisitor().visit(tree)

    def _propagate_calls(self) -> None:
        """Callees of traced functions (same module, bare-name calls) trace
        with their caller. Iterated to a fixpoint."""
        changed = True
        while changed:
            changed = False
            for fi in list(self.functions.values()):
                if fi.tier != "traced":
                    continue
                for node in scope_walk(fi.node):
                    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
                        callee = self._resolve(node.func.id, fi)
                        if callee is not None and callee.tier is None:
                            self._mark_traced(callee)
                            changed = True

    def _apply_config(self) -> None:
        rel = self.relpath
        for suffix, quals in config.STREAM_SCOPES.items():
            if rel.endswith(suffix):
                for q in quals:
                    if q in self.functions:
                        self.functions[q].tier = "stream"
        for suffix, quals in config.EXTRA_TRACED.items():
            if rel.endswith(suffix):
                for q in quals:
                    if q in self.functions:
                        self._mark_traced(self.functions[q])
        for suffix, quals in config.EXEMPT_SCOPES.items():
            if rel.endswith(suffix):
                for q in quals:
                    if q in self.functions:
                        self.functions[q].tier = None
                        self.functions[q].exempt = True

    def top_level_name(self, fi: FuncInfo) -> str:
        return fi.qualname.split(".")[0]


def scope_walk(func_node: ast.AST):
    """Walk a function body WITHOUT descending into nested function defs
    (each scope is checked once, under its own tier)."""
    stack = list(ast.iter_child_nodes(func_node))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            stack.extend(ast.iter_child_nodes(node))


def iter_lint_files(paths: list[str]) -> list[pathlib.Path]:
    """Expand CLI paths to the .py files in scope (config.LINT_ROOTS)."""
    out: list[pathlib.Path] = []
    for p in paths:
        path = pathlib.Path(p)
        if path.is_file():
            out.append(path)
            continue
        for f in sorted(path.rglob("*.py")):
            rel = f.as_posix()
            if any(root in rel for root in config.LINT_ROOTS):
                out.append(f)
    return out


def lint_file(path: pathlib.Path, max_waivers: int | None = None) -> list[Violation]:
    """Lint one file: run every rule, apply waivers, check waiver hygiene.

    ``max_waivers=None`` skips the global-ratchet check (it is cross-file;
    ``lint_paths`` applies it once over the whole run).
    """
    from spjoin_lint import rules as rules_mod
    from spjoin_lint import waivers as waivers_mod

    source = path.read_text()
    tree = ast.parse(source, filename=str(path))
    relpath = path.as_posix()
    idx = ModuleIndex(tree, relpath)

    violations: list[Violation] = []
    for rule in rules_mod.ALL_RULES:
        violations.extend(rule(idx))

    wvs = waivers_mod.parse_waivers(source, relpath)
    by_line = waivers_mod.waivers_by_target(wvs)
    for v in violations:
        for w in by_line.get(v.line, []):
            if v.rule in w.rules:
                v.waived = True
                w.used = True

    # waiver-hygiene: justified, known rule, actually used.
    for w in wvs:
        unknown = [r for r in w.rules if r not in config.RULES]
        if unknown:
            violations.append(
                Violation(
                    relpath, w.line, "waiver-hygiene",
                    f"waiver names unknown rule(s) {unknown}; known rules: "
                    f"{list(config.RULES)}",
                )
            )
        if len(w.justification) < config.MIN_JUSTIFICATION:
            violations.append(
                Violation(
                    relpath, w.line, "waiver-hygiene",
                    "waiver has no (or a trivial) justification — write "
                    "`# spjoin-lint: allow[rule] -- why this sync/cast is "
                    "sound here`",
                )
            )
        if not w.used:
            violations.append(
                Violation(
                    relpath, w.line, "waiver-hygiene",
                    "unused waiver (suppresses nothing on its target line) — "
                    "remove it and lower config.MAX_WAIVERS",
                )
            )
    violations = [v for v in violations if not v.waived]
    violations.sort(key=lambda v: (v.line, v.rule))
    return violations


def lint_paths(paths: list[str]) -> tuple[list[Violation], int]:
    """Lint every in-scope file under ``paths``.

    Returns (violations, n_waivers). The waiver-count ratchet
    (``config.MAX_WAIVERS``) is applied across the whole run; exceeding it
    appends one waiver-hygiene violation.
    """
    from spjoin_lint import waivers as waivers_mod

    violations: list[Violation] = []
    n_waivers = 0
    files = iter_lint_files(paths)
    for f in files:
        violations.extend(lint_file(f))
        n_waivers += len(waivers_mod.parse_waivers(f.read_text(), f.as_posix()))
    if n_waivers > config.MAX_WAIVERS:
        violations.append(
            Violation(
                paths[0] if paths else ".", 0, "waiver-hygiene",
                f"{n_waivers} waivers in tree exceed the ratchet "
                f"(MAX_WAIVERS={config.MAX_WAIVERS}). The ratchet only moves "
                f"down: fix the new violation for real, or make the case for "
                f"raising it in review",
            )
        )
    return violations, n_waivers
