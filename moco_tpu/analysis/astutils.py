"""Shared AST machinery for the mocolint rules.

Everything here is deliberately *approximate*: a linter wants high-value
findings at near-zero false-positive rate, not soundness. The key
primitives:

- import-alias resolution (`jnp.einsum` -> ``jax.numpy.einsum``,
  ``from jax import lax`` -> ``jax.lax``), so rules match on dotted
  qualnames instead of guessing at surface spellings;
- jitted-scope discovery: functions decorated with or passed to
  `jax.jit`/`shard_map`/`pmap`, closed transitively over module-local
  calls and nested defs (``step_fn`` passed to ``shard_map`` pulls its
  helper ``loss_fn`` into scope);
- a small branch-aware statement walker for the flow-sensitive rules
  (PRNG reuse, stop_gradient taint, donated-buffer liveness): `if`
  branches analyze independently and merge, loop bodies run twice so
  cross-iteration reuse is seen.
"""

from __future__ import annotations

import ast
from typing import Callable, Iterator, Optional


# ---------------------------------------------------------------------------
# import / qualname resolution


def collect_imports(tree: ast.Module) -> dict[str, str]:
    """Local binding -> dotted origin, e.g. {'jnp': 'jax.numpy',
    'lax': 'jax.lax', 'shard_map': 'jax.shard_map'}."""
    imports: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.asname:
                    imports[a.asname] = a.name
                else:
                    root = a.name.split(".")[0]
                    imports[root] = root
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            for a in node.names:
                if a.name == "*":
                    continue
                origin = f"{mod}.{a.name}" if mod else a.name
                imports[a.asname or a.name] = origin
    return imports


def qualname(node: ast.AST, imports: dict[str, str]) -> Optional[str]:
    """Dotted name of an expression through the import map, or None for
    anything that isn't a plain Name/Attribute chain."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        return ".".join([imports.get(node.id, node.id)] + parts[::-1])
    return None


def jit_kind(qual: Optional[str]) -> Optional[str]:
    """'jit' / 'shard_map' / 'pmap' when `qual` names a compile wrapper."""
    if not qual:
        return None
    if qual in ("jax.jit", "jax.pjit") or qual.endswith((".jit", ".pjit")):
        return "jit"
    if qual == "shard_map" or qual.endswith(".shard_map"):
        return "shard_map"
    if qual == "pmap" or qual.endswith(".pmap"):
        return "pmap"
    return None


def decorator_qual(dec: ast.AST, imports: dict[str, str]) -> Optional[str]:
    """Resolve a decorator to the wrapper it applies: handles bare names,
    attribute chains, `@jax.jit(...)` calls, and `@partial(jax.jit, ...)`."""
    if isinstance(dec, ast.Call):
        q = qualname(dec.func, imports)
        if q and (q == "partial" or q.endswith(".partial")) and dec.args:
            return qualname(dec.args[0], imports)
        return q
    return qualname(dec, imports)


# ---------------------------------------------------------------------------
# module context


class ModuleContext:
    """Everything a rule needs about one parsed file."""

    def __init__(self, tree: ast.Module, source: str, path: str):
        self.tree = tree
        self.path = path
        self.source_lines = source.splitlines()
        # whole-program backrefs, attached by analysis.callgraph when the
        # engine analyzes a file SET; None for a lone-module analysis
        self.program = None
        self.module_name: Optional[str] = None
        self.imports = collect_imports(tree)
        self.functions: list[ast.FunctionDef] = [
            n
            for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        self.defs_by_name: dict[str, list[ast.FunctionDef]] = {}
        for f in self.functions:
            self.defs_by_name.setdefault(f.name, []).append(f)
        self.constants = self._module_constants(tree)
        self.jitted = self._find_jitted()

    @staticmethod
    def _module_constants(tree: ast.Module) -> dict[str, str]:
        """Module-level NAME = "string" assignments (axis-name constants)."""
        out: dict[str, str] = {}
        for node in tree.body:
            if (
                isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)
            ):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        out[t.id] = node.value.value
        return out

    def _find_jitted(self) -> set[ast.FunctionDef]:
        """Functions compiled by jit/shard_map/pmap, closed over nested
        defs and module-local calls (one trace pulls all of them in)."""
        roots: list[ast.FunctionDef] = []
        for f in self.functions:
            for dec in f.decorator_list:
                if jit_kind(decorator_qual(dec, self.imports)):
                    roots.append(f)
                    break
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call) and jit_kind(qualname(node.func, self.imports)):
                if node.args and isinstance(node.args[0], ast.Name):
                    roots.extend(self.defs_by_name.get(node.args[0].id, []))
        jitted: set[ast.FunctionDef] = set()
        stack = list(roots)
        while stack:
            f = stack.pop()
            if f in jitted:
                continue
            jitted.add(f)
            for n in ast.walk(f):
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) and n is not f:
                    stack.append(n)
                elif isinstance(n, ast.Call) and isinstance(n.func, ast.Name):
                    stack.extend(self.defs_by_name.get(n.func.id, []))
        return jitted

    def qual(self, node: ast.AST) -> Optional[str]:
        return qualname(node, self.imports)


def walk_own(fn: ast.FunctionDef) -> Iterator[ast.AST]:
    """Walk a function's own body, NOT descending into nested function /
    class definitions (those are analyzed as their own scopes)."""
    stack: list[ast.AST] = list(fn.body)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef, ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def names_in(node: ast.AST) -> Iterator[ast.Name]:
    for n in ast.walk(node):
        if isinstance(n, ast.Name):
            yield n


def call_args(node: ast.Call) -> Iterator[ast.AST]:
    """All argument expressions of a call (positional, *args, keywords)."""
    for a in node.args:
        yield a.value if isinstance(a, ast.Starred) else a
    for kw in node.keywords:
        yield kw.value


def stmt_exprs(stmt: ast.stmt) -> list[ast.AST]:
    """The expressions a flow rule should scan for one statement: the
    whole node for simple statements, only the controlling expression for
    compound ones (bodies are walked separately by FlowVisitor)."""
    if isinstance(stmt, ast.If) or isinstance(stmt, ast.While):
        return [stmt.test]
    if isinstance(stmt, (ast.For, ast.AsyncFor)):
        return [stmt.iter]
    if isinstance(stmt, (ast.With, ast.AsyncWith)):
        return [item.context_expr for item in stmt.items]
    return [stmt]


# ---------------------------------------------------------------------------
# branch-aware statement walker for flow-sensitive rules


class FlowVisitor:
    """Sequential statement walk with `if` branch forking/merging and a
    double pass over loop bodies (so a key consumed once per iteration
    without re-derivation is seen as reused).

    Subclasses implement `visit_stmt(stmt, state)` mutating `state`, plus
    `fork(state)` and `merge(a, b)`. Nested function defs are visited in
    place with the enclosing state (closures capture it); their
    parameters are reported through `enter_function`.
    """

    def run(self, fn: ast.FunctionDef, state) -> None:
        self.enter_function(fn, state)
        self._block(fn.body, state)

    def enter_function(self, fn: ast.FunctionDef, state) -> None:  # override
        pass

    def fork(self, state):  # override
        raise NotImplementedError

    def merge(self, a, b):  # override
        raise NotImplementedError

    def visit_stmt(self, stmt: ast.stmt, state) -> None:  # override
        pass

    @staticmethod
    def _terminates(stmts: list[ast.stmt]) -> bool:
        """Does this branch leave the enclosing block (no fall-through)?"""
        return bool(stmts) and isinstance(
            stmts[-1], (ast.Return, ast.Raise, ast.Continue, ast.Break)
        )

    def _block(self, stmts: list[ast.stmt], state) -> None:
        for stmt in stmts:
            if isinstance(stmt, ast.If):
                self.visit_stmt(stmt, state)  # the test expression itself
                body_state = self.fork(state)
                else_state = self.fork(state)
                self._block(stmt.body, body_state)
                self._block(stmt.orelse, else_state)
                # a branch that returns/raises contributes nothing to the
                # fall-through state (early-return idiom)
                if self._terminates(stmt.body) and not self._terminates(stmt.orelse):
                    merged = else_state
                elif self._terminates(stmt.orelse) and not self._terminates(stmt.body):
                    merged = body_state
                elif self._terminates(stmt.body) and self._terminates(stmt.orelse):
                    merged = self.fork(state)  # code below is unreachable
                else:
                    merged = self.merge(body_state, else_state)
                state.clear()
                state.update(merged)
            elif isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
                self.visit_stmt(stmt, state)
                for _ in range(2):  # second pass exposes cross-iteration reuse
                    self._block(stmt.body, state)
                self._block(stmt.orelse, state)
            elif isinstance(stmt, ast.Try):
                self._block(stmt.body, state)
                for handler in stmt.handlers:
                    h_state = self.fork(state)
                    self._block(handler.body, h_state)
                    merged = self.merge(state, h_state)
                    state.clear()
                    state.update(merged)
                self._block(stmt.orelse, state)
                self._block(stmt.finalbody, state)
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                self.visit_stmt(stmt, state)
                self._block(stmt.body, state)
            elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                inner = self.fork(state)
                self.enter_function(stmt, inner)
                self._block(stmt.body, inner)
            else:
                self.visit_stmt(stmt, state)
