"""tracer-leak: Python control flow on traced values inside jit bodies.

Inside a function compiled directly by ``jax.jit`` (decorator form),
values derived from non-static parameters are tracers: a Python ``if`` /
``while`` / ``assert`` on one raises ``TracerBoolConversionError`` at
trace time (or, worse, silently bakes in one branch when the value is a
weakly-typed constant), and iterating or shaping with one fails the same
way.  Concretising accessors (``.shape`` / ``.ndim`` / ``.dtype`` /
``.size``, ``len()``, ``is None``) sanitize the value — branching on
those is static and fine.
"""

from __future__ import annotations

import ast

from ..core import (Rule, SourceModule, call_name, fn_param_names,
                    jitted_functions, shape_arg)

_SANITIZE_ATTRS = {"shape", "ndim", "dtype", "size", "aval", "weak_type"}
_SANITIZE_CALLS = {"len", "isinstance", "type", "hasattr", "getattr"}


def _expr_tainted(node: ast.AST, tainted: set[str]) -> bool:
    """Whether ``node`` evaluates to a tracer-derived value."""
    if isinstance(node, ast.Name):
        return node.id in tainted
    if isinstance(node, ast.Attribute):
        if node.attr in _SANITIZE_ATTRS:
            return False
        return _expr_tainted(node.value, tainted)
    if isinstance(node, ast.Call):
        if call_name(node) in _SANITIZE_CALLS:
            return False
        if (_expr_tainted(node.func, tainted)
                or any(_expr_tainted(a, tainted) for a in node.args)):
            return True
        return any(_expr_tainted(kw.value, tainted) for kw in node.keywords)
    if isinstance(node, ast.Compare):
        if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
            return False
        return (_expr_tainted(node.left, tainted)
                or any(_expr_tainted(c, tainted) for c in node.comparators))
    if isinstance(node, (ast.Constant, ast.Lambda)):
        return False
    return any(_expr_tainted(child, tainted)
               for child in ast.iter_child_nodes(node)
               if isinstance(child, ast.expr))


class TracerLeakRule(Rule):
    name = "tracer-leak"
    description = ("Python if/while/assert, iteration or shape use of "
                   "values derived from traced jax.jit parameters")

    def check_module(self, mod: SourceModule):
        for info in jitted_functions(mod):
            yield from self._scan(mod, info.fn, info.static_argnames)

    def _scan(self, mod: SourceModule, fn, static: set[str]):
        tainted = {p for p in fn_param_names(fn)
                   if p not in static and p not in ("self", "cls")}
        found: list = []

        def shape_uses(expr: ast.AST):
            for node in ast.walk(expr):
                if not isinstance(node, ast.Call):
                    continue
                shape = shape_arg(node)
                if shape is not None and _expr_tainted(shape, tainted):
                    found.append(mod.finding(
                        self.name, node,
                        f"traced value used as a shape in jitted "
                        f"`{fn.name}` — shapes must be static"))

        def visit(stmts):
            for stmt in stmts:
                if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    # nested defs run in the parent's trace; closure taint
                    # carries over (their own params are fresh bindings)
                    visit(stmt.body)
                    continue
                if isinstance(stmt, (ast.If, ast.While)):
                    if _expr_tainted(stmt.test, tainted):
                        kw = "while" if isinstance(stmt, ast.While) else "if"
                        found.append(mod.finding(
                            self.name, stmt,
                            f"Python `{kw}` on a traced value in jitted "
                            f"`{fn.name}` — tracers have no concrete truth "
                            f"value; use jnp.where/lax.cond or mark the "
                            f"argument static"))
                    shape_uses(stmt.test)
                    visit(stmt.body)
                    visit(stmt.orelse)
                    continue
                if isinstance(stmt, ast.Assert):
                    if _expr_tainted(stmt.test, tainted):
                        found.append(mod.finding(
                            self.name, stmt,
                            f"`assert` on a traced value in jitted "
                            f"`{fn.name}` — the check evaluates a tracer "
                            f"at trace time"))
                    continue
                if isinstance(stmt, ast.For):
                    if _expr_tainted(stmt.iter, tainted):
                        found.append(mod.finding(
                            self.name, stmt,
                            f"iterating a traced value in jitted "
                            f"`{fn.name}` — use lax.scan/fori_loop"))
                    shape_uses(stmt.iter)
                    visit(stmt.body)
                    visit(stmt.orelse)
                    continue
                if isinstance(stmt, ast.Assign):
                    shape_uses(stmt.value)
                    tgt = [t.id for t in stmt.targets
                           if isinstance(t, ast.Name)]
                    for t in stmt.targets:
                        if isinstance(t, (ast.Tuple, ast.List)):
                            tgt += [e.id for e in t.elts
                                    if isinstance(e, ast.Name)]
                    if _expr_tainted(stmt.value, tainted):
                        tainted.update(tgt)
                    else:
                        tainted.difference_update(tgt)
                    continue
                if isinstance(stmt, (ast.With, ast.AsyncWith)):
                    visit(stmt.body)
                    continue
                if isinstance(stmt, ast.Try):
                    visit(stmt.body)
                    for h in stmt.handlers:
                        visit(h.body)
                    visit(stmt.finalbody)
                    continue
                # expression / return / augassign statements: shape uses only
                for node in ast.iter_child_nodes(stmt):
                    if isinstance(node, ast.expr):
                        shape_uses(node)

        visit(fn.body)
        yield from found
