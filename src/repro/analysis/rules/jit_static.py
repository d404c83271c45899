"""jit-static-discipline: shape/bound/branch args must be static.

A parameter of a directly-jitted function that is consumed as a shape,
a ``range()`` loop bound, or a Python branch condition must appear in
``static_argnames`` — otherwise the first call crashes on a tracer (or
the function silently retraces per value if the caller passes weak-typed
Python ints).  Conversely, parameters that ARE declared static must have
hashable defaults: a ``[]``/``{}``/``set()`` default raises
``ValueError: unhashable static argument`` on the first cache lookup.
"""

from __future__ import annotations

import ast

from ..core import (Rule, SourceModule, call_name, jitted_functions,
                    shape_arg)

_UNHASHABLE = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp,
               ast.SetComp)


def _names_in(node: ast.AST) -> set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


class JitStaticDisciplineRule(Rule):
    name = "jit-static-discipline"
    description = ("jax.jit arguments consumed as shapes/loop bounds/branch "
                   "conditions must be in static_argnames, and declared "
                   "statics must have hashable defaults")

    def check_module(self, mod: SourceModule):
        for info in jitted_functions(mod):
            yield from self._check_fn(mod, info.fn, info.static_argnames)

    def _check_fn(self, mod: SourceModule, fn, static: set[str]):
        a = fn.args
        params = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
        dynamic = {p for p in params
                   if p not in static and p not in ("self", "cls")}

        # 1. unhashable defaults on declared static args
        pos = a.posonlyargs + a.args
        for param, default in zip(pos[len(pos) - len(a.defaults):],
                                  a.defaults):
            if param.arg in static and isinstance(default, _UNHASHABLE):
                yield mod.finding(
                    self.name, default,
                    f"static argument `{param.arg}` of jitted `{fn.name}` "
                    f"has an unhashable default — jit's cache lookup "
                    f"hashes static values")
        for param, default in zip(a.kwonlyargs, a.kw_defaults):
            if (default is not None and param.arg in static
                    and isinstance(default, _UNHASHABLE)):
                yield mod.finding(
                    self.name, default,
                    f"static argument `{param.arg}` of jitted `{fn.name}` "
                    f"has an unhashable default — jit's cache lookup "
                    f"hashes static values")

        if not dynamic:
            return

        # 2. dynamic params consumed where only static values work
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                name = call_name(node)
                shape = shape_arg(node)
                if shape is not None:
                    used = _names_in(shape) & dynamic
                    for p in sorted(used):
                        yield mod.finding(
                            self.name, node,
                            f"argument `{p}` of jitted `{fn.name}` is used "
                            f"as a shape but is not in static_argnames")
                elif name == "range":
                    used = set()
                    for arg in node.args:
                        used |= _names_in(arg) & dynamic
                    for p in sorted(used):
                        yield mod.finding(
                            self.name, node,
                            f"argument `{p}` of jitted `{fn.name}` is used "
                            f"as a loop bound but is not in static_argnames")
            elif isinstance(node, (ast.If, ast.While)):
                # only DIRECT param uses here; derived-value control flow
                # is tracer-leak's domain
                used = ({node.test.id} & dynamic
                        if isinstance(node.test, ast.Name) else set())
                for p in sorted(used):
                    yield mod.finding(
                        self.name, node,
                        f"argument `{p}` of jitted `{fn.name}` is used as "
                        f"a branch condition but is not in static_argnames")
