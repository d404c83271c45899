"""Analyzer core: findings, parsed modules, rule protocol, suppressions.

A :class:`SourceModule` wraps one parsed file (AST + source lines + the
inline ``# repro-lint: disable=<rule>`` suppressions collected from its
comment tokens).  A :class:`Project` is the set of modules one analyzer
invocation sees — rules that need cross-file context (the host-sync
rule's call-graph reachability) get the whole project; simple per-file
rules override :meth:`Rule.check_module`.

Suppression semantics: a trailing comment suppresses findings on its own
line; a comment alone on a line suppresses the next line.  ``disable=all``
suppresses every rule.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import re
import tokenize

_SUPPRESS_RE = re.compile(r"#\s*repro-lint:\s*disable=([\w,\-]+)")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation at a source location."""

    rule: str
    path: str          # path as given to the analyzer (repo-relative in CI)
    line: int
    message: str
    snippet: str = ""  # stripped source line, used for baseline fingerprints

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def _collect_suppressions(text: str) -> dict[int, set[str]]:
    """line -> suppressed rule names, from ``# repro-lint: disable=...``
    comments.  Trailing comments bind to their own line; a comment alone
    on its line binds to the following line."""
    out: dict[int, set[str]] = {}
    try:
        tokens = tokenize.generate_tokens(io.StringIO(text).readline)
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            m = _SUPPRESS_RE.search(tok.string)
            if not m:
                continue
            rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
            line = tok.start[0]
            own_line = tok.line[: tok.start[1]].strip() == ""
            out.setdefault(line + 1 if own_line else line, set()).update(rules)
    except tokenize.TokenError:
        pass
    return out


class SourceModule:
    """One parsed source file."""

    def __init__(self, path: str, rel: str, text: str):
        self.path = path
        self.rel = rel
        self.text = text
        self.lines = text.splitlines()
        self.tree = ast.parse(text, filename=str(path))
        self.suppressions = _collect_suppressions(text)

    def line(self, lineno: int) -> str:
        if 0 < lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""

    def suppressed(self, rule: str, lineno: int) -> bool:
        rules = self.suppressions.get(lineno)
        return bool(rules) and ("all" in rules or rule in rules)

    def finding(self, rule: str, where, message: str) -> Finding:
        lineno = getattr(where, "lineno", where)
        return Finding(rule=rule, path=self.rel, line=lineno,
                       message=message, snippet=self.line(lineno))


class Project:
    """All modules one analyzer invocation covers."""

    def __init__(self, modules: list[SourceModule]):
        self.modules = list(modules)


class Rule:
    """A named check.  Override :meth:`check_module` for per-file rules or
    :meth:`check_project` when cross-file context is needed."""

    name = ""
    description = ""

    def check_project(self, project: Project):
        for mod in project.modules:
            yield from self.check_module(mod)

    def check_module(self, mod: SourceModule):
        return iter(())


# -- shared AST helpers ------------------------------------------------------

def dotted(node: ast.AST) -> str:
    """Dotted name of an attribute chain: ``jax.device_get``,
    ``self._decode_paged``; non-name roots render as ``?``."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    elif parts:
        parts.append("?")
    return ".".join(reversed(parts))


def call_name(node: ast.Call) -> str:
    return dotted(node.func)


_SHAPE_FNS = {"jnp.zeros", "jnp.ones", "jnp.full", "jnp.empty",
              "jnp.arange", "jnp.broadcast_to", "jax.ShapeDtypeStruct",
              "np.zeros", "np.ones", "np.full", "np.empty"}
# the argument that is the shape: the first, but the second of broadcast_to
_SHAPE_ARG = {"jnp.broadcast_to": 1}


def shape_arg(node: ast.Call) -> ast.AST | None:
    """The argument a shape-taking call (``jnp.zeros``, ...) reads as its
    shape, or None when ``node`` is no such call or omits it."""
    name = call_name(node)
    k = _SHAPE_ARG.get(name, 0)
    if name in _SHAPE_FNS and len(node.args) > k:
        return node.args[k]
    return None


def iter_functions(tree: ast.AST):
    """Every FunctionDef/AsyncFunctionDef in the tree (methods included,
    nested included)."""
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _jit_static_names(call: ast.Call) -> set[str]:
    """static_argnames from a ``jax.jit(...)``/``partial(jax.jit, ...)``
    call node."""
    names: set[str] = set()
    for kw in call.keywords:
        if kw.arg != "static_argnames":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, str):
            names.add(v.value)
        elif isinstance(v, (ast.Tuple, ast.List, ast.Set)):
            for el in v.elts:
                if isinstance(el, ast.Constant) and isinstance(el.value, str):
                    names.add(el.value)
    return names


def _donation_spec(call: ast.Call):
    """(donate_argnums, donate_argnames) from a jit-like call node."""
    nums: list[int] = []
    names: list[str] = []
    for kw in call.keywords:
        v = kw.value
        if kw.arg == "donate_argnums":
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                nums.append(v.value)
            elif isinstance(v, (ast.Tuple, ast.List)):
                nums += [el.value for el in v.elts
                         if isinstance(el, ast.Constant)
                         and isinstance(el.value, int)]
        elif kw.arg == "donate_argnames":
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                names.append(v.value)
            elif isinstance(v, (ast.Tuple, ast.List)):
                names += [el.value for el in v.elts
                          if isinstance(el, ast.Constant)
                          and isinstance(el.value, str)]
    return nums, names


_JIT_NAMES = {"jax.jit", "jit"}
_PARTIAL_NAMES = {"partial", "functools.partial"}


@dataclasses.dataclass
class JitInfo:
    """A function compiled directly by ``jax.jit`` (via decorator)."""

    fn: ast.FunctionDef
    static_argnames: set[str]
    donate_argnums: list[int]
    donate_argnames: list[str]
    decorator: ast.AST


def jit_decorator_info(fn: ast.FunctionDef) -> JitInfo | None:
    """Recognise ``@jax.jit``, ``@jax.jit(...)`` and
    ``@partial(jax.jit, ...)`` decorators."""
    for dec in fn.decorator_list:
        if dotted(dec) in _JIT_NAMES:
            return JitInfo(fn, set(), [], [], dec)
        if isinstance(dec, ast.Call):
            name = call_name(dec)
            if name in _JIT_NAMES:
                nums, names = _donation_spec(dec)
                return JitInfo(fn, _jit_static_names(dec), nums, names, dec)
            if (name in _PARTIAL_NAMES and dec.args
                    and dotted(dec.args[0]) in _JIT_NAMES):
                nums, names = _donation_spec(dec)
                return JitInfo(fn, _jit_static_names(dec), nums, names, dec)
    return None


def jitted_functions(mod: SourceModule) -> list[JitInfo]:
    out = []
    for fn in iter_functions(mod.tree):
        info = jit_decorator_info(fn)
        if info is not None:
            out.append(info)
    return out


def fn_param_names(fn: ast.FunctionDef) -> list[str]:
    a = fn.args
    names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return names
