"""Per-file analysis context shared by every rule.

One parse + one pre-walk per file computes everything the rules need:

* **parent links** — ``parent(node)`` / ``ancestors(node)`` /
  ``enclosing_functions(node)``;
* **import tracking** — ``qualname(node)`` resolves a ``Name`` /
  ``Attribute`` chain through the file's import aliases to a dotted
  module path (``jnp.asarray`` -> ``jax.numpy.asarray``, ``partial``
  -> ``functools.partial``), so rules match *what* is called, not what
  it happens to be spelled;
* **scope tracking** — ``binds(name, at)`` reports whether ``name`` is
  rebound by a parameter / assignment / def / import in any scope
  enclosing ``at`` (used to tell the ``hash`` builtin from a local
  variable called ``hash``);
* **traced regions** — the set of function bodies JAX traces:
  ``jax.jit``-decorated defs, functions passed to ``jax.jit(...)``,
  and the body callables of ``lax.scan`` / ``while_loop`` /
  ``fori_loop`` / ``cond`` / ``shard_map``, plus anything lexically
  nested inside one.  ``in_traced(node)`` is what the host-sync and
  traced-truthiness rules key on;
* **suppressions** — inline ``# reprolint: disable=<rules> -- <why>``
  (same line) and ``# reprolint: disable-next=<rules> -- <why>``
  (next line) directives, parsed with their required reason.
"""
from __future__ import annotations

import ast
import re
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Set, Tuple

SUPPRESS_RE = re.compile(
    r"#\s*reprolint:\s*(?P<kind>disable(?:-next)?)\s*=\s*"
    r"(?P<rules>[\w,-]+)\s*(?:--\s*(?P<reason>.+?)\s*)?$")

#: decorators / wrappers whose callee function JAX traces
_JIT_NAMES = ("jax.jit", "jax.pmap")
#: (fqname, positional indices of traced callables) — control-flow
#: primitives whose body arguments execute under trace
_TRACED_CALLEE_ARGS = {
    "jax.lax.scan": (0,),
    "jax.lax.while_loop": (0, 1),
    "jax.lax.fori_loop": (2,),
    "jax.lax.cond": (1, 2),
    "jax.lax.switch": None,  # every arg from 1 on
    "jax.shard_map": (0,),
    "jax.checkpoint": (0,),
    "jax.remat": (0,),
    "jax.vmap": (0,),
    "jax.grad": (0,),
    "jax.value_and_grad": (0,),
}

_SCOPE_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda,
                ast.ClassDef, ast.Module)
_FUNC_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


@dataclass
class Suppression:
    """One parsed ``# reprolint: disable[-next]=...`` directive."""
    line: int                    # line the directive sits on
    applies_to: int              # line whose findings it suppresses
    rules: Tuple[str, ...]       # rule names, or ("all",)
    reason: Optional[str]        # text after ``--`` (required)
    used: bool = False


class FileContext:
    """Parsed file + the shared analyses rules key on."""

    def __init__(self, path: str, relpath: str, source: str):
        self.path = path
        self.relpath = relpath          # repo-root-relative, posix
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        self._parents: Dict[int, ast.AST] = {}
        self.imports: Dict[str, str] = {}   # alias -> dotted module path
        self._index()
        self.suppressions = self._parse_suppressions()
        self._traced_roots = self._find_traced_roots()

    # ------------------------------------------------------------------
    # structure
    # ------------------------------------------------------------------
    def _index(self):
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self._parents[id(child)] = node
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.imports[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0])
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    self.imports[a.asname or a.name] = (
                        f"{node.module}.{a.name}")

    def parent(self, node: ast.AST) -> Optional[ast.AST]:
        return self._parents.get(id(node))

    def ancestors(self, node: ast.AST) -> Iterator[ast.AST]:
        cur = self.parent(node)
        while cur is not None:
            yield cur
            cur = self.parent(cur)

    def enclosing_functions(self, node: ast.AST) -> List[ast.AST]:
        """Innermost-first chain of enclosing function/lambda nodes."""
        return [a for a in self.ancestors(node)
                if isinstance(a, _FUNC_NODES)]

    def enclosing_class(self, node: ast.AST) -> Optional[ast.ClassDef]:
        for a in self.ancestors(node):
            if isinstance(a, ast.ClassDef):
                return a
        return None

    # ------------------------------------------------------------------
    # names
    # ------------------------------------------------------------------
    def qualname(self, node: ast.AST) -> Optional[str]:
        """Dotted path of a Name/Attribute chain with import aliases
        resolved; None for anything that is not a plain chain."""
        parts: List[str] = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        root = self.imports.get(node.id, node.id)
        return ".".join([root] + parts[::-1])

    def call_qualname(self, call: ast.Call) -> Optional[str]:
        return self.qualname(call.func)

    @staticmethod
    def _scope_nodes(scope: ast.AST) -> Iterator[ast.AST]:
        """Walk a scope's body without descending into nested scopes
        (the nested def/lambda/class node itself IS yielded — its name
        binds in the outer scope — but not its body)."""
        body = getattr(scope, "body", [])
        stack = list(body) if isinstance(body, list) else []
        while stack:
            node = stack.pop()
            yield node
            if not isinstance(node, _FUNC_NODES + (ast.ClassDef,)):
                stack.extend(ast.iter_child_nodes(node))

    def binds(self, name: str, at: ast.AST) -> bool:
        """True if ``name`` is bound by a parameter, assignment, def,
        or import in any scope enclosing ``at`` (i.e. it is NOT the
        builtin there)."""
        scopes = [a for a in self.ancestors(at)
                  if isinstance(a, _SCOPE_NODES)]
        if self.tree not in scopes:
            scopes.append(self.tree)
        for scope in scopes:
            if isinstance(scope, _FUNC_NODES):
                args = scope.args
                params = (args.args + args.posonlyargs + args.kwonlyargs
                          + ([args.vararg] if args.vararg else [])
                          + ([args.kwarg] if args.kwarg else []))
                if any(p.arg == name for p in params):
                    return True
            for sub in self._scope_nodes(scope):
                if isinstance(sub, ast.Name) and sub.id == name \
                        and isinstance(sub.ctx, ast.Store):
                    return True
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                    ast.ClassDef)) and sub.name == name:
                    return True
                if isinstance(sub, (ast.Import, ast.ImportFrom)):
                    for a in sub.names:
                        if (a.asname or a.name.split(".")[0]) == name:
                            return True
        return False

    # ------------------------------------------------------------------
    # traced regions
    # ------------------------------------------------------------------
    def _local_defs(self) -> Dict[str, List[ast.AST]]:
        defs: Dict[str, List[ast.AST]] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, []).append(node)
        return defs

    def _is_jit_expr(self, node: ast.AST) -> bool:
        """``jax.jit`` itself, or ``functools.partial(jax.jit, ...)``."""
        q = self.qualname(node)
        if q in _JIT_NAMES:
            return True
        if isinstance(node, ast.Call) \
                and self.call_qualname(node) == "functools.partial" \
                and node.args and self.qualname(node.args[0]) in _JIT_NAMES:
            return True
        return False

    def _find_traced_roots(self) -> Set[int]:
        roots: Set[int] = set()
        defs = self._local_defs()

        def mark(arg: ast.AST):
            if isinstance(arg, ast.Lambda):
                roots.add(id(arg))
            elif isinstance(arg, ast.Name):
                for d in defs.get(arg.id, []):
                    roots.add(id(d))
            elif isinstance(arg, ast.Call):
                # functools.partial(body, ...) passed as the callee
                if self.call_qualname(arg) == "functools.partial" \
                        and arg.args:
                    mark(arg.args[0])

        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if any(self._is_jit_expr(d) for d in node.decorator_list):
                    roots.add(id(node))
            if not isinstance(node, ast.Call):
                continue
            q = self.call_qualname(node)
            if q in _JIT_NAMES:  # jax.jit(fn, ...)
                if node.args:
                    mark(node.args[0])
            elif q in _TRACED_CALLEE_ARGS or (
                    q and q.endswith((".scan", ".while_loop", ".fori_loop",
                                      ".cond", ".shard_map"))
                    and q.startswith("jax.")):
                idxs = _TRACED_CALLEE_ARGS.get(
                    q, _TRACED_CALLEE_ARGS.get(
                        "jax.lax." + q.rsplit(".", 1)[-1]))
                if idxs is None:
                    idxs = range(1, len(node.args))
                for i in idxs:
                    if i < len(node.args):
                        mark(node.args[i])
        return roots

    def in_traced(self, node: ast.AST) -> bool:
        """True when ``node`` sits inside a function body JAX traces
        (including functions lexically nested in one)."""
        return any(id(f) in self._traced_roots
                   for f in self.enclosing_functions(node))

    # ------------------------------------------------------------------
    # suppressions
    # ------------------------------------------------------------------
    def _next_code_line(self, after: int) -> int:
        """First line past ``after`` that is not blank or pure comment
        (a ``disable-next`` reason may wrap onto continuation comment
        lines; the directive still targets the code below them)."""
        for i in range(after + 1, len(self.lines) + 1):
            stripped = self.lines[i - 1].strip()
            if stripped and not stripped.startswith("#"):
                return i
        return after + 1

    def _parse_suppressions(self) -> List[Suppression]:
        out = []
        for i, line in enumerate(self.lines, start=1):
            m = SUPPRESS_RE.search(line)
            if not m:
                continue
            rules = tuple(r.strip() for r in m.group("rules").split(",")
                          if r.strip())
            applies = (self._next_code_line(i)
                       if m.group("kind") == "disable-next" else i)
            out.append(Suppression(line=i, applies_to=applies,
                                   rules=rules, reason=m.group("reason")))
        return out

    def suppression_for(self, rule: str, line: int) -> \
            Optional[Suppression]:
        for s in self.suppressions:
            if s.applies_to == line and (rule in s.rules
                                         or "all" in s.rules):
                return s
        return None
