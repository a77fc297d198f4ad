"""Kernel and import-boundary rules.

* ``pallas-index`` — a bare Python int as a TOP-LEVEL element of a
  ``pl.load`` / ``pl.store`` / ``pl.swap`` index tuple, which older JAX
  releases rejected in interpret mode (``'int' object has no attribute
  'shape'``).  The installed jax 0.9.0 has none of these functions;
  kernels index refs directly.  Ints nested inside ``pl.ds(0, 1)`` or
  arithmetic (``s * bk``) are not flagged.
* ``jax-free-boundary`` — module-level jax imports in the modules the
  streaming path deliberately keeps jax-free (``core/``, ``sim/``,
  ``serving/stream.py`` and the lazy ``serving/__init__.py``): a single
  top-level ``import jax`` there makes every soak / golden-replay
  consumer pay the full jax import.  Function-level (deferred) imports
  and ``if TYPE_CHECKING:`` blocks are allowed.
"""
from __future__ import annotations

import ast
from typing import Iterator, Optional, Sequence

from ..engine import Finding, Module, Rule

PALLAS = "jax.experimental.pallas"
INDEXED_OPS = frozenset({"load", "store", "swap"})

#: Modules that must stay importable without jax (PR 7's streaming path).
JAX_FREE_PREFIXES: tuple[str, ...] = ("repro/core/", "repro/sim/",
                                      "repro/analysis/")
JAX_FREE_FILES: frozenset[str] = frozenset({
    "repro/serving/stream.py",
    "repro/serving/__init__.py",
})


def _bare_int(node: ast.AST) -> bool:
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        node = node.operand
    return (isinstance(node, ast.Constant)
            and type(node.value) is int)


class PallasIndexRule(Rule):
    name = "pallas-index"
    description = ("bare Python int inside a pl.load/pl.store/pl.swap "
                   "index tuple (older JAX rejects it in interpret mode)")

    def check(self, mod: Module) -> Iterator[Finding]:
        aliases = {name for name, origin in mod.imports.items()
                   if origin == PALLAS}
        if not aliases:
            return
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if not (isinstance(func, ast.Attribute)
                    and func.attr in INDEXED_OPS
                    and isinstance(func.value, ast.Name)
                    and func.value.id in aliases):
                continue
            if len(node.args) < 2:
                continue
            idx = node.args[1]
            elements = idx.elts if isinstance(idx, ast.Tuple) else [idx]
            bad = [e for e in elements if _bare_int(e)]
            if bad:
                rendered = ", ".join(ast.unparse(e) for e in bad)
                yield Finding(
                    self.name, mod.rel, node.lineno, node.col_offset,
                    f"bare Python int ({rendered}) as a top-level element "
                    f"of a {func.value.id}.{func.attr} index tuple — "
                    "older JAX rejects it in interpret mode, and jax 0.9 "
                    "has no such function; index the ref directly",
                    mod.qualname(node.lineno))


class JaxImportRule(Rule):
    name = "jax-free-boundary"
    description = ("module-level jax import in a module the streaming "
                   "path keeps jax-free")

    def __init__(self, prefixes: Optional[Sequence[str]] = None,
                 files: Optional[Sequence[str]] = None) -> None:
        self.prefixes = tuple(JAX_FREE_PREFIXES if prefixes is None
                              else prefixes)
        self.files = frozenset(JAX_FREE_FILES if files is None else files)

    def applies_to(self, rel: str) -> bool:
        return rel.startswith(self.prefixes) or rel in self.files

    def _module_level(self, body: Sequence[ast.stmt]) -> Iterator[ast.stmt]:
        """Statements executed at import time: recurse into module-level
        control flow and class bodies, skip function bodies and
        ``if TYPE_CHECKING:`` blocks."""
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if isinstance(stmt, ast.If):
                test = stmt.test
                if (isinstance(test, ast.Name)
                        and test.id == "TYPE_CHECKING") or (
                        isinstance(test, ast.Attribute)
                        and test.attr == "TYPE_CHECKING"):
                    continue
                yield from self._module_level(stmt.body)
                yield from self._module_level(stmt.orelse)
                continue
            yield stmt
            if isinstance(stmt, ast.ClassDef):
                yield from self._module_level(stmt.body)
            elif isinstance(stmt, ast.Try):
                yield from self._module_level(stmt.body)
                yield from self._module_level(stmt.orelse)
                yield from self._module_level(stmt.finalbody)
                for handler in stmt.handlers:
                    yield from self._module_level(handler.body)
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                yield from self._module_level(stmt.body)

    def check(self, mod: Module) -> Iterator[Finding]:
        for stmt in self._module_level(mod.tree.body):
            names: list[str] = []
            if isinstance(stmt, ast.Import):
                names = [a.name for a in stmt.names]
            elif isinstance(stmt, ast.ImportFrom) and stmt.module:
                names = [stmt.module]
            for name in names:
                if name == "jax" or name.startswith("jax."):
                    yield Finding(
                        self.name, mod.rel, stmt.lineno, stmt.col_offset,
                        f"module-level import of {name!r} in a jax-free "
                        "module — the streaming path must import without "
                        "jax; defer the import into the function that "
                        "needs it", mod.qualname(stmt.lineno))
                    break
