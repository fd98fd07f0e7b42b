"""Repo-rule lint: an AST pass over ``src/``.

Three rules, each encoding a convention the repo already documents but
until now only enforced by review:

* **raw-shard-map** — ``jax.shard_map`` / ``jax.make_mesh`` / the
  ``jax.experimental.shard_map`` module may only be touched by
  ``repro/compat.py`` (the thin wrapper every other module must import
  from, so the Auto axis types and the ``check_vma`` default are chosen
  once).
* **np-in-traced** — a ``np.*`` *call* inside a jit/custom_vjp-traced
  function executes at trace time and bakes its result into the jaxpr as
  a constant: silent recompiles, no grad, wrong under vmap.  Traced code
  should use ``jnp``; trace-time *constants* belong outside the
  function.
* **mutable-config-closure** — a jitted function that closes over a
  module-level mutable literal (dict/list/set) reads it at trace time;
  later mutation silently does nothing until an unrelated retrace picks
  it up.  Hoist the value to an argument or freeze it (tuple /
  dataclass).
"""

from __future__ import annotations

import ast
import pathlib

from repro.analysis import Violation

# the one module allowed to touch the raw entry points it wraps
COMPAT_SUFFIX = ("repro", "compat.py")

_TRACED_DECORATOR_TAILS = ("jit", "custom_vjp", "custom_jvp")


def _attr_chain(node):
    """Dotted-name string for Name/Attribute chains, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_traced_decorator(dec) -> bool:
    """jax.jit / jit / custom_vjp, possibly via functools.partial(...)."""
    if isinstance(dec, ast.Call):
        chain = _attr_chain(dec.func)
        if chain and chain.split(".")[-1] == "partial":
            return any(_is_traced_decorator(a) for a in dec.args)
        dec = dec.func
    chain = _attr_chain(dec)
    return bool(chain) and chain.split(".")[-1] in _TRACED_DECORATOR_TAILS


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str, relpath: str):
        self.relpath = relpath
        self.is_compat = pathlib.PurePath(path).parts[-2:] == COMPAT_SUFFIX
        self.violations: list[Violation] = []
        self.mutable_globals: set[str] = set()
        self._depth = 0

    def _flag(self, rule: str, node, message: str) -> None:
        self.violations.append(Violation(
            "lint", rule, f"{self.relpath}:{node.lineno}", message))

    # -- rule: raw-shard-map ------------------------------------------------

    def visit_Import(self, node):
        if not self.is_compat:
            for alias in node.names:
                if alias.name.startswith("jax.experimental.shard_map"):
                    self._flag("raw-shard-map", node,
                               f"import of {alias.name}: go through "
                               f"repro.compat instead")
        self.generic_visit(node)

    def visit_ImportFrom(self, node):
        if not self.is_compat and node.module:
            if node.module.startswith("jax.experimental.shard_map"):
                self._flag("raw-shard-map", node,
                           f"from {node.module} import ...: go through "
                           f"repro.compat instead")
            elif node.module == "jax":
                for alias in node.names:
                    if alias.name in ("shard_map", "make_mesh"):
                        self._flag("raw-shard-map", node,
                                   f"from jax import {alias.name}: go "
                                   f"through repro.compat instead")
        self.generic_visit(node)

    def visit_Attribute(self, node):
        if not self.is_compat and node.attr in ("shard_map", "make_mesh"):
            chain = _attr_chain(node)
            if chain and chain.split(".")[0] == "jax":
                self._flag("raw-shard-map", node,
                           f"{chain}: go through repro.compat instead")
        self.generic_visit(node)

    # -- rules: np-in-traced, mutable-config-closure ------------------------

    def visit_Assign(self, node):
        if self._depth == 0:
            mutable = isinstance(node.value, (ast.Dict, ast.List, ast.Set))
            if (isinstance(node.value, ast.Call)
                    and _attr_chain(node.value.func) in ("dict", "list",
                                                         "set")):
                mutable = True
            if mutable:
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        self.mutable_globals.add(tgt.id)
        self.generic_visit(node)

    def visit_FunctionDef(self, node):
        traced = any(_is_traced_decorator(d) for d in node.decorator_list)
        if traced:
            self._check_traced_body(node)
        self._depth += 1
        self.generic_visit(node)
        self._depth -= 1

    visit_AsyncFunctionDef = visit_FunctionDef

    def _check_traced_body(self, fn):
        locals_ = {a.arg for a in (fn.args.args + fn.args.posonlyargs
                                   + fn.args.kwonlyargs)}
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Store):
                locals_.add(sub.id)
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Call):
                chain = _attr_chain(sub.func)
                if chain and chain.split(".")[0] in ("np", "numpy"):
                    self._flag(
                        "np-in-traced", sub,
                        f"{chain}() inside traced function "
                        f"'{fn.name}' runs at trace time (constant-folded "
                        f"into the jaxpr) — use jnp, or hoist it out")
            elif (isinstance(sub, ast.Name)
                    and isinstance(sub.ctx, ast.Load)
                    and sub.id in self.mutable_globals
                    and sub.id not in locals_):
                self._flag(
                    "mutable-config-closure", sub,
                    f"traced function '{fn.name}' closes over mutable "
                    f"module-level '{sub.id}' — mutations after trace are "
                    f"silently ignored; pass it as an argument or freeze "
                    f"it")


def lint_source(source: str, path: str, relpath: str | None = None
                ) -> list[Violation]:
    tree = ast.parse(source, filename=path)
    linter = _Linter(path, relpath or path)
    # module-level mutable bindings must be known before function bodies
    # are checked, so collect them in a first pass
    for node in tree.body:
        if isinstance(node, ast.Assign):
            linter.visit_Assign(node)
    linter.mutable_globals -= {"__all__"}
    linter.visit(tree)
    return linter.violations


def run(root=None) -> tuple[list[Violation], list[str]]:
    """Lint every .py file under ``src/`` (fixtures excluded — they exist
    to violate the rules).  Returns ``(violations, covered_files)``."""
    if root is None:
        root = pathlib.Path(__file__).resolve().parents[2]  # src/
    root = pathlib.Path(root)
    violations, covered = [], []
    for path in sorted(root.rglob("*.py")):
        if "fixtures" in path.parts:
            continue
        rel = str(path.relative_to(root))
        covered.append(rel)
        violations.extend(lint_source(path.read_text(), str(path), rel))
    return violations, covered
