"""Recompile/program-discipline rules (family ``jit``).

- ``jit-raw`` — a raw ``jax.jit`` call/decorator outside
  ``obs/compile_ledger.py`` (the one sanctioned wrapper).  Every repo
  jit must route through ``obs.instrumented_jit`` / ``CountingJit`` so
  its compiles land in the compile ledger; raw sites are exactly the
  blind spots the early bench rounds could not attribute (34-321s of
  warmup with no program names).  A site whose jit is wrapped by a CountingJit one
  level up is still flagged — waive it with an inline suppression so
  the indirection is visible and counted.
- ``jit-closure`` — ``jax.jit``/``instrumented_jit`` applied to a
  ``lambda``, or invoked inside a loop.  jax caches compiled programs
  by FUNCTION IDENTITY; a fresh closure per call site defeats the cache
  and recompiles every time (the exact bug class PR 9's
  ``fresh_train_programs`` fixture had to work around — see
  ``models/gbdt.py _SHARED_JITS``).
"""

from __future__ import annotations

import ast
from typing import List

from ..core import Finding, Project, family

# the one module allowed to say jax.jit: the instrumented wrapper itself
_SANCTIONED = ("obs/compile_ledger.py",)

_JIT_WRAPPERS = {"jit", "instrumented_jit"}


def _is_jax_jit(node: ast.AST) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == "jit"
            and isinstance(node.value, ast.Name)
            and node.value.id == "jax")


def _wrapper_name(func: ast.AST) -> str:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return ""


@family("jit")
def check_jit(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for m in project.modules:
        if any(m.rel.endswith(s) for s in _SANCTIONED):
            continue
        # parent + loop-depth tracking in one walk
        loop_stack: List[ast.AST] = []

        def visit(node, in_loop: bool):
            if _is_jax_jit(node):
                findings.append(Finding(
                    "jit-raw", m.rel, node.lineno,
                    "raw jax.jit — route through obs.instrumented_jit "
                    "(or CountingJit) so the compile ledger records this "
                    "program's compiles, shapes and seconds"))
            if isinstance(node, ast.Call):
                name = _wrapper_name(node.func)
                if name in _JIT_WRAPPERS or _is_jax_jit(node.func):
                    if any(isinstance(a, ast.Lambda) for a in node.args):
                        findings.append(Finding(
                            "jit-closure", m.rel, node.lineno,
                            f"{name or 'jax.jit'}(lambda ...) — jax "
                            f"caches programs by function identity; a "
                            f"fresh lambda per call recompiles every "
                            f"time (cache the jitted callable instead, "
                            f"like models/gbdt.py _SHARED_JITS)"))
                    elif in_loop:
                        findings.append(Finding(
                            "jit-closure", m.rel, node.lineno,
                            f"{name or 'jax.jit'}(...) inside a loop — "
                            f"every iteration builds a new traced "
                            f"callable, defeating jax's "
                            f"function-identity program cache"))
            entering_loop = isinstance(node, (ast.For, ast.While,
                                              ast.AsyncFor))
            for child in ast.iter_child_nodes(node):
                visit(child, in_loop or entering_loop)

        visit(m.tree, False)
    return findings
