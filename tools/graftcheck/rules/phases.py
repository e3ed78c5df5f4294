"""Phase-taxonomy rules (family ``phases``) — the former standalone
``tools/lint_phase_scopes.py``, migrated onto the shared graftcheck
walker so the whole suite costs one read+parse per file.  The standalone
entry point still works and delegates here; its ``check()`` contract
(a list of human-readable violation strings) is preserved verbatim for
``tests/test_phase_lint.py``.

Checks:

1. every ``obs.span("X")`` / tracing-span literal under the package is
   declared in HOST_PHASES, and every declared host phase is used
   (``obs.span`` is the one entry point of a host phase);
2. every ``jax.named_scope("X")`` in the jitted device files is declared
   in DEVICE_PHASES, and vice versa (names nest with ``/``:
   ``split/sort``, ``hist/kernel``);
3. DEVICE_PARENT maps every device phase onto a declared host phase and
   covers every JITTED_HOST_PHASE;
4. every phase resolves through ``phases.span_series`` to a valid,
   UNIQUE Prometheus-safe histogram series name.
"""

from __future__ import annotations

import importlib.util
import pathlib
import re
from typing import Dict, List, Optional

from ..core import Finding, Project, family

SCOPE_RE = re.compile(
    r"(?:obs\.span|spans\.span"
    r"|obs\.trace_span|obs\.trace_begin|tracing\.span|TRACER\.(?:span|begin)"
    r")\(\s*[\"']([^\"']+)[\"']")
NAMED_RE = re.compile(r"jax\.named_scope\(\s*[\"']([^\"']+)[\"']")
SERIES_RE = re.compile(r"^phase_seconds_[a-z_][a-z0-9_]*$")

# the jitted paths carrying the device taxonomy: the fused round
# (models/gbdt.py train_step, the growers, the histogram kernel's own
# scope, the data-parallel shards' exchange in parallel/comm.py and
# their shard_map boundary in parallel/grow.py) plus
# the compiled-forest inference program (serve/forest.py)
DEVICE_FILES = ("models/gbdt.py", "ops/grow.py", "ops/ordered_grow.py",
                "ops/leafhist.py", "ops/rank_lambda.py", "parallel/comm.py",
                "parallel/grow.py", "serve/forest.py")


def _load_phases(pkg: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "lightgbm_tpu_obs_phases", pkg / "obs" / "phases.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _scan_texts(texts: Dict[str, str], rx) -> Dict[str, List[str]]:
    found: Dict[str, List[str]] = {}
    for rel, text in texts.items():
        for m in rx.finditer(text):
            found.setdefault(m.group(1), []).append(rel)
    return found


def scope_errors(root, pkg, project: Optional[Project] = None
                 ) -> List[str]:
    """The standalone lint's ``check()``: violation strings, [] == clean.

    ``project`` (when given) supplies already-read file texts — the
    graftcheck run passes its shared Project so this family adds zero
    file reads; the standalone entry point omits it and one is built."""
    root = pathlib.Path(root)
    pkg = pathlib.Path(pkg)
    if project is None:
        project = Project(root, pkg_rel=str(pkg.relative_to(root)))
    phases = _load_phases(pkg)
    errors: List[str] = []

    # obs/ declares the taxonomy (docstrings mention the call forms); it
    # is not a scope *user*
    host_texts = {}
    device_texts = {}
    for m in project.modules:
        rel_to_pkg = pathlib.PurePosixPath(m.rel).relative_to(
            pathlib.PurePosixPath(project.pkg_rel))
        if "obs" not in rel_to_pkg.parts:
            host_texts[m.rel] = m.text
        if str(rel_to_pkg) in DEVICE_FILES:
            device_texts[m.rel] = m.text

    host_used = _scan_texts(host_texts, SCOPE_RE)
    for name, sites in sorted(host_used.items()):
        if name not in phases.HOST_PHASES:
            errors.append(
                f"obs.span({name!r}) in {sites} is not declared in "
                f"obs/phases.py HOST_PHASES")
    for name in sorted(phases.HOST_PHASES - set(host_used)):
        errors.append(
            f"HOST_PHASES declares {name!r} but no obs.span uses it")

    dev_used = _scan_texts(device_texts, NAMED_RE)
    for name, sites in sorted(dev_used.items()):
        if name not in phases.DEVICE_PHASES:
            errors.append(
                f"jax.named_scope({name!r}) in {sites} is not declared in "
                f"obs/phases.py DEVICE_PHASES")
    for name in sorted(phases.DEVICE_PHASES - set(dev_used)):
        errors.append(
            f"DEVICE_PHASES declares {name!r} but no jax.named_scope in "
            f"{DEVICE_FILES} uses it")

    for name in sorted(phases.DEVICE_PHASES):
        parent = phases.DEVICE_PARENT.get(name)
        if parent is None:
            errors.append(f"DEVICE_PARENT has no mapping for {name!r}")
        elif parent not in phases.HOST_PHASES:
            errors.append(
                f"DEVICE_PARENT maps {name!r} -> {parent!r}, which is not "
                f"a declared host phase")
    covered = set(phases.DEVICE_PARENT.values())
    for name in sorted(phases.JITTED_HOST_PHASES - covered):
        errors.append(
            f"jitted host phase {name!r} has no device phase mapped onto "
            f"it — traces inside it would be unattributable")

    # -- 4: phase taxonomy <-> metrics namespace (obs/spans.py) ---------
    span_series = getattr(phases, "span_series", None)
    if span_series is None:
        errors.append("obs/phases.py no longer defines span_series() — "
                      "the span/metrics namespace is unmapped")
        return errors
    seen: Dict[str, str] = {}
    for name in sorted(phases.HOST_PHASES | phases.DEVICE_PHASES):
        series = span_series(name)
        if not SERIES_RE.match(series):
            errors.append(
                f"span_series({name!r}) = {series!r} is not a valid "
                f"phase histogram series name ({SERIES_RE.pattern})")
        if series in seen:
            errors.append(
                f"phases {seen[series]!r} and {name!r} collide onto the "
                f"same span series {series!r}")
        seen[series] = name
    return errors


@family("phases")
def check_phases(project: Project) -> List[Finding]:
    anchor = f"{project.pkg_rel}/obs/phases.py"
    if not (project.pkg / "obs" / "phases.py").exists():
        return []   # fixture trees without a taxonomy have nothing to sync
    return [Finding("phase-taxonomy", anchor, 1, msg)
            for msg in scope_errors(project.root, project.pkg,
                                    project=project)]
