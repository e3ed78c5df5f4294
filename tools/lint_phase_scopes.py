#!/usr/bin/env python
"""Static lint: keep the host (obs.span) and device (named_scope) phase
taxonomies from drifting apart.

``obs.span("GBDT::x")`` times a host phase (and enters it on the
profiler's clock as ``lgbt:GBDT::x``); the jitted programs annotate
device ops with ``jax.named_scope("x")``, which the program's phase map
(obs/compile_ledger.py, obs/devtrace.py) joins to the device events of a
LIGHTGBM_TPU_TRACE_DIR window.  The two taxonomies only stay joinable
(trace time attributed back to the host account) if both match the
declarations in ``lightgbm_tpu/obs/phases.py``.  Checks:

1. every ``obs.span("X")`` literal under lightgbm_tpu/ is declared in
   HOST_PHASES, and every declared host phase is used in code;
2. every ``jax.named_scope("X")`` in the jitted device files
   (models/gbdt.py, ops/grow.py, ops/ordered_grow.py, ops/leafhist.py,
   ops/rank_lambda.py, parallel/comm.py, parallel/grow.py, serve/forest.py) is declared in DEVICE_PHASES, and vice versa; names
   nest with ``/`` (``split/sort``);
3. DEVICE_PARENT maps every device phase onto a declared host phase, and
   every JITTED_HOST_PHASE is covered by at least one device phase —
   a rename on either side fails here instead of silently splitting the
   accounts.
4. every phase named in phases.py (host AND device) resolves through
   ``phases.span_series`` to a valid, UNIQUE Prometheus-safe histogram
   series name — the span/metrics namespace (obs/spans.py, obs/prom.py)
   and the phase taxonomy cannot diverge, and no two phases can silently
   alias onto one series.

``obs.span("X")`` is the one entry point of a host phase
(``timetag.scope`` is gone; obs/spans.py feeds its account).  The
causal-tracing call forms count as users too
(``obs.trace_span("X")`` / ``obs.trace_begin("X")``, obs/tracing.py):
trace span names are the SAME taxonomy, so a name
invented at a tracing call site fails here instead of minting an
unregistered series.  The serving-fleet spans (``Serve::dispatch`` /
``Serve::reload`` / ``Serve::drain``, serve/fleet.py) and the
fault-tolerance spans (``Serve::hedge`` on the hedged-retry dispatch
path, ``Serve::eject`` / ``Serve::probe`` in the health watchdog,
serve/health.py) ride the same rule: declared in HOST_PHASES, used at
their call sites, one unique ``phase_seconds_*`` series each.

Since the graftcheck suite landed, the implementation lives in
``tools/graftcheck/rules/phases.py`` as the ``phases`` rule family and
runs on the shared walker — one read+parse per file for ALL rule
families instead of a private scan.  This entry point is preserved:
``python tools/lint_phase_scopes.py`` (and tests/test_phase_lint.py)
behave exactly as before; phases.py is loaded by file path so the lint
never imports the package (or jax).
"""

from __future__ import annotations

import pathlib
import sys
from typing import List

ROOT = pathlib.Path(__file__).resolve().parent.parent
PKG = ROOT / "lightgbm_tpu"

sys.path.insert(0, str(ROOT))

from tools.graftcheck.rules import phases as _phases  # noqa: E402

# the shared regexes/constants, re-exported for callers and tests
SCOPE_RE = _phases.SCOPE_RE
NAMED_RE = _phases.NAMED_RE
SERIES_RE = _phases.SERIES_RE
DEVICE_FILES = _phases.DEVICE_FILES


def check() -> List[str]:
    """Return a list of violations (empty == clean)."""
    return _phases.scope_errors(ROOT, PKG)


def main() -> int:
    errors = check()
    for e in errors:
        print(f"lint_phase_scopes: {e}", file=sys.stderr)
    if not errors:
        print("lint_phase_scopes: host/device phase taxonomies in sync")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
