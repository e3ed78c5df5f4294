"""TIMETAG-style phase account.

The reference compiles scoped wall-clock accumulators under #ifdef TIMETAG
(serial_tree_learner.cpp:10-37: init_train/init_split/hist/find_split/
split; gbdt.cpp:20-59: boosting/train_score/valid_score/metric/bagging/
tree) and prints the totals at shutdown.  Here the same host taxonomy
(obs/phases.py HOST_PHASES) has ONE entry point, ``obs.span``; this
module keeps only the serializing mode's switch and its account:

- ``ENABLED`` (LIGHTGBM_TPU_TIMETAG=1, or ``enable()``): while on, a span
  given ``sync(x)`` blocks on that device value before stopping its
  clock, so device time is attributed to the phase that produced it.
  This serializes the pipeline exactly like the reference's TIMETAG
  builds perturb theirs — a measurement mode, not a production mode.
- ``add(name, seconds)`` / ``get_timings()``: the per-phase totals the
  spans feed while the mode is on, printed at exit.

Device time by phase without serializing anything is the trace window's
job: jitted code carries ``jax.named_scope`` phases, the program exports
the map from compiled instruction to phase, and ``obs/devtrace.py``
reduces a profiler window by it (obs/trace.py).
"""

from __future__ import annotations

import atexit
import os
from collections import defaultdict
from typing import Dict

from . import log

ENABLED = os.environ.get("LIGHTGBM_TPU_TIMETAG", "") not in ("", "0")


def enable(on: bool = True) -> None:
    """Programmatic switch (the env var only sets the initial state)."""
    global ENABLED
    ENABLED = on

_acc: Dict[str, float] = defaultdict(float)
_cnt: Dict[str, int] = defaultdict(int)


def add(name: str, seconds: float) -> None:
    """Accumulate an externally measured duration under ``name`` —
    ``obs.span`` feeds its measurements here when TIMETAG is enabled so
    the two instruments share one account."""
    _acc[name] += seconds
    _cnt[name] += 1


def get_timings() -> Dict[str, float]:
    return dict(_acc)


def reset() -> None:
    _acc.clear()
    _cnt.clear()


def report() -> None:
    """Print accumulated phase costs (GBDT::~GBDT's 'xxx costs:' lines)."""
    for name in sorted(_acc):
        log.info("%s costs: %f (%d calls)", name, _acc[name], _cnt[name])


@atexit.register
def _report_at_exit() -> None:
    if ENABLED and _acc:
        report()
