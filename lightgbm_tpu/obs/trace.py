"""Gated device trace capture over a window of boosting iterations.

``LIGHTGBM_TPU_TRACE_DIR=/path`` (or the ``trace_dir`` config key) arms a
one-shot ``jax.profiler`` trace spanning ``trace_num_iters`` iterations
starting at ``trace_start_iter`` (default: skip the first 5 so compile
and warmup don't drown the steady state).

The chip's trace names a device event by its HLO text and carries no
``jax.named_scope`` path, so the window does not break down by phase on
its own.  The program does it: while a capture is ARMED (built, window
not yet closed) every program that compiles — or is first dispatched
inside the window — also exports ``phase_map.<program>.json`` next to
the trace (obs/compile_ledger.py: instruction name -> leaf phase of
obs/phases.py, from the compiled text), and when the window closes
``obs/devtrace.py`` joins the device events to those maps and writes
``device_phases.json``: ms a round per phase, what the compiler inserted
under each, what no map names, busy and window seconds by the device's
clock, idle gaps named by the ``lgbt:`` host span (obs/spans.py) that
covers them.  ``python -m lightgbm_tpu obs-report --device-trace <dir>``
prints it; the raw trace still opens in Perfetto or TensorBoard's
profile plugin.  With no ``trace_dir`` nothing of this runs.

Unlike LIGHTGBM_TPU_TIMETAG this never serializes the pipeline: the only
synchronizations are one ``block_until_ready`` at window open (the host
runs a round ahead of the device: without it the tail of the round before
lands inside, and "rounds in the window" is off by up to one) and one at
window close so the last iteration's device work lands inside the
capture.
"""

from __future__ import annotations

import atexit
import json
import os
import weakref
from typing import Any, Dict, Optional

from ..utils import diskguard, log
from . import devtrace, phases

# One process-wide atexit hook over weakly-held captures: never leave a
# dangling profiler session, never pin a booster's capture for the
# process lifetime (CV folds / long-lived embedders build many).
_ACTIVE: "weakref.WeakSet[TraceCapture]" = weakref.WeakSet()


# The armed capture, read once per instrumented dispatch by
# obs/compile_ledger.py (None: one attribute read is all tracing costs).
_armed: Optional["TraceCapture"] = None


def armed() -> Optional["TraceCapture"]:
    return _armed


@atexit.register
def _abort_all() -> None:
    for tc in list(_ACTIVE):
        tc.close()


def _wait(sync) -> None:
    """Block until the device has finished ``sync`` (None: no wait)."""
    if sync is not None:
        import jax
        try:
            jax.block_until_ready(sync)
        except Exception:  # pragma: no cover
            pass


class TraceCapture:
    """One-shot trace window: ``iter_begin``/``iter_end`` from the
    training loop, ``close()`` when the owning loop finishes (a window
    the run ended inside is stopped there, not at process exit);
    start/stop failures degrade to a one-shot warning."""

    def __init__(self, trace_dir: str, start_iter: int = 5,
                 num_iters: int = 2):
        self.trace_dir = str(trace_dir)
        self.start_iter = max(int(start_iter), 0)
        self.num_iters = max(int(num_iters), 1)
        self._active = False
        self._done = False
        self._started_at = -1
        self._rounds = 0              # iterations closed inside the window
        self._maps: Dict[str, list] = {}  # program -> map files written
        self._report: Optional[Dict[str, Any]] = None
        _ACTIVE.add(self)
        global _armed
        _armed = self

    @classmethod
    def from_config(cls, config=None) -> Optional["TraceCapture"]:
        """Build from LIGHTGBM_TPU_TRACE_DIR (wins) or config keys
        ``trace_dir``/``trace_start_iter``/``trace_num_iters``; None when
        tracing is not requested."""
        trace_dir = os.environ.get("LIGHTGBM_TPU_TRACE_DIR", "")
        start, num = 5, 2
        if config is not None:
            trace_dir = trace_dir or str(config.get("trace_dir", "") or "")
            start = int(config.get("trace_start_iter", start))
            num = int(config.get("trace_num_iters", num))
        if not trace_dir:
            return None
        return cls(trace_dir, start, num)

    # -- phase maps (obs/compile_ledger.py) -------------------------------
    def wants_map(self, program: str, compiled: bool) -> bool:
        """A compile event while armed, or the first dispatch inside the
        window of a program compiled before."""
        return not self._done and (
            compiled or (self._active and program not in self._maps))

    def write_map(self, program: str,
                  pm: Optional[Dict[str, Any]]) -> Optional[str]:
        """``phase_map.<program>.json`` (a later shape variant of the
        same program: ``phase_map.<program>.<n>.json``).  ``pm`` None
        notes a failed attempt, so that it is not made at every call."""
        written = self._maps.setdefault(program, [])
        if pm is None:
            return None
        stem = phases.sanitize(program) \
            + (f".{len(written) + 1}" if written else "")
        path = os.path.join(self.trace_dir, devtrace.MAP_FILE.format(stem))
        os.makedirs(self.trace_dir, exist_ok=True)
        diskguard.write_text(path, json.dumps(dict(pm, program=program)),
                             sink="trace")
        written.append(path)
        return path

    def take_report(self) -> Optional[Dict[str, Any]]:
        """The closed window's ``device_phases.json`` dict, once (the
        events record of the window's last round carries it)."""
        report, self._report = self._report, None
        return report

    # -- window ----------------------------------------------------------
    def iter_begin(self, it: int, sync=None) -> None:
        """Open the window at iteration ``it`` (once); blocks on ``sync``
        first so the device has finished the rounds dispatched before
        and the window holds whole rounds only."""
        if self._done or self._active or it < self.start_iter:
            return
        import jax
        _wait(sync)
        try:
            os.makedirs(self.trace_dir, exist_ok=True)
            jax.profiler.start_trace(self.trace_dir)
        except Exception as e:  # pragma: no cover - backend-dependent
            self._done = True
            log.warn_once("obs_trace_start",
                          "device trace capture failed to start: %s", e)
            return
        self._active = True
        self._started_at = it
        log.info("telemetry: device trace started at iteration %d -> %s",
                 it, self.trace_dir)

    def iter_end(self, it: int, sync=None) -> None:
        """Close the window once ``num_iters`` iterations are inside it
        (counted from where it actually STARTED — continued training may
        resume past start_iter); blocks on ``sync`` first so the async
        device work of the final iteration is captured, not cut off."""
        if not self._active:
            return
        self._rounds += 1
        if it + 1 < self._started_at + self.num_iters:
            return
        _wait(sync)
        self._stop()

    # -- teardown --------------------------------------------------------
    def _stop(self) -> None:
        import jax
        self._active = False
        self._retire()
        try:
            jax.profiler.stop_trace()
            log.info("telemetry: device trace written to %s", self.trace_dir)
        except Exception as e:  # pragma: no cover - backend-dependent
            log.warn_once("obs_trace_stop",
                          "device trace capture failed to stop: %s", e)
            return
        try:
            # this capture's own maps only: a directory used twice may
            # hold another run's
            self._report = devtrace.reduce_dir(
                self.trace_dir, max(self._rounds, 1),
                map_paths=[p for ps in self._maps.values() for p in ps])
        except Exception as e:
            # the reduction reads a file another library wrote: it must
            # never take the training run down with it
            log.warn_once("obs_trace_reduce",
                          "device trace could not be reduced to phases: "
                          "%s: %s", type(e).__name__, e)

    def _retire(self) -> None:
        global _armed
        self._done = True
        if _armed is self:
            _armed = None

    def close(self) -> None:
        """Stop recording now if a window is still open (the run ended
        before ``num_iters`` iterations passed) and retire the capture.
        Idempotent."""
        if self._active:
            self._stop()
        self._retire()
