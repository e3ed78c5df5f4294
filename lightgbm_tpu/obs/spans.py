"""Unified wall-time spans feeding per-phase histograms.

``span(name)`` is the always-on timer the metrics pipeline is built on,
and the ONE place a host phase (obs/phases.py HOST_PHASES) is entered:
it measures host wall clock between enter and exit and lands ONE
histogram observe in the process registry under the series name
``phases.span_series(name)`` (``GBDT::tree`` ->
``phase_seconds_gbdt_tree``).  It never blocks on device values by
default, so it can stay on in production — for async dispatches it
honestly measures dispatch time, and the device side remains the trace
capture's job.  Its other sinks:

- the profiler's clock: every span enters a
  ``jax.profiler.TraceAnnotation("lgbt:" + name)``, so a profiler window
  (obs/trace.py) holds the host phases on the device trace's timeline
  and ``obs/devtrace.py`` names each device idle gap by the span that
  covers its start.  One enter and exit; a no-op outside a window.
- when LIGHTGBM_TPU_TIMETAG is enabled, a span ALSO feeds the timetag
  accumulator for ``name`` (one account, two sinks) and honors
  ``sync(x)`` requests — the serializing measurement mode attributes
  device time to the span's phase (utils/timetag.py keeps the account).

``timed(name)`` wraps a function in a span — decorator sugar for
hot-path-free helpers (model export, report generation).

Two optional instruments piggyback on the span boundaries (both off by
default, both gated on one module-attribute read):

- causal tracing (obs/tracing.py): when the tracer is armed, every span
  also records a parent-linked trace span (contextvar propagation), so
  ``GBDT::iteration`` / ``Serve::batch`` land in the Chrome trace export
  with trace IDs for free.  The yielded handle's ``trace`` attribute is
  the tracing SpanHandle (None when disabled) — the batcher uses it to
  record many-to-one coalesce edges.
- memwatch (obs/memwatch.py): when enabled, span exit samples the HBM
  watermark gauges under the span's phase name.

And one that is on from package import to the end of a job's first
round and off after it (obs/setup.py): while a set-up account is open a
span also appends ``(name, start, end, parent)`` to it, parent being the
enclosing open span on this thread.  With no account open that is one
module-attribute read.
"""

from __future__ import annotations

import functools
import time
from contextlib import contextmanager
from typing import Callable, Optional, Sequence

from . import (compile_ledger, devprof, devtrace, memwatch, phases,
               registry, setup, tracing)


# span names are a small fixed set (the phase taxonomy); memoize the
# name -> series string math so a span costs perf_counter + one observe
_series_cache: dict = {}


def _series(name: str) -> str:
    s = _series_cache.get(name)
    if s is None:
        s = _series_cache[name] = phases.span_series(name)
    return s


class _SpanHandle:
    """Yielded by ``span``: ``sync(x)`` registers device values to block
    on before the clock stops — honored only under the serializing
    TIMETAG mode, so production spans never force a host sync (the
    handle, and its reference to the value, die with the span).
    ``trace`` is the causal-tracing span handle (None unless the tracer
    is armed, obs/tracing.py)."""

    __slots__ = ("value", "trace")

    def __init__(self):
        self.value = None
        self.trace = None

    def sync(self, value) -> None:
        self.value = value


@contextmanager
def span(name: str, buckets: Optional[Sequence[float]] = None,
         reg: Optional[registry.Registry] = None,
         start: Optional[float] = None):
    """Time this block into the ``span_series(name)`` wall-time
    histogram, the profiler's trace (``lgbt:<name>``), the timetag
    accumulator when that mode is on and the set-up account while one is
    open.  ``start``: a ``time.perf_counter`` reading the span counts
    from in place of now (the package's import began before ``obs``
    could be imported)."""
    import jax
    from ..utils import timetag
    r = reg if reg is not None else registry.REGISTRY
    handle = _SpanHandle()
    serialize = timetag.ENABLED
    token = None
    if tracing.TRACER.enabled:
        handle.trace = tracing.TRACER.begin(name)
        token = tracing.push(handle.trace)
    t0 = time.perf_counter() if start is None else start
    acct = setup.ACTIVE
    if acct is not None:
        if start is None:
            # hear the compilations of set-up from its first live span
            # on (never at import, where ``start`` is given)
            compile_ledger.listen()
        idx = acct.enter(name, t0)
    try:
        with jax.profiler.TraceAnnotation(devtrace.HOST_SPAN_PREFIX + name):
            yield handle
    finally:
        if serialize and handle.value is not None:
            # counted sync (obs/devprof.py): the serializing TIMETAG
            # mode's perturbation shows up in its own profile
            devprof.sync(handle.value, source=name)
        dt = time.perf_counter() - t0
        if acct is not None:
            acct.exit(idx, t0 + dt)
        r.observe(_series(name), dt, buckets)
        if serialize:
            timetag.add(name, dt)
        if handle.trace is not None:
            tracing.pop(token)
            tracing.TRACER.end(handle.trace)
        if memwatch.ENABLED:
            memwatch.sample(name, reg=r)


def timed(name: str, buckets: Optional[Sequence[float]] = None) -> Callable:
    """Decorator form: ``@obs.timed("Report::render")`` times every call
    of the wrapped function into the phase histogram."""
    def deco(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with span(name, buckets):
                return fn(*args, **kwargs)
        return wrapper
    return deco
