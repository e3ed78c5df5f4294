"""Structured run telemetry: always-on counters/gauges, a per-iteration
JSONL event stream, collective-traffic accounting, and gated device trace
capture.

The only prior instrument, the TIMETAG mode (``utils/timetag.py``), must
*serialize the async pipeline* to attribute device time to a phase — a
measurement mode that cannot stay on during real runs.  This subsystem is the opposite trade,
in the spirit of XGBoost's GPU monitor counters (Mitchell & Frank,
arXiv:1806.11248): cheap host-side bookkeeping that is always on, so
every optimization round has a before/after phase breakdown instead of
one end-to-end number.  Pieces:

- ``registry``: process-wide monotonic counters (iterations, trees grown,
  bagging draws, host<->device transfers, collective bytes) and gauges
  (HBM estimate vs. budget from ``models/gbdt.py estimate_train_memory``).
  ``snapshot()`` folds in the timetag phase timers when those are enabled.
- ``events``: per-iteration JSONL records (phase wall times, eval metric
  values, bag count, grown-tree shape, cumulative collective bytes)
  written by an ``EventRecorder`` hooked into ``GBDT.train_one_iter``,
  ``engine.train(events_file=...)`` and ``callback.log_telemetry()``.
- collective-traffic accounting lives on the comm strategies themselves
  (``parallel/comm.py`` ``traffic_per_tree``) — static shape math only,
  nothing added to the jitted path.
- ``trace`` + ``devtrace``: ``LIGHTGBM_TPU_TRACE_DIR`` (or the
  ``trace_dir`` config key) wraps a window of boosting iterations in a
  ``jax.profiler`` trace.  The chip's trace carries no
  ``jax.named_scope`` path, so the program joins the two itself: while
  the capture is armed ``compile_ledger`` exports each compiled
  program's ``{instruction -> leaf phase}`` map, and at window close
  ``devtrace`` reduces the device events by it into
  ``device_phases.json`` (ms a round per phase of ``phases.py``
  ROUND_PHASES, compiler-inserted copies under the phase that causes
  them, idle gaps named by host span).
- ``spans``: ``obs.span(name)`` / ``@obs.timed`` — the one entry point
  of a host phase: always-on wall-time histograms (``span_series`` maps
  the ``phases.py`` taxonomy onto metric names) and a
  ``jax.profiler.TraceAnnotation("lgbt:<name>")`` on the profiler's
  clock.
- ``prom`` + ``metrics_server``: Prometheus text exposition 0.0.4 over
  the registry, served at ``GET /metrics`` by the standalone training
  listener (``metrics_port`` / ``LIGHTGBM_TPU_METRICS_PORT``) and by
  the serve subsystem's HTTP front end.
- ``report``: ``python -m lightgbm_tpu obs-report`` — offline summary
  of an ``--events-file`` stream (per-phase totals, slowest iterations,
  NaN/saturation incidents, collective traffic, eval trajectory), of a
  compile ledger (``--compile=``), of trace-event files (``--traces``)
  and of a reduced device window (``--device-trace``).
- ``compile_ledger``: process-wide account of every XLA compilation —
  program name, abstract input shapes, wall seconds, whether the
  persistent cache served it, and the seconds split by stage
  (``trace_s``, ``lower_s``, ``backend_s``, ``cache_read_s``,
  ``other_s``, from the public ``jax.monitoring`` listeners) — captured
  by ``instrumented_jit`` at the repo's own jit entry points, feeding
  ``compile_count``/``compile_seconds`` registry series and an
  append-only ``compile_ledger.jsonl``
  (``LIGHTGBM_TPU_COMPILE_LEDGER``/``compile_ledger_file``).  The
  compilations no instrumented call made are counted beside it
  (``compile_unledgered_count``/``compile_unledgered_seconds``).
- ``setup``: the set-up account — from package import to the end of a
  job's first round, every ``obs.span`` with its start, end and parent
  and every compilation's stages under the span that was open; reduced
  once at close to self seconds by span, the uncovered share and the
  stage sums by program (``obs.setup_account()``, the events stream's
  one ``setup`` record, the ``setup_*`` gauges, ``obs-report --setup``;
  docs/OBSERVABILITY.md §Set-up account).
- ``memwatch``: HBM watermark gauges (live/peak device bytes, per span
  phase) sampled at span boundaries; off by default
  (``memwatch``/``LIGHTGBM_TPU_MEMWATCH``).
- ``devprof`` + ``devcaps``: device-time attribution — sampled
  per-program device-seconds histograms via forced syncs at the
  InstrumentedJit dispatch seam, static-cost roofline gauges against a
  per-platform capability table, and H2D/D2H transfer accounting per
  phase; off by default (``devprof``/``LIGHTGBM_TPU_DEVPROF``),
  surfaced by ``obs-report --profile`` and bench.py's ``profile`` block
  (docs/OBSERVABILITY.md §Device-time attribution).
- ``tracing``: parent-linked span trees with trace IDs — one trace per
  serve HTTP request (queue -> coalesced batch -> device predict, with
  explicit many-to-one coalesce edges) and per boosting round — exported
  as Perfetto-loadable Chrome trace-event JSON
  (``trace_events_file``/``LIGHTGBM_TPU_TRACE_EVENTS``).
"""

from . import devcaps, devprof, devtrace, drift, setup  # noqa: F401
from .compile_ledger import (InstrumentedJit, abstract_shapes,  # noqa: F401
                             instrumented_jit)
from .events import (SCHEMA_VERSION, EventRecorder,  # noqa: F401
                     read_events, read_setup)
from .phases import (DEVICE_PARENT, DEVICE_PHASES,  # noqa: F401
                     HOST_PHASES, JITTED_HOST_PHASES,
                     TRANSFER_PHASES, span_series)
from .prom import labeled_name, split_series  # noqa: F401
from .registry import (DEFAULT_BYTE_BUCKETS,  # noqa: F401
                       DEFAULT_TIME_BUCKETS, REGISTRY, Registry,
                       get_counter, get_gauge, get_histogram,
                       histogram_quantile, inc, merge, observe, reset,
                       restore, set_gauge, snapshot)
from .setup import setup_account  # noqa: F401
from .spans import span, timed  # noqa: F401
from .trace import TraceCapture  # noqa: F401
from .tracing import TRACER  # noqa: F401


def trace_span(name, args=None, parent=None):
    """Context manager: one causal-tracing span (no histogram observe —
    use ``obs.span`` for timed phases).  No-op while the tracer is
    disarmed."""
    return TRACER.span(name, args=args, parent=parent)


def trace_begin(name, parent=None, args=None):
    """Open a tracing span to be ended by ``trace_end`` — possibly from
    another thread (the batcher ends request queue spans from its
    worker).  Returns None while the tracer is disarmed."""
    return TRACER.begin(name, parent=parent, args=args)


def trace_end(handle, args=None):
    TRACER.end(handle, args=args)


def trace_link(src, dst):
    """Record a many-to-one coalesce edge ``src -> dst``."""
    TRACER.link(src, dst)


__all__ = [
    "REGISTRY", "Registry", "inc", "set_gauge", "observe", "get_counter",
    "get_gauge", "get_histogram", "histogram_quantile",
    "DEFAULT_TIME_BUCKETS", "DEFAULT_BYTE_BUCKETS",
    "snapshot", "merge", "reset", "restore",
    "span", "timed", "span_series", "labeled_name", "split_series",
    "EventRecorder", "read_events", "read_setup", "SCHEMA_VERSION",
    "setup", "setup_account",
    "TraceCapture",
    "instrumented_jit", "InstrumentedJit", "abstract_shapes",
    "TRACER", "trace_span", "trace_begin", "trace_end", "trace_link",
    "HOST_PHASES", "DEVICE_PHASES", "DEVICE_PARENT", "JITTED_HOST_PHASES",
    "TRANSFER_PHASES", "devprof", "devcaps", "devtrace", "drift",
]
