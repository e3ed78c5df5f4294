"""Process-wide account of every XLA compilation.

Early bench rounds showed training throughput flat while warmup swung
34-321 s of XLA compiles (PERF.md, "Carried over") — and nothing could
say WHICH programs compiled, for which shapes, or how long each took.
This module is that account:

- ``instrumented_jit(fn, program=...)`` wraps a function in ``jax.jit``
  (or wraps an already-jitted callable) and detects each compilation the
  same way ``serve/batcher.py``'s ``CountingJit`` always has — the jit's
  executable-cache size grows exactly when a call shape-missed.  On a
  compile the wrapper records the program name, the abstract shapes of
  the arguments that caused it, and the wall seconds of the compiling
  call (dominated by XLA compile time; the dispatch of the freshly
  compiled program rides along, which is the honest host-side
  measurement without private profiler hooks).
- every event feeds the obs registry: the ``compile_count`` counter, a
  ``compile_seconds`` wall-time histogram (DEFAULT_TIME_BUCKETS reaches
  300 s — the compile regime), and a per-program
  ``compile_count_<program>`` counter, all rendered at ``/metrics`` by
  ``obs/prom.py``.
- events append to an in-memory ledger (``events()``, bounded) and — when
  ``compile_ledger_file`` / the ``LIGHTGBM_TPU_COMPILE_LEDGER`` env var
  names a path — to an append-only JSONL file, one line per compile,
  crash-safe by construction (each line is flushed as it happens).

- every event says whether the persistent compilation cache served it
  (``cache_hit``, from jax's own monitoring events), so cold and warm
  compile seconds separate.
- every event splits its ``seconds`` by stage, from the public
  ``jax.monitoring`` listeners (one duration and one time-span listener
  beside the cache-hit one, registered at the first instrumented
  dispatch or the first span of a set-up account, never at import):
  ``trace_s`` (jaxpr tracing), ``lower_s`` (jaxpr to MLIR),
  ``backend_s`` (the backend compile, or on a cache hit the whole
  cached path: key, read, deserialisation), ``cache_read_s`` (inside
  ``backend_s``; on a hit only), ``saved_s`` (what jax says the hit
  saved), ``other_s`` (``seconds`` less the first three: executable
  load, argument placement, dispatch) and ``modules`` (backend compiles
  inside the call).  What the listeners hear on the calling thread
  while the compiling call runs belongs to that event; a stage nested
  inside another (an inner jit traced inside the outer trace, an eager
  operation compiled while tracing) counts once, under the outer.
- compilations OUTSIDE an instrumented call (eager ``jax.numpy`` on
  device arrays, an un-instrumented ``jax.jit``) are heard by the same
  listeners and kept as a bounded table by jax's ``fun_name``
  (``unledgered()``), the counter ``compile_unledgered_count`` and the
  histogram ``compile_unledgered_seconds`` (its sum is the seconds).
  No event is written for them and ``events()`` does not grow.
- while a set-up account is open (obs/setup.py) each event's stages
  enter it as ``Compile::*`` spans under the host span that was open.
- while an ``obs.TraceCapture`` is armed (``trace_dir``), a compile
  event — or the first dispatch inside the window of a program compiled
  earlier — also exports the program's PHASE MAP: the compiled text
  parsed into ``{instruction name -> leaf phase}`` (obs/devtrace.py
  ``phase_map``), written to ``<trace_dir>/phase_map.<program>.json``,
  with ``ops_scoped`` / ``ops_unscoped`` added to the entry.  With no
  capture armed nothing is lowered twice and nothing is parsed.

Calls made while another jit is tracing are passed straight through
(``jax.core.trace_state_clean``): an inner jit inlined into an outer
trace is not a compilation of its own, and instrumenting it there would
record trace-time side effects into the account.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from . import devprof, devtrace, registry, setup, trace

ENV_PATH = "LIGHTGBM_TPU_COMPILE_LEDGER"

# In-memory ledger cap: a runaway shape leak should saturate the list,
# not the process.  The JSONL file (when configured) keeps every event.
MAX_EVENTS = 4096

_lock = threading.Lock()
_events: List[Dict[str, Any]] = []
_dropped = 0
_path: Optional[str] = os.environ.get(ENV_PATH, "").strip() or None

# jax.monitoring events heard (jax/_src/dispatch.py, compiler.py,
# compilation_cache.py); the listeners are registered by ``listen()``,
# not at import
_STAGE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"

_listening = False
_tls = threading.local()
# stages a thread may hold outside an instrumented call before a backend
# compile hands them on: traces that never compile (``jax.eval_shape``)
# must not pile up.  A call's own stages are not capped: lowering a large
# program reports thousands of nested traces AFTER the outer trace, and
# dropping the oldest there would drop the stage itself
_MAX_HELD = 4096
# compilations outside any instrumented call, by jax's fun_name:
# [count, backend_s, trace_s + lower_s]
_unledgered: Dict[str, List[float]] = {}


class _Stages:
    """The compile stages one thread has heard, flat and in order of
    their ends: ``(stage, start, end, cache_read_s)`` on jax's wall
    clock.  jax reports a nested stage before the one that holds it (an
    inner jit's trace inside the outer trace, an eager operation
    compiled while tracing), so a stage that arrives absorbs every
    earlier one that starts inside it: the partition stays flat and no
    second is counted twice.  A cache read comes as a duration from
    inside a backend span, and belongs to the next one to end."""

    __slots__ = ("spans", "hits", "misses", "saved_s", "read_s", "mark",
                 "cap")

    def __init__(self, cap: Optional[int] = None):
        self.cap = cap
        self.spans: List[tuple] = []
        self.hits = self.misses = 0
        self.saved_s = self.read_s = 0.0
        self.mark = 0.0         # end of what was already handed on

    def add(self, stage: str, start: float, end: float) -> None:
        start = max(start, self.mark)
        spans, read_s = self.spans, 0.0
        while spans and spans[-1][1] >= start:
            spans.pop()
        if self.cap is not None and len(spans) >= self.cap:
            del spans[0]
        if stage == "backend":
            read_s, self.read_s = self.read_s, 0.0
        spans.append((stage, start, end, read_s))

    def totals(self):
        """``({stage: seconds}, cache_read_s, backend compiles)`` of the
        flat partition."""
        tot = {"trace": 0.0, "lower": 0.0, "backend": 0.0}
        read_s, modules = 0.0, 0
        for stage, start, end, read in self.spans:
            tot[stage] += end - start
            read_s += read
            modules += stage == "backend"
        return tot, read_s, modules

    def fields(self, seconds: float) -> Dict[str, Any]:
        """The ledger entry's stage fields for a call of ``seconds``."""
        tot, read_s, modules = self.totals()
        out = {k + "_s": round(v, 6) for k, v in tot.items()}
        out["other_s"] = round(max(seconds - sum(tot.values()), 0.0), 6)
        out["modules"] = modules
        if self.hits:
            out["cache_read_s"] = round(read_s, 6)
            out["saved_s"] = round(self.saved_s, 6)
        return out


def _heard() -> _Stages:
    """Where this thread's events go: the instrumented call in flight,
    else the thread's own account of unledgered compilations."""
    st = getattr(_tls, "call", None)
    if st is None:
        st = getattr(_tls, "ambient", None)
        if st is None:
            st = _tls.ambient = _Stages(cap=_MAX_HELD)
    return st


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        _heard().hits += 1
    elif event == _CACHE_MISS:
        _heard().misses += 1


def _on_duration(event: str, duration: float, **_kw) -> None:
    # the two cache durations come with no span of their own
    if event == _CACHE_READ:
        _heard().read_s += duration
    elif event == _CACHE_SAVED:
        _heard().saved_s += duration


def _on_span(event: str, start: float, end: float, fun_name: str = "",
             **_kw) -> None:
    stage = _STAGE_OF.get(event)
    if stage is None:
        return
    t_in = time.perf_counter()
    call = getattr(_tls, "call", None)
    st = call if call is not None else _heard()
    st.add(stage, start, end)
    if stage == "backend" and call is None:
        _note_unledgered(st, str(fun_name))
    acct = setup.ACTIVE
    if acct is not None:
        acct.overhead_s += time.perf_counter() - t_in


def _note_unledgered(st: _Stages, name: str) -> None:
    """A backend compile outside every instrumented call ends a module:
    it and the trace and lowering heard before it on this thread go to
    the table, the two registry series and the open set-up account."""
    tot, _, _ = st.totals()
    st.mark = st.spans[-1][2]
    st.spans.clear()
    st.hits = st.misses = 0
    st.saved_s = st.read_s = 0.0
    backend_s, front_s = tot["backend"], tot["trace"] + tot["lower"]
    registry.inc("compile_unledgered_count")
    registry.observe("compile_unledgered_seconds", backend_s + front_s)
    with _lock:
        setup.bump(_unledgered, name, backend_s, front_s)
    acct = setup.ACTIVE
    if acct is not None:
        acct.unledgered(name, backend_s, front_s)


def listen() -> None:
    """Register the three ``jax.monitoring`` listeners, once a process."""
    global _listening
    if _listening:
        return
    with _lock:
        if _listening:
            return
        _listening = True
    import jax
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_time_span_listener(_on_span)


def unledgered() -> Dict[str, Dict[str, float]]:
    """Compilations no instrumented call made, by jax's ``fun_name``:
    ``{name: {count, backend_s, trace_lower_s}}`` (bounded: past
    ``setup.MAX_NAMES`` names the rest share one row)."""
    with _lock:
        return {k: {"count": int(v[0]), "backend_s": round(v[1], 6),
                    "trace_lower_s": round(v[2], 6)}
                for k, v in _unledgered.items()}


def configure(path: Optional[str] = None) -> Optional[str]:
    """Set the JSONL sink path for a run.  The
    ``LIGHTGBM_TPU_COMPILE_LEDGER`` env var wins over the argument (same
    precedence as the metrics port); no env and no argument clears the
    sink — each run's configuration is authoritative, so a second
    ``engine.train`` in the same process cannot keep appending to the
    previous run's file.  The in-memory ledger is unaffected (always
    on).  Returns the effective path (None = in-memory only)."""
    global _path
    env = os.environ.get(ENV_PATH, "").strip()
    with _lock:
        _path = env or (str(path) if path else None)
        return _path


def ledger_path() -> Optional[str]:
    with _lock:
        return _path


def reset() -> None:
    """Clear the in-memory ledger (tests).  Registry counters and any
    JSONL file already written are left alone."""
    global _dropped
    with _lock:
        _events.clear()
        _dropped = 0


def events() -> List[Dict[str, Any]]:
    """Copy of the in-memory compile events, oldest first."""
    with _lock:
        return [dict(e) for e in _events]


def total_seconds() -> float:
    with _lock:
        return sum(float(e["seconds"]) for e in _events)


def slowest(k: int = 5) -> List[Dict[str, Any]]:
    """The k slowest compile events (for bench tails and reports)."""
    evs = events()
    evs.sort(key=lambda e: -float(e["seconds"]))
    return evs[: max(int(k), 0)]


def summary(k: int = 5) -> Dict[str, Any]:
    """The in-memory account as one JSON-ready block — bench.py's
    ``compile_events`` key in both modes (one schema, one source)."""
    return {
        "count": len(events()),
        "seconds_total": round(total_seconds(), 3),
        "slowest": [{"program": e["program"], "shapes": e["shapes"],
                     "seconds": e["seconds"]} for e in slowest(k)],
    }


def record(program: str, shapes: str, seconds: float,
           cost: Optional[Dict[str, Any]] = None,
           cache_hit: Optional[bool] = None,
           phase_map: Optional[Dict[str, Any]] = None,
           stages: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Append one compile event; feeds the registry series and the JSONL
    sink.  Called by the instrumented jits — safe to call directly for
    compilations detected by other means.  ``cost`` is the program's
    static cost-analysis row (``_cost_analysis``); the three fields are
    present on every event — None when profiling was off or the backend
    reported nothing — so ledger consumers see one schema.
    ``cache_hit``: the persistent cache served the executable (None:
    not known).  ``phase_map``: the counts of ``_export_phase_map``,
    present only when a map was built.  ``stages``: the split of
    ``seconds`` the listeners heard (``_Stages.fields``)."""
    global _dropped
    registry.inc("compile_count")
    registry.inc("compile_count_" + _sanitize(program))
    registry.observe("compile_seconds", float(seconds))
    cost = cost or {}
    ev = {
        "program": str(program),
        "shapes": str(shapes),
        "seconds": round(float(seconds), 6),
        "t": round(time.time(), 3),
        "flops": cost.get("flops"),
        "bytes_accessed": cost.get("bytes_accessed"),
        "output_bytes": cost.get("output_bytes"),
        "cache_hit": cache_hit,
    }
    if stages:
        ev.update(stages)
    if phase_map:
        ev.update(phase_map)
    with _lock:
        ev["count"] = registry.get_counter("compile_count")
        if len(_events) < MAX_EVENTS:
            _events.append(ev)
        else:
            _dropped += 1
        path = _path
    if path:
        # guarded append (utils/diskguard.py): a full disk degrades the
        # ledger to in-memory-only with one warning and a
        # sink_write_errors_total count — the account must never kill
        # the run it measures (unless the run asked for
        # sink_error_policy=fatal; policy=None honors it)
        from ..utils import diskguard
        diskguard.append_line(path, json.dumps(ev), sink="compile_ledger")
    return ev


def read_ledger(path: str) -> List[Dict[str, Any]]:
    """Parse a compile_ledger.jsonl back into event dicts (a torn final
    line from a crashed run is dropped, not fatal)."""
    out: List[Dict[str, Any]] = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return out


def _sanitize(name: str) -> str:
    from . import phases
    return phases.sanitize(name)


# ---------------------------------------------------------------------------
# the jit wrapper


def abstract_shapes(args: tuple, kwargs: Optional[dict] = None,
                    limit: int = 16) -> str:
    """Compact abstract-shape signature of a call: ``f32[1024,28],i32[28]``
    per array leaf (scalars/statics render as short reprs), capped at
    ``limit`` leaves."""
    import jax
    leaves = jax.tree_util.tree_leaves((args, kwargs or {}))
    parts: List[str] = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            dt = np.dtype(dtype)
            parts.append(f"{dt.kind}{dt.itemsize * 8}"
                         f"[{','.join(str(d) for d in shape)}]")
        else:
            parts.append(repr(leaf)[:24])
    if len(parts) > limit:
        parts = parts[:limit] + [f"+{len(parts) - limit} more"]
    return ",".join(parts)


def _in_trace() -> bool:
    """True while another jit is tracing this call (inner jits inline —
    not a compilation of their own)."""
    try:
        # jax 0.9 took trace_state_clean out of jax.core; without it every
        # inner jit read as a top-level dispatch
        from jax._src.core import trace_state_clean
    except ImportError:  # pragma: no cover - jax internals moved
        from jax.core import trace_state_clean
    return not trace_state_clean()


def _cost_analysis(fn, args: tuple,
                   kwargs: dict) -> Optional[Dict[str, float]]:
    """``flops`` / ``bytes_accessed`` / ``output_bytes`` from XLA's
    static cost model for the executable this call shape compiled, or
    None when the backend reports nothing.  Re-lowers and AOT-compiles
    (cache-served, but not free) — only called while devprof is on, on
    compile events."""
    try:
        ca = fn.lower(*args, **kwargs).compile().cost_analysis()
    except Exception:
        return None
    if not isinstance(ca, dict):
        return None

    def _pick(*names: str) -> Optional[float]:
        for n in names:
            v = ca.get(n)
            if v is not None:
                try:
                    return float(v)
                except (TypeError, ValueError):
                    continue
        return None

    out = {
        "flops": _pick("flops"),
        "bytes_accessed": _pick("bytes accessed", "bytes_accessed"),
        "output_bytes": _pick("bytes accessed output",
                              "bytes_accessed_output"),
    }
    return out if any(v is not None for v in out.values()) else None


def _shape_structs(args: tuple, kwargs: dict):
    """The call's arguments with every device array replaced by its
    ``ShapeDtypeStruct``, taken BEFORE the call: the step donates its
    score, and a donated array cannot be lowered from afterwards."""
    import jax

    def one(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype, weak_type=x.weak_type,
                sharding=x.sharding if x.committed else None)
        return x
    return jax.tree_util.tree_map(one, (args, kwargs))


def _export_phase_map(fn, structs, capture,
                      program: str) -> Optional[Dict[str, Any]]:
    """Lower and AOT-compile ``fn`` at ``structs`` (served by the
    persistent cache where there is one), parse the compiled text into
    the program's phase map and hand it to the armed capture.  Returns
    the counts for the ledger entry; None (one warning) when the backend
    gives no text.

    The persistent cache's key leaves debug info out, so it may serve an
    executable compiled from the same operations under OTHER scopes: its
    ``op_name``s are then not this program's.  The phases that only the
    lowered module or only the compiled text holds are listed as
    ``stale_phases`` and warned about (``devtrace.stale_phases``)."""
    t0 = time.perf_counter()
    try:
        sargs, skwargs = structs
        lowered = fn.lower(*sargs, **skwargs)
        text = lowered.compile().as_text()
        pm = devtrace.phase_map(text)
        pm["stale_phases"] = devtrace.stale_phases(
            text, lowered.as_text(debug_info=True))
        if pm["stale_phases"]:
            from ..utils import log
            log.warning(
                "phase map of %s: the compiled text and the program "
                "disagree on phases %s: the persistent compilation cache "
                "served an executable compiled under other scopes (its "
                "key leaves metadata out); remove the entry, or run once "
                "with JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY=1",
                program, pm["stale_phases"])
        capture.write_map(program, pm)
    except Exception as e:
        from ..utils import log
        capture.write_map(program, None)      # tried: not again per call
        log.warn_once("phase_map_" + program,
                      "no phase map for program %s: %s: %s", program,
                      type(e).__name__, e)
        return None
    return {"ops_scoped": pm["ops_scoped"],
            "ops_unscoped": pm["ops_unscoped"],
            "stale_phases": pm["stale_phases"],
            "phase_map_s": round(time.perf_counter() - t0, 3)}


class InstrumentedJit:
    """Wrap a jitted callable; every XLA compilation it triggers lands
    in the compile ledger (and the ``compile_count``/``compile_seconds``
    registry series) with the program name and the abstract shapes that
    caused it.

    Compile detection reads the jit's executable-cache size before/after
    each call (the ``CountingJit`` technique, now shared)."""

    def __init__(self, fn: Callable, program: str):
        self._fn = fn
        self.program = str(program)

    # underlying-jit passthroughs (so stacked wrappers keep detecting,
    # and callers can inspect the lowered program — e.g. the donation
    # tests checking input/output buffer aliasing)
    def lower(self, *args, **kwargs):
        return self._fn.lower(*args, **kwargs)

    def _cache_size(self) -> int:
        return int(self._fn._cache_size())

    def _dispatch(self, *args, **kwargs):
        """The one seam every instrumented dispatch passes through —
        where ``testing.faults.oom_on_program`` injects, where a real
        XLA ``RESOURCE_EXHAUSTED`` surfaces, and where devprof samples
        device time.  Off costs one module-attribute read; inside
        another jit's trace the sampler must not run (a block_until_ready
        on tracers is meaningless)."""
        if devprof.ENABLED and not _in_trace():
            return devprof.timed_dispatch(self.program, self._fn,
                                          args, kwargs,
                                          cache_size=self._cache_size)
        return self._fn(*args, **kwargs)

    def _call_guarded(self, *args, **kwargs):
        """Dispatch with device-OOM containment: an XLA
        ``RESOURCE_EXHAUSTED`` escaping this program is re-raised as a
        named ``DeviceOOM`` diagnosis (utils/resource.py) carrying the
        program name, the abstract shapes of THIS call, a memwatch
        snapshot and the last admission table — instead of the raw
        allocator backtrace."""
        try:
            return self._dispatch(*args, **kwargs)
        except Exception as exc:
            from ..utils import resource
            resource.reraise_if_oom(exc, self.program,
                                    abstract_shapes(args, kwargs))
            raise

    def _call_counted(self, *args, **kwargs):
        """Run the jit; returns ``(out, compiled)`` and records the
        ledger event when the call compiled."""
        if _in_trace():
            return self._call_guarded(*args, **kwargs), False
        capture = trace.armed()       # None unless trace_dir is set
        structs = (_shape_structs(args, kwargs)
                   if capture is not None else None)
        before = self._cache_size()
        listen()
        outer, heard = getattr(_tls, "call", None), _Stages()
        _tls.call = heard
        t0 = time.perf_counter()
        try:
            out = self._call_guarded(*args, **kwargs)
        finally:
            _tls.call = outer
        dt = time.perf_counter() - t0
        compiled = self._cache_size() > before
        pm = None
        if capture is not None and capture.wants_map(self.program, compiled):
            pm = _export_phase_map(self._fn, structs, capture, self.program)
        if compiled:
            cost = None
            if devprof.ENABLED:
                cost = _cost_analysis(self._fn, args, kwargs)
                if cost:
                    devprof.note_cost(self.program, cost)
            ev = record(self.program, abstract_shapes(args, kwargs), dt,
                        cost=cost, cache_hit=heard.hits > 0, phase_map=pm,
                        stages=heard.fields(dt))
            acct = setup.ACTIVE
            if acct is not None:
                acct.compiled(ev, heard.spans, heard.misses)
        return out, compiled

    def __call__(self, *args, **kwargs):
        return self._call_counted(*args, **kwargs)[0]


def instrumented_jit(fn: Optional[Callable] = None, *,
                     program: Optional[str] = None, **jit_kwargs):
    """``jax.jit`` with a compile ledger attached.

    Use as a decorator (``@instrumented_jit(program="grow_tree",
    static_argnames=("params",))``) or as a call
    (``instrumented_jit(f, program="train_gradients")``).  Every extra
    kwarg reaches ``jax.jit`` unchanged — in particular
    ``donate_argnums`` for round-to-round buffer donation (the shared
    train_step donates its score argument; models/gbdt.py).  A callable
    that is already jitted (has ``lower``) is wrapped as-is — pass no
    extra jit kwargs in that case."""
    def wrap(f: Callable) -> InstrumentedJit:
        import jax
        jitted = f if (hasattr(f, "lower") and not jit_kwargs) \
            else jax.jit(f, **jit_kwargs)
        return InstrumentedJit(
            jitted, program or getattr(f, "__name__", "jit"))
    return wrap(fn) if fn is not None else wrap
