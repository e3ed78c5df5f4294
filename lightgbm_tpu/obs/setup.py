"""The set-up account: where the seconds from package import to the end
of the first boosting round went.

The round has its phase table (obs/devtrace.py); set-up had nothing of
the kind, and on the chip it is the dearest part of a short job (64 to
122 s warm, 190 to 370 s cold, before a 51 s window).  While an account
is open, ``obs.span`` also appends ``(name, start, end, parent)`` here
(parent: the enclosing open span on the same thread), and the compile
ledger adds the stages of each compilation as ``Compile::trace``,
``Compile::lower``, ``Compile::backend`` and ``Compile::cache_read``
spans under the host span that was open when they ran.  One account a
training job:

- it opens at package import (``open_account`` from
  ``lightgbm_tpu/__init__``, origin at the top of that file), so a
  ``Dataset.construct`` that runs before ``engine.train`` is inside it;
  a later ``engine.train`` in the same process opens a new one at its
  entry (``ensure_open``);
- it closes when the first booster's first ``train_one_iter`` returns
  (``close``): the list is reduced once to a dict, the per-span append
  is off again (``ACTIVE`` is None: one attribute read a span), and a
  run of 10,000 rounds carries nothing;
- the reduced form goes to ``setup_account()``, to the events stream as
  ONE record ``{"setup": {...}}`` ahead of the first iteration's, to the
  registry as ``setup_*`` gauges, and ``obs-report --setup`` prints it.

Dispatch is asynchronous: ``GBDT::first_round`` is the host's seconds
(trace, lowering, compile or cache load, enqueue), not the device's; the
first wait for the device comes after the account has closed.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from . import registry

MAX_SPANS = 512         # then counted as ``dropped``
MAX_NAMES = 256         # rows of an unledgered-compile table
OTHER = "(other)"

STAGE_SPANS = {"trace": "Compile::trace", "lower": "Compile::lower",
               "backend": "Compile::backend"}
CACHE_READ_SPAN = "Compile::cache_read"
STAGE_FIELDS = ("trace_s", "lower_s", "backend_s", "cache_read_s", "other_s")

# the open account, read by obs.span at every entry; None after close
ACTIVE: Optional["Account"] = None
_last: Optional[Dict[str, Any]] = None


def bump(table: Dict[str, List[float]], name: str, backend_s: float,
         front_s: float) -> None:
    """Add one compilation to a bounded ``{name: [count, backend_s,
    trace_s + lower_s]}`` table."""
    row = table.get(name)
    if row is None:
        if len(table) >= MAX_NAMES:
            name = OTHER
        row = table.setdefault(name, [0, 0.0, 0.0])
    row[0] += 1
    row[1] += backend_s
    row[2] += front_s


class Account:
    """One job's set-up, open: the span list and what the compile ledger
    hands in.  Spans are kept on ``time.perf_counter``; ``t0_wall`` is
    ``time.time()`` at the same origin, so jax's stage spans, the compile
    ledger's ``t`` and a harness's wall stamps lie on the same axis."""

    def __init__(self, t0_wall: Optional[float] = None,
                 t0_perf: Optional[float] = None):
        self.t0_wall = time.time() if t0_wall is None else float(t0_wall)
        self.t0_perf = (time.perf_counter() if t0_perf is None
                        else float(t0_perf))
        # [name, start, end, parent, program]
        self.spans: List[list] = []
        self.dropped = 0
        self.overhead_s = 0.0       # spent in here and in the listeners
        self.device_bytes_placed = 0
        self._compiles: List[Tuple[Dict[str, Any], int]] = []
        self._unledgered: Dict[str, List[float]] = {}
        self._unledgered_by_span: Dict[str, float] = {}
        self._lock = threading.Lock()
        self._tls = threading.local()

    # -- spans ----------------------------------------------------------
    def _stack(self) -> List[int]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _append(self, name: str, start: float, end: Optional[float],
                parent: int, program: Optional[str] = None) -> int:
        with self._lock:
            if len(self.spans) >= MAX_SPANS:
                self.dropped += 1
                return -1
            self.spans.append([name, start, end, parent, program])
            return len(self.spans) - 1

    def _parent(self) -> int:
        """The span open on this thread (-1: none)."""
        stack = self._stack()
        return stack[-1] if stack else -1

    def enter(self, name: str, start: float) -> int:
        """Open a span on this thread; the index ``exit`` wants back
        (-1: past the cap, counted as dropped)."""
        t_in = time.perf_counter()
        parent = self._parent()
        idx = self._append(name, start, None, parent)
        # a dropped span's children hang from its nearest kept ancestor
        self._stack().append(idx if idx >= 0 else parent)
        self.overhead_s += time.perf_counter() - t_in
        return idx

    def exit(self, idx: int, end: float) -> None:
        t_in = time.perf_counter()
        stack = self._stack()
        if stack:
            stack.pop()
        if idx >= 0:
            self.spans[idx][2] = end
        self.overhead_s += time.perf_counter() - t_in

    # -- from the compile ledger ----------------------------------------
    def compiled(self, event: Dict[str, Any], stages: List[tuple],
                 cache_misses: int) -> None:
        """One ledger event and its flat stage spans (jax's wall clock),
        as children of the span open on this thread."""
        t_in = time.perf_counter()
        parent = self._parent()
        shift = self.t0_perf - self.t0_wall
        program = event["program"]
        for stage, start, end, read_s in stages:
            idx = self._append(STAGE_SPANS[stage], start + shift,
                               end + shift, parent, program)
            if read_s and idx >= 0:
                # jax gives the read a duration only: it ended just
                # before the backend span did
                self._append(CACHE_READ_SPAN, end + shift - read_s,
                             end + shift, idx, program)
        with self._lock:
            self._compiles.append((event, int(cache_misses)))
        self.overhead_s += time.perf_counter() - t_in

    def unledgered(self, name: str, backend_s: float,
                   front_s: float) -> None:
        """A compilation no instrumented call made, charged to the span
        open on this thread."""
        parent = self._parent()
        where = self.spans[parent][0] if parent >= 0 else ""
        with self._lock:
            bump(self._unledgered, name, backend_s, front_s)
            self._unledgered_by_span[where] = \
                self._unledgered_by_span.get(where, 0.0) \
                + backend_s + front_s

    # -- the reduced form -----------------------------------------------
    def reduce(self, t_close: float) -> Dict[str, Any]:
        with self._lock:
            spans = [list(sp) for sp in self.spans]
            compiles = list(self._compiles)
            unledgered = {k: list(v) for k, v in self._unledgered.items()}
            by_span = dict(self._unledgered_by_span)
        seconds = t_close - self.t0_perf
        for sp in spans:
            if sp[2] is None:               # still open at close
                sp[2] = t_close
        child_s = [0.0] * len(spans)
        roots = []
        for sp in spans:
            if sp[3] >= 0:
                child_s[sp[3]] += sp[2] - sp[1]
            else:
                roots.append((sp[1], sp[2]))
        self_s: Dict[str, float] = {}
        total_s: Dict[str, float] = {}
        for sp, below in zip(spans, child_s):
            self_s[sp[0]] = self_s.get(sp[0], 0.0) + sp[2] - sp[1] - below
            total_s[sp[0]] = total_s.get(sp[0], 0.0) + sp[2] - sp[1]
        covered, reach = 0.0, self.t0_perf
        for start, end in sorted(roots):
            start, end = max(start, reach), min(end, t_close)
            if end > start:
                covered += end - start
                reach = end

        def rel(t: float) -> float:
            return round(t - self.t0_perf, 6)
        out_spans = []
        for name, start, end, parent, program in spans:
            row = {"name": name, "start": rel(start), "end": rel(end),
                   "parent": parent}
            if program is not None:
                row["program"] = program
            out_spans.append(row)

        comp: Dict[str, Any] = {"count": len(compiles), "cache_hits": 0,
                                "cache_misses": 0, "seconds": 0.0}
        comp.update({f: 0.0 for f in STAGE_FIELDS})
        by_program: Dict[str, Dict[str, Any]] = {}
        for ev, misses in compiles:
            row = by_program.setdefault(
                ev["program"], {"count": 0, "cache_hits": 0, "seconds": 0.0,
                                **{f: 0.0 for f in STAGE_FIELDS}})
            for acc in (comp, row):
                acc["seconds"] += float(ev["seconds"])
                acc["cache_hits"] += bool(ev.get("cache_hit"))
                for f in STAGE_FIELDS:
                    acc[f] += float(ev.get(f) or 0.0)
            row["count"] += 1
            if ev.get("saved_s") is not None:
                row["saved_s"] = row.get("saved_s", 0.0) + ev["saved_s"]
            comp["cache_misses"] += misses
        for acc in [comp] + list(by_program.values()):
            for k, v in acc.items():
                if isinstance(v, float):
                    acc[k] = round(v, 6)
        comp["by_program"] = by_program
        top = sorted(unledgered.items(), key=lambda kv: -(kv[1][1] + kv[1][2]))
        comp["unledgered"] = {
            "count": int(sum(v[0] for v in unledgered.values())),
            "seconds": round(sum(v[1] + v[2]
                                 for v in unledgered.values()), 6),
            "top": [{"name": k, "count": int(v[0]),
                     "backend_s": round(v[1], 6),
                     "trace_lower_s": round(v[2], 6)} for k, v in top[:10]],
            "by_span": {k: round(v, 6) for k, v in sorted(by_span.items())},
        }
        return {
            "origin_wall": round(self.t0_wall, 6),
            "seconds": round(seconds, 6),
            "uncovered_s": round(seconds - covered, 6),
            "overhead_s": round(self.overhead_s, 6),
            "dropped": self.dropped,
            "device_bytes_placed": int(self.device_bytes_placed),
            "self_s": {k: round(v, 6) for k, v in self_s.items()},
            "total_s": {k: round(v, 6) for k, v in total_s.items()},
            "compile": comp,
            "spans": out_spans,
        }


# ---------------------------------------------------------------------------
# the process's one open account


def open_account(t0_wall: Optional[float] = None,
                 t0_perf: Optional[float] = None) -> Account:
    """Open a new account (dropping an open one); the origin defaults
    to now."""
    global ACTIVE
    ACTIVE = Account(t0_wall, t0_perf)
    return ACTIVE


def ensure_open() -> None:
    """``engine.train``'s entry: the account opened at import if it is
    still open (this is the process's first job), else a new one."""
    if ACTIVE is None:
        open_account()


def placed(num_bytes: int) -> None:
    """``Dataset::to_device`` reports what it put on the device."""
    acct = ACTIVE
    if acct is not None:
        acct.device_bytes_placed += int(num_bytes)


_GAUGES = (
    ("setup_compile_trace_seconds", "compile", "trace_s"),
    ("setup_compile_lower_seconds", "compile", "lower_s"),
    ("setup_compile_backend_seconds", "compile", "backend_s"),
    ("setup_cache_read_seconds", "compile", "cache_read_s"),
    ("setup_compile_other_seconds", "compile", "other_s"),
    ("setup_first_round_seconds", "total_s", "GBDT::first_round"),
    ("setup_dataset_construct_seconds", "total_s", "Dataset::construct"),
    ("setup_booster_init_seconds", "total_s", "Booster::init"),
)


def close(recorder=None) -> Optional[Dict[str, Any]]:
    """Freeze the open account: reduce it once, publish the gauges, hand
    the record to ``recorder`` (an ``obs.EventRecorder`` or None).
    Nothing to do (None) when no account is open."""
    global ACTIVE, _last
    acct = ACTIVE
    if acct is None:
        return None
    ACTIVE = None
    out = _last = acct.reduce(time.perf_counter())
    registry.set_gauge("setup_seconds", out["seconds"])
    registry.set_gauge("setup_uncovered_seconds", out["uncovered_s"])
    for gauge, block, key in _GAUGES:
        registry.set_gauge(gauge, out[block].get(key, 0.0))
    if recorder is not None:
        recorder.write_setup(out)
    return out


def setup_account() -> Optional[Dict[str, Any]]:
    """The newest closed account's reduced form (None before the first
    round of the process's first job has returned)."""
    return _last


def render(acct: Dict[str, Any]) -> str:
    """``obs-report --setup``: the span tree with self seconds, the
    stage table by program and the ten heaviest unledgered
    compilations."""
    spans = acct["spans"]
    kids: Dict[int, List[int]] = {}
    for i, sp in enumerate(spans):
        kids.setdefault(sp["parent"], []).append(i)
    seconds = acct["seconds"] or 1.0
    lines = [
        f"set-up account: {acct['seconds']:.3f} s from the origin to the "
        f"end of the first round; uncovered {acct['uncovered_s']:.3f} s "
        f"({100.0 * acct['uncovered_s'] / seconds:.1f}%), the instrument "
        f"itself {acct['overhead_s']:.4f} s, {acct['dropped']} spans "
        f"dropped, {acct['device_bytes_placed']:,} bytes placed",
        f"{'start':>9} {'seconds':>9} {'self':>9}  span"]

    def walk(i: int, depth: int) -> None:
        sp = spans[i]
        below = sum(spans[k]["end"] - spans[k]["start"]
                    for k in kids.get(i, ()))
        dur = sp["end"] - sp["start"]
        name = sp["name"] + (f" [{sp['program']}]" if "program" in sp else "")
        lines.append(f"{sp['start']:9.3f} {dur:9.3f} {dur - below:9.3f}  "
                     f"{'  ' * depth}{name}")
        for k in kids.get(i, ()):
            walk(k, depth + 1)
    for i in kids.get(-1, ()):
        walk(i, 0)

    comp = acct["compile"]
    lines += ["", f"compilations in the ledger: {comp['count']} "
                  f"({comp['cache_hits']} from the persistent cache, "
                  f"{comp['cache_misses']} written to it), seconds by "
                  f"stage:",
              f"  {'program':<24} {'n':>3} {'hit':>3} {'seconds':>9} "
              f"{'trace':>8} {'lower':>8} {'backend':>8} {'cache_rd':>8} "
              f"{'other':>8}"]
    rows = sorted(comp["by_program"].items(),
                  key=lambda kv: -kv[1]["seconds"]) + [("(all)", comp)]
    for prog, r in rows:
        lines.append(
            f"  {prog:<24} {r['count']:>3} {r['cache_hits']:>3} "
            f"{r['seconds']:9.3f} {r['trace_s']:8.3f} {r['lower_s']:8.3f} "
            f"{r['backend_s']:8.3f} {r['cache_read_s']:8.3f} "
            f"{r['other_s']:8.3f}")
    un = comp["unledgered"]
    where = ", ".join(f"{k or '(no span)'} {v:.3f}"
                      for k, v in un["by_span"].items())
    lines += ["", f"compilations outside the ledger: {un['count']} in "
                  f"{un['seconds']:.3f} s" + (f" (under {where})"
                                              if where else ""),
              f"  {'name':<40} {'n':>4} {'backend':>8} {'trace+lower':>11}"]
    for r in un["top"]:
        lines.append(f"  {r['name'][:40]:<40} {r['count']:>4} "
                     f"{r['backend_s']:8.3f} {r['trace_lower_s']:11.3f}")
    return "\n".join(lines)
