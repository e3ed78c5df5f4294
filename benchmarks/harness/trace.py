"""Reduction from a profiler trace (``.xplane.pb``) to device events.

A device event is a tuple ``(name, text, start_ns, dur_ns)``: ``text`` is
the name and every string-valued stat of the event joined by spaces, so a
metric's regular expression can match the operation's name, its HLO
category or the ``jax.named_scope`` path, whichever the trace carries.
All reductions below work on plain lists of such tuples, so the self-test
checks them against a small recorded list with no profiler at all.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = re.compile(r"^/host:CPU$")


def find_xplane(trace_dir: str):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def _event_text(ev) -> str:
    parts = [ev.name]
    try:
        for k, v in ev.stats:
            if isinstance(v, str) and v:
                parts.append(f"{k}={v}")
    except Exception:
        pass
    return " ".join(parts)


def load(trace_dir: str):
    """{"devices": {ordinal: [events]}, "host": [events], "lines": {...}}
    from the newest trace under ``trace_dir``, or None."""
    path = find_xplane(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData
    prof = ProfileData.from_file(path)
    out = {"devices": {}, "host": [], "lines": {}, "path": path}
    for plane in prof.planes:
        names = []
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            names.append(line.name)
            if m and line.name == OPS_LINE:
                evs = [(e.name, _event_text(e), int(e.start_ns),
                        int(e.duration_ns)) for e in line.events]
                out["devices"][int(m.group(1))] = evs
            elif HOST_PLANE.match(plane.name):
                for e in line.events:
                    if e.name.startswith("bench_"):
                        out["host"].append((e.name, e.name, int(e.start_ns),
                                            int(e.duration_ns)))
        out["lines"][plane.name] = names
    return out


def self_times(events):
    """[(name, text, start, self_ns)]: each event's duration less what
    events nested inside it on the same line cover (a ``while`` or a
    ``conditional`` holds its body's operations)."""
    evs = sorted(events, key=lambda e: (e[2], -e[3]))
    out = []
    stack = []          # [index into out, end_ns]
    for name, text, start, dur in evs:
        end = start + dur
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            parent = out[stack[-1][0]]
            covered = min(end, stack[-1][1]) - start
            parent[3] -= max(covered, 0)
        out.append([name, text, start, dur])
        stack.append([len(out) - 1, end])
    return [(n, t, s, max(d, 0)) for n, t, s, d in out]


def busy_ns(events) -> int:
    """Length of the union of the events' intervals."""
    total, cur_s, cur_e = 0, None, None
    for _, _, start, dur in sorted(events, key=lambda e: e[2]):
        end = start + dur
        if cur_e is None or start > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = start, end
        else:
            cur_e = max(cur_e, end)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def span_ns(events) -> int:
    """From the first event's start to the last event's end: the traced
    window by the device's own clock."""
    if not events:
        return 0
    return max(s + d for _, _, s, d in events) - min(e[2] for e in events)


def matching_ns(self_timed, pattern: str, exclude: str = None) -> int:
    """Sum of self time of the events whose text matches ``pattern`` (and
    not ``exclude``)."""
    rx = re.compile(pattern)
    ex = re.compile(exclude) if exclude else None
    return sum(d for _, text, _, d in self_timed
               if rx.search(text) and not (ex and ex.search(text)))


_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"[a-z]+[0-9]+\[[0-9,]*\]")


def short_name(name: str) -> str:
    """An event is named by its whole HLO text on this chip: keep the
    result's name, the opcode, the first shape and, for a kernel, the
    first operand's shape."""
    if " = " not in name:
        return name[:96]
    lhs, rhs = name.split(" = ", 1)
    op = _OPCODE.search(" " + rhs)
    shapes = _SHAPE.findall(rhs)
    out = lhs + " " + (op.group(1) if op else "?")
    if shapes:
        out += " " + shapes[0]
    if "tpu_custom_call" in rhs and op:
        operand = _SHAPE.search(rhs[op.end():])
        if operand:
            out += " <- " + operand.group(0)
    return out[:96]


def top_ops(self_timed, k: int = 10):
    """[[name, seconds]] of the k (short) names with most self time."""
    acc = {}
    for name, _, _, d in self_timed:
        name = short_name(name)
        acc[name] = acc.get(name, 0) + d
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, d / 1e9] for n, d in top]


def idle_gaps(events, host_spans, k: int = 10):
    """[[what the host was doing, seconds]] for the k longest gaps between
    device events, each named by the harness span that covers its start
    (``outside_spans`` where none does)."""
    evs = sorted(events, key=lambda e: e[2])
    gaps, cur_e = [], None
    for _, _, start, dur in evs:
        if cur_e is not None and start > cur_e:
            gaps.append((cur_e, start - cur_e))
        cur_e = max(cur_e or 0, start + dur)
    gaps.sort(key=lambda g: -g[1])
    out = []
    for at, length in gaps[:k]:
        who = "outside_spans"
        for name, _, s, d in host_spans:
            if s <= at < s + d:
                who = name
                break
        out.append([who, length / 1e9])
    return out


def summarize(trace_dir: str, limit: int = 40) -> str:
    """A by-hand look: planes, lines, and the heaviest events with their
    stats.  For the builder, not for a metric."""
    path = find_xplane(trace_dir)
    if path is None:
        return "no trace found under " + trace_dir
    from jax.profiler import ProfileData
    prof = ProfileData.from_file(path)
    lines = [f"trace {path} ({os.path.getsize(path)} bytes)"]
    for plane in prof.planes:
        lines.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            lines.append(f"  line {line.name!r}: {len(evs)} events")
            if DEVICE_PLANE.match(plane.name) and evs:
                heavy = sorted(evs, key=lambda e: -e.duration_ns)[:limit]
                for e in heavy:
                    stats = {}
                    try:
                        stats = {k: (v if not isinstance(v, str)
                                     else v[:300]) for k, v in e.stats}
                    except Exception as ex:
                        stats = {"stats_error": str(ex)}
                    lines.append(f"    {e.duration_ns / 1e6:9.3f} ms "
                                 f"{e.name[:120]!r} {stats}")
    return "\n".join(lines)
