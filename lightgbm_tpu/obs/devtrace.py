"""The one device-trace reducer: a profiler window -> named phases.

The chip's trace names a device event by the HLO text of its
instruction (``%sort.890 = (u8[...], ...) sort(...)``) and hands out no
``jax.named_scope`` path.  The compiled program does carry the path, as
``metadata={op_name="jit(step_fn)/.../split/sort/sort"}`` on every
instruction that came from a JAX primitive.  So the join is made here, in
two halves, both pure functions over text and plain lists (the tests need
no profiler and no chip):

- ``phase_map(hlo_text)``: the compiled text -> ``{instruction name ->
  leaf phase}`` (``phases.leaf_phase`` of its ``op_name``).  A fusion
  takes its root's ``op_name``.  An instruction the compiler made itself
  (``copy``, ``copy-start``/``copy-done``, ``bitcast``, layout changes,
  the odd fusion that lost its metadata) is put down to the phase of the
  producer of its first operand, followed until one is named, and flagged
  ``inserted``: window and eviction copies show under the phase that
  causes them.  ``obs/compile_ledger.py`` calls it where the program is
  compiled, while a ``TraceCapture`` is armed, and writes
  ``<trace_dir>/phase_map.<program>.json``.
- ``reduce_events(ops, modules, maps, ...)``: device events
  ``(name, start_ns, dur_ns)`` -> per phase ``ms_per_round`` and
  ``inserted_ms_per_round`` (and its three heaviest instructions) by
  SELF time (a ``while`` or ``conditional``
  holds its body), joined by instruction name within the program that
  the ``XLA Modules`` line says was running (instruction names repeat
  across programs), ``unattributed`` for names in no map, busy and window
  seconds by the device's own clock, and the ten longest idle gaps, each
  named by the ``lgbt:`` host span (obs/spans.py) that covers its start.

``reduce_dir`` reads the newest ``.xplane.pb`` under a trace directory
with ``jax.profiler.ProfileData`` and writes ``device_phases.json``;
``TraceCapture._stop`` calls it, ``python -m lightgbm_tpu obs-report
--device-trace <dir>`` prints the table (``render``).
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from . import phases

SCHEMA = 1
REPORT_FILE = "device_phases.json"
MAP_FILE = "phase_map.{}.json"   # per program (obs/trace.py write_map)
HOST_SPAN_PREFIX = "lgbt:"
UNSCOPED = "unscoped"            # has an op_name, no taxonomy phase

Event = Tuple[str, int, int]     # (name, start_ns, dur_ns)

# ---------------------------------------------------------------------------
# compiled text -> {instruction -> leaf phase}

_INSTR = re.compile(r"^\s*(ROOT )?%([\w.\-]+) = ")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$")
_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLED = re.compile(r"\b(calls|body|condition|to_apply|true_computation"
                     r"|false_computation)=%([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")

# never a device event of their own: nothing to join, kept out of the map
_NO_EVENT = frozenset({"parameter", "constant", "get-tuple-element",
                       "tuple", "bitcast"})


def _after_shape(rest: str) -> str:
    """``rest`` starts at an instruction's result shape; returns what
    follows it (``opcode(operands), attrs``).  A tuple shape is
    parenthesised and holds spaces; layouts nest parentheses too."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    return rest[i + 1:].lstrip()
        return ""
    return rest.partition(" ")[2]


def _operands(body: str) -> List[str]:
    """The ``%name``s inside the operand list ``(...)`` that ``body``
    starts with."""
    depth = 0
    for i, ch in enumerate(body):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return _OPERAND.findall(body[:i])
    return []


def parse_hlo(text: str) -> Dict[str, Any]:
    """``{"module": name, "instructions": {name: record}}`` from
    ``compile().as_text()``.  A record: ``comp`` (its computation),
    ``root``, ``opcode``, ``operands`` (names), ``op_name``, ``located``
    (its metadata holds a source location: the program's own operation),
    ``called`` ([(attribute, computation)])."""
    module, comp = "", ""
    instrs: Dict[str, Dict[str, Any]] = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                comp = c.group(1)
            elif not module:
                h = _MODULE.match(line)
                if h is not None:
                    module = h.group(1)
            continue
        body = _after_shape(line[m.end():])
        opcode, paren, _ = body.partition("(")
        op = _OP_NAME.search(line)
        called = _CALLED.findall(line)
        br = _BRANCHES.search(line)
        if br is not None:
            called += [("branch", b) for b in _OPERAND.findall(br.group(1))]
        instrs[m.group(2)] = {
            "comp": comp, "root": bool(m.group(1)), "opcode": opcode,
            "operands": _operands(body[len(opcode):]) if paren else [],
            "op_name": op.group(1) if op else None,
            "located": "stack_frame_id=" in line or "source_file=" in line,
            "called": called}
    return {"module": module, "instructions": instrs}


def phase_map(text: str) -> Dict[str, Any]:
    """The phase map of one compiled program (module docstring).  Keys:
    ``module``; ``phases`` ``{instruction: phase}`` for every instruction
    that can be a device event (``unscoped`` where it has an ``op_name``
    under no declared phase); ``inserted`` (names resolved through an
    operand, the caller or, for a copy of an argument, its user);
    ``ops_scoped`` / ``ops_unscoped`` / ``ops_by_phase`` (counts over all
    instructions that carry an ``op_name`` of their own, fused ones
    included); ``mixed`` ``{fusion: [phases]}`` for a fusion that holds
    operations of other phases than its root's (it counts whole under
    the root's: those neighbours blur); ``unscoped_op_names`` (up to 20,
    for whoever places the missing scope)."""
    parsed = parse_hlo(text)
    instrs = parsed["instructions"]
    caller: Dict[str, str] = {}      # computation -> instruction calling it
    applied, fused = set(), set()    # reducers/comparators; fusion bodies
    roots: Dict[str, str] = {}
    first_user: Dict[str, str] = {}
    for name, rec in instrs.items():
        for operand in rec["operands"]:
            first_user.setdefault(operand, name)
        for attr, comp in rec["called"]:
            caller.setdefault(comp, name)
            if attr == "to_apply" and rec["opcode"] != "call":
                applied.add(comp)
            elif rec["opcode"] == "fusion":
                fused.add(comp)
        if rec["root"]:
            roots[rec["comp"]] = name

    named: Dict[str, Optional[str]] = {}     # op_name where it names a primitive
    own: Dict[str, Optional[str]] = {}
    for name, rec in instrs.items():
        op = rec["op_name"]
        if op and op.rpartition("/")[2].startswith(("jit(", "shard_map")) \
                and phases.leaf_phase(op + "/") is None:
            # a literal that jax hoisted to the top of a jitted function
            # (or of a shard_map's body) carries the CALL's path and no
            # primitive: nameless, unless the call itself sits under a
            # scope
            op = None
        elif op and op.rpartition("/")[0].endswith("/shard_map") \
                and phases.leaf_phase(op) is None:
            # the compiler inlines a shard_map's body and puts the call's
            # path before what was bare or nameless in it
            # (``.../shard_map/shift-right-logical.22``: an instruction
            # it made itself, nameless in the serial program): bare or
            # nameless again, so it resolves through its operands as
            # there.  The learners' bodies hold one call of a jitted
            # grower and no operation of their own that this could hide
            op = op.rpartition("/")[2] if rec["located"] else None
        named[name] = op
        ph = phases.leaf_phase(op) if op else None
        if rec["opcode"] == "fusion":
            for attr, comp in rec["called"]:
                root = instrs.get(roots.get(comp, ""))
                if attr == "calls" and root and root["op_name"]:
                    ph = phases.leaf_phase(root["op_name"]) or ph
        if ph is None and op and op.startswith("jit("):
            ph = UNSCOPED
        own[name] = ph

    resolved: Dict[str, Optional[str]] = {}

    def producer_phase(name: str) -> Optional[str]:
        """Own phase, else the first operand's producer's, followed
        until one is named, else the phase of whatever calls this
        computation (a loop's boundary copies belong to the loop)."""
        chain: List[str] = []
        cur: Optional[str] = name
        ph = None
        while cur is not None:
            if cur in resolved:
                ph = resolved[cur]
                break
            chain.append(cur)
            ph = own[cur]
            if ph is not None:
                break
            ops = instrs[cur]["operands"]
            nxt = ops[0] if ops and ops[0] in instrs else None
            if nxt is None or nxt in chain:
                nxt = caller.get(instrs[cur]["comp"])
            cur = nxt if nxt not in chain else None
        for n in chain:
            resolved[n] = ph
        return ph

    def resolve(name: str) -> Optional[str]:
        """``producer_phase``; a copy of one of the program's own
        arguments has no producer, and goes to its first user's."""
        ph = producer_phase(name)
        seen = {name}
        while ph is None and first_user.get(name) not in seen | {None}:
            name = first_user[name]
            seen.add(name)
            ph = producer_phase(name)
        return ph

    out_phases: Dict[str, str] = {}
    inserted: List[str] = []
    by_phase: Dict[str, int] = {}
    inside: Dict[str, set] = {}      # fused computation -> phases in it
    unscoped_names: Dict[str, int] = {}
    for name, rec in instrs.items():
        if rec["comp"] in applied:
            continue
        op = named[name]
        if op and own[name] is not None \
                and rec["opcode"] not in ("parameter", "constant"):
            by_phase[own[name]] = by_phase.get(own[name], 0) + 1
            if own[name] == UNSCOPED:
                unscoped_names[op] = unscoped_names.get(op, 0) + 1
        if rec["comp"] in fused:
            if op and own[name] is not None:
                inside.setdefault(rec["comp"], set()).add(own[name])
            continue
        if rec["opcode"] in _NO_EVENT:
            continue
        ph = resolve(name)
        if ph is None:
            continue
        out_phases[name] = ph
        if not op:
            inserted.append(name)
    mixed = {}
    for name, ph in out_phases.items():
        others = set().union(*(inside.get(comp, ()) for attr, comp
                               in instrs[name]["called"]
                               if attr == "calls")) - {ph}
        if others:
            mixed[name] = sorted(others)
    unscoped = by_phase.pop(UNSCOPED, 0)
    top = sorted(unscoped_names.items(), key=lambda kv: -kv[1])[:20]
    return {"schema": SCHEMA, "module": parsed["module"],
            "phases": out_phases, "inserted": inserted,
            "ops_scoped": sum(by_phase.values()), "ops_unscoped": unscoped,
            "ops_by_phase": by_phase, "mixed": mixed,
            "unscoped_op_names": [k for k, _ in top]}


_LOC_NAME = re.compile(r'loc\("([^"]+)"')


def stale_phases(compiled_text: str, lowered_text: str) -> List[str]:
    """The phases that only one of the two texts holds: none for an
    executable compiled from this lowering (``as_text(debug_info=True)``).
    The persistent cache's key leaves debug info out, so it may serve an
    executable compiled from the same operations under OTHER scopes; a
    scope renamed, removed, added or refined since then shows here (one
    moved between two phases that both sides hold does not)."""
    ours = {phases.leaf_phase(n) for n in _LOC_NAME.findall(lowered_text)}
    theirs = {phases.leaf_phase(n) for n in _OP_NAME.findall(compiled_text)}
    return sorted((ours ^ theirs) - {None})


# ---------------------------------------------------------------------------
# device events -> phases

def instruction_name(event_name: str) -> str:
    """``%sort.890 = (...) sort(...)`` -> ``sort.890``; a backend that
    names events by the bare instruction keeps it."""
    m = _INSTR.match(event_name)
    if m is not None:
        return m.group(2)
    return event_name.split(" ", 1)[0].lstrip("%")


def self_times(events: Iterable[Event]) -> List[Tuple[str, int, int]]:
    """``[(name, start_ns, self_ns)]``: each event's duration less what
    the events nested inside it on the same line cover."""
    out: List[List[Any]] = []
    stack: List[Tuple[int, int]] = []        # (index into out, end_ns)
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            covered = min(end, stack[-1][1]) - start
            out[stack[-1][0]][2] -= max(covered, 0)
        out.append([name, start, dur])
        stack.append((len(out) - 1, end))
    return [(n, s, max(d, 0)) for n, s, d in out]


def _gaps(events: Sequence[Event]) -> Tuple[int, List[Tuple[int, int]]]:
    """(busy_ns, [(gap_start_ns, gap_ns)]) of the union of intervals."""
    busy, gaps = 0, []
    cur_s = cur_e = None
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if cur_e is None or start > cur_e:
            if cur_e is not None:
                busy += cur_e - cur_s
                gaps.append((cur_e, start - cur_e))
            cur_s, cur_e = start, start + dur
        else:
            cur_e = max(cur_e, start + dur)
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


def name_gaps(gaps: Sequence[Tuple[int, int]], host_spans: Sequence[Event],
              k: int = 10) -> List[List[Any]]:
    """``[[span, seconds]]`` for the k longest gaps, each named by the
    innermost ``lgbt:`` host span that covers its start
    (``outside_spans`` where none does)."""
    out = []
    for at, length in sorted(gaps, key=lambda g: -g[1])[:k]:
        who, who_start = "outside_spans", -1
        for name, s, d in host_spans:
            if s <= at < s + d and s > who_start:
                who, who_start = name, s
        out.append([who, length / 1e9])
    return out


def _module_of(modules: Sequence[Event]):
    """start -> the program running then (``XLA Modules`` event names
    read ``jit_step_fn(1234)``: the id goes)."""
    mods = sorted(modules, key=lambda e: e[1])
    starts = [m[1] for m in mods]

    def lookup(at: int) -> str:
        i = bisect.bisect_right(starts, at) - 1
        if i >= 0 and at < mods[i][1] + mods[i][2]:
            return mods[i][0].split("(", 1)[0]
        return ""
    return lookup


def reduce_events(ops: Sequence[Event], modules: Sequence[Event],
                  maps: Sequence[Dict[str, Any]], host_spans=(),
                  rounds: int = 1) -> Dict[str, Any]:
    """One device's ``XLA Ops`` events reduced by the phase maps (module
    docstring).  ``maps`` are ``phase_map`` dicts; an event is looked up
    in the maps of the module it ran in, then (a trace without a modules
    line) in all of them."""
    rounds = max(int(rounds), 1)
    by_module: Dict[str, List[Dict[str, Any]]] = {}
    for m in maps:
        by_module.setdefault(m.get("module", ""), []).append(m)
    inserted = {id(m): set(m.get("inserted", ())) for m in maps}
    blur: Dict[Tuple[str, str], int] = {}    # (counted under, also holds)
    module_of = _module_of(modules)
    acc: Dict[str, List[Any]] = {}   # phase -> [ns, inserted ns, n, by instr]
    loose: Dict[str, int] = {}
    loose_ns = 0
    for name, start, self_ns in self_times(ops):
        instr = instruction_name(name)
        hit = None
        for m in by_module.get(module_of(start), None) or maps:
            ph = m["phases"].get(instr)
            if ph is not None:
                hit = (ph, instr in inserted[id(m)])
                for other in m.get("mixed", {}).get(instr, ()):
                    blur[(ph, other)] = blur.get((ph, other), 0) + self_ns
                break
        if hit is None:
            loose_ns += self_ns
            loose[instr] = loose.get(instr, 0) + self_ns
            continue
        row = acc.setdefault(hit[0], [0, 0, 0, {}])
        row[0] += self_ns
        row[1] += self_ns if hit[1] else 0
        row[2] += 1
        row[3][instr] = row[3].get(instr, 0) + self_ns
    busy, gaps = _gaps(ops)
    window = (max(s + d for _, s, d in ops) - min(s for _, s, _ in ops)) \
        if ops else 0
    per = 1e6 * rounds
    order = {p: i for i, p in enumerate(phases.ROUND_PHASES)}
    names = sorted(acc, key=lambda p: (order.get(p, len(order)), p))
    top_loose = sorted(loose.items(), key=lambda kv: -kv[1])[:10]
    return {
        "schema": SCHEMA, "rounds": rounds,
        "busy_s": busy / 1e9, "window_s": window / 1e9,
        "phases": {p: {"ms_per_round": acc[p][0] / per,
                       "inserted_ms_per_round": acc[p][1] / per,
                       "events": acc[p][2],
                       "top": [[n, ns / per] for n, ns in sorted(
                           acc[p][3].items(), key=lambda kv: -kv[1])[:3]]}
                   for p in names},
        "unattributed": {"ms_per_round": loose_ns / per,
                         "top": [[n, ns / per] for n, ns in top_loose]},
        # fusions across two phases: whole under the first, though they
        # also hold operations of the second (an upper bound on the blur)
        "mixed": [[a, b, ns / per] for (a, b), ns in sorted(
            blur.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": name_gaps(gaps, host_spans),
    }


# ---------------------------------------------------------------------------
# a trace directory

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def find_xplane(trace_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def load_maps(paths: Iterable[str]) -> List[Dict[str, Any]]:
    out = []
    for path in paths:
        with open(path) as fh:
            out.append(json.load(fh))
    return out


def load_planes(path: str) -> Dict[str, Any]:
    """``{"devices": {ordinal: {"ops": [...], "modules": [...]}},
    "host": [lgbt: spans]}`` from one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    prof = ProfileData.from_file(path)
    out: Dict[str, Any] = {"devices": {}, "host": []}
    for plane in prof.planes:
        dev = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev is not None and line.name in ("XLA Ops", "XLA Modules"):
                key = "ops" if line.name == "XLA Ops" else "modules"
                out["devices"].setdefault(
                    int(dev.group(1)), {"ops": [], "modules": []})[key] = [
                    (e.name, int(e.start_ns), int(e.duration_ns))
                    for e in line.events]
            elif dev is None:
                out["host"] += [
                    (e.name, int(e.start_ns), int(e.duration_ns))
                    for e in line.events
                    if e.name.startswith(HOST_SPAN_PREFIX)]
    return out


def reduce_dir(trace_dir: str, rounds: int = 1,
               map_paths: Optional[Iterable[str]] = None
               ) -> Optional[Dict[str, Any]]:
    """Reduce the newest window under ``trace_dir`` by the phase maps
    ``map_paths`` (every ``phase_map.*.json`` there when None) and write
    ``device_phases.json`` there.  The first device's phases are the
    report's; every device's busy and window seconds and its phases' ms a
    round are listed.  A
    backend whose trace has no ``/device:TPU:n`` plane (the CPU) gets a
    report with no phases.  None when there is no trace to read."""
    path = find_xplane(trace_dir)
    if path is None:
        return None
    planes = load_planes(path)
    maps = load_maps(sorted(glob.glob(os.path.join(trace_dir,
                                                 MAP_FILE.format("*"))))
                     if map_paths is None else map_paths)
    devices = planes["devices"]
    per_device = {}
    report = None
    for ordinal in sorted(devices):
        r = reduce_events(devices[ordinal]["ops"], devices[ordinal]["modules"],
                          maps, planes["host"], rounds)
        # a sharded round: the exchange phases hold each shard's wait
        # for the slowest, so they differ by device where the rest agrees
        per_device[str(ordinal)] = {
            "busy_s": r["busy_s"], "window_s": r["window_s"],
            "phases_ms_per_round": {p: v["ms_per_round"]
                                    for p, v in r["phases"].items()}}
        report = report or r
    if report is None:
        report = reduce_events([], [], maps, planes["host"], rounds)
    report["devices"] = per_device
    report["trace"] = os.path.relpath(path, trace_dir)
    report["programs"] = sorted({m.get("program", m.get("module", ""))
                                for m in maps})
    report["host_spans"] = len(planes["host"])
    from ..utils import diskguard
    diskguard.write_text(os.path.join(trace_dir, REPORT_FILE),
                         json.dumps(report, indent=1), sink="trace")
    return report


def render(report: Dict[str, Any]) -> str:
    """The report as a table (``obs-report --device-trace``)."""
    busy_ms = 1e3 * report["busy_s"] / max(report["rounds"], 1)
    lines = [f"device trace: {report['rounds']} round(s), busy "
             f"{report['busy_s']:.6f} s of {report['window_s']:.6f} s "
             f"by the device's clock ({busy_ms:.1f} ms a round)",
             f"{'phase':<20}{'ms/round':>12}{'inserted':>12}{'share':>9}"
             f"{'events':>9}"]
    rows = [(p, v["ms_per_round"], v["inserted_ms_per_round"], v["events"])
            for p, v in report["phases"].items()]
    rows.append(("unattributed", report["unattributed"]["ms_per_round"],
                 0.0, len(report["unattributed"]["top"])))
    for p, ms, ins, n in rows:
        share = 100.0 * ms / busy_ms if busy_ms else 0.0
        lines.append(f"{p:<20}{ms:>12.3f}{ins:>12.3f}{share:>8.2f}%{n:>9}")
    total = sum(r[1] for r in rows)
    lines.append(f"{'sum':<20}{total:>12.3f}")
    for p, v in report["phases"].items():
        if busy_ms and v["ms_per_round"] >= 0.01 * busy_ms:
            lines.append(f"  {p}: " + ", ".join(
                f"{n} {ms:.1f}" for n, ms in v.get("top", ())))
    for a, b, ms in report.get("mixed", ()):
        lines.append(f"  fused: {ms:.3f} ms/round under {a} also holds {b}")
    for name, ms in report["unattributed"]["top"]:
        lines.append(f"  unattributed {name}: {ms:.3f} ms/round")
    for who, secs in report["idle_gaps"]:
        lines.append(f"  idle gap {1e3 * secs:.3f} ms during {who}")
    return "\n".join(lines)
