"""Per-layer metrics: one small data file each under ``layer_metrics/``,
read here by one of a few reductions.  A reader that finds nothing to
read returns None and the metric is left out of the line."""

from __future__ import annotations

import dataclasses
import json
import os

from . import counts, trace

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS_DIR = os.path.join(os.path.dirname(HERE), "layer_metrics")


@dataclasses.dataclass
class Context:
    trace_dir: str
    traced: tuple            # (first_round, last_round) or None
    traced_trees: list       # dump_model trees of the traced rounds
    rows: int
    features: int
    peaks: dict
    compiles_in_window: int
    peak_bytes: int
    setup_compile_s: float
    chips: int
    # filled by prepare()
    events: list = None      # self-timed events of device 0
    busy_s: float = None
    window_s: float = None
    rounds: int = 0

    def counters(self) -> dict:
        return {"compiles_in_window": self.compiles_in_window,
                "peak_bytes": self.peak_bytes,
                "setup_compile_s": self.setup_compile_s}


def specs(cell_name: str, kind: str):
    out = []
    for fn in sorted(os.listdir(METRICS_DIR)):
        if not fn.endswith(".json"):
            continue
        with open(os.path.join(METRICS_DIR, fn)) as f:
            spec = json.load(f)
        if spec["name"] + ".json" != fn:
            raise ValueError(f"{fn}: holds the metric {spec['name']!r}")
        if "workloads" in spec and cell_name not in spec["workloads"]:
            continue
        if kind not in spec.get("kinds", [kind]):
            continue
        out.append(spec)
    return out


# ---- reductions -----------------------------------------------------------

def _matched_s(spec, ctx):
    if not ctx.events:
        return None
    ns = trace.matching_ns(ctx.events, spec["match"], spec.get("exclude"))
    return ns / 1e9 if ns > 0 else None


def device_ms_per_round(spec, ctx):
    s = _matched_s(spec, ctx)
    if s is None or not ctx.rounds:
        return None
    return 1e3 * s / ctx.rounds


def share_of_window(spec, ctx):
    """``of: idle`` is 100 x (1 - busy / window); else the matched
    events' share of the window."""
    if not ctx.window_s or ctx.busy_s is None:
        return None
    if spec.get("of") == "idle":
        return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
    s = _matched_s(spec, ctx)
    return None if s is None else 100.0 * s / ctx.window_s


def count(spec, ctx):
    v = ctx.counters().get(spec["counter"])
    return None if v is None else v * float(spec.get("scale", 1.0))


def roofline_pct(spec, ctx):
    """The least time the chip could take for the counted work over the
    time it took: the matched events' device time, or the traced window
    (first device event to last, by the device's clock) where the file
    says ``over: window``."""
    if not ctx.traced_trees or not ctx.peaks:
        return None
    work = counts.COUNT_FUNCTIONS[spec["count_function"]](
        ctx.traced_trees, ctx.rows, ctx.features)
    least = counts.least_seconds(work, ctx.peaks)["seconds"]
    took = ctx.window_s if spec.get("over") == "window" \
        else _matched_s(spec, ctx)
    if not took:
        return None
    return 100.0 * least / took


REDUCTIONS = {"device_ms_per_round": device_ms_per_round,
              "share_of_window": share_of_window, "count": count,
              "roofline_pct": roofline_pct}


def prepare(ctx: Context):
    """Load the trace once: device 0's self-timed events, busy seconds
    averaged over the chips used, and the traced window's length: from
    the first device event to the last on the chips used, by the device's
    own clock.  No host clock enters a ``device_trace`` metric; the wait
    between ``start_trace`` and the first round's first operation is the
    harness's own and is left out."""
    if ctx.traced is None:
        return None
    tr = trace.load(ctx.trace_dir)
    if tr is None or not tr["devices"]:
        return None
    first, last = ctx.traced
    ctx.rounds = last - first + 1
    ords = sorted(tr["devices"])[:ctx.chips]
    ctx.window_s = trace.span_ns(
        [e for o in ords for e in tr["devices"][o]]) / 1e9
    ctx.busy_s = sum(trace.busy_ns(tr["devices"][o]) for o in ords) \
        / len(ords) / 1e9
    ctx.events = trace.self_times(tr["devices"][ords[0]])
    return tr


def read_all(cell_name: str, ctx: Context, kind: str):
    tr = prepare(ctx)
    metrics = {}
    for spec in specs(cell_name, kind):
        v = REDUCTIONS[spec["reduce"]](spec, ctx)
        if v is not None:
            metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
    breakdown = None
    if tr is not None:
        raw = tr["devices"][sorted(tr["devices"])[0]]
        breakdown = {"device_ops": trace.top_ops(ctx.events, 10),
                     "idle_gaps": trace.idle_gaps(raw, tr["host"], 10)}
    busy = {"busy_s": ctx.busy_s or 0.0, "window_s": ctx.window_s or 0.0}
    return metrics, breakdown, busy


def rehearse_all(cell_name: str, ctx: Context, say, kind: str):
    """The same readers on a rehearsal: says which found something to
    read, prints no value."""
    try:
        prepare(ctx)
    except Exception as e:
        say(f"rehearsal: no device trace to read here ({type(e).__name__})")
    for spec in specs(cell_name, kind):
        try:
            v = REDUCTIONS[spec["reduce"]](spec, ctx)
        except Exception as e:
            v = None
            say(f"rehearsal: reader {spec['name']} raised {e!r}")
        say(f"rehearsal: reader {spec['name']}: "
            + ("found something to read" if v is not None else "nothing"))
