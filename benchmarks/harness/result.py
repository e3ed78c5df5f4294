"""Percentile, the result's last line, and the lines of compared numbers."""

from __future__ import annotations

import json
import math
import sys


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation between order
    statistics, over all values."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of nothing")
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def within(c: dict) -> bool:
    """A compared number is a finite value at or under its limit."""
    v = c["value"]
    return v is not None and math.isfinite(v) and v <= c["limit"]


def verdict(compared: dict) -> bool:
    """``compared`` maps a short name to {"value", "limit"}: correct when
    something was compared and every number is within its limit."""
    return bool(compared) and all(within(c) for c in compared.values())


def last_line(*, correct, attempted, failed, metrics, device, compared,
              breakdown=None, extra=None) -> str:
    """The one JSON object a run ends with.  ``compared`` comes last."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed),
           "metrics": {k: {"value": v["value"], "unit": v["unit"]}
                       for k, v in metrics.items()},
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if extra:
        out.update(extra)
    out["compared"] = compared
    return json.dumps(out)


def print_compared(compared: dict, correct: bool) -> None:
    """Each number compared beside its limit, as the last lines of
    standard error."""
    for name, c in compared.items():
        state = "ok" if within(c) else "OVER"
        print(f"compared {name}: value {c['value']!r} "
              f"limit {c['limit']!r} {state}",
              file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
