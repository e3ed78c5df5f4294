"""Per-platform device capability table for roofline attribution.

``devprof`` (obs/devprof.py) turns sampled per-program device timings
into achieved-FLOP/s and percent-of-roofline gauges; that math needs
peak compute and memory-bandwidth numbers for the device actually
running.  This module is that table — small, static, and overridable:

- TPU entries are the vendor-published per-chip peak dense (bf16)
  FLOP/s and HBM bandwidth.  ``jax.local_devices()[0].device_kind``
  strings ("TPU v4", "TPU v5 lite", ...) select the row by substring.
- a device that is not in the table (the CPU backend among them) has no
  peak: ``capabilities`` returns None for both numbers and the roofline
  column stays empty rather than rendering against a guess.
- ``LIGHTGBM_TPU_PEAK_FLOPS`` / ``LIGHTGBM_TPU_PEAK_BYTES_PER_SEC``
  env vars override both numbers for any platform (measured-peak
  calibration beats any table).

Roofline caveats (docs/OBSERVABILITY.md §Device-time attribution): the
FLOP counts come from XLA's static cost analysis (pre-fusion estimates),
the peaks are dense-matmul numbers no histogram scatter reaches, and the
sampled timings include dispatch queueing — so ``roofline_pct`` is a
comparative instrument ("program A sits at 4%, program B at 40%"), not
an absolute utilization measurement.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

ENV_PEAK_FLOPS = "LIGHTGBM_TPU_PEAK_FLOPS"
ENV_PEAK_BYTES = "LIGHTGBM_TPU_PEAK_BYTES_PER_SEC"

# device_kind substring (lowercase) -> (peak FLOP/s, peak HBM bytes/s),
# per chip.  Longest match wins, so "tpu v5p" beats "tpu v5".
_TABLE: Dict[str, tuple] = {
    "tpu v2": (45.0e12, 700.0e9),
    "tpu v3": (123.0e12, 900.0e9),
    "tpu v4": (275.0e12, 1228.0e9),
    "tpu v5 lite": (197.0e12, 819.0e9),
    "tpu v5e": (197.0e12, 819.0e9),
    "tpu v5p": (459.0e12, 2765.0e9),
    "tpu v5": (459.0e12, 2765.0e9),
    "tpu v6e": (918.0e12, 1640.0e9),
}


def _env_float(name: str) -> Optional[float]:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return None
    try:
        v = float(raw)
    except ValueError:
        from ..utils import log
        log.warning("%s=%r is not a number; ignoring", name, raw)
        return None
    return v if v > 0 else None


def capabilities(device: Any = None) -> Dict[str, Any]:
    """Capability row for ``device`` (default: first local device):
    ``{"platform", "device_kind", "peak_flops", "peak_bytes_per_sec",
    "source"}``.  ``source`` says where the peaks came from (``env`` /
    ``table`` / ``unknown``); unknown platforms get None peaks rather
    than a guess."""
    platform = "unknown"
    kind = "unknown"
    if device is None:
        try:
            import jax
            device = jax.local_devices()[0]
        except Exception:  # pragma: no cover - no backend at all
            device = None
    if device is not None:
        platform = str(getattr(device, "platform", "unknown"))
        kind = str(getattr(device, "device_kind", platform))
    flops = bw = None
    source = "unknown"
    key = kind.lower()
    best = ""
    for sub in _TABLE:
        if sub in key and len(sub) > len(best):
            best = sub
    if best:
        flops, bw = _TABLE[best]
        source = "table"
    env_flops = _env_float(ENV_PEAK_FLOPS)
    env_bw = _env_float(ENV_PEAK_BYTES)
    if env_flops is not None or env_bw is not None:
        flops = env_flops if env_flops is not None else flops
        bw = env_bw if env_bw is not None else bw
        source = "env"
    return {"platform": platform, "device_kind": kind,
            "peak_flops": flops, "peak_bytes_per_sec": bw,
            "source": source}


def roofline(flops: Optional[float], bytes_accessed: Optional[float],
             seconds: float,
             caps: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Pure roofline math for one sampled execution:

    - ``achieved_flops``: flops / seconds (None without a flop count);
    - ``roofline_pct``: 100 * (roofline-optimal time / measured time),
      where the optimal time is ``max(flops/peak_flops,
      bytes/peak_bandwidth)`` — the classic roofline bound: a program is
      limited by whichever of compute and memory traffic takes longer.

    Any missing ingredient (no cost counts, unknown peaks, non-positive
    measurement) yields None for the affected field instead of a made-up
    number."""
    if seconds is None or seconds <= 0.0:
        return {"achieved_flops": None, "roofline_pct": None}
    caps = caps if caps is not None else capabilities()
    achieved = (float(flops) / seconds) if flops else None
    peak_f = caps.get("peak_flops")
    peak_b = caps.get("peak_bytes_per_sec")
    bounds = []
    if flops and peak_f:
        bounds.append(float(flops) / float(peak_f))
    if bytes_accessed and peak_b:
        bounds.append(float(bytes_accessed) / float(peak_b))
    pct = (100.0 * max(bounds) / seconds) if bounds else None
    return {"achieved_flops": achieved, "roofline_pct": pct}
