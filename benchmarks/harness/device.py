"""The look for a chip, the table of peaks, and the device's memory."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


class NoChip(RuntimeError):
    pass


def peaks_table() -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    return {k: v for k, v in table.items() if not k.startswith("_")}


def find_chip(chips: int) -> dict:
    """The devices a measured run uses, or ``NoChip``.  Never falls back:
    a CPU, a device kind the peak table lacks, or fewer chips than the
    cell asks for all refuse."""
    import jax
    try:
        devs = jax.devices()
    except Exception as e:                      # backend failed to start
        raise NoChip(f"JAX found no device: {e}") from e
    if devs[0].platform != "tpu":
        raise NoChip(f"platform is {devs[0].platform!r}, not 'tpu': "
                     "a measured run needs the chip (use --rehearse here)")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chip(s), JAX sees "
                     f"{len(devs)}")
    kind = devs[0].device_kind
    table = peaks_table()
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in harness/peaks.json "
                     f"(known: {sorted(table)})")
    return {"platform": "tpu", "kind": kind, "count": chips,
            "peaks": table[kind], "devices": devs[:chips]}


def rehearsal_device() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": 1,
            "peaks": None, "devices": [d]}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device, 0 where the backend does
    not say (the CPU)."""
    peak = 0
    for d in devices:
        try:
            stats = d.memory_stats() or {}
        except Exception:
            stats = {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
