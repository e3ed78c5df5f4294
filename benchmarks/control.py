"""Readings for the limits of a training cell's comparison, at the cell's
own size on the chip: the reference put in the program's place.

    python3 benchmarks/control.py --workload <cell> --seeds 1,2,3 \
        --modes float32,bfloat16:2,half_batch:1,state_unchanged,leaf_altered:1,\
table_twentieth_sample:1,table_bins80:1

For every seed it makes the cell's data, bins it with plain quantile
bounds of its own, grows the cell's first trees with ``reference.grow``
in the given mode, and has the same comparison that decides ``correct``
(``correct.compare``) read them.  ``float32`` is the reference against
itself (what a sound run could read at best), ``bfloat16`` the control
(the nearest precision below the configuration's), the others the faults
a training cell can have.  ``table_twentieth_sample`` and
``table_bins80`` are the control for the bound table (``bin_cdf_gap``): the reference's plain
quantile table made from a twentieth of the sample (10,000 rows for
200,000), and one with four fifths
of the configuration's bins, with float32 trees grown on it, so that the
table alone is at fault.  One JSON line per seed and mode; nothing here
is a metric, and the benchmark's own runs never call this.  A mode may
say how many trees it grows (``bfloat16:2``; three where it does not).
``--rehearse`` runs the cell's toy size on any backend.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from harness import correct, data, device, reference, result  # noqa: E402

MODES = {"float32": ("float32", None), "bfloat16": ("bfloat16", None),
         "half_batch": ("float32", "half_batch"),
         "state_unchanged": ("float32", "state_unchanged"),
         "leaf_altered": ("float32", "leaf_altered"),
         "table_twentieth_sample": ("float32", None),
         "table_bins80": ("float32", None)}
# a degraded bound table: (share of the configuration's bins, share of the
# sound table's sample)
TABLES = {"table_twentieth_sample": (1.0, 0.05), "table_bins80": (0.8, 1.0)}
SAMPLE = 200000         # rows the sound table is made from, as FindBin's


def read(cfg, limits, rows_n, seed, modes, n_trees=3):
    X, y = data.make(cfg["data"], rows_n, seed)
    max_bin = int(cfg["max_bin"])
    sound = reference.quantile_bounds(X, max_bin, seed, sample=SAMPLE)
    sound_rows = reference.Rows(X, y, sound)
    out = []
    for mode in modes:
        mode, _, trees_of_mode = mode.partition(":")
        precision, fault = MODES[mode]
        t0 = time.time()
        if mode in TABLES:
            bins, sample = TABLES[mode]
            bounds = reference.quantile_bounds(
                X, int(max_bin * bins), seed,
                sample=int(min(SAMPLE, rows_n) * sample))
            rows = reference.Rows(X, y, bounds)
        else:
            bounds, rows = sound, sound_rows
        trees, losses = reference.grow(rows, bounds, cfg,
                                       int(trees_of_mode or n_trees),
                                       precision=precision, fault=fault)
        compared, notes = correct.compare(rows, bounds, trees, losses, cfg,
                                          limits)
        out.append({"seed": seed, "mode": mode,
                    "correct": result.verdict(compared),
                    "values": {k: v["value"] for k, v in compared.items()},
                    "seconds": round(time.time() - t0, 1), "notes": notes})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--modes", default=",".join(MODES))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    with open(os.path.join(HERE, "workloads", args.workload + ".json")) as f:
        cell = json.load(f)
    with open(os.path.join(HERE, "configs", cell["config"] + ".json")) as f:
        cfg = json.load(f)
    if not args.rehearse:
        try:
            device.find_chip(int(cell["chips"]))
        except device.NoChip as e:
            print(f"benchmarks/control.py: {e}", file=sys.stderr)
            return 2
    rows_n = int(cell["rehearse"]["num_data"] if args.rehearse
                 else cfg["num_data"])
    for seed in (int(s) for s in args.seeds.split(",")):
        for line in read(cfg, cell.get("limits", {}), rows_n, seed,
                         args.modes.split(",")):
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
