"""Readings for the limits of a ranking cell's comparison, at the cell's
own size on the chip: the plain ranking reference put in the program's
place.  ``control.py``'s twin for kind ``train_rank`` (that file reads the
binary cells and is not edited).

    python3 benchmarks/control_rank.py --workload <cell> --seeds 1,2,3 \
        --modes float32:1,bfloat16:1,no_normaliser:1,no_discount:1,\
half_queries:1,table_bins80:1

For every seed it makes the cell's data, bins it with plain bound tables
of its own (``reference_rank.own_bounds``), grows the cell's first trees
with ``reference.grow`` over the ranking objective in the given mode, and
has the comparison that decides ``correct`` (``correct_rank.compare``)
read them.  ``float32`` is the reference against itself (what a sound run
could read at best); ``bfloat16`` the control (scores, pair arithmetic,
histogram products and leaf values in the nearest precision below the
configuration's); ``no_normaliser`` the ``0.01 + |gap|`` division left
out of the lambdas; ``no_discount`` the discount term dropped from
delta-NDCG; ``half_queries`` the second half of the queries left out of
every sum; ``table_bins80`` sound float32 trees on a bound table with four
fifths of the bins.  One JSON line per seed and mode; nothing here is a
metric, and the benchmark's own runs never call this.  A mode may say how
many trees it grows (``bfloat16:2``; three where it does not);
``--leaves N`` stops every grown tree at N leaves (the plain reference
makes a pass over all rows a split: a 255-leaf tree at 3.77M x 136 is
minutes of the chip, and a fault shows in a tree's first splits).
``--rehearse`` runs the cell's toy size on any backend.  A further
ranking cell needs nothing new here.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from harness import (correct_rank, data_rank, device, reference,  # noqa: E402
                     reference_rank, result)

# mode -> (precision of reference.grow, fault planted in the objective)
MODES = {"float32": ("float32", None), "bfloat16": ("bfloat16", None),
         "no_normaliser": ("float32", "no_normaliser"),
         "no_discount": ("float32", "no_discount"),
         "half_queries": ("float32", "half_queries"),
         "table_bins80": ("float32", None)}


def read(cfg, limits, rows_n, seed, modes, n_trees=3):
    X, y, group = data_rank.make(cfg["data"], rows_n, seed)
    max_bin = int(cfg["max_bin"])
    sound = reference_rank.own_bounds(X, max_bin, seed)
    sound_rows = reference_rank.RankRows(X, y, group, sound, cfg["stated"],
                                         max_bin)
    out = []
    for mode in modes:
        mode, _, trees_of_mode = mode.partition(":")
        precision, fault = MODES[mode]
        t0 = time.time()
        bounds, rows = sound, sound_rows
        if mode == "table_bins80":
            bounds = reference_rank.own_bounds(X, max_bin * 4 // 5, seed)
            rows = reference_rank.RankRows(X, y, group, bounds,
                                           cfg["stated"], max_bin)
        grown_on = rows.keeping(len(group) // 2) \
            if fault == "half_queries" else rows
        reference_rank.bind(fault)
        trees, ndcgs = reference.grow(grown_on, bounds, cfg,
                                      int(trees_of_mode or n_trees),
                                      precision=precision)
        reference_rank.bind()
        compared, notes = correct_rank.compare(rows, bounds, trees, ndcgs,
                                               cfg, limits)
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
    ap.add_argument("--leaves", type=int, default=None)
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
            print(f"benchmarks/control_rank.py: {e}", file=sys.stderr)
            return 2
    rows_n = int(cell["rehearse"]["num_data"] if args.rehearse
                 else cfg["num_data"])
    if args.leaves:
        cfg = dict(cfg, num_leaves=args.leaves)
    for seed in (int(s) for s in args.seeds.split(",")):
        for line in read(cfg, cell.get("limits", {}), rows_n, seed,
                         args.modes.split(",")):
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
