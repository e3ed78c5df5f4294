"""Readings for the limits of a sparse cell's comparison, at the cell's
own size on the chip: the plain sparse reference put in the program's
place.  ``control.py``'s twin for kind ``train_sparse`` (that file reads
the dense binary cells and is not edited).

    python3 benchmarks/control_sparse.py --workload <cell> --seeds 1,2,3 \
        --modes float32:1,bfloat16:2,half_rows:1,zero_bin_dropped:1,\
decode_off_by_one:1,table_bins80:1

For every seed it makes the cell's matrix, bins it with plain bound tables
of its own (``reference_sparse.own_bounds``), grows the cell's first trees
with ``reference_sparse.grow`` in the given mode, and has the comparison
that decides ``correct`` (``correct_sparse.compare``) read them.
``float32`` is the reference against itself (what a sound run could read
at best); ``bfloat16`` the control (gradients, hessians, leaf values and
scores in the nearest precision below the configuration's; its first
tree is exact, since +-0.5 and 0.25 are bfloat16 numbers: two trees at
least); ``half_rows`` the second half of the rows left out of every sum;
``zero_bin_dropped`` and ``decode_off_by_one`` the two faults of the
mechanism this kind of cell is there for, in the reference's own terms
(a bundle member's default bin left unreconstructed: the bin that holds
zero never gains the leaf's total less the stored sums; a member decoded
one slot off at the partition: rows sent by a bin read one too low);
``table_bins80`` sound float32 trees on a bound table with four fifths of
the bins.  One JSON line per seed and mode; nothing here is a metric, and
the benchmark's own runs never call this.  A mode may say how many trees
it grows (``bfloat16:2``; three where it does not); ``--leaves N`` stops
every grown tree at N leaves.  ``--rehearse`` runs the cell's toy size on
any backend.  A further sparse cell needs nothing new here.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from harness import (correct_sparse, data_sparse, device,  # noqa: E402
                     reference_sparse, result)

# mode -> (precision of reference_sparse.grow, fault planted in it)
MODES = {"float32": ("float32", None), "bfloat16": ("bfloat16", None),
         "half_rows": ("float32", "half_rows"),
         "zero_bin_dropped": ("float32", "zero_bin_dropped"),
         "decode_off_by_one": ("float32", "decode_off_by_one"),
         "table_bins80": ("float32", None)}


def read(cfg, limits, rows_n, seed, modes, n_trees=3):
    X, y = data_sparse.make(cfg["data"], rows_n, seed)
    max_bin = int(cfg["max_bin"])
    sound = reference_sparse.own_bounds(X, max_bin, seed)
    sound_rows = reference_sparse.SparseRows(X, y, sound, max_bin)
    out = []
    for mode in modes:
        mode, _, trees_of_mode = mode.partition(":")
        precision, fault = MODES[mode]
        t0 = time.time()
        bounds, rows = sound, sound_rows
        if mode == "table_bins80":
            bounds = reference_sparse.own_bounds(X, max_bin * 4 // 5, seed)
            rows = reference_sparse.SparseRows(X, y, bounds, max_bin)
        trees, losses = reference_sparse.grow(
            rows, bounds, cfg, int(trees_of_mode or n_trees),
            precision=precision, fault=fault)
        compared, notes = correct_sparse.compare(rows, bounds, trees, losses,
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
            print(f"benchmarks/control_sparse.py: {e}", file=sys.stderr)
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
