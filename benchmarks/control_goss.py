"""Readings for the limits of a GOSS cell's comparison: the plain
reference grown in the program's place, sound and with planted faults.
``control_sparse.py``'s twin for kind ``train_goss``.

    python3 benchmarks/control_goss.py --workload allstate12m-goss-train \
        --seeds 1,2 --modes float32,bfloat16,ties_overshoot,\
no_amplification,wrong_iteration [--leaves N] [--rehearse]

For every seed it makes the cell's matrix, bins it with plain bound tables
of its own (``reference_sparse.own_bounds``), grows the cell's warm-up
trees with ``reference_sparse.grow``, the first ``goss.warmup`` on every
row and the sampled ones on the rows of its own GOSS draw
(``reference_goss.draw`` on the gradients ``grow`` makes, and
``reference_goss.GossRows`` summing the kept rows), and has the
comparison that decides ``correct`` (``correct_goss.compare``) read them
with their losses and samples.  ``float32`` is the reference against
itself; ``bfloat16`` the control (gradients, hessians, leaf values and
scores in the nearest precision below the configuration's); the three
faults of the draw this cell is there for: ``ties_overshoot`` (a ``>=``
threshold keeps every row tied with the ``top_cnt``-th),
``no_amplification`` (the drawn rows' gradients left as they are),
``wrong_iteration`` (the draw keyed by the next iteration).  One JSON
line per seed and mode; nothing here is a metric, and the benchmark's own
runs never call this.  ``--leaves N`` stops every grown tree at N
leaves; ``--rehearse`` runs the cell's toy size on any backend.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from harness import (correct_goss, data_sparse, device,  # noqa: E402
                     reference_goss, reference_sparse, result)

# mode -> (precision of reference_sparse.grow, fault planted in the draw)
MODES = {"float32": ("float32", None), "bfloat16": ("bfloat16", None),
         "ties_overshoot": ("float32", "ties_overshoot"),
         "no_amplification": ("float32", "no_amplification"),
         "wrong_iteration": ("float32", "wrong_iteration")}


def grow(rows, bounds, cfg, n_trees: int, precision="float32", fault=None):
    """``reference_sparse.grow`` with a GOSS draw: ``(trees, losses,
    statuses)``, a status array for every sampled tree.  The draw sits
    where ``grow`` takes its gradients (its module's ``_objective``),
    for the call and no longer."""
    goss = cfg["goss"]
    warm = int(goss["warmup"])
    sampled = reference_goss.GossRows(rows)
    statuses = []
    plain = reference_sparse._objective
    calls = [0]

    def objective(score, rows_, sig, low=False):
        loss, g, h = plain(score, rows_, sig, low)
        it = calls[0]
        calls[0] += 1
        sampled.kept = None
        if warm <= it < n_trees:
            kept, factor, status = reference_goss.draw(g, h, goss, it, fault)
            sampled.kept = kept
            statuses.append(status)
            g, h = g * factor, h * factor
        return loss, g, h

    reference_sparse._objective = objective
    try:
        trees, losses = reference_sparse.grow(sampled, bounds, cfg, n_trees,
                                              precision=precision)
    finally:
        reference_sparse._objective = plain
    return trees, losses, statuses


def read(cfg, limits, rows_n, seed, modes, n_check=3):
    X, y = data_sparse.make(cfg["data"], rows_n, seed)
    max_bin = int(cfg["max_bin"])
    bounds = reference_sparse.own_bounds(X, max_bin, seed)
    rows = reference_sparse.SparseRows(X, y, bounds, max_bin)
    warm = int(cfg["goss"]["warmup"])
    out = []
    for mode in modes:
        precision, fault = MODES[mode]
        t0 = time.time()
        trees, losses, statuses = grow(rows, bounds, cfg, warm + n_check,
                                       precision, fault)
        compared, notes = correct_goss.compare(
            rows, bounds, trees, losses[warm:], statuses, cfg, limits)
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
            print(f"benchmarks/control_goss.py: {e}", file=sys.stderr)
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
