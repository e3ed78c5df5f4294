"""The comparison that decides ``correct`` for a training cell.

What is compared is what the timed path itself produced at the timed
size: the first trees of the same ``lightgbm_tpu.train`` call that the
window then drives (its warm-up rounds: same compiled step, same state),
and the program's own loss after each of them.  The plain reference
(``reference.py``) follows those trees with its own binning of the raw
rows, its own gradients and its own sums.  Numbers, each with a limit of
its own (a cell's file gives the limits under ``limits``; a number with
no limit there fails, so that a cell cannot pass by leaving one out):

- ``bin_table_faults``: features whose bound table breaks a bin table's
  rules (at most max_bin bins, rising, last infinite).  Exact: limit 0.
- ``bin_cdf_gap``: the program's bound table against the reference's own
  quantiles of all the cell's rows.  For every feature and every finite
  bound k (from 0), the share of the rows that the reference's own count
  puts at or under the bound, against (k + 1) / max_bin, which is where
  an equal-count table of max_bin bins over these continuous features
  puts it; the widest gap.  The bound tables are the program's answer
  (every threshold is one of them) and the reference follows the trees
  with them, so this is what holds ``Dataset.construct``'s FindBin: a
  table from a 200,000-row sample reads its sampling noise (about 3e-3),
  one from 10,000 rows four to five times that, one with four fifths of
  the bins 0.2.
- ``count_mismatch``: nodes and leaves whose row count differs from the
  reference's own walk of the raw rows.  Exact: limit 0.  Covers binning
  (value to bin), the partition of rows to leaves, and rows left out.
- ``value_gap``: worst node or leaf: the gap between the program's value
  and the reference's -G/(H + l2) x learning rate, against the larger of
  the reference's value and the median leaf's.
- ``gain_gap``: worst split: the gain the program reports for it against
  the gain the reference computes for the same split, against the larger
  of that and the median split's.  The first tree's first split holds the
  sums of the first gradient over all rows.
- ``split_gap``: the widest gap by which a chosen split's gain lies below
  the best gain the reference sees at that node, as a share of the best;
  and, for the order of growth, by which a split made later (or never)
  beats one made earlier while both were open.
- ``loss_gap``: each step's loss, the program's own ``eval_train`` against
  the reference's, as a share of the reference's; the worst step.
"""

from __future__ import annotations

import time

import numpy as np

from . import reference

NUMBERS = ("bin_table_faults", "bin_cdf_gap", "count_mismatch",
           "value_gap", "gain_gap", "split_gap", "loss_gap")


def bin_cdf_gap(occupancy, n_rows, n_bins_f, max_bin) -> float:
    """``occupancy`` [F, B]: the reference's own count of rows in every
    bin of the table under test; ``n_bins_f`` the table's bins a feature.
    The widest gap, over the finite bounds, between the share of rows at
    or under bound k and (k + 1) / max_bin."""
    cum = np.cumsum(np.asarray(occupancy, np.float64), axis=1) / n_rows
    gap = 0.0
    for f, nb in enumerate(n_bins_f):
        if nb > 1:
            want = np.arange(1, nb) / float(max_bin)
            gap = max(gap, float(np.max(np.abs(cum[f, :nb - 1] - want))))
    return gap


def numbers_from(followed, program_losses, occupancy, n_rows, n_bins_f,
                 max_bin, table_faults, lr):
    count_mismatch = 0
    value_gap = 0.0
    gain_gap = 0.0
    split_gap = 0.0
    for t in followed:
        S, leaf = t["S"], t["leaf"]
        count_mismatch += int((t["ref_leaf_count"] != leaf["count"]).sum())
        count_mismatch += int((t["ref_node_count"] != S["count"]).sum())
        # a leaf's value carries the learning rate; a split node's is its
        # output before the split and before shrinkage, the root's 0 by
        # the model format's convention (so the root is not compared here:
        # its sums are held by the first split's gain, under gain_gap)
        for prog, ref in ((leaf["value"], t["ref_leaf_value"]),
                          (S["value"][1:], t["ref_node_value"][1:] / lr)):
            if len(ref):
                denom = np.maximum(np.abs(ref), np.median(np.abs(ref)))
                value_gap = max(value_gap,
                                float(np.max(np.abs(prog - ref) / denom)))
        best, chosen = t["best"], t["chosen"]
        if len(chosen):
            denom = np.maximum(np.abs(chosen), np.median(np.abs(chosen)))
            gain_gap = max(gain_gap, float(np.max(
                np.abs(S["gain"] - chosen) / denom)))
        for k in range(len(best)):
            if not np.isfinite(chosen[k]) or best[k] <= 0:
                split_gap = max(split_gap, 1.0)     # a split not allowed
            else:
                split_gap = max(split_gap, (best[k] - chosen[k]) / best[k])
        # order of growth: while split k was made, every node open then
        # and split later (or never) may not have had a better gain
        n = len(best)
        for k in range(n):
            for j in range(k + 1, n):
                if S["parent"][j] < k and chosen[j] > chosen[k] > 0:
                    split_gap = max(split_gap,
                                    (chosen[j] - chosen[k]) / chosen[j])
            for i, lb in enumerate(t["leaf_best"]):
                if leaf["parent"][i] < k and np.isfinite(lb) \
                        and lb > chosen[k] > 0:
                    split_gap = max(split_gap, (lb - chosen[k]) / lb)
    loss_gap = None
    if len(program_losses) == len(followed) and followed:
        loss_gap = max(abs(p - t["loss"]) / t["loss"]
                       for p, t in zip(program_losses, followed))
    return {"bin_table_faults": float(table_faults),
            "bin_cdf_gap": bin_cdf_gap(occupancy, n_rows, n_bins_f, max_bin),
            "count_mismatch": float(count_mismatch),
            "value_gap": value_gap, "gain_gap": gain_gap,
            "split_gap": float(split_gap),
            "loss_gap": loss_gap}


def compare(rows, bounds, trees, program_losses, cfg, limits):
    max_bin = int(cfg["max_bin"])
    faults = reference.check_bounds(bounds, max_bin)
    try:
        followed = reference.follow(rows, trees, bounds, cfg)
        vals = numbers_from(followed, program_losses,
                            followed[0]["occupancy"], rows.n,
                            [len(b) for b in bounds], max_bin, faults,
                            float(cfg["learning_rate"]))
        notes = f"seconds {followed[0]['spent']}; losses " \
            + ", ".join(f"{t['loss']:.6f}" for t in followed)
    except ValueError as e:             # not a tree the reference can read
        vals = {k: None for k in NUMBERS}
        vals["bin_table_faults"] = float(faults)
        notes = f"the trees could not be followed: {e}"
    compared = {k: {"value": vals[k], "limit": limits.get(k, -1.0)}
                for k in NUMBERS}
    return compared, notes


def check_train(X32, y, bounds, trees, program_losses, cfg, limits):
    t0 = time.time()
    rows = reference.Rows(X32, y, bounds)
    t1 = time.time()
    compared, notes = compare(rows, bounds, trees, program_losses, cfg,
                              limits)
    return compared, (f"binning {t1 - t0:.1f} s, following "
                      f"{time.time() - t1:.1f} s; {notes}")
