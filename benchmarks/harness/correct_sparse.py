"""The comparison that decides ``correct`` for a sparse cell.

``correct.py``'s seven numbers with one replaced; five are computed by
``correct.numbers_from`` itself (not edited) from what
``reference_sparse.follow`` returns.  A further sparse cell gives its
limits under ``limits`` in its own file, all seven: a number with no
limit fails.

- ``bin_table_faults``, ``count_mismatch``, ``value_gap``, ``gain_gap``,
  ``split_gap``, ``loss_gap``: as ``correct.py`` says.  ``count_mismatch``
  holds the sparse binning (a stored value to its bin, a row that stores
  nothing to the bin of zero), the bundle encoding and the split member's
  decode at the partition; ``gain_gap`` and ``split_gap`` hold the search
  over original features, the default bins' reconstruction in it.
- ``bin_table_gap`` (for ``bin_cdf_gap``): ``correct_rank.bin_table_gap``,
  by the reference's own count of ALL rows, the zeros that are not stored
  among them.  A one-hot table's columns are discrete (an indicator has
  two values) or continuous; ``bin_cdf_gap`` alone would hold a two-valued
  column to a share of 1 / max_bin.

The program searches its splits in float32, the configuration's stated
precision, and a candidate's gain is the DIFFERENCE of terms G^2 / H of
the node's own size: it is resolved to an ulp of the node's term, not of
the gain.  On a balanced label the term is small beside the gains (G is
near zero); at 0.7% positives and scores starting from zero G is half the
rows and the term is about the node's row count, so a node of 4,000 rows
resolves gains to 5e-4 while its children may have 1e-4 left to gain.
The reference searches in float64.  So ``gain_gap`` and ``split_gap`` are
read beyond ``RESOLUTION`` of the node's term (8 ulp of float32: the
reading at the rehearsal size is 1 ulp): a gain is compared, a chosen
split is held against the best one, and two open nodes' order of growth
is held, each with that much allowed to either side; at a node where the
reference sees no more than that to gain, which split was made (or that
none was) is not compared.  A fault of the search reads a share of the
gain itself and is not hidden by it: PERF.md section 2 has the readings.
A leaf of negatives alone has exactly zero to gain in float64;
``correct_rank.GAIN_SLACK`` is kept beside the resolution for that case
(ROADMAP M12).
"""

from __future__ import annotations

import time

import numpy as np

from . import correct, correct_rank, reference, reference_sparse

GAIN_SLACK = correct_rank.GAIN_SLACK
RESOLUTION = 8 * 2.0 ** -23

NUMBERS = ("bin_table_faults", "bin_table_gap", "count_mismatch",
           "value_gap", "gain_gap", "split_gap", "loss_gap")


def search_gaps(followed):
    """``gain_gap`` and ``split_gap`` as ``correct.numbers_from`` defines
    them, each difference read beyond what a float32 search resolves at
    the nodes it concerns."""
    gain_gap = split_gap = 0.0
    for t in followed:
        S, best, chosen = t["S"], t["best"], t["chosen"]
        tol = GAIN_SLACK + RESOLUTION * t["node_term"]
        leaf_tol = GAIN_SLACK + RESOLUTION * t["leaf_term"]
        n = len(best)
        if n:
            # where the split made is no allowed split of the reference's
            # and the node has nothing resolvable to gain, the program's
            # own gain stands
            nothing = best <= tol
            ref = np.where(nothing | ~np.isfinite(chosen), S["gain"], chosen)
            denom = np.maximum(np.abs(ref), np.median(np.abs(ref)))
            gain_gap = max(gain_gap, float(np.max(np.maximum(
                np.abs(S["gain"] - ref) - tol, 0.0) / denom)))
        for k in range(n):
            if best[k] <= tol[k]:
                continue                    # nothing resolvable to gain
            if not np.isfinite(chosen[k]):
                split_gap = max(split_gap, 1.0)     # a split not allowed
                continue
            split_gap = max(split_gap, max(
                0.0, best[k] - chosen[k] - tol[k]) / best[k])
            # order of growth: while split k was made, no node open then
            # and split later (or never) had resolvably more to gain
            for j in range(k + 1, n):
                if S["parent"][j] < k and np.isfinite(chosen[j]):
                    over = chosen[j] - chosen[k] - tol[k] - tol[j]
                    if over > 0:
                        split_gap = max(split_gap, over / chosen[j])
            for i, lb in enumerate(t["leaf_best"]):
                if t["leaf"]["parent"][i] < k and np.isfinite(lb):
                    over = lb - chosen[k] - tol[k] - leaf_tol[i]
                    if over > 0:
                        split_gap = max(split_gap, over / lb)
    return gain_gap, float(split_gap)


def compare(rows, bounds, trees, program_losses, cfg, limits):
    max_bin = int(cfg["max_bin"])
    faults = reference.check_bounds(bounds, max_bin)
    table_gap, where = correct_rank.bin_table_gap(
        rows, [len(b) for b in bounds], max_bin)
    try:
        followed = reference_sparse.follow(rows, trees, bounds, cfg)
        # counts, values and losses as correct.py reads them (no
        # occupancy: bin_cdf_gap is not one of this cell's numbers)
        vals = correct.numbers_from(
            followed, program_losses, np.zeros((1, 1)), rows.n, [1],
            max_bin, faults, float(cfg["learning_rate"]))
        del vals["bin_cdf_gap"]
        vals["gain_gap"], vals["split_gap"] = search_gaps(followed)
        notes = f"seconds {followed[0]['spent']}; bin_table_gap at {where}; " \
            "losses " + ", ".join(f"{t['loss']:.6f}" for t in followed)
    except ValueError as e:             # not a tree the reference can read
        vals = {k: None for k in NUMBERS}
        vals["bin_table_faults"] = float(faults)
        notes = f"the trees could not be followed: {e}"
    vals["bin_table_gap"] = table_gap
    compared = {k: {"value": vals[k], "limit": limits.get(k, -1.0)}
                for k in NUMBERS}
    return compared, notes


def check_train(X, y, bounds, trees, program_losses, cfg, limits):
    """``X``: the sparse matrix the program was handed; ``bounds``: its
    bound table a REAL column (a column it found trivial: one bin)."""
    t0 = time.time()
    rows = reference_sparse.SparseRows(X, y, bounds, int(cfg["max_bin"]))
    t1 = time.time()
    compared, notes = compare(rows, bounds, trees, program_losses, cfg,
                              limits)
    return compared, (f"binning and column counts {t1 - t0:.1f} s, "
                      f"following {time.time() - t1:.1f} s; {notes}")
