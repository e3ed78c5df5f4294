"""The comparison that decides ``correct`` for a ranking cell.

``correct.py``'s seven numbers with two replaced; the other five are
computed by ``correct.numbers_from`` itself (not edited) from what
``reference.follow`` returns once ``reference_rank.objective`` is bound in
the binary objective's place.  A further ranking cell gives its limits
under ``limits`` in its own file, all seven: a number with no limit fails.

- ``bin_table_faults``, ``count_mismatch``, ``value_gap``, ``gain_gap``,
  ``split_gap``: as ``correct.py`` says.  The first split's gain and the
  leaf sums hold the first gradient (the lambdas at all-zero scores).
- ``ndcg_gap`` (for ``loss_gap``): the program's own ``eval_train`` NDCG
  at the stated cut-offs (1, 3, 5, 10) after each of the compared rounds,
  against the reference's NDCG of its own scores; the widest gap as a
  share of the reference's value.  It holds the scores the program ranks
  by, its ranks under ties and its ideal DCG.
- ``bin_table_gap`` (for ``bin_cdf_gap``): the program's bound table
  against the reference's own count of ALL rows, on continuous and
  discrete columns alike, as a share of the rows.  By the reference's
  sort of a column: D its distinct values, H those with more rows than an
  equal-count bin (N / max_bin: any equal-count table gives such a value
  a bin of its own); per bin, its rows and the rows of its heaviest
  single value.  The widest over the features of
  * D <= max_bin: the rows that share a bin with a heavier value, summed
    over the bins (0 where every value has a bin of its own; a table
    with too few bins for the values reads the merged values' rows);
  * D > max_bin, H = 0 (continuous): ``correct.bin_cdf_gap``, the share
    of rows at or under bound k against (k + 1) / max_bin;
  * D > max_bin, H > 0 (zero-heavy, many-valued counts): the widest bin
    beyond its heaviest single value, less 1 / max_bin: no bin wider
    than an equal-count table allows.
  It does not hold WHERE an equal-count table puts its bounds on a
  column with heavy values (there (k + 1) / max_bin is not where a sound
  table puts bound k), only that no bin is too wide.
"""

from __future__ import annotations

import time

import numpy as np

from . import correct, reference, reference_rank

# Where every document of a leaf has the same gradient-to-hessian ratio
# (round 1: a leaf of label-0 documents alone) every split of it gains
# exactly zero in float64 and one ulp of the parent's term in the program's
# float32 search, which then makes such a split once nothing better is
# left (seen at the rehearsal size: a gain of 7.6e-6).  ``reference.follow``
# reads such a split as forbidden.  So it is told a ``min_gain_to_split``
# lower by this much, which is taken off its gains again; and at a node
# where it then sees no more than this to gain, which of the zero-gain
# splits was made is not compared: the program's own gain stands.
GAIN_SLACK = 1e-4

NUMBERS = ("bin_table_faults", "bin_table_gap", "count_mismatch",
           "value_gap", "gain_gap", "split_gap", "ndcg_gap")


def bin_table_gap(rows, n_bins_f, max_bin: int):
    """The number and, for the notes, the feature and rule that gave it."""
    worst, where = 0.0, "none"
    n = float(rows.n)
    for f, nb in enumerate(n_bins_f):
        count = rows.bin_count[f, :nb]
        beyond = count - rows.bin_heaviest[f, :nb]
        if rows.distinct[f] <= max_bin:
            gap, rule = beyond.sum() / n, "discrete"
        elif rows.heavy_values[f] == 0:
            gap = correct.bin_cdf_gap(count[None, :], n, [nb], max_bin)
            rule = "continuous"
        else:
            gap, rule = max(0.0, beyond.max() / n - 1.0 / max_bin), "heavy"
        if gap > worst:
            worst, where = float(gap), f"feature {f} ({rule})"
    return worst, where


def compare(rows, bounds, trees, program_ndcg, cfg, limits):
    """``program_ndcg``: one list of NDCG values (at the stated cut-offs)
    a compared tree, as the program's ``eval_train`` gave them."""
    max_bin = int(cfg["max_bin"])
    faults = reference.check_bounds(bounds, max_bin)
    n_bins_f = [len(b) for b in bounds]
    table_gap, where = bin_table_gap(rows, n_bins_f, max_bin)
    stated = dict(cfg["stated"])
    stated["min_gain_to_split"] = float(stated["min_gain_to_split"]) \
        - GAIN_SLACK
    try:
        followed = reference.follow(rows, trees, bounds,
                                    dict(cfg, stated=stated))
        for t in followed:
            for key in ("chosen", "best", "leaf_best"):
                t[key] = t[key] - GAIN_SLACK        # -inf stays -inf
            nothing = np.isfinite(t["chosen"]) & (t["best"] <= GAIN_SLACK)
            for key in ("chosen", "best"):
                t[key] = np.where(nothing, t["S"]["gain"], t[key])
            t["leaf_best"] = np.where(t["leaf_best"] <= GAIN_SLACK, -np.inf,
                                      t["leaf_best"])
        vals = correct.numbers_from(
            followed, [], followed[0]["occupancy"], rows.n, n_bins_f,
            max_bin, faults, float(cfg["learning_rate"]))
        ndcg_gap = None
        if len(program_ndcg) == len(followed) and followed:
            ndcg_gap = max(
                float(np.max(np.abs(np.asarray(p) - t["loss"]) / t["loss"]))
                for p, t in zip(program_ndcg, followed))
        vals["ndcg_gap"] = ndcg_gap
        notes = f"seconds {followed[0]['spent']}; bin_table_gap at {where}; " \
            "reference NDCG " + "; ".join(
                ",".join(f"{v:.6f}" for v in t["loss"]) for t in followed)
    except ValueError as e:             # not a tree the reference can read
        vals = {k: None for k in NUMBERS}
        vals["bin_table_faults"] = float(faults)
        notes = f"the trees could not be followed: {e}"
    vals["bin_table_gap"] = table_gap
    compared = {k: {"value": vals[k], "limit": limits.get(k, -1.0)}
                for k in NUMBERS}
    return compared, notes


def check_train(X32, y, group, bounds, trees, program_ndcg, cfg, limits):
    t0 = time.time()
    reference_rank.bind()
    rows = reference_rank.RankRows(X32, y, group, bounds, cfg["stated"],
                                   int(cfg["max_bin"]))
    t1 = time.time()
    compared, notes = compare(rows, bounds, trees, program_ndcg, cfg, limits)
    return compared, (f"binning and column counts {t1 - t0:.1f} s, "
                      f"following {time.time() - t1:.1f} s; {notes}")
