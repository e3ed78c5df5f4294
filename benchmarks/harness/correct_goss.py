"""The comparison that decides ``correct`` for a GOSS cell.

``correct_sparse.py``'s seven numbers, from what ``reference_goss.follow``
returns over the sampled rounds, read by ``correct.numbers_from`` and
``correct_sparse.search_gaps`` (neither edited), and one more:

- ``sample_mismatch``: over the followed rounds, the rows whose status
  (left out, of the top set, drawn and amplified) in the program's
  round differs from the reference's own draw.  Exact: limit 0.  It holds
  the exact top set (a ``>=`` threshold keeps every tie), the stateless
  draw (seed, iteration, row) and the amplification; ``count_mismatch``,
  ``value_gap`` and ``gain_gap`` then hold what the kept rows' sums make
  of them.
"""

from __future__ import annotations

import time

import numpy as np

from . import correct, correct_rank, correct_sparse, reference
from . import reference_goss, reference_sparse

NUMBERS = correct_sparse.NUMBERS + ("sample_mismatch",)


def sample_mismatch(program, ref):
    """Rows whose status differs, summed over the rounds; None where the
    program handed over no status of a round."""
    if program is None or len(program) != len(ref) or not ref:
        return None
    total = 0
    for p, r in zip(program, ref):
        p = np.asarray(p)
        if p.shape != r.shape:
            return None
        total += int((p != r).sum())
    return float(total)


def compare(rows, bounds, trees, program_losses, program_status, cfg,
            limits):
    max_bin = int(cfg["max_bin"])
    faults = reference.check_bounds(bounds, max_bin)
    table_gap, where = correct_rank.bin_table_gap(
        rows, [len(b) for b in bounds], max_bin)
    try:
        followed, statuses = reference_goss.follow(rows, trees, bounds, cfg)
        vals = correct.numbers_from(
            followed, program_losses, np.zeros((1, 1)), rows.n, [1],
            max_bin, faults, float(cfg["learning_rate"]))
        del vals["bin_cdf_gap"]
        vals["gain_gap"], vals["split_gap"] = \
            correct_sparse.search_gaps(followed)
        vals["sample_mismatch"] = sample_mismatch(program_status, statuses)
        kept = [int((s > 0).sum()) for s in statuses]
        notes = f"seconds {followed[0]['spent']}; bin_table_gap at " \
            f"{where}; kept {kept}; losses " \
            + ", ".join(f"{t['loss']:.6f}" for t in followed)
    except ValueError as e:             # not a tree the reference can read
        vals = {k: None for k in NUMBERS}
        vals["bin_table_faults"] = float(faults)
        notes = f"the trees could not be followed: {e}"
    vals["bin_table_gap"] = table_gap
    compared = {k: {"value": vals[k], "limit": limits.get(k, -1.0)}
                for k in NUMBERS}
    return compared, notes


def check_train(X, y, bounds, trees, program_losses, program_status, cfg,
                limits):
    """``trees``: every tree up to the last followed one, the first
    ``cfg["goss"]["warmup"]`` walked; ``program_losses`` and
    ``program_status`` those of the followed rounds."""
    t0 = time.time()
    rows = reference_sparse.SparseRows(X, y, bounds, int(cfg["max_bin"]))
    t1 = time.time()
    compared, notes = compare(rows, bounds, trees, program_losses,
                              program_status, cfg, limits)
    return compared, (f"binning and column counts {t1 - t0:.1f} s, "
                      f"following {time.time() - t1:.1f} s; {notes}")
