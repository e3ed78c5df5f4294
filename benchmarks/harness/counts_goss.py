"""The necessary work of a GOSS round, whatever implements it.

``goss_sample_work`` is the count function of ``goss_sample_roofline``
(``layer_metrics/goss_sample_roofline.json``).  ``harness/counts.py`` is
the yardstick's and is not edited: a GOSS kind calls ``bind`` with the
run's row count and enters the result in ``counts.COUNT_FUNCTIONS`` under
``goss_sample_work`` before the readers run (``kinds/train_goss.py``).

The select's necessary work a round: each real row's score read once (4
bytes) and its kept mask and factor written (8 bytes); bytes-bound, the
one compare a row against the int8 peak negligible.  Finding the
``top_cnt``-th score exactly may take more passes than one (the kernel
takes 8 over the keys and up to 6 over the tied rows' indices); that is
the implementation's, and the share says how far it is from one pass.

``kept_rows``: the rows a sampled round's histograms visit.  The
yardstick's ``histogram_work`` charges the root pass ``rows`` rows and
every split its smaller child's; in a GOSS round the root holds the KEPT
rows, about ``(top_rate + other_rate) N``, which its tree's root count
says exactly.  A GOSS kind hands that, averaged over the traced trees,
to ``hist_roofline`` and ``train_step_mfu`` as ``rows``, as a sparse kind
hands them the stored entries a row as ``features``
(``counts_sparse.py``); ``round_work``'s gradient and score passes are
then counted over the kept rows too, which can only read low.
"""

from __future__ import annotations

from . import counts

BYTES_PER_ROW = 12


def bind(num_data: int):
    """``goss_sample_work`` for a run of ``num_data`` real rows, in the
    readers' signature ``(trees, rows, features)``; a round a traced
    tree."""
    def goss_sample_work(trees, rows: int, features: int) -> dict:
        rounds = len(trees)
        return {"rows": num_data * rounds,
                "bytes": num_data * BYTES_PER_ROW * rounds,
                "ops": num_data * rounds}
    return goss_sample_work


def kept_rows(trees) -> int:
    """The mean root count of ``trees`` (``dump_model`` form)."""
    return int(round(sum(counts.node_count(t["tree_structure"])
                         for t in trees) / len(trees)))
