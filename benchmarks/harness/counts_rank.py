"""The work LambdaRank's pair gradient needs, whatever implements it.

``rank_pair_work`` is the count function of ``rank_lambda_roofline``
(``layer_metrics/rank_lambda_roofline.json``).  ``harness/counts.py`` is
the yardstick's and is not edited: a ranking kind calls ``bind`` with the
run's query sizes and labels and enters the result in
``counts.COUNT_FUNCTIONS`` under ``rank_pair_work`` before the readers
run (``kinds/train_rank.py``).  A further ranking cell does the same; a
further pair kernel gets a function of its own here.

Necessary work of one round: the REAL pairs, documents of one query whose
labels differ (``sum over queries of (n^2 - sum_l n_l^2) / 2``), never the
slots a padded size class computes, at ``OPS_PER_PAIR`` operations a pair;
and ``BYTES_PER_DOCUMENT`` a document.  The 24 operations of a pair: the
label comparison 1, the score gap 1, the gain gap 1, the discount gap and
its absolute value 2, their product with the inverse maximum DCG 2, the
normaliser (absolute value, add, divide) 3, the sigmoid (multiply,
exponential, add, divide) 4, the lambda 1, the hessian (subtract, three
multiplies) 4, a comparison with the query's best-is-worst flag 1, and the
four additions into the two documents' sums 4.  The ranks are a sort's
work (n log n a query) and are left out: it is under 1% of the pairs'.
The 16 bytes of a document: score and label read, gradient and hessian
written, 4 each.

``counts.least_seconds`` holds operations against the chip's int8 peak,
the only operation peak ``peaks.json`` has: float32 vector arithmetic
cannot come near it, so the share reads low and can never read over 100%.
"""

from __future__ import annotations

import numpy as np

OPS_PER_PAIR = 24
BYTES_PER_DOCUMENT = 16


def real_pairs(sizes, labels) -> int:
    """Pairs of documents of one query whose labels differ."""
    sizes = np.asarray(sizes, np.int64)
    labels = np.asarray(labels).astype(np.int64)
    query = np.repeat(np.arange(len(sizes)), sizes)
    per_label = np.zeros((len(sizes), int(labels.max()) + 1), np.int64)
    np.add.at(per_label, (query, labels), 1)
    return int(((sizes ** 2 - (per_label ** 2).sum(axis=1)) // 2).sum())


def bind(sizes, labels):
    """``rank_pair_work`` for a run with these queries: the signature the
    readers call, ``(trees, rows, features)``; a round a traced tree."""
    pairs = real_pairs(sizes, labels)
    docs = int(np.asarray(sizes, np.int64).sum())

    def rank_pair_work(trees, rows: int, features: int) -> dict:
        rounds = len(trees)
        return {"pairs": pairs * rounds,
                "bytes": docs * BYTES_PER_DOCUMENT * rounds,
                "ops": pairs * OPS_PER_PAIR * rounds}
    return rank_pair_work
