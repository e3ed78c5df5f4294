"""The necessary work of a round over a SPARSE table.

``counts.histogram_work`` (the yardstick's, not edited) charges a visited
row ``features + 8`` bytes and ``3 x features`` additions, "whatever
implements it".  For a sparse table the work that nothing can avoid is a
row's STORED entries: 30 of Allstate's one-hot table, not its 4,228
columns (a dense reading nobody makes) and not the 66 to 77 bundled
columns the program happens to hold them in.  A sparse kind therefore
hands the readers ``stored_per_row`` as ``features``
(``kinds/train_sparse.py``), and ``hist_roofline`` and ``train_step_mfu``
hold the program's kernels against that: with 4,228 there, the histogram
kernel, which reads C bytes a row, would read over 100% of its roofline;
with C there, the yardstick would move with the bundling.

No count function is entered in ``counts.COUNT_FUNCTIONS`` from here: the
column-space split search (``lightgbm_tpu/ops/bundle.py``) is XLA's, and
this chip's trace cannot name an XLA fusion's phase (PERF.md section 3).
A search kernel would get its own here: a child reads ``C x B`` slots of
nine int32 digit sums once, by bytes.
"""

from __future__ import annotations


def stored_per_row(X):
    """Stored entries a row of a sparse matrix: an int where every row
    stores the same number, as a one-hot table does."""
    per_row = X.nnz / X.shape[0]
    return int(per_row) if per_row == int(per_row) else per_row
