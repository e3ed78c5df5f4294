"""A sparse matrix's stored entries, column by column (CSC).

What ``Dataset.construct`` needs of a ``scipy.sparse`` matrix without
ever holding a dense copy of it (docs/SPARSE.md): a column's stored
values and rows, and a row subset for the FindBin sample.  Everything
here is O(stored entries); a one-hot table of ten million rows and
thousands of columns is gigabytes stored and hundreds of gigabytes
dense.  Nothing imports scipy: the matrix is read through its
``indptr`` / ``indices`` / ``data`` arrays, and a CSR (or any other
format with ``tocsc``) is converted once.

An explicitly stored zero is a zero, a stored NaN a NaN: the dense
matrix of the same values bins to the same bytes
(tests/test_sparse_ingest.py).
"""

from __future__ import annotations

import numpy as np

# entries looked up at a time by ``take_rows`` (its int32 temporaries
# are this long)
_CHUNK_ENTRIES = 1 << 25


class SparseColumns:
    """``[num_rows, num_cols]`` in compressed sparse column form, rows
    ascending and unique within a column."""

    def __init__(self, indptr, indices, data, num_rows: int):
        self.indptr = np.asarray(indptr, np.int64)
        self.indices = np.asarray(indices)
        self.data = np.asarray(data)
        self.num_rows = int(num_rows)

    @classmethod
    def from_scipy(cls, X) -> "SparseColumns":
        """From anything with ``tocsc`` (csr_matrix, csc_matrix, coo...).
        A CSC in canonical form is read in place, never copied."""
        X = X.tocsc()
        if not X.has_canonical_format:
            X = X.copy()
            X.sum_duplicates()          # sorts the rows of a column too
        return cls(X.indptr, X.indices, X.data, X.shape[0])

    @property
    def shape(self):
        return self.num_rows, len(self.indptr) - 1

    @property
    def nnz(self) -> int:
        return int(self.indptr[-1])

    def rows(self, f: int) -> np.ndarray:
        """Rows of column ``f``'s stored entries, ascending."""
        return self.indices[self.indptr[f]:self.indptr[f + 1]]

    def values(self, f: int) -> np.ndarray:
        """Column ``f``'s stored values as float64, in ``rows`` order."""
        return self.data[self.indptr[f]:self.indptr[f + 1]].astype(
            np.float64)

    def take_rows(self, idx: np.ndarray) -> "SparseColumns":
        """The rows ``idx`` (ascending, unique), renumbered 0.. in that
        order: the FindBin sample.  One lookup a stored entry."""
        idx = np.asarray(idx, np.int64)
        lookup = np.full(self.num_rows, -1, np.int32)
        lookup[idx] = np.arange(len(idx), dtype=np.int32)
        cols = self.shape[1]
        out_ptr = np.zeros(cols + 1, np.int64)
        out_rows, out_vals = [], []
        lo = 0
        while lo < cols:
            # whole columns, about _CHUNK_ENTRIES entries of them
            hi = int(np.searchsorted(
                self.indptr, self.indptr[lo] + _CHUNK_ENTRIES, "right")) - 1
            hi = min(max(hi, lo + 1), cols)
            a, b = int(self.indptr[lo]), int(self.indptr[hi])
            new = lookup[self.indices[a:b]]
            keep = new >= 0
            kept_before = np.concatenate([[0], np.cumsum(keep)])
            out_ptr[lo + 1:hi + 1] = out_ptr[lo] \
                + kept_before[self.indptr[lo + 1:hi + 1] - a]
            out_rows.append(new[keep])
            out_vals.append(self.data[a:b][keep])
            lo = hi
        if not out_rows:                # a matrix of no columns
            out_rows, out_vals = [self.indices[:0]], [self.data[:0]]
        return SparseColumns(out_ptr, np.concatenate(out_rows),
                             np.concatenate(out_vals), len(idx))
