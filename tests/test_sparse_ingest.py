"""A ``scipy.sparse`` matrix is binned from its stored entries (PR 36,
io/sparse.py, docs/SPARSE.md): the same mappers, bundle plan and ``bins``
as the dense matrix of the same values gives, byte for byte, and never a
dense copy."""

import numpy as np
import pytest
import scipy.sparse as sp

from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.io.sparse import SparseColumns

pytestmark = pytest.mark.sparse


class RefusesDense(sp.csc_matrix):
    """Counts and refuses every attempt to make it dense."""
    attempts = 0

    def toarray(self, *a, **k):
        type(self).attempts += 1
        raise MemoryError("toarray on a matrix that refuses to be dense")

    todense = toarray


def _table(n=3000, seed=0):
    """One-hot blocks, dense numerics, a sparse count column, a column of
    one stored entry, an all-zero column, NaNs."""
    rng = np.random.RandomState(seed)
    X = np.zeros((n, 42))
    for b in range(5):
        X[np.arange(n), b * 6 + rng.randint(0, 6, n)] = 1.0
    X[:, 30:36] = rng.randn(n, 6)
    X[:, 36] = np.where(rng.rand(n) < 0.1, rng.randint(1, 5, n), 0)
    X[5, 37] = 2.0                      # one stored entry
    # column 38: all zero.  Two sparse columns that CONFLICT on a few rows
    a, b = rng.rand(n) < 0.08, rng.rand(n) < 0.08
    X[a, 39], X[b, 40] = 1.0, 3.0
    X[rng.rand(n) < 0.01, 41] = np.nan
    y = (X[:, 0] + X[:, 30] + rng.randn(n) > 0.5).astype(np.float64)
    return X, y


def _same(dense, sparse):
    assert dense.used_feature_map == sparse.used_feature_map
    assert np.array_equal(dense.real_to_inner, sparse.real_to_inner)
    for a, b in zip(dense.mappers, sparse.mappers):
        assert repr(a.to_state()) == repr(b.to_state())     # NaN == NaN
    assert (dense.bundle_plan is None) == (sparse.bundle_plan is None)
    if dense.bundle_plan is not None:
        assert dense.bundle_plan.signature() == sparse.bundle_plan.signature()
        assert dense.bundle_plan.sample_conflicts == \
            sparse.bundle_plan.sample_conflicts
    assert dense.bins.dtype == sparse.bins.dtype
    assert np.array_equal(dense.bins, sparse.bins)


KW = dict(min_data_in_leaf=0, min_data_in_bin=3)


@pytest.mark.parametrize("fmt", ["csr", "csc", "coo"])
@pytest.mark.parametrize("bundle,conflict_rate", [
    (False, 0.0), (True, 0.0), (True, 0.05)])
def test_sparse_ingest_is_dense_ingest(fmt, bundle, conflict_rate):
    X, y = _table()
    S = getattr(sp, fmt + "_matrix")(X)
    dense = BinnedDataset.from_matrix(
        X, y, enable_bundle=bundle, max_conflict_rate=conflict_rate, **KW)
    sparse = BinnedDataset.from_sparse(
        S, y, enable_bundle=bundle, max_conflict_rate=conflict_rate, **KW)
    _same(dense, sparse)
    if bundle:
        assert dense.bundle_plan is not None
        # the conflicting pair shares a column only under a budget
        conflicts = dense.bundle_plan.sample_conflicts
        assert (conflicts > 0) == (conflict_rate > 0)


@pytest.mark.parametrize("case", ["stored_zeros", "sampled", "unsorted",
                                  "small_max_bin", "uint16"])
def test_sparse_ingest_edge_cases(case):
    X, y = _table(n=6000, seed=3)
    kw = dict(KW, enable_bundle=True)
    S = sp.csc_matrix(X)
    if case == "stored_zeros":
        # explicitly stored zeros are zeros: in a one-hot block and in a
        # numeric column
        S = sp.csc_matrix(X)
        S.data[::7] = 0.0
        X = S.toarray()
        assert S.nnz > np.count_nonzero(X)
    elif case == "sampled":
        kw["bin_construct_sample_cnt"] = 1500       # FindBin on a row draw
    elif case == "unsorted":
        S = sp.csc_matrix(X)
        for j in range(S.shape[1]):                 # rows descending
            a, b = S.indptr[j], S.indptr[j + 1]
            S.indices[a:b] = S.indices[a:b][::-1].copy()
            S.data[a:b] = S.data[a:b][::-1].copy()
        S.has_sorted_indices = False
    elif case == "small_max_bin":
        kw["max_bin"] = 15
    elif case == "uint16":
        X[:, 30] = np.arange(len(X))                # 300 bins: uint16
        S = sp.csc_matrix(X)
        kw["max_bin"] = 300
    dense = BinnedDataset.from_matrix(X, y, **kw)
    sparse = BinnedDataset.from_sparse(S, y, **kw)
    _same(dense, sparse)
    assert sparse.bins.dtype == (np.uint16 if case == "uint16" else np.uint8)


def test_take_rows_is_fancy_indexing():
    X, _ = _table(n=2000, seed=5)
    cols = SparseColumns.from_scipy(sp.csr_matrix(X))
    assert cols.shape == X.shape and cols.nnz == np.count_nonzero(
        np.nan_to_num(X, nan=1.0))
    idx = np.sort(np.random.RandomState(1).choice(2000, 300, replace=False))
    took = cols.take_rows(idx)
    want = sp.csc_matrix(X[idx])
    assert np.array_equal(took.indptr, want.indptr)
    assert np.array_equal(took.indices, want.indices)
    assert np.array_equal(took.data, want.data, equal_nan=True)


@pytest.mark.parametrize("fmt", ["csr", "csc"])
def test_training_on_a_sparse_matrix_never_makes_it_dense(fmt):
    """``lightgbm_tpu.train`` on a matrix that refuses ``toarray``: the
    dense matrix's model, a sparse validation set binned on the training
    bundles, the ingest's gauges."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs
    X, y = _table(n=4000, seed=7)
    X = np.nan_to_num(X)
    p = {"objective": "binary", "num_leaves": 15, "verbose": -1,
         "min_data_in_leaf": 20, "min_data_in_bin": 3, "metric": "auc"}
    RefusesDense.attempts = 0
    S = RefusesDense(sp.csc_matrix(X))
    if fmt == "csr":
        S = S.tocsr()                   # a plain CSR: converted once
        V = sp.csr_matrix(X[:500])
    else:
        V = RefusesDense(sp.csc_matrix(X[:500]))
    train = lgb.Dataset(S, label=y, params=p)
    valid = lgb.Dataset(V, label=y[:500], reference=train, params=p)
    got = {}
    bst = lgb.train(p, train, num_boost_round=4, valid_sets=[valid],
                    evals_result=got, verbose_eval=False)
    assert RefusesDense.attempts == 0
    assert bst._booster._grower_kind == "ordered"
    assert bst._booster._bundle is not None
    gauges = obs.snapshot()["gauges"]
    assert gauges["sparse_stored_entries"] == S.nnz
    assert gauges["sparse_stored_per_row"] == pytest.approx(S.nnz / 4000)
    assert gauges["efb_columns"] == train._binned.num_columns < 42
    dense = lgb.Dataset(X, label=y, params=p)
    dvalid = lgb.Dataset(X[:500], label=y[:500], reference=dense, params=p)
    want = {}
    ref = lgb.train(p, dense, num_boost_round=4, valid_sets=[dvalid],
                    evals_result=want, verbose_eval=False)
    trees = lambda b: b.model_to_string().split("feature importances")[0]
    assert trees(bst) == trees(ref)
    assert got == want
    assert np.array_equal(valid._binned.bins, dvalid._binned.bins)
