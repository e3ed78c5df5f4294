"""Leaf-ordered grower (ops/ordered_grow.py) must produce EXACTLY the
same tree as the unordered cached learner (ops/grow.py SerialComm): both
accumulate identical int32 fixed-point digit sums over identical row
sets, so every split decision, leaf value, leaf assignment and score
delta matches bit-for-bit."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from lightgbm_tpu.ops.grow import GrowParams, grow_tree
from lightgbm_tpu.ops.ordered_grow import grow_tree_ordered, leaf_delta


def _data(n=20000, f=6, seed=0, cat_feature=False):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, 32, size=(f, n)).astype(np.uint8)
    grad = rng.normal(size=n).astype(np.float32)
    hess = (np.abs(rng.normal(size=n)) + 0.1).astype(np.float32)
    w = np.ones(n, np.float32)
    num_bin = np.full(f, 32, np.int32)
    is_cat = np.zeros(f, bool)
    if cat_feature:
        is_cat[1] = True
    feat_mask = np.ones(f, bool)
    return (jnp.asarray(bins), jnp.asarray(num_bin), jnp.asarray(is_cat),
            jnp.asarray(feat_mask), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(w))


@pytest.mark.parametrize("num_leaves,cat,min_hess", [
    (15, False, 1.0), (31, True, 1.0), (7, False, 1.0),
    # the ranking cell's regime in its first rounds: the hessian floor
    # stops growth well before num_leaves, and every step after that is
    # a rejected one that still rewrites the histogram cache's rows
    (255, False, 400.0)])
def test_ordered_matches_unordered(num_leaves, cat, min_hess):
    bins, num_bin, is_cat, feat_mask, g, h, w = _data(cat_feature=cat)
    params = GrowParams(num_leaves=num_leaves, max_bin=32,
                        min_data_in_leaf=20,
                        min_sum_hessian_in_leaf=min_hess)
    bins_rm = jnp.asarray(np.ascontiguousarray(np.asarray(bins).T))
    lr = jnp.float32(0.1)

    t_ref, leaf_ref, delta_ref = grow_tree(bins, num_bin, is_cat, feat_mask,
                                           g, h, w, lr, params,
                                           bins_rm=bins_rm)
    t_ord, leaf_ord, delta_ord = grow_tree_ordered(
        bins, num_bin, is_cat, feat_mask, g, h, w, lr, params,
        bins_rm=bins_rm)

    assert int(t_ord.num_leaves) == int(t_ref.num_leaves)
    if min_hess > 1.0:
        assert 8 < int(t_ref.num_leaves) < num_leaves // 2
    for field in ("split_feature", "split_bin", "left_child", "right_child",
                  "leaf_count", "leaf_parent", "leaf_depth"):
        np.testing.assert_array_equal(
            np.asarray(getattr(t_ord, field)),
            np.asarray(getattr(t_ref, field)), err_msg=field)
    for field in ("split_gain", "internal_value", "leaf_value"):
        np.testing.assert_allclose(
            np.asarray(getattr(t_ord, field)),
            np.asarray(getattr(t_ref, field)), rtol=1e-6, atol=1e-7,
            err_msg=field)
    np.testing.assert_array_equal(np.asarray(leaf_ord),
                                  np.asarray(leaf_ref))
    np.testing.assert_allclose(np.asarray(delta_ord),
                               np.asarray(delta_ref), rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("n,seed,overruns", [(40000, 0, False),
                                             (64000, 3, True)])
def test_ordered_matches_unordered_across_size_classes(n, seed, overruns):
    """One 31-leaf tree whose splits take at least three different size
    classes (40,000 rows: the root in the 65,536 class, then 32,768,
    16,384 and 8,192), every ``TreeArrays`` field, ``leaf_id`` and the
    score delta EXACTLY equal to ops/grow.py's: the dispatch hands the
    row lanes through the classes a step does not take, and one that
    dropped or reordered a lane there could hide behind a single-class
    tree.  The second case holds a split whose smaller child lies so far
    right in the sorted window that its histogram window would overrun
    it (``hist_window`` then ends the window with the array)."""
    from lightgbm_tpu.ops.ordered_grow import _size_classes
    bins, num_bin, is_cat, feat_mask, g, h, w = _data(n=n, seed=seed,
                                                      cat_feature=True)
    params = GrowParams(num_leaves=31, max_bin=32, min_data_in_leaf=20,
                        min_sum_hessian_in_leaf=1.0)
    lr = jnp.float32(0.1)
    t_ref, leaf_ref, delta_ref = grow_tree(bins, num_bin, is_cat, feat_mask,
                                           g, h, w, lr, params)
    t_ord, leaf_ord, delta_ord = grow_tree_ordered(
        bins, num_bin, is_cat, feat_mask, g, h, w, lr, params)
    assert int(t_ref.num_leaves) == 31

    # the size class and the child window of every split, from the counts
    classes = _size_classes(n)
    counts = np.asarray(t_ref.internal_count)
    leaf_counts = np.asarray(t_ref.leaf_count)
    taken, overrun = set(), False
    for node in range(30):
        P = next((c for c in classes if counts[node] <= c), classes[-1])
        left = int(np.asarray(t_ref.left_child)[node])
        cnt_l = counts[left] if left >= 0 else leaf_counts[~left]
        cnt_r = counts[node] - cnt_l
        win = max(P // 8, 4096) if min(cnt_l, cnt_r) <= P // 8 else P // 2
        taken.add(P)
        overrun |= bool(cnt_l > cnt_r and cnt_l + win > P)
    assert len(taken) >= 3, taken
    assert overrun == overruns

    for field in t_ref._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(t_ord, field)),
            np.asarray(getattr(t_ref, field)), err_msg=field)
    np.testing.assert_array_equal(np.asarray(leaf_ord),
                                  np.asarray(leaf_ref))
    np.testing.assert_array_equal(np.asarray(delta_ord),
                                  np.asarray(delta_ref))


def test_ordered_with_bagging_weights():
    bins, num_bin, is_cat, feat_mask, g, h, w = _data(n=9000)
    rng = np.random.RandomState(1)
    w = jnp.asarray((rng.uniform(size=9000) < 0.7).astype(np.float32))
    params = GrowParams(num_leaves=15, max_bin=32, min_data_in_leaf=20,
                        min_sum_hessian_in_leaf=1.0)
    bins_rm = jnp.asarray(np.ascontiguousarray(np.asarray(bins).T))
    lr = jnp.float32(0.1)
    t_ref, leaf_ref, _ = grow_tree(bins, num_bin, is_cat, feat_mask,
                                   g, h, w, lr, params, bins_rm=bins_rm)
    t_ord, leaf_ord, _ = grow_tree_ordered(bins, num_bin, is_cat, feat_mask,
                                           g, h, w, lr, params,
                                           bins_rm=bins_rm)
    assert int(t_ord.num_leaves) == int(t_ref.num_leaves)
    np.testing.assert_array_equal(np.asarray(t_ord.split_feature),
                                  np.asarray(t_ref.split_feature))
    np.testing.assert_array_equal(np.asarray(leaf_ord),
                                  np.asarray(leaf_ref))


@pytest.mark.parametrize("data", ["plain", "bundled"])
def test_compact_inactive_matches_riding(data):
    """compact_inactive=True (bagging compaction, gbdt.cpp:271-278) must
    produce the identical tree AND identical leaf routing / deltas for
    EVERY row — active rows via segments after the partition kernel's
    compaction, zero-weight rows routed node by node through the tree
    (``route_rows``; on EFB columns by the split member's decode)."""
    dec = None
    if data == "bundled":
        _, y, ds = _bundled(9000, 3, "numeric_member")
        (bins, num_bin, is_cat, feat_mask, g, h, _, _), dec = \
            _grow_args(ds, y)
        base = GrowParams(num_leaves=31, max_bin=255, min_data_in_leaf=0,
                          min_sum_hessian_in_leaf=5.0)
    else:
        bins, num_bin, is_cat, feat_mask, g, h, _ = _data(
            n=9000, cat_feature=True)
        base = GrowParams(num_leaves=15, max_bin=32, min_data_in_leaf=20,
                          min_sum_hessian_in_leaf=1.0)
    rng = np.random.RandomState(5)
    w = jnp.asarray((rng.uniform(size=9000) < 0.35).astype(np.float32))
    lr = jnp.float32(0.1)
    t_ref, leaf_ref, delta_ref = grow_tree_ordered(
        bins, num_bin, is_cat, feat_mask, g, h, w, lr, base, bundle=dec)
    t_cmp, leaf_cmp, delta_cmp = grow_tree_ordered(
        bins, num_bin, is_cat, feat_mask, g, h, w, lr,
        base._replace(compact_inactive=True), bundle=dec)
    assert int(t_ref.num_leaves) > 8
    assert int(t_cmp.num_leaves) == int(t_ref.num_leaves)
    for field in ("split_feature", "split_bin", "left_child", "right_child",
                  "leaf_count", "leaf_parent", "leaf_depth"):
        np.testing.assert_array_equal(
            np.asarray(getattr(t_cmp, field)),
            np.asarray(getattr(t_ref, field)), err_msg=field)
    np.testing.assert_array_equal(np.asarray(leaf_cmp), np.asarray(leaf_ref))
    np.testing.assert_allclose(np.asarray(delta_cmp), np.asarray(delta_ref),
                               rtol=1e-6, atol=1e-7)


def test_ordered_saturation_stops():
    bins, num_bin, is_cat, feat_mask, g, h, w = _data(n=512)
    params = GrowParams(num_leaves=31, max_bin=32, min_data_in_leaf=300,
                        min_sum_hessian_in_leaf=1.0)
    t, leaf, delta = grow_tree_ordered(bins, num_bin, is_cat, feat_mask,
                                       g, h, w, jnp.float32(0.1), params)
    assert int(t.num_leaves) == 1
    np.testing.assert_array_equal(np.asarray(leaf), 0)
    np.testing.assert_array_equal(np.asarray(delta), 0.0)


def test_uint16_bins_fall_back_to_cached_learner():
    """max_bin > 256 stores uint16 bins; the ordered grower's i32 lane
    packing is uint8-only, so GBDT must route to the cached learner and
    still train correctly."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    X = rng.normal(size=(3000, 4))
    y = (X[:, 0] > 0).astype(np.float64)
    bst = lgb.train({"objective": "binary", "max_bin": 500,
                     "num_leaves": 15, "verbose": -1,
                     "min_data_in_leaf": 20},
                    lgb.Dataset(X, label=y, params={"max_bin": 500}),
                    num_boost_round=5)
    assert bst.num_trees() == 5
    p = bst.predict(X[:50])
    assert np.isfinite(p).all()


def _train(params, X, y, rounds=3):
    import lightgbm_tpu as lgb
    p = {"objective": "binary", "num_leaves": 15, "verbose": -1,
         "min_data_in_leaf": 20, **params}
    return lgb.train(p, lgb.Dataset(X, label=y, params=p),
                     num_boost_round=rounds)


def _dense(seed=1, n=3000, f=4):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, f))
    return X, (X[:, 0] + 0.2 * X[:, 1] > 0).astype(np.float64)


def _one_hot(seed=1, n=3000, blocks=3, size=5):
    """Blocks with at most one non-zero a row: what EFB bundles."""
    rng = np.random.RandomState(seed)
    X = np.zeros((n, blocks * size))
    for b in range(blocks):
        X[np.arange(n), b * size + rng.randint(0, size, n)] = \
            rng.randint(1, 4, n)
    return X, (X[:, 0] - X[:, size + 1] + rng.normal(0, .5, n) > 0) * 1.0


def test_stale_serial_grow_key_is_ignored():
    """``serial_grow`` was an option until PR 32; a conf file that still
    names it is read like any unknown key and trains the default's
    model, on the grower the data chooses."""
    X, y = _dense()
    stale = _train({"serial_grow": "cached"}, X, y, rounds=5)
    assert stale._booster._grower_kind == "ordered"
    assert stale.model_to_string().split("parameters")[0] == \
        _train({}, X, y, rounds=5).model_to_string().split("parameters")[0]


# what the data looks like -> the grower _choose_grower names, what the
# device holds for it, and the parameters of a run that must grow the
# same trees on another grower (None: nothing comparable)
_CHOICES = {
    "uint8_unbundled": (_dense, {}, "ordered", None),
    "max_bin_500": (_dense, {"max_bin": 500}, "cached", None),
    "efb_bundle": (_one_hot, {}, "ordered", {"enable_bundle": False}),
    "efb_bundle_screening": (_one_hot, {"feature_screen_ratio": 0.5,
                                        "feature_screen_warmup": 1},
                             "cached", None),
    "screening": (_dense, {"feature_screen_ratio": 0.5,
                           "feature_screen_warmup": 1}, "cached", None),
    # (float sums here, integer sums there: a pure leaf's last splits are
    # rounding noise of a gain of 2e-4 in either, and are kept out)
    "hist_cache_degrade": (_dense, {"memory_policy": "degrade",
                                    "histogram_pool_size": 0.001,
                                    "min_gain_to_split": 1e-3},
                           "nocache", {"min_gain_to_split": 1e-3}),
    "data_parallel_uint8": (_dense, {"tree_learner": "data",
                                     "num_machines": 4}, "ordered", {}),
    "data_parallel_uint16": (_dense, {"tree_learner": "data",
                                      "num_machines": 4, "max_bin": 500},
                             "nocache", None),   # float sums: near ties
}


@pytest.mark.parametrize("case", list(_CHOICES))
def test_grower_is_chosen_from_the_data_and_trains(case):
    """One choice, from what the booster can observe, and every choice
    trains: finite scores, and where another grower takes the same data
    the same trees."""
    from lightgbm_tpu.utils import log
    log.reset_warn_once()
    make, params, kind, same_as = _CHOICES[case]
    X, y = make()
    bst = _train(params, X, y)
    g = bst._booster
    assert g._grower_kind == kind and g._choose_grower()[0] == kind
    td = g.train_data
    if params.get("tree_learner") == "data":
        # leaf-ordered shards keep their layout resident, one block a
        # device; full passes read columns only
        assert g._parallel_grow_active
        assert (td.bins_words is not None) == (kind == "ordered")
        assert (td.bins_rm is not None) == (kind == "ordered")
    else:
        assert (td.bins_words is not None) == (td.bins.dtype == np.uint8)
    assert bst.num_trees() == 3
    assert np.isfinite(bst.predict(X[:100], raw_score=True)).all()
    if same_as is not None:
        other = _train(same_as, X, y)
        assert other._booster._grower_kind != kind \
            or other._booster._parallel_grow_active \
            != g._parallel_grow_active \
            or (other._booster._bundle is None) != (g._bundle is None)
        for a, c in zip(g.models, other._booster.models):
            np.testing.assert_array_equal(a.split_feature, c.split_feature)
            np.testing.assert_array_equal(a.threshold_in_bin,
                                          c.threshold_in_bin)
            np.testing.assert_allclose(a.leaf_value, c.leaf_value,
                                       rtol=2e-4, atol=2e-6)


def test_misaligned_valid_set_rejected():
    """AddValidData with independently binned data must fatal
    (Dataset::CheckAlign semantics), not silently mis-score."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.models.gbdt import GBDT
    rng = np.random.RandomState(2)
    X = rng.normal(size=(500, 3))
    y = (X[:, 0] > 0).astype(np.float64)
    X2 = rng.normal(size=(300, 3)) * 5.0      # different value range
    ds = BinnedDataset.from_matrix(X, y, max_bin=32, min_data_in_leaf=10)
    bad = BinnedDataset.from_matrix(X2, y[:300], max_bin=32,
                                    min_data_in_leaf=10)
    good = ds.create_valid(X2, y[:300])
    cfg = Config({"objective": "binary", "num_leaves": 7, "metric": "auc"})
    b = GBDT(cfg, ds)
    b.add_valid_dataset(good)                 # aligned: fine
    with pytest.raises(Exception):
        b.add_valid_dataset(bad)


# ---- EFB bundles on the leaf-ordered grower (PR 36) -----------------------

def _bundled(n, seed, kind):
    """A table EFB bundles, binned: ``two_bin`` one-hot blocks alone (a
    bundle of two-bin members); ``numeric_member`` two mutually exclusive
    sparse columns of eight values beside them (a bundle with many-bin
    members); ``singletons`` dense numerics and a frequent level beside
    the bundles."""
    from lightgbm_tpu.io.dataset import BinnedDataset
    rng = np.random.RandomState(seed)
    cols = []
    for size in (6, 12, 3, 40):
        lvl = np.minimum(rng.zipf(1.5, n) - 1, size - 1)
        hot = np.zeros((n, size))
        hot[np.arange(n), lvl] = 1.0
        cols.append(hot)
    if kind == "numeric_member":
        a = np.zeros((n, 2))
        which = rng.randint(0, 6, n)
        for j in range(2):
            a[which == j, j] = rng.randint(1, 9, (which == j).sum())
        cols.append(a)
    if kind == "singletons":
        cols.append(rng.randn(n, 3))
    X = np.concatenate(cols, 1)
    y = (X[:, 0] - X[:, 7] + 0.3 * X[:, 30] + 0.5 * X[:, -1]
         + rng.randn(n) * 0.5 > 0.4).astype(np.float64)
    ds = BinnedDataset.from_matrix(X, y, max_bin=255, min_data_in_leaf=0,
                                   min_data_in_bin=3, enable_bundle=True)
    assert ds.bundle_plan is not None
    return X, y, ds


def _grow_args(ds, y, seed=5):
    from lightgbm_tpu.ops.bundle import BundleDecode
    n = ds.num_data
    rng = np.random.RandomState(seed)
    g = (rng.randn(n) + (y * 2 - 1) * 0.3).astype(np.float32)
    h = (np.abs(rng.randn(n)) + 0.1).astype(np.float32)
    f = ds.num_features
    dec = None
    if ds.bundle_plan is not None:
        dec = BundleDecode.from_tables(ds.bundle_plan.decode_arrays(
            [m.num_bin for m in ds.mappers],
            [m.default_bin for m in ds.mappers], 255))
    return (jnp.asarray(ds.bins), jnp.asarray(ds.num_bin_per_feature()),
            jnp.zeros(f, bool), jnp.ones(f, bool), jnp.asarray(g),
            jnp.asarray(h), jnp.ones(n, jnp.float32), jnp.float32(0.1)), dec


def _assert_same_growth(got, ref):
    """Every ``TreeArrays`` field, float gains and values included, the
    leaf of every row and the score delta EQUAL."""
    for field in ref[0]._fields:
        np.testing.assert_array_equal(np.asarray(getattr(got[0], field)),
                                      np.asarray(getattr(ref[0], field)),
                                      err_msg=field)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(ref[1]))
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(ref[2]))


@pytest.mark.parametrize("kind,leaves", [
    ("two_bin", 63), ("two_bin", 255), ("numeric_member", 63),
    ("singletons", 63), ("singletons", 255)])
def test_ordered_grows_the_unbundled_trees_on_bundles(kind, leaves):
    """``max_conflict_rate=0``: bundling changes no integer sum, and the
    search reads integers (ops/split.py ``find_best_split_sums``).  The
    grower on the bundled columns (the split member decoded from the
    lanes' byte, the features searched where they lie) against the grower
    on the unbundled columns: bit for bit."""
    from lightgbm_tpu.io.dataset import BinnedDataset
    X, y, ds = _bundled(20000, 1, kind)
    wide = BinnedDataset.from_matrix(X, y, max_bin=255, min_data_in_leaf=0,
                                     min_data_in_bin=3, enable_bundle=False)
    assert wide.num_columns > ds.num_columns and wide.bundle_plan is None
    (args, dec), (wargs, _) = _grow_args(ds, y), _grow_args(wide, y)
    if kind == "numeric_member":
        assert len(np.asarray(dec.multi)) == 2
    params = GrowParams(num_leaves=leaves, max_bin=255, min_data_in_leaf=0,
                        min_sum_hessian_in_leaf=5.0)
    got = grow_tree_ordered(*args, params, bundle=dec)
    ref = grow_tree_ordered(*wargs, params)
    assert int(got[0].num_leaves) == int(ref[0].num_leaves) > leaves // 2
    _assert_same_growth(got, ref)
    used = set(np.asarray(ref[0].split_feature)[:leaves - 1].tolist())
    members = {f for m in ds.bundle_plan.bundles for f in m}
    assert used & members and (kind == "two_bin" or used - members)


@pytest.mark.parametrize("other", ["cached", "cached_unbundled"])
@pytest.mark.parametrize("kind", ["two_bin", "numeric_member", "singletons"])
def test_ordered_on_bundles_grows_the_cached_growers_trees(kind, other):
    """Against the cached grower (gathered rows, ``find_best_split_sums``
    over the expanded ``[F, 9, B]``) on the same bundles and on the
    unbundled columns: node for node and bit for bit, one search
    arithmetic for every grower that holds integer sums.  And the leaf
    values are the rows' own float64 sums' to float32's rounding."""
    from lightgbm_tpu.io.dataset import BinnedDataset
    X, y, ds = _bundled(20000, 2, kind)
    params = GrowParams(num_leaves=63, max_bin=255, min_data_in_leaf=0,
                        min_sum_hessian_in_leaf=5.0)
    args, dec = _grow_args(ds, y)
    got = grow_tree_ordered(*args, params, bundle=dec)
    if other == "cached_unbundled":
        ds = BinnedDataset.from_matrix(
            X, y, max_bin=255, min_data_in_leaf=0, min_data_in_bin=3,
            enable_bundle=False)
        args, dec = _grow_args(ds, y)
    bins_rm = jnp.asarray(np.ascontiguousarray(ds.bins.T))
    ref = grow_tree(*args, params, bins_rm=bins_rm, bundle=dec)
    n = int(ref[0].num_leaves)
    assert n > 31
    _assert_same_growth(got, ref)
    g, h = np.asarray(args[4], np.float64), np.asarray(args[5], np.float64)
    leaf = np.asarray(got[1])
    truth = -0.1 * np.bincount(leaf, weights=g, minlength=n) \
        / np.bincount(leaf, weights=h, minlength=n)
    off = np.abs(np.asarray(got[0].leaf_value)[:n] - truth) \
        / np.maximum(np.abs(truth), 1e-3)
    assert off.max() < 1e-5


def _leaf_delta_by_search(start, cnt, num_leaves, shrunk, row_ord, n):
    """The form ``leaf_delta`` replaced (PR 37), kept as its oracle: the
    live starts sorted with their leaves, a binary search of every
    position, the leaf gathered from the table, one scatter to row order
    and the value gathered by the leaf."""
    leaf_iota = jnp.arange(start.shape[0], dtype=jnp.int32)
    live = (leaf_iota < num_leaves) & (cnt > 0)
    sv = jnp.where(live, start, jnp.int32(n))
    sv_sorted, leaf_sorted = jax.lax.sort((sv, leaf_iota), num_keys=1,
                                          is_stable=True)
    pos = jnp.arange(n, dtype=jnp.int32)
    seg = jnp.searchsorted(sv_sorted, pos, side="right") - 1
    leaf_id = jnp.zeros(n, jnp.int32).at[row_ord[:n]].set(
        leaf_sorted[seg], unique_indices=True)
    return leaf_id, shrunk[leaf_id]


def _segments(rng, n, live, cuts=None):
    """``live`` segments that tile [0, n), in a shuffled leaf order."""
    if cuts is None:
        cuts = np.sort(rng.choice(np.arange(1, n), live - 1, replace=False))
    start = np.concatenate([[0], cuts]).astype(np.int32)
    cnt = np.diff(np.concatenate([start, [n]])).astype(np.int32)
    order = rng.permutation(live)
    return start[order], cnt[order]


def _leaf_delta_case(case, L, rng):
    """(start, cnt, num_leaves, shrunk, row_ord, n) of one case."""
    n = 5003 if case == "ragged_n" else 4096
    shrunk = rng.normal(size=L).astype(np.float32)
    num_leaves = L
    start, cnt = _segments(rng, n, L)
    if case == "stopped_early":
        # growth stopped: the table's tail holds what was never written
        num_leaves = max(1, L // 3)
        start, cnt = _segments(rng, n, num_leaves)
        start = np.concatenate([start, rng.randint(0, n, L - num_leaves)])
        cnt = np.concatenate([cnt, rng.randint(1, n, L - num_leaves)])
    elif case == "empty_leaves":
        # live leaves that hold no row (a bag's), at starts of others
        full = max(1, L - L // 4)
        s_, c_ = _segments(rng, n, full)
        where = rng.permutation(L)
        start, cnt = np.zeros(L, np.int64), np.zeros(L, np.int64)
        start[where[:full]], cnt[where[:full]] = s_, c_
        start[where[full:]] = rng.choice(s_, L - full)
    elif case == "one_leaf":
        num_leaves = 1
        start, cnt = np.zeros(L, np.int64), np.zeros(L, np.int64)
        cnt[0] = n
    elif case == "one_row_segments":
        # every leaf but the last a single row
        start, cnt = _segments(rng, n, L, cuts=np.arange(1, L))
    elif case == "ends":
        # one row at position 0, one row at position n - 1
        inner = np.sort(rng.choice(np.arange(2, n - 1), max(L - 3, 0),
                                   replace=False))
        cuts = np.concatenate([[1], inner, [n - 1]])[:L - 1] if L > 2 \
            else np.array([n - 1])
        start, cnt = _segments(rng, n, L, cuts=cuts)
    elif case == "signed_zero_and_extreme":
        shrunk[L // 2] = -3e38
        shrunk[0], shrunk[-1] = -0.0, 3e38
    row_ord = rng.permutation(n)
    if case == "permuted_row_ord":
        # the grower's lane: the order reversed, the lane longer than n
        row_ord = np.concatenate([np.arange(n)[::-1], np.arange(n, n + 64)])
    return (jnp.asarray(start, jnp.int32), jnp.asarray(cnt, jnp.int32),
            jnp.int32(num_leaves), jnp.asarray(shrunk),
            jnp.asarray(row_ord, jnp.int32), n)


@pytest.mark.parametrize("L", [2, 31, 63, 255, 256])
@pytest.mark.parametrize("case", [
    "all_live", "stopped_early", "empty_leaves", "one_leaf",
    "one_row_segments", "ends", "ragged_n", "signed_zero_and_extreme",
    "permuted_row_ord"])
def test_leaf_delta_selects_what_the_search_found(case, L):
    """``leaf_delta`` (dense compares against the sorted segment starts
    and the leaf numbers) against the binary search and table gathers it
    replaced: the leaf of every row and its value BIT for bit, ``-0.0``
    and 3e38 too."""
    args = _leaf_delta_case(case, L, np.random.RandomState(L))
    want_leaf, want_delta = _leaf_delta_by_search(*args)
    got_leaf, got_delta = jax.jit(leaf_delta, static_argnums=5)(*args)
    assert got_leaf.dtype == jnp.int32 and got_delta.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got_leaf),
                                  np.asarray(want_leaf))
    np.testing.assert_array_equal(np.asarray(got_delta).view(np.int32),
                                  np.asarray(want_delta).view(np.int32))
    if case == "one_leaf":
        assert not np.asarray(got_leaf).any()
    if case == "signed_zero_and_extreme":
        got = np.asarray(got_delta)
        assert np.signbit(got[np.asarray(got_leaf) == 0]).all()
        assert (got[np.asarray(got_leaf) == L - 1] == np.float32(3e38)).all()
