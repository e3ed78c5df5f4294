"""Leaf-ordered grower (ops/ordered_grow.py) must produce EXACTLY the
same tree as the unordered cached learner (ops/grow.py SerialComm): both
accumulate identical int32 fixed-point digit sums over identical row
sets, so every split decision, leaf value, leaf assignment and score
delta matches bit-for-bit."""

import numpy as np
import pytest
import jax.numpy as jnp

from lightgbm_tpu.ops.grow import GrowParams, grow_tree
from lightgbm_tpu.ops.ordered_grow import grow_tree_ordered


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


def test_compact_inactive_matches_riding():
    """compact_inactive=True (bagging compaction, gbdt.cpp:271-278) must
    produce the identical tree AND identical leaf routing / deltas for
    EVERY row — active rows via segments, zero-weight rows via the
    out-of-bag tree walk."""
    bins, num_bin, is_cat, feat_mask, g, h, w = _data(n=9000, cat_feature=True)
    rng = np.random.RandomState(5)
    w = jnp.asarray((rng.uniform(size=9000) < 0.35).astype(np.float32))
    base = GrowParams(num_leaves=15, max_bin=32, min_data_in_leaf=20,
                      min_sum_hessian_in_leaf=1.0)
    bins_rm = jnp.asarray(np.ascontiguousarray(np.asarray(bins).T))
    lr = jnp.float32(0.1)
    t_ref, leaf_ref, delta_ref = grow_tree_ordered(
        bins, num_bin, is_cat, feat_mask, g, h, w, lr, base,
        bins_rm=bins_rm)
    t_cmp, leaf_cmp, delta_cmp = grow_tree_ordered(
        bins, num_bin, is_cat, feat_mask, g, h, w, lr,
        base._replace(compact_inactive=True), bins_rm=bins_rm)
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
    "efb_bundle": (_one_hot, {}, "cached", {"enable_bundle": False}),
    "screening": (_dense, {"feature_screen_ratio": 0.5,
                           "feature_screen_warmup": 1}, "cached", None),
    "hist_cache_degrade": (_dense, {"memory_policy": "degrade",
                                    "histogram_pool_size": 0.001},
                           "nocache", {}),
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
            != g._parallel_grow_active
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
