"""Distributed tree-growth parity on an 8-virtual-device CPU mesh.

The reference validates parallel learning only by running two local
processes by hand (examples/parallel_learning/); here every parallel
learner is checked for exact structural parity against the serial grower
on the same data — the strongest guarantee the reference's design implies
(data/feature-parallel are mathematically exact reformulations; voting is
exact whenever the elected set contains the true best feature).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from lightgbm_tpu.ops.grow import GrowParams, grow_tree
from lightgbm_tpu.ops.ordered_grow import grow_tree_ordered, pack_word_lanes
from lightgbm_tpu.ops.split import SplitParams
from lightgbm_tpu.parallel import make_parallel_grow


def _make_data(seed=0, n=512, f=6, B=16):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, B, size=(f, n)).astype(np.int32)
    g = rng.normal(size=n).astype(np.float32)
    h = np.abs(rng.normal(size=n)).astype(np.float32) + 0.1
    return bins, g, h


def _mesh(n=8, axis="shard"):
    devs = jax.devices()
    assert len(devs) >= n, "conftest must force 8 CPU devices"
    return Mesh(np.array(devs[:n]), (axis,))


def _grow_serial(bins, g, h, params, B):
    F, N = bins.shape
    num_bin = jnp.full((F,), B, jnp.int32)
    is_cat = jnp.zeros((F,), bool)
    feat_mask = jnp.ones((F,), bool)
    w = jnp.ones((N,), jnp.float32)
    return grow_tree(jnp.asarray(bins), num_bin, is_cat, feat_mask,
                     jnp.asarray(g), jnp.asarray(h), w,
                     jnp.float32(0.1), params)


def _grow_parallel(mode, bins, g, h, params, B, n_dev=8, **kw):
    mesh = _mesh(n_dev)
    F, N = bins.shape
    fn = make_parallel_grow(mesh, mode, params, **kw)
    num_bin = jnp.full((F,), B, jnp.int32)
    is_cat = jnp.zeros((F,), bool)
    feat_mask = jnp.ones((F,), bool)
    w = jnp.ones((N,), jnp.float32)
    return fn(jnp.asarray(bins), num_bin, is_cat, feat_mask,
              jnp.asarray(g), jnp.asarray(h), w, jnp.float32(0.1))


def _assert_tree_equal(ta, tb, structural_only=False):
    assert int(ta.num_leaves) == int(tb.num_leaves)
    np.testing.assert_array_equal(np.asarray(ta.split_feature),
                                  np.asarray(tb.split_feature))
    np.testing.assert_array_equal(np.asarray(ta.split_bin),
                                  np.asarray(tb.split_bin))
    np.testing.assert_array_equal(np.asarray(ta.left_child),
                                  np.asarray(tb.left_child))
    np.testing.assert_array_equal(np.asarray(ta.right_child),
                                  np.asarray(tb.right_child))
    if not structural_only:
        np.testing.assert_allclose(np.asarray(ta.leaf_value),
                                   np.asarray(tb.leaf_value),
                                   rtol=2e-4, atol=2e-6)
        np.testing.assert_array_equal(np.asarray(ta.leaf_count),
                                      np.asarray(tb.leaf_count))


PARAMS = GrowParams(num_leaves=15, max_bin=16, min_data_in_leaf=5,
                    min_sum_hessian_in_leaf=1e-3)


@pytest.mark.parametrize("bins_dtype", [np.int32, np.uint8])
def test_data_parallel_matches_serial(bins_dtype):
    """Both data-parallel programs against the serial cached learner:
    wide bins keep ops/grow.py's full passes with the float histogram
    all-reduced; uint8 bins grow a leaf-ordered shard on every device
    (the tests below hold that one to the serial ordered grower)."""
    bins, g, h = _make_data()
    bins = bins.astype(bins_dtype)
    ts, leaf_s, delta_s = _grow_serial(bins, g, h, PARAMS, 16)
    tp, leaf_p, delta_p = _grow_parallel("data", bins, g, h, PARAMS, 16)
    _assert_tree_equal(ts, tp)
    np.testing.assert_array_equal(np.asarray(leaf_s), np.asarray(leaf_p))
    np.testing.assert_allclose(np.asarray(delta_s), np.asarray(delta_p),
                               rtol=2e-4, atol=2e-6)


def test_feature_parallel_matches_serial():
    bins, g, h = _make_data(seed=1)
    ts, _, _ = _grow_serial(bins, g, h, PARAMS, 16)
    tp, leaf_p, _ = _grow_parallel("feature", bins, g, h, PARAMS, 16)
    _assert_tree_equal(ts, tp)


def test_feature_parallel_uneven_features():
    # 6 features over 8 shards and 10 features over 8 shards (padding paths)
    for f in (6, 10):
        bins, g, h = _make_data(seed=2, f=f)
        ts, _, _ = _grow_serial(bins, g, h, PARAMS, 16)
        tp, _, _ = _grow_parallel("feature", bins, g, h, PARAMS, 16)
        _assert_tree_equal(ts, tp)


def test_data_parallel_uneven_rows():
    bins, g, h = _make_data(seed=3, n=509)  # not divisible by 8
    ts, leaf_s, delta_s = _grow_serial(bins, g, h, PARAMS, 16)
    tp, leaf_p, delta_p = _grow_parallel("data", bins, g, h, PARAMS, 16)
    _assert_tree_equal(ts, tp)
    np.testing.assert_array_equal(np.asarray(leaf_s), np.asarray(leaf_p))


def test_voting_parallel_matches_serial_with_full_topk():
    # top_k >= F makes the election lossless -> exact parity with serial.
    bins, g, h = _make_data(seed=4)
    ts, _, _ = _grow_serial(bins, g, h, PARAMS, 16)
    tp, _, _ = _grow_parallel("voting", bins, g, h, PARAMS, 16, top_k=6)
    _assert_tree_equal(ts, tp)


def test_voting_election_uses_per_feature_max_not_sum():
    """GlobalVoting keeps the per-feature MAX of count-weighted local gains
    over machines, then top-k (voting_parallel_tree_learner.cpp:157-186).

    Planted data, 8 shards x 64 rows, 2 features, top_k=1:
      * feature 0: mild gain 16 on EVERY shard (sum rule would score
        8*16=128 and elect it),
      * feature 1: gain 64 on shard 0 only, constant elsewhere (max rule
        scores it 64 > 16 and elects it).
    All shard leaf counts equal mean_num_data, so the weights are the raw
    local gains.  The root split feature therefore reveals the election
    rule: max -> 1, sum -> 0 (0 is also the serial/global-gain choice)."""
    n, per = 512, 64
    g = np.zeros(n, np.float32)
    f0 = np.zeros(n, np.int32)
    f1 = np.zeros(n, np.int32)
    for s in range(8):
        lo = s * per
        # f0: bins 0/1 halves; 24-of-32 label agreement -> G=+-16, gain 16
        f0[lo:lo + 32] = 0
        f0[lo + 32:lo + per] = 1
        g[lo:lo + 24] = -1.0
        g[lo + 24:lo + 32] = 1.0
        g[lo + 32:lo + 56] = 1.0
        g[lo + 56:lo + per] = -1.0
    # f1: perfect separation on shard 0 (gain 64), constant elsewhere
    f1[:per] = (g[:per] > 0).astype(np.int32)
    bins = np.stack([f0, f1])
    h = np.ones(n, np.float32)
    params = GrowParams(num_leaves=2, max_bin=16, min_data_in_leaf=5,
                        min_sum_hessian_in_leaf=1e-3)
    ts, _, _ = _grow_serial(bins, g, h, params, 16)
    assert int(ts.split_feature[0]) == 0  # global gain prefers feature 0
    tp, _, _ = _grow_parallel("voting", bins, g, h, params, 16, top_k=1)
    assert int(tp.num_leaves) == 2
    assert int(tp.split_feature[0]) == 1  # max-rule election won


def test_voting_parallel_small_topk_reasonable():
    # With top_k < F voting is approximate; the tree must still be a valid
    # gainful tree (num_leaves grown, finite leaf values).
    bins, g, h = _make_data(seed=5, f=12)
    tp, leaf_p, delta_p = _grow_parallel("voting", bins, g, h, PARAMS, 16,
                                         top_k=3)
    assert int(tp.num_leaves) > 1
    assert np.isfinite(np.asarray(tp.leaf_value)).all()
    assert np.isfinite(np.asarray(delta_p)).all()


@pytest.mark.parametrize("learner", ["data", "feature", "voting"])
def test_end_to_end_distributed_training_matches_serial(learner):
    """Full GBDT training with a distributed tree_learner produces the same
    model (all split decisions + leaf values) as serial training."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.models.gbdt import GBDT

    rng = np.random.RandomState(7)
    X = rng.normal(size=(600, 8))
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + 0.1 * rng.normal(size=600) > 0)
    base = {"objective": "binary", "num_leaves": 8, "max_bin": 32,
            "min_data_in_leaf": 10, "min_sum_hessian_in_leaf": 1e-3,
            "num_iterations": 5, "top_k": 8}
    ds = BinnedDataset.from_matrix(X, y.astype(np.float32), max_bin=32,
                                   min_data_in_leaf=10)
    gb_s = GBDT(Config(dict(base)), ds)
    gb_s.train(5)
    gb_p = GBDT(Config(dict(base, tree_learner=learner, num_machines=8)), ds)
    gb_p.train(5)
    assert len(gb_s.models) == len(gb_p.models)
    for ts, tp in zip(gb_s.models, gb_p.models):
        assert ts.num_leaves == tp.num_leaves
        np.testing.assert_array_equal(ts.split_feature, tp.split_feature)
        np.testing.assert_allclose(ts.leaf_value, tp.leaf_value,
                                   rtol=2e-4, atol=2e-6)


def test_mesh_size_2_and_4():
    bins, g, h = _make_data(seed=6)
    ts, _, _ = _grow_serial(bins, g, h, PARAMS, 16)
    for n_dev in (2, 4):
        tp, _, _ = _grow_parallel("data", bins, g, h, PARAMS, 16, n_dev=n_dev)
        _assert_tree_equal(ts, tp)


# ---------------------------------------------------------------------------
# data-parallel over leaf-ordered shards (uint8 bins): every device grows
# its own row block with ops/ordered_grow.py, one histogram exchange a split
# ---------------------------------------------------------------------------

def _oracle_grow(bins, g, h, w, B, params):
    """tests/test_grow.py's brute-force leaf-wise oracle over weighted
    rows: [(leaf, feature, threshold)] in split order and every row's
    leaf."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "_grow_oracle", os.path.join(os.path.dirname(__file__),
                                     "test_grow.py"))
    tg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tg)
    F, n = bins.shape
    p = SplitParams(min_data_in_leaf=params.min_data_in_leaf,
                    min_sum_hessian_in_leaf=params.min_sum_hessian_in_leaf)
    gw, hw = g * w, h * w
    leaf = np.zeros(n, np.int64)
    num_leaves, splits = 1, []
    for _ in range(params.num_leaves - 1):
        best = None
        for l in range(num_leaves):
            m = leaf == l
            hist = tg._np_hist(bins[:, m], gw[m], hw[m], w[m], B)
            cand = tg._np_best_split(hist, gw[m].sum(), hw[m].sum(),
                                     w[m].sum(), np.full(F, B), np.zeros(F),
                                     p)
            if cand["feat"] >= 0 and (best is None
                                      or cand["gain"] > best[1]["gain"]):
                best = (l, cand)
        if best is None:
            break
        l, cand = best
        splits.append((cand["feat"], cand["t"]))
        leaf[(leaf == l) & (bins[cand["feat"]] > cand["t"])] = num_leaves
        num_leaves += 1
    return splits, leaf


def _sharded_case(case):
    """(bins uint8 [F, N], g, h, w, params) on seeded data."""
    rng = np.random.RandomState({"uneven": 11, "zero_weight": 12,
                                 "sorted": 13, "min_data": 14}[case])
    n, f, B = 600, 4, 16
    params = PARAMS._replace(num_leaves=7)
    if case == "uneven":
        n = 603                       # neither 2 nor 4 divides it
    bins = rng.randint(0, B, size=(f, n)).astype(np.uint8)
    # integer-valued gradients: float sums are exact in any order, so the
    # oracle's float64 argmax and the programs' float32 one agree
    g = rng.randint(-8, 9, size=n).astype(np.float32)
    h = np.ones(n, np.float32)
    w = np.ones(n, np.float32)
    if case == "zero_weight":
        w = (rng.rand(n) < 0.6).astype(np.float32)    # bagged-out rows
    if case == "sorted":
        # rows in the order of feature 0: a split on it leaves whole
        # shards on one side, so the globally smaller child is one
        # shard's larger one and another shard holds none of a leaf
        g = g + 6.0 * (bins[0] > 9) - 5.0 * (bins[0] < 3)
        order = np.argsort(bins[0], kind="stable")
        bins, g = bins[:, order], g[order]
    if case == "min_data":
        # no shard of 150 (or 300) rows could make two children of 140
        # rows by itself: the bound has to be read on global counts
        params = params._replace(min_data_in_leaf=140)
    return bins, g, h, w, params


@pytest.mark.parametrize("n_dev", [2, 4])
@pytest.mark.parametrize("case", ["uneven", "zero_weight", "sorted",
                                  "min_data"])
def test_ordered_shards_match_serial_and_oracle(case, n_dev):
    bins, g, h, w, params = _sharded_case(case)
    F, N = bins.shape
    B = 16
    meta = (jnp.full((F,), B, jnp.int32), jnp.zeros((F,), bool),
            jnp.ones((F,), bool))
    rows = (jnp.asarray(g), jnp.asarray(h), jnp.asarray(w), jnp.float32(0.1))
    ts, leaf_s, delta_s = grow_tree_ordered(jnp.asarray(bins), *meta, *rows,
                                            params)
    fn = make_parallel_grow(_mesh(n_dev), "data", params)
    tp, leaf_p, delta_p = fn(jnp.asarray(bins), *meta, *rows)
    # structure, thresholds, counts and leaf ids to the bit; values within
    # the tolerance (only the root's float sums depend on the shards' order)
    _assert_tree_equal(ts, tp)
    np.testing.assert_array_equal(np.asarray(ts.internal_count),
                                  np.asarray(tp.internal_count))
    np.testing.assert_array_equal(np.asarray(leaf_s), np.asarray(leaf_p))
    np.testing.assert_allclose(np.asarray(delta_s), np.asarray(delta_p),
                               rtol=2e-4, atol=2e-6)
    assert int(tp.num_leaves) > 2
    if case == "min_data":
        counts = np.asarray(tp.leaf_count)[:int(tp.num_leaves)]
        assert counts.min() >= 140 and counts.min() < 2 * 140
    if case == "sorted":
        # the case is what it says: some shard holds nothing of some leaf
        blocks = np.array_split(np.asarray(leaf_p)[:N - N % n_dev], n_dev)
        assert any(len(np.unique(b)) < int(tp.num_leaves) for b in blocks)
    splits, leaf_o = _oracle_grow(bins, g, h, w, B, params)
    k = int(tp.num_leaves) - 1
    assert [(int(f), int(t)) for f, t in zip(
        np.asarray(tp.split_feature)[:k], np.asarray(tp.split_bin)[:k])] \
        == splits
    live = w > 0      # a bagged-out row's leaf is the walk's, not a segment's
    np.testing.assert_array_equal(np.asarray(leaf_p)[live], leaf_o[live])


def test_ordered_shards_take_the_resident_layout():
    """``bins_rm`` and ``pack_word_lanes`` over the mesh (what
    models/gbdt.py keeps on the devices, one block a shard) give the tree
    the per-tree derivation gives."""
    bins, g, h, w, params = _sharded_case("zero_weight")
    F, N = bins.shape
    mesh = _mesh(4)
    meta = (jnp.full((F,), 16, jnp.int32), jnp.zeros((F,), bool),
            jnp.ones((F,), bool))
    rows = (jnp.asarray(g), jnp.asarray(h), jnp.asarray(w), jnp.float32(0.1))
    fn = make_parallel_grow(mesh, "data", params)
    t0, leaf0, _ = fn(jnp.asarray(bins), *meta, *rows)
    rm = jnp.asarray(np.ascontiguousarray(bins.T))
    words = pack_word_lanes(jnp.asarray(bins), mesh)
    assert len(words) == 1 and words[0].shape[0] == 4 * (N // 4 + 8192)
    t1, leaf1, _ = fn(jnp.asarray(bins), *meta, *rows, bins_rm=rm,
                      bins_words=words)
    for a, b in zip(t0, t1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(leaf0), np.asarray(leaf1))


def test_digit_sums_cross_shards_in_halves():
    """Whole int32 digit sums over ALL shards would wrap once one
    (feature, bin) held 2^31 / 128 rows; the exchange sums 16-bit halves
    instead.  Four shards whose sums total past 2^31: the histogram is
    the float32 of the exact total, the row counts are exact integers,
    and a parent's halves minus a child's are the sibling's."""
    from jax.sharding import PartitionSpec as P
    from lightgbm_tpu.ops import leafhist
    from lightgbm_tpu.parallel.comm import HistExchange

    rng = np.random.RandomState(3)
    k, F, B = 4, 2, 8
    local = rng.randint(-(1 << 30), 1 << 30, size=(k, F, 9, B),
                        dtype=np.int64)
    rows = rng.randint(8_000_000, 10_000_000, size=(k, F, B))
    local[:, :, 6, :] = 64 * rows          # the weight stream: 64 a row
    local[:, :, 7:, :] = 0
    child = local // 3
    child[:, :, 6, :] = 64 * (rows // 3)
    assert (np.abs(local.sum(0)) >= 1 << 31).any()
    ex = HistExchange("shard", k)

    def body(a, b):
        whole, part = ex.hist(a[0]), ex.hist(b[0], root=True)
        return whole, whole - part

    whole, sibling = jax.jit(jax.shard_map(
        body, mesh=_mesh(k), in_specs=(P("shard"), P("shard")),
        out_specs=(P(), P()), check_vma=False))(
            jnp.asarray(local, jnp.int32), jnp.asarray(child, jnp.int32))
    assert whole.shape == (F, 18, B)
    scales = jnp.ones((3,), jnp.float32)
    for halves, exact in ((whole, local.sum(0)),
                          (sibling, (local - child).sum(0))):
        np.testing.assert_array_equal(
            np.asarray(leafhist.digit_row_counts(halves)),
            exact[:, 6, :] // 64)
        # one float32 rounding of each exact digit total, then the
        # digits' weights in float32 as for plain sums
        want = leafhist.combine_digit_sums(
            jnp.asarray(exact.astype(np.float32)), scales)
        got = leafhist.combine_digit_sums(halves, scales)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_data_parallel_booster_grows_ordered_shards():
    """Data-parallel over uint8 unbundled bins keeps the leaf-ordered
    layout resident, accounts the halves it exchanges and grows the
    serial trees; uint16 bins keep ops/grow.py's float histograms."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.models import gbdt as gbdt_mod

    rng = np.random.RandomState(9)
    X = rng.normal(size=(600, 5))
    y = (X[:, 0] - X[:, 1] > 0).astype(np.float32)
    base = {"objective": "binary", "num_leaves": 6, "max_bin": 32,
            "min_data_in_leaf": 10, "min_sum_hessian_in_leaf": 1e-3}
    ds = BinnedDataset.from_matrix(X, y, max_bin=32, min_data_in_leaf=10)
    dp = dict(base, tree_learner="data", num_machines=4)
    ordered = gbdt_mod.GBDT(Config(dict(dp)), ds)
    assert ordered.train_data.bins_words is not None
    assert ordered._comm_traffic == {
        "pmax": {"calls": 1, "bytes": 12},
        "psum": {"calls": 7, "bytes": 4 + 6 * 5 * 18 * 32 * 4}}
    Xw = rng.normal(size=(4000, 5))
    wide = BinnedDataset.from_matrix(Xw, (Xw[:, 0] > 0).astype(np.float32),
                                     max_bin=400, min_data_in_leaf=10)
    assert wide.bins.dtype == np.uint16
    full = gbdt_mod.GBDT(Config(dict(dp, max_bin=400)), wide)
    assert full.train_data.bins_words is None
    assert set(full._comm_traffic) == {"psum"}
    serial = gbdt_mod.GBDT(Config(dict(base)), ds)
    for g in (ordered, serial):
        g.train(3)
    for a, c in zip(ordered.models, serial.models):
        np.testing.assert_array_equal(a.split_feature, c.split_feature)
        np.testing.assert_allclose(a.leaf_value, c.leaf_value,
                                   rtol=2e-4, atol=2e-6)


def test_sharded_train_step_compiles_no_data_in():
    """The data-parallel round takes labels, bin counts and every other
    per-dataset array as arguments: two data sets of one shape lower to
    the same program text, so the persistent compile cache serves the
    second (closed over, 42M labels were a 168 MB constant on every chip
    and every seed compiled cold)."""
    import lightgbm_tpu as lgb

    def lowered(seed):
        rng = np.random.RandomState(seed)
        X = rng.normal(size=(2000, 5))
        y = (X[:, 0] + X[:, 1] * rng.normal(size=2000) > 0).astype(float)
        g = lgb.Booster(params={"objective": "binary", "num_leaves": 5,
                                "verbose": -1, "tree_learner": "data",
                                "num_machines": 4},
                        train_set=lgb.Dataset(X, label=y))._booster
        shards = g._grad_arrays["label"].addressable_shards
        assert len({s.device for s in shards}) == 4
        return g._make_train_step().lower(
            g.train_data.score, g._feature_masks_all(), g._bagging_mask(0),
            jnp.float32(0.1), g._select_view()).as_text()
    a, b = lowered(0), lowered(1)
    assert a == b
    assert "dense<\"0x" not in a        # no large constant of any kind
