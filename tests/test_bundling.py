"""Exclusive feature bundling (EFB) — parity pins (docs/SPARSE.md).

The acceptance contract of the wide-sparse subsystem:
  * zero-conflict bundling trains BIT-IDENTICAL models to unbundled
    training on the same data: bundling changes no integer digit sum, and
    every grower with a histogram cache searches those integers with one
    arithmetic (ops/split.py ``find_best_split_sums``; the leaf-ordered
    grower's column-space form of it and the cached learner's expansion,
    ops/bundle.py),
  * ``max_conflict_rate=0`` on dense data is a no-op (no bundles, plain
    layout, baseline bit-match by construction),
  * a bundled-trained model lives entirely in ORIGINAL feature space:
    raw predict, the CompiledForest serve path, and a model-file
    round-trip all bit-match each other.
"""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.bundling import BundlePlan, plan_bundles
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.models.gbdt import GBDT

pytestmark = pytest.mark.sparse


def one_hot_data(n=2500, blocks=8, block_size=6, seed=0, act=0.7,
                 levels=5):
    """One-hot-ish blocks: at most one active feature per block per row,
    small integer levels — perfectly exclusive within a block."""
    rng = np.random.RandomState(seed)
    F = blocks * block_size
    X = np.zeros((n, F))
    for b in range(blocks):
        choice = rng.randint(0, block_size, n)
        vals = rng.randint(1, levels, n).astype(float)
        on = rng.rand(n) < act
        X[np.arange(n)[on], (b * block_size + choice)[on]] = vals[on]
    logit = (X[:, 0] - 0.5 * X[:, block_size + 1]
             + 0.3 * X[:, 2 * block_size + 1]
             + rng.normal(0, 0.5, n))
    y = (logit > np.median(logit)).astype(np.float64)
    return X, y


def train_gbdt(X, y, *, enable_bundle, iters=6, extra=None, max_bin=63):
    """Bundled and unbundled data both grow leaf-ordered (the booster
    chooses from the data; PR 36), the bundled columns decoded at the
    split and searched in column space, so every bit-identity asserted
    below is also that search's parity with the plain one."""
    p = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 20,
         "min_sum_hessian_in_leaf": 1e-3,
         "max_bin": max_bin, "num_iterations": iters}
    p.update(extra or {})
    ds = BinnedDataset.from_matrix(X, y, max_bin=max_bin,
                                   min_data_in_leaf=20,
                                   enable_bundle=enable_bundle)
    booster = GBDT(Config(p), ds)
    for _ in range(iters):
        booster.train_one_iter()
    booster._flush_pending()
    return booster, ds


# ---------------------------------------------------------------------------
# planner
# ---------------------------------------------------------------------------

def test_planner_bundles_exclusive_features():
    X, y = one_hot_data()
    ds = BinnedDataset.from_matrix(X, y, max_bin=63, min_data_in_leaf=20,
                                   enable_bundle=True)
    plan = ds.bundle_plan
    assert plan is not None
    assert plan.sample_conflicts == 0
    assert ds.num_columns < ds.num_features
    assert plan.features_bundled > 0
    # every used feature appears in exactly one column
    seen = sorted(f for m in plan.column_members for f in m)
    assert seen == list(range(ds.num_features))
    # offsets of a bundle carve disjoint sub-ranges within max_bin
    for members, offs in zip(plan.column_members, plan.column_offsets):
        if len(members) == 1:
            continue
        end = 1
        for f, o in zip(members, offs):
            assert o == end
            end += ds.mappers[f].num_bin - 1
        assert end <= 63 + 1


def test_dense_data_builds_no_bundles():
    rng = np.random.RandomState(3)
    X = rng.normal(size=(1500, 10))
    y = (X[:, 0] > 0).astype(float)
    ds = BinnedDataset.from_matrix(X, y, max_bin=63, min_data_in_leaf=20,
                                   enable_bundle=True)
    assert ds.bundle_plan is None
    assert ds.num_columns == ds.num_features


def test_is_enable_sparse_false_disables_bundling():
    X, y = one_hot_data()
    ds = BinnedDataset.from_matrix(X, y, max_bin=63, min_data_in_leaf=20,
                                   enable_bundle=True,
                                   is_enable_sparse=False)
    assert ds.bundle_plan is None


def test_max_conflict_rate_budget():
    # two sparse features that conflict on ~10% of rows: rate 0 keeps
    # them apart, a generous rate bundles them
    rng = np.random.RandomState(5)
    n = 2000
    X = np.zeros((n, 2))
    a = rng.rand(n) < 0.15
    b = rng.rand(n) < 0.15
    X[a, 0] = rng.randint(1, 4, a.sum())
    X[b, 1] = rng.randint(1, 4, b.sum())
    sample = X.copy()
    from lightgbm_tpu.io.dataset import build_mappers_from_sample
    mappers = build_mappers_from_sample(
        sample, n, max_bin=63, min_data_in_bin=1, min_data_in_leaf=1)
    strict = plan_bundles(sample, mappers, [0, 1],
                          max_conflict_rate=0.0, max_total_bin=63)
    loose = plan_bundles(sample, mappers, [0, 1],
                         max_conflict_rate=0.5, max_total_bin=63)
    overlap = int(np.count_nonzero(a & b))
    assert overlap > 0
    assert strict is None                      # conflicts forbid merging
    assert loose is not None and len(loose.bundles) == 1
    assert loose.sample_conflicts == overlap


def test_config_validates_max_conflict_rate():
    with pytest.raises(ValueError):
        Config({"max_conflict_rate": -0.1})
    with pytest.raises(ValueError):
        Config({"max_conflict_rate": 1.0})
    Config({"max_conflict_rate": 0.99})        # in range: fine


# ---------------------------------------------------------------------------
# training parity pins
# ---------------------------------------------------------------------------

def test_zero_conflict_bundled_training_bit_identical():
    X, y = one_hot_data()
    b0, ds0 = train_gbdt(X, y, enable_bundle=False)
    b1, ds1 = train_gbdt(X, y, enable_bundle=True)
    assert ds1.bundle_plan is not None and ds1.bundle_plan.sample_conflicts == 0
    assert ds1.num_columns < ds0.num_columns
    assert b1.save_model_to_string() == b0.save_model_to_string()
    p0 = b0.predict(X[:400])
    p1 = b1.predict(X[:400])
    assert np.array_equal(p0, p1)


def test_default_grow_bundled_matches_unbundled_ordered():
    # both grow leaf-ordered; with a screener the bundled data goes back
    # to the cached learner: exact cross-grower parity keeps all three
    # models bit-identical until the screener's first mask
    X, y = one_hot_data(seed=1)
    b0, _ = train_gbdt(X, y, enable_bundle=False)
    b1, _ = train_gbdt(X, y, enable_bundle=True)
    assert (b0._grower_kind, b1._grower_kind) == ("ordered", "ordered")
    assert "EFB columns decoded at the split" in b1._choose_grower()[1]
    assert b1.save_model_to_string() == b0.save_model_to_string()
    b2, _ = train_gbdt(X, y, enable_bundle=True,
                       extra={"feature_screen_ratio": 0.5,
                              "feature_screen_warmup": 100})
    assert b2._grower_kind == "cached"
    assert "screening" in b2._choose_grower()[1]
    # the cached learner expands the integer sums and searches them as
    # the plain columns are searched: bit for bit the unbundled model
    trees = lambda b: b.save_model_to_string().split("parameters")[0] \
        .split("feature importances")[0]
    assert trees(b2) == trees(b0)


def test_goss_and_dart_compose_with_bundling():
    from lightgbm_tpu.models.dart import DART
    from lightgbm_tpu.models.goss import GOSS
    X, y = one_hot_data(seed=4)
    for cls, extra in ((GOSS, {"boosting_type": "goss"}),
                       (DART, {"boosting_type": "dart"})):
        p = {"objective": "binary", "num_leaves": 15,
             "min_data_in_leaf": 20, "min_sum_hessian_in_leaf": 1e-3,
             "max_bin": 63, "num_iterations": 4, **extra}
        ds = BinnedDataset.from_matrix(X, y, max_bin=63,
                                       min_data_in_leaf=20,
                                       enable_bundle=True)
        assert ds.bundle_plan is not None
        b = cls(Config(p), ds)
        for _ in range(4):
            b.train_one_iter()
        assert np.isfinite(b.predict_raw(X[:100])).all()


def test_bagging_composes_with_bundling():
    X, y = one_hot_data(seed=6)
    b0, _ = train_gbdt(X, y, enable_bundle=False,
                       extra={"bagging_fraction": 0.6, "bagging_freq": 1})
    b1, _ = train_gbdt(X, y, enable_bundle=True,
                       extra={"bagging_fraction": 0.6, "bagging_freq": 1})
    # same RNG streams + exact expansion -> bagged runs stay bit-equal
    assert b1.save_model_to_string() == b0.save_model_to_string()


def test_valid_set_rides_training_bundles():
    X, y = one_hot_data(seed=7)
    Xv, yv = one_hot_data(n=800, seed=8)
    p = {"objective": "binary", "metric": "auc", "num_leaves": 15,
         "min_data_in_leaf": 20, "min_sum_hessian_in_leaf": 1e-3,
         "max_bin": 63, "num_iterations": 5}
    ds = BinnedDataset.from_matrix(X, y, max_bin=63, min_data_in_leaf=20,
                                   enable_bundle=True)
    valid = ds.create_valid(Xv, yv)
    assert valid.bundle_plan is ds.bundle_plan
    b = GBDT(Config(p), ds)
    b.add_valid_dataset(valid)
    for _ in range(5):
        b.train_one_iter()
    vals = b.eval_metrics()
    assert np.isfinite(vals["valid_1"]["auc"])
    # device-replayed valid scores == host predict on the raw rows
    host = b.predict_raw(Xv)[0]
    dev = b.valid_data[0].host_score()[0]
    np.testing.assert_allclose(dev, host, rtol=0, atol=2e-4)


@pytest.mark.parametrize("learner", ["data", "feature", "voting"])
def test_parallel_learners_compose_with_bundling(learner):
    # conftest forces 8 virtual CPU devices; every distributed strategy
    # must accept the bundled column matrix (expansion happens after the
    # reduce / before the election — docs/SPARSE.md strategy matrix)
    if len(jax.devices()) < 2:
        pytest.skip("needs virtual devices")
    X, y = one_hot_data(n=1000, seed=21)
    p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 20,
         "min_sum_hessian_in_leaf": 1e-3, "max_bin": 63,
         "num_iterations": 2, "tree_learner": learner, "num_machines": 2}
    ds = BinnedDataset.from_matrix(X, y, max_bin=63, min_data_in_leaf=20,
                                   enable_bundle=True)
    assert ds.bundle_plan is not None
    b = GBDT(Config(p), ds)
    for _ in range(2):
        b.train_one_iter()
    b._flush_pending()
    assert len(b.models) == 2
    F = ds.num_total_features
    for t in b.models:
        n = t.num_leaves - 1
        assert (t.split_feature[:n] < F).all()
    assert np.isfinite(b.predict_raw(X[:100])).all()


# ---------------------------------------------------------------------------
# model artifacts stay in original feature space
# ---------------------------------------------------------------------------

def test_bundled_model_predict_paths_bit_match(tmp_path):
    import lightgbm_tpu as lgb
    from lightgbm_tpu.serve.forest import CompiledForest
    X, y = one_hot_data(seed=9)
    params = {"objective": "binary", "num_leaves": 15,
              "min_data_in_leaf": 20, "min_sum_hessian_in_leaf": 1e-3,
              "max_bin": 63, "verbose": -1, "enable_bundle": True}
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=6)
    assert bst._booster.train_set.bundle_plan is not None
    # trees store original feature indices only
    F = X.shape[1]
    for t in bst._booster.models:
        n = t.num_leaves - 1
        assert (t.split_feature[:n] >= 0).all()
        assert (t.split_feature[:n] < F).all()

    Xq = X[:512]
    bst.compile()
    raw = bst.predict(Xq, raw_score=True)          # Booster.predict path
    cf = CompiledForest.from_booster(bst)
    raw_cf = cf.predict(Xq, raw_score=True)        # the serve /predict path
    assert np.array_equal(raw, raw_cf)

    # model-file round-trip: loaded model predicts bit-identically
    path = str(tmp_path / "bundled.txt")
    bst.save_model(path)
    loaded = lgb.Booster(model_file=path)
    loaded.compile()
    raw_loaded = loaded.predict(Xq, raw_score=True)
    assert np.array_equal(raw, raw_loaded)


# ---------------------------------------------------------------------------
# loaders agree
# ---------------------------------------------------------------------------

def test_two_round_loader_builds_identical_bundles(tmp_path):
    X, y = one_hot_data(n=1200, seed=11)
    path = str(tmp_path / "sparse.tsv")
    with open(path, "w") as fh:
        for i in range(X.shape[0]):
            fh.write("\t".join([f"{y[i]:g}"] +
                               [f"{v:g}" for v in X[i]]) + "\n")
    from lightgbm_tpu.io.streaming import load_file_two_round
    ds_mem = BinnedDataset.from_matrix(X, y, max_bin=63,
                                       min_data_in_leaf=20,
                                       enable_bundle=True)
    ds_str = load_file_two_round(path, max_bin=63, min_data_in_leaf=20,
                                 enable_bundle=True)
    assert ds_mem.bundle_plan is not None and ds_str.bundle_plan is not None
    assert ds_str.bundle_plan.signature() == ds_mem.bundle_plan.signature()
    assert np.array_equal(ds_str.bins, ds_mem.bins)
    assert np.array_equal(ds_str.metadata.label, ds_mem.metadata.label)


def test_binary_cache_roundtrips_bundle_plan(tmp_path):
    X, y = one_hot_data(n=1000, seed=12)
    ds = BinnedDataset.from_matrix(X, y, max_bin=63, min_data_in_leaf=20,
                                   enable_bundle=True)
    path = str(tmp_path / "ds.bin")
    ds.save_binary(path)
    back = BinnedDataset.load_binary(path)
    assert back.bundle_plan is not None
    assert back.bundle_plan.signature() == ds.bundle_plan.signature()
    assert np.array_equal(back.bins, ds.bins)
    assert back.num_features == ds.num_features
    assert back.num_columns == ds.num_columns


def test_bundle_plan_state_roundtrip():
    plan = BundlePlan([[0, 2], [1]], [[1, 4], [0]], 3, sample_conflicts=7)
    back = BundlePlan.from_state(plan.to_state())
    assert back.signature() == plan.signature()
    assert back.sample_conflicts == 7
    assert BundlePlan.from_state(None) is None


# ---------------------------------------------------------------------------
# bench_regress passthrough (informational keys)
# ---------------------------------------------------------------------------

def test_bench_regress_passes_sparse_keys_through():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_regress", os.path.join(os.path.dirname(__file__), "..",
                                      "tools", "bench_regress.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    base = {"metric": "boosting_iters_per_sec_ctrlike500k", "value": 2.0,
            "unit": "iters/sec", "auc": 0.761,
            "efb": {"enabled": False, "columns": 2000,
                    "num_features": 2000, "bundles": 0},
            "screening": {"ratio": 0.0, "active_features_last": 2000}}
    cand = {"metric": "boosting_iters_per_sec_ctrlike500k", "value": 5.0,
            "unit": "iters/sec", "auc": 0.760,
            "efb": {"enabled": True, "columns": 40,
                    "num_features": 2000, "bundles": 38},
            "screening": {"ratio": 0.5, "active_features_last": 1000}}
    verdict = mod.compare(base, cand, threshold_pct=5.0)
    assert verdict["ok"]
    assert verdict["efb_candidate"]["columns"] == 40
    assert verdict["efb_baseline"]["columns"] == 2000
    assert verdict["screening_candidate"]["ratio"] == 0.5
    assert verdict["auc_baseline"] == 0.761
    # old baselines without the keys stay comparable
    old = {"metric": "boosting_iters_per_sec_ctrlike500k", "value": 2.0,
           "unit": "iters/sec"}
    v2 = mod.compare(old, cand, threshold_pct=5.0)
    assert v2["ok"] and "efb_baseline" not in v2


# ---------------------------------------------------------------------------
# the column-space split search (ops/bundle.py, PR 36)
# ---------------------------------------------------------------------------

def _random_layout(rng, B=63, last=(2, 2)):
    """A bundle plan over features of mixed bin counts: identity
    columns (one categorical), bundles of two-bin members, a bundle with
    many-bin members; ``last``: the bin counts of the last bundle's."""
    num_bins, cols, offs, is_cat = [], [], [], []
    def column(member_bins):
        members, o, off = [], 1, []
        for nb in member_bins:
            members.append(len(num_bins))
            num_bins.append(nb)
            is_cat.append(False)
            off.append(o)
            o += nb - 1
        assert o <= B
        cols.append(members)
        offs.append(off)
    for nb in (int(rng.randint(2, B)), B, 5):           # identity columns
        cols.append([len(num_bins)])
        offs.append([0])
        num_bins.append(nb)
        is_cat.append(nb == 5)
    column([2] * 40)
    column([2] * 7 + [6, 2, 9] + [2] * 5)
    column(list(last))
    plan = BundlePlan(cols, offs, len(num_bins))
    return plan, np.asarray(num_bins, np.int32), np.asarray(is_cat)


@pytest.mark.parametrize("case", ["random", "ties", "masked", "hessian_floor",
                                  "unsplittable", "padded"])
def test_column_space_search_is_the_expanded_search(case):
    """``find_best_split_columns`` on the bundled columns against
    ``find_best_split_sums`` over ``expand_digit_sums``' ``[F, 9, B]`` on
    random digit sums of a batch of two leaves: the same gain, feature,
    threshold and sums of both sides, bit for bit (the sums are exact
    integers either way); and against ``find_best_split`` over the
    expanded float32 histograms: the same feature and threshold wherever
    no other candidate comes within float32's rounding of the best.  ``ties`` repeats members' slots, so
    that equal gains meet across the three kinds of feature and the
    lowest original feature must win.  ``padded`` searches the layout as
    the device holds it on a rung of ``bundled_shape``: columns that hold
    no feature (every row in their bin 0), features of no slot, ``multi``
    filled up with -1; the record is the plain layout's."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops import leafhist
    from lightgbm_tpu.ops.bundle import (BundleDecode, column_search,
                                         expand_digit_sums,
                                         find_best_split_columns)
    from lightgbm_tpu.ops.split import (SplitParams, find_best_split,
                                        find_best_split_sums, sums_totals)
    B = 63
    for seed in range(6):
        rng = np.random.RandomState(seed)
        # three many-bin members where the layout is padded, so that
        # ``multi`` is filled up to four
        plan, num_bin, is_cat = _random_layout(
            rng, B, last=(2, 4) if case == "padded" else (2, 2))
        dn = plan.decode_arrays(num_bin, np.zeros_like(num_bin), B)
        dec = BundleDecode.from_tables(dn)
        C, F = plan.num_columns, plan.num_features
        Cp, Fp = (C + 3, F + 5) if case == "padded" else (C, F)
        # digit sums as a leaf's could be: every column sums to one total
        rows = rng.randint(200, 4000, size=2)
        sums = np.zeros((2, Cp, 9, B), np.int32)
        for leaf in range(2):
            # a row's nine digits go to one slot of EVERY column
            dig = rng.randint(-100, 100, (rows[leaf], 9))
            dig[:, 3:6] = np.abs(dig[:, 3:6])       # hessians
            dig[:, 6:] = [0, 0, 1]                  # a row counts once
            if case == "ties":
                dig[:, :3] = (dig[:, :1] // 50) * 50
                dig[:, 3:6] = 40
            for c, members in enumerate(plan.column_members):
                slots = 1 + sum(num_bin[f] - 1 for f in members) \
                    if len(members) > 1 else num_bin[members[0]]
                where = rng.randint(0, slots, rows[leaf])
                if case == "ties":
                    # two columns in step, slot for slot: their features'
                    # gains are EQUAL wherever both have a candidate
                    where = (np.arange(rows[leaf]) * 7 + c % 2) % min(
                        slots, 3)
                np.add.at(sums[leaf, c], (slice(None), where), dig.T)
            sums[leaf, C:, :, 0] = dig.sum(axis=0)[None, :]
        scales = jnp.asarray([1e-2, 1e-3, 1.0], jnp.float32)
        sums = jnp.asarray(sums)
        tg, th, tc = sums_totals(sums, scales)
        mask = np.ones(F, bool)
        if case == "masked":
            mask[rng.rand(F) < 0.5] = False
        p = SplitParams(min_data_in_leaf=0, min_sum_hessian_in_leaf=(
            float(th.min()) * 0.3 if case == "hessian_floor" else 1e-3))
        can = jnp.asarray([True, case != "unsplittable"])
        args = (jnp.asarray(num_bin), jnp.asarray(is_cat), jnp.asarray(mask))
        padded = BundleDecode.from_tables(plan.decode_arrays(
            num_bin, np.zeros_like(num_bin), B, (Cp, Fp)))
        assert padded.multi.shape[0] == (4 if case == "padded" else 2)
        got = find_best_split_columns(
            sums, scales, can, p, column_search(
                padded, jnp.pad(args[0], (0, Fp - F), constant_values=1),
                jnp.pad(args[1], (0, Fp - F)), jnp.pad(args[2], (0, Fp - F))))
        # the plain search over the expanded sums of the plan's own columns
        sums = sums[:, :C]
        expanded = expand_digit_sums(sums, dec)
        want = find_best_split_sums(expanded, scales, *args, can, p)
        ok = np.asarray(want.feature) >= 0
        assert ok[0] and ok[1] == (case != "unsplittable")
        for field in want._fields:
            w, g = np.asarray(getattr(want, field)), np.asarray(
                getattr(got, field))
            if field.startswith(("left_", "right_")):
                w, g = w[ok], g[ok]         # an unsplittable leaf's: unread
            np.testing.assert_array_equal(g, w, err_msg=f"{field} {seed}")
        # the float32 search over the expanded histograms
        floats = find_best_split(
            leafhist.combine_digit_sums(expanded, scales), tg, th, tc,
            *args, can, p)
        # (a gain is a difference of the leaf's own terms in float32)
        np.testing.assert_allclose(np.asarray(floats.gain)[ok],
                                   np.asarray(got.gain)[ok], rtol=1e-3,
                                   atol=1e-4)
        if case != "ties":
            np.testing.assert_array_equal(np.asarray(floats.feature),
                                          np.asarray(got.feature))
            np.testing.assert_array_equal(np.asarray(floats.threshold),
                                          np.asarray(got.threshold))


# ---------------------------------------------------------------------------
# the bundled layout's rung on the device (ops/ordered_grow.py, PR 36)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plan_shape,device_shape", [
    ((5, 20), (8, 20)),             # whole words up to 8 of them
    ((31, 33), (32, 34)),
    ((35, 700), (40, 704)),         # even counts of words to 16
    ((66, 4228), (80, 4352)),       # multiples of 4 words to 32:
    ((71, 4226), (80, 4352)),       #   the one-hot cell's seeds, one rung
    ((74, 4228), (80, 4352)),
    ((80, 4352), (80, 4352)),       # a rung is its own rung
    ((81, 4353), (96, 4608)),
])
def test_bundled_shape_ladder(plan_shape, device_shape):
    from lightgbm_tpu.ops.ordered_grow import bundled_shape
    assert bundled_shape(*plan_shape) == device_shape


def test_nearby_bundle_plans_share_one_compiled_round():
    """Two tables of the same width whose plans differ in columns (8 and
    6) lie on one rung (8 columns: two bin words), so the second booster
    compiles no training program; the pad columns and features change no
    tree (the parity pins above run on the padded layout)."""
    from lightgbm_tpu.obs import compile_ledger

    def train_events():
        return [e for e in compile_ledger.events()
                if e["program"] in ("train_step", "pack_words")]
    Xa, ya = one_hot_data(blocks=8, block_size=6, seed=5)
    Xb, yb = one_hot_data(blocks=6, block_size=8, seed=6)
    a, dsa = train_gbdt(Xa, ya, enable_bundle=True, iters=2)
    before = len(train_events())
    b, dsb = train_gbdt(Xb, yb, enable_bundle=True, iters=2)
    assert dsa.num_columns != dsb.num_columns
    assert a._device_shape == b._device_shape == (8, 48)
    assert a.train_data.bins.shape[0] == b.train_data.bins.shape[0] == 8
    assert a._bundle.col.shape == b._bundle.col.shape
    assert train_events()[before:] == []
    # a screener's compacted views keep the plan's own shape (they grow
    # on ops/grow.py)
    c, dsc = train_gbdt(Xb, yb, enable_bundle=True, iters=1,
                        extra={"feature_screen_ratio": 0.5})
    assert c._device_shape == (dsc.num_columns, dsc.num_features)
