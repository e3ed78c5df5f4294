"""Exclusive feature bundling (EFB) — parity pins (docs/SPARSE.md).

The acceptance contract of the wide-sparse subsystem:
  * zero-conflict bundling trains BIT-IDENTICAL models to unbundled
    training on the same data (the integer digit-sum expansion makes
    this exact, ops/bundle.py),
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
    """Bundled data grows on the cached learner, unbundled data
    leaf-ordered (the booster chooses from the data), so every
    bit-identity asserted below is also the growers' parity."""
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
    # bundled data grows on the cached learner, unbundled leaf-ordered;
    # exact cross-grower parity keeps the models bit-identical
    X, y = one_hot_data(seed=1)
    b0, _ = train_gbdt(X, y, enable_bundle=False)
    b1, _ = train_gbdt(X, y, enable_bundle=True)
    assert (b0._grower_kind, b1._grower_kind) == ("ordered", "cached")
    assert b1.save_model_to_string() == b0.save_model_to_string()


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
