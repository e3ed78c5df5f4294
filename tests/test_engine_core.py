"""End-to-end training quality gates, modeled on the reference's
tests/python_package_test/test_engine.py thresholds, plus deterministic
parity gates against golden numbers measured from the compiled reference CLI
on the bundled example datasets (same conf, sampling disabled)."""

import os

import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.io.parser import parse_file
from lightgbm_tpu.models import GBDT, create_boosting

_EXAMPLES = "/root/reference/examples"
_HAS_EXAMPLES = os.path.isdir(_EXAMPLES)


def _make_synthetic_binary(n=3000, f=10, seed=7):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, f))
    logit = X[:, 0] * 1.5 - X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
    y = (logit + rng.normal(scale=0.5, size=n) > 0).astype(np.float64)
    return X, y


def _train(cfg_dict, X, y, Xv=None, yv=None, side=None):
    cfg = Config(cfg_dict)
    ds = BinnedDataset.from_matrix(X, y, max_bin=cfg.max_bin,
                                   min_data_in_leaf=cfg.min_data_in_leaf)
    if side:
        ds.metadata.load_side_files(side)
    booster = create_boosting(cfg, ds)
    if Xv is not None:
        vs = ds.create_valid(Xv, yv)
        if side:
            pass
        booster.add_valid_dataset(vs)
    booster.train(cfg.num_iterations)
    return booster


def test_synthetic_binary_quality():
    X, y = _make_synthetic_binary()
    Xv, yv = _make_synthetic_binary(seed=8)
    b = _train({"objective": "binary", "metric": "binary_logloss,auc",
                "num_leaves": 31, "num_iterations": 50, "min_data_in_leaf": 20,
                "min_sum_hessian_in_leaf": 1.0, "max_bin": 63}, X, y, Xv, yv)
    m = b.eval_metrics()["valid_1"]
    assert m["auc"] > 0.93
    assert m["binary_logloss"] < 0.35


def test_synthetic_regression_quality():
    rng = np.random.RandomState(0)
    X = rng.normal(size=(3000, 8))
    y = X[:, 0] * 2 + np.sin(X[:, 1] * 3) + rng.normal(scale=0.1, size=3000)
    Xv = rng.normal(size=(500, 8))
    yv = Xv[:, 0] * 2 + np.sin(Xv[:, 1] * 3) + rng.normal(scale=0.1, size=500)
    b = _train({"objective": "regression", "metric": "l2", "num_leaves": 63,
                "num_iterations": 60, "min_data_in_leaf": 10,
                "min_sum_hessian_in_leaf": 0.1, "max_bin": 127,
                "learning_rate": 0.1}, X, y, Xv, yv)
    # reference "l2" metric is RMSE
    assert b.eval_metrics()["valid_1"]["l2"] < 0.35


def test_early_stopping_and_best_iteration():
    X, y = _make_synthetic_binary(n=1200)
    Xv, yv = _make_synthetic_binary(n=600, seed=9)
    cfg = Config({"objective": "binary", "metric": "binary_logloss",
                  "num_leaves": 63, "num_iterations": 200,
                  "min_data_in_leaf": 5, "min_sum_hessian_in_leaf": 0.1,
                  "max_bin": 63, "learning_rate": 0.3,
                  "early_stopping_round": 5})
    ds = BinnedDataset.from_matrix(X, y, max_bin=63, min_data_in_leaf=5)
    b = GBDT(cfg, ds)
    b.add_valid_dataset(ds.create_valid(Xv, yv))
    b.train(200)
    # stopped early, with a recorded best iteration
    assert b.iter_ < 200
    assert 0 < b.best_iteration <= b.iter_


def test_model_save_load_predict_roundtrip(tmp_path):
    X, y = _make_synthetic_binary(n=1500)
    b = _train({"objective": "binary", "num_leaves": 15, "num_iterations": 10,
                "min_data_in_leaf": 10, "min_sum_hessian_in_leaf": 1.0,
                "max_bin": 63}, X, y)
    pred = b.predict(X)
    text = b.save_model_to_string()
    b2 = GBDT(Config({"objective": "binary"}), None)
    b2.load_model_from_string(text)
    pred2 = b2.predict(X)
    np.testing.assert_allclose(pred, pred2, rtol=1e-9)
    # file round trip
    path = str(tmp_path / "model.txt")
    b.save_model_to_file(path)
    b3 = GBDT(Config({}), None)
    b3.load_model_from_string(open(path).read())
    np.testing.assert_allclose(b.predict_raw(X), b3.predict_raw(X), rtol=1e-9)


def test_bagging_and_feature_fraction_still_learn():
    X, y = _make_synthetic_binary()
    b = _train({"objective": "binary", "metric": "auc", "num_leaves": 31,
                "num_iterations": 40, "min_data_in_leaf": 20,
                "min_sum_hessian_in_leaf": 1.0, "max_bin": 63,
                "bagging_fraction": 0.7, "bagging_freq": 2,
                "feature_fraction": 0.7, "is_training_metric": True}, X, y)
    assert b.eval_metrics()["training"]["auc"] > 0.95


def test_dart_goss_learn():
    X, y = _make_synthetic_binary(n=2000)
    for bt in ("dart", "goss"):
        b = _train({"objective": "binary", "metric": "auc",
                    "boosting_type": bt, "num_leaves": 15,
                    "num_iterations": 30, "min_data_in_leaf": 20,
                    "min_sum_hessian_in_leaf": 1.0, "max_bin": 63,
                    "learning_rate": 0.25, "is_training_metric": True}, X, y)
        assert b.eval_metrics()["training"]["auc"] > 0.9, bt


def test_goss_sampling_actually_runs():
    """Regression (round-3 review): the fused train step must not bypass
    GOSS._gradients — after the warmup period the row mask must be a real
    top-gradient subsample (some zero weights, amplified small-gradient
    rows), not all ones."""
    import numpy as np
    X, y = _make_synthetic_binary(n=2000)
    # GOSS warmup lasts 1/learning_rate rounds: lr=0.5 -> sampling from
    # round 2 on
    b = _train({"objective": "binary", "boosting_type": "goss",
                "top_rate": 0.2, "other_rate": 0.1, "num_leaves": 7,
                "num_iterations": 8, "min_data_in_leaf": 20,
                "max_bin": 63, "learning_rate": 0.5}, X, y)
    w = np.asarray(b._row_weight)
    kept = np.count_nonzero(w)
    assert kept < len(w), "GOSS never sampled: row weights are all ones"
    # kept fraction ~ top_rate + other_rate (amplification rides the
    # gradients, so the mask itself is 0/1)
    assert kept <= int(0.45 * len(w))
    assert kept >= int(0.15 * len(w))


def test_multiclass_quality():
    rng = np.random.RandomState(3)
    n = 3000
    X = rng.normal(size=(n, 6))
    y = (X[:, 0] > 0.5).astype(int) + (X[:, 1] > 0).astype(int) + \
        (X[:, 2] > -0.5).astype(int)  # 4 classes 0..3
    b = _train({"objective": "multiclass", "num_class": 4,
                "metric": "multi_logloss,multi_error", "num_leaves": 31,
                "num_iterations": 30, "min_data_in_leaf": 10,
                "min_sum_hessian_in_leaf": 0.1, "max_bin": 63,
                "is_training_metric": True}, X, y)
    m = b.eval_metrics()["training"]
    assert m["multi_error"] < 0.05
    prob = b.predict(X)
    assert prob.shape == (4, n)
    np.testing.assert_allclose(prob.sum(axis=0), 1.0, rtol=1e-5)


def test_rollback_one_iter():
    X, y = _make_synthetic_binary(n=1000)
    b = _train({"objective": "binary", "num_leaves": 15, "num_iterations": 5,
                "min_data_in_leaf": 10, "min_sum_hessian_in_leaf": 1.0,
                "max_bin": 63, "is_training_metric": True,
                "metric": "binary_logloss"}, X, y)
    before = b.eval_metrics()["training"]["binary_logloss"]
    score_before = np.asarray(b.train_data.score).copy()
    b.train_one_iter()
    b.rollback_one_iter()
    np.testing.assert_allclose(np.asarray(b.train_data.score), score_before,
                               atol=1e-6)
    assert b.num_trees() == 5


@pytest.mark.skipif(not _HAS_EXAMPLES, reason="reference examples not present")
def test_reference_binary_parity_deterministic():
    """Golden-number gate: deterministic run (no sampling) on the reference's
    binary example must match the compiled reference CLI's printed metrics
    (measured in this environment) to 4 decimal places at iteration 30:
      training auc 0.933725, logloss 0.415342;
      valid auc 0.818853, logloss 0.525583."""
    y, X, _ = parse_file(f"{_EXAMPLES}/binary_classification/binary.train")
    yt, Xt, _ = parse_file(f"{_EXAMPLES}/binary_classification/binary.test")
    cfg = Config({"objective": "binary", "metric": ["auc", "binary_logloss"],
                  "num_leaves": 63, "num_iterations": 30, "max_bin": 255,
                  "min_data_in_leaf": 50, "min_sum_hessian_in_leaf": 5.0,
                  "learning_rate": 0.1, "is_training_metric": True,
                  "feature_fraction": 1.0, "bagging_freq": 0})
    ds = BinnedDataset.from_matrix(X, y, max_bin=255, min_data_in_leaf=50)
    ds.metadata.load_side_files(f"{_EXAMPLES}/binary_classification/binary.train")
    vs = ds.create_valid(Xt, yt)
    vs.metadata.load_side_files(f"{_EXAMPLES}/binary_classification/binary.test")
    b = GBDT(cfg, ds)
    b.add_valid_dataset(vs)
    b.train(30)
    m = b.eval_metrics()
    assert abs(m["training"]["auc"] - 0.933725) < 1e-4
    assert abs(m["training"]["binary_logloss"] - 0.415342) < 1e-4
    assert abs(m["valid_1"]["auc"] - 0.818853) < 1e-4
    assert abs(m["valid_1"]["binary_logloss"] - 0.525583) < 1e-4


@pytest.mark.skipif(not _HAS_EXAMPLES, reason="reference examples not present")
def test_reference_regression_parity_deterministic():
    """Reference CLI (sampling disabled) golden numbers for the regression
    example: sqrt-L2 at iter 100 (measured in this environment)."""
    y, X, _ = parse_file(f"{_EXAMPLES}/regression/regression.train")
    cfg = Config({"objective": "regression", "metric": "l2", "num_leaves": 31,
                  "num_iterations": 30, "max_bin": 255,
                  "min_data_in_leaf": 100, "min_sum_hessian_in_leaf": 5.0,
                  "learning_rate": 0.05, "is_training_metric": True,
                  "feature_fraction": 1.0, "bagging_freq": 0})
    ds = BinnedDataset.from_matrix(X, y, max_bin=255, min_data_in_leaf=100)
    b = GBDT(cfg, ds)
    b.train(30)
    # golden: measured from .refbuild/lightgbm with identical flags
    golden = _reference_cli_regression_golden()
    if golden is not None:
        assert abs(b.eval_metrics()["training"]["l2"] - golden) < 2e-4
    else:
        assert b.eval_metrics()["training"]["l2"] < 0.55


def _reference_cli_regression_golden():
    """Runs the compiled reference CLI if present to produce the golden
    number; returns None when unavailable."""
    import subprocess, tempfile, re
    exe = "/root/repo/.refbuild/lightgbm"
    if not os.path.exists(exe):
        return None
    with tempfile.TemporaryDirectory() as td:
        out = subprocess.run(
            [exe, "task=train", "objective=regression", "metric=l2",
             "num_leaves=31", "num_trees=30", "max_bin=255",
             "min_data_in_leaf=100", "min_sum_hessian_in_leaf=5.0",
             "learning_rate=0.05", "is_training_metric=true",
             "feature_fraction=1.0", "bagging_freq=0",
             f"data={_EXAMPLES}/regression/regression.train",
             f"output_model={td}/m.txt"],
            capture_output=True, text=True, cwd=td)
        matches = re.findall(r"Iteration:30, training l2 : ([0-9.]+)",
                             out.stdout + out.stderr)
        return float(matches[-1]) if matches else None


def test_goss_keeps_exactly_top_cnt_on_ties():
    """ArgMaxAtK semantics (goss.hpp:79-124): with massively tied |g*h|
    the kept top set must still be exactly top_rate*N rows (round-2
    VERDICT weak #8: a >= threshold rule kept every tie)."""
    import jax.numpy as jnp
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.models.goss import GOSS

    rng = np.random.RandomState(0)
    n = 1000
    X = rng.normal(size=(n, 3))
    y = (X[:, 0] > 0).astype(np.float64)
    cfg = Config({"objective": "binary", "boosting": "goss",
                  "top_rate": 0.2, "other_rate": 0.0,
                  "num_leaves": 4, "min_data_in_leaf": 10})
    ds = BinnedDataset.from_matrix(X, y, max_bin=16, min_data_in_leaf=10)
    from lightgbm_tpu.ops import goss as goss_ops
    g = GOSS(cfg, ds)
    g.iter_ = 100                       # past the 1/learning_rate warmup
    # all gradients identical in magnitude -> every row ties
    grad = jnp.ones((1, g._padded_rows), jnp.float32)
    hess = jnp.ones((1, g._padded_rows), jnp.float32)
    mask, _ = goss_ops.sample(grad, hess, g._ones_weight, g._goss_round(),
                              g._goss)
    assert int(np.count_nonzero(np.asarray(mask))) == int(0.2 * n)
    # the lowest row indices win the ties, and no pad row is drawn
    assert np.asarray(mask)[:int(0.2 * n)].all()


def test_goss_samples_with_custom_fobj():
    """GOSS sampling is objective-agnostic (reference Bagging step runs
    for custom objectives too): a custom fobj must still trigger the
    draw via _transform_host_gradients."""
    import lightgbm_tpu as lgb

    X, y = _make_synthetic_binary(n=1500)

    def fobj(preds, ds_):
        p = 1.0 / (1.0 + np.exp(-preds))
        return p - ds_.get_label(), p * (1 - p)

    bst = lgb.train({"objective": "none", "boosting": "goss",
                     "top_rate": 0.2, "other_rate": 0.1, "num_leaves": 7,
                     "learning_rate": 0.5, "verbose": -1,
                     "min_data_in_leaf": 20},
                    lgb.Dataset(X, label=y), num_boost_round=8, fobj=fobj)
    w = np.asarray(bst._booster._row_weight)
    assert np.count_nonzero(w) < len(w), \
        "GOSS never sampled under a custom fobj"
