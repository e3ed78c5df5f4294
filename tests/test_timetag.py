"""TIMETAG phase account (utils/timetag.py) fed by ``obs.span``, the one
entry point of a host phase: the reference's phase taxonomy (gbdt.cpp:
20-59, serial_tree_learner.cpp:10-37) accumulated host-side with device
sync while the serializing mode is on (ported one for one from the
``timetag.scope`` cases that this file held before the scope went)."""

import numpy as np

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.utils import timetag


def test_phase_accumulators():
    rng = np.random.RandomState(3)
    X = rng.normal(size=(500, 5))
    y = (X[:, 0] > 0).astype(np.float64)
    timetag.enable(True)
    timetag.reset()
    try:
        ds = lgb.Dataset(X, label=y)
        vs = ds.create_valid(X[:100], y[:100])
        lgb.train({"objective": "binary", "num_leaves": 7, "verbose": -1,
                   "metric": "auc", "is_training_metric": True},
                  ds, num_boost_round=3, valid_sets=[vs])
        t = timetag.get_timings()
    finally:
        timetag.enable(False)
    # the standard path runs one fused dispatch per round: gradients +
    # growth + train-score land in GBDT::tree (models/gbdt.py
    # _make_train_step)
    for phase in ("GBDT::tree", "GBDT::valid_score", "GBDT::host_tree",
                  "GBDT::metric", "GBDT::bagging"):
        assert phase in t and t[phase] >= 0.0, (phase, t)
    # set-up is spanned too (io/dataset.py, models/gbdt.py)
    for phase in ("Bin::sample", "Bin::find_bin", "Bin::apply",
                  "Dataset::to_device"):
        assert phase in t and t[phase] >= 0.0, (phase, t)
    timetag.reset()
    assert timetag.get_timings() == {}


def test_phase_accumulators_custom_fobj():
    """The custom-fobj path keeps the reference's per-phase taxonomy
    (gradients arrive from the host, so boosting/tree/train_score are
    separate dispatches)."""
    rng = np.random.RandomState(4)
    X = rng.normal(size=(300, 4))
    y = (X[:, 0] > 0).astype(np.float64)

    def fobj(preds, ds_):
        p = 1.0 / (1.0 + np.exp(-preds))
        return p - ds_.get_label(), p * (1 - p)

    timetag.enable(True)
    timetag.reset()
    try:
        ds = lgb.Dataset(X, label=y)
        lgb.train({"objective": "none", "num_leaves": 7, "verbose": -1},
                  ds, num_boost_round=2, fobj=fobj)
        t = timetag.get_timings()
    finally:
        timetag.enable(False)
    for phase in ("GBDT::boosting", "GBDT::tree", "GBDT::train_score",
                  "GBDT::host_tree"):
        assert phase in t and t[phase] >= 0.0, (phase, t)
    timetag.reset()


def test_disabled_is_noop(monkeypatch):
    """With the serializing mode off a span feeds no timetag account and
    never blocks on the value handed to ``sync``."""
    from lightgbm_tpu.obs import devprof
    synced = []
    monkeypatch.setattr(devprof, "sync",
                        lambda value, source=None: synced.append(source))
    timetag.enable(False)
    timetag.reset()
    with obs.span("GBDT::metric") as s:
        s.sync(np.zeros(3))
    assert timetag.get_timings() == {}
    assert synced == []
    timetag.enable(True)
    try:
        with obs.span("GBDT::metric") as s:
            s.sync(np.zeros(3))
        assert synced == ["GBDT::metric"]
        assert "GBDT::metric" in timetag.get_timings()
    finally:
        timetag.enable(False)
        timetag.reset()
