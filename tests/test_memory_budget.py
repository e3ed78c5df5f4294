"""Memory guardrails: fail fast with an HBM estimate instead of dying in
XLA allocation (the dense-only design's replacement for the reference's
sparse bins, sparse_bin.hpp:67-384, and LRU histogram pool,
feature_histogram.hpp:299-455)."""

import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.models.gbdt import GBDT, estimate_train_memory
from lightgbm_tpu.utils.log import LightGBMError


def _tiny_dataset(n=400, f=6):
    rng = np.random.RandomState(0)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] > 0).astype(np.float32)
    return BinnedDataset.from_matrix(X, y, max_bin=32, min_data_in_leaf=5)


def test_estimate_components_scale_with_problem():
    small = estimate_train_memory(1000, 8, 31, 64, 1)
    big_rows = estimate_train_memory(100_000, 8, 31, 64, 1)
    big_cache = estimate_train_memory(1000, 8, 1023, 256, 1)
    assert set(small) == {"bins_device", "packed_payload",
                         "scores_and_gradients", "score_double_buffer",
                         "histogram_cache", "linear_fit", "working",
                         "total"}
    assert all(v >= 0 for v in small.values())
    assert big_rows["bins_device"] > small["bins_device"]
    assert big_rows["total"] > small["total"]
    # cache term is exactly L * F * 9 * B * 4 bytes
    assert big_cache["histogram_cache"] == 1023 * 8 * 9 * 256 * 4
    assert small["total"] == sum(v for k, v in small.items() if k != "total")


def test_estimate_linear_component():
    """linear_tree (docs/LINEAR_TREES.md §Memory): linear_k bills the
    raw f32 copy, the phi gathers, and the [L, K+1, K+1] normal
    equations; linear_k=0 (the default) is exactly the old estimate."""
    base = estimate_train_memory(1000, 8, 31, 64, 1)
    lin = estimate_train_memory(1000, 8, 31, 64, 1, linear_k=4)
    assert base["linear_fit"] == 0
    m = 5
    assert lin["linear_fit"] == (1000 * 8 * 4 + 2 * 1000 * m * 4
                                 + 3 * 31 * m * m * 4)
    assert lin["total"] == base["total"] + lin["linear_fit"]
    assert lin["total"] == sum(v for k, v in lin.items() if k != "total")


def test_estimate_flags_zero_their_components():
    base = estimate_train_memory(1000, 8, 31, 64, 1)
    donated = estimate_train_memory(1000, 8, 31, 64, 1, donate_score=True)
    nocache = estimate_train_memory(1000, 8, 31, 64, 1, leaf_cache=False)
    assert base["score_double_buffer"] == 1000 * 4
    assert donated["score_double_buffer"] == 0
    assert donated["total"] == base["total"] - base["score_double_buffer"]
    assert nocache["histogram_cache"] == 0
    assert nocache["total"] == base["total"] - base["histogram_cache"]
    for est in (base, donated, nocache):
        assert est["total"] == sum(v for k, v in est.items()
                                   if k != "total")


def test_oversize_config_fails_fast_with_breakdown(monkeypatch):
    ds = _tiny_dataset()
    monkeypatch.setenv("LGBT_DEVICE_MEMORY_BYTES", "1000000")  # 1 MB budget
    cfg = Config({"objective": "binary", "num_leaves": 4095, "max_bin": 255,
                  "min_data_in_leaf": 1, "num_iterations": 1})
    with pytest.raises(LightGBMError) as ei:
        GBDT(cfg, ds)
    msg = str(ei.value)
    assert "exceeds the device budget" in msg
    assert "histogram_cache" in msg          # the breakdown is actionable
    assert "num_leaves" in msg               # and says what to shrink


def test_within_budget_trains(monkeypatch):
    ds = _tiny_dataset()
    monkeypatch.setenv("LGBT_DEVICE_MEMORY_BYTES", str(1 << 33))  # 8 GB
    cfg = Config({"objective": "binary", "num_leaves": 7, "max_bin": 32,
                  "min_data_in_leaf": 5, "num_iterations": 2})
    gb = GBDT(cfg, ds)
    gb.train(2)
    assert len(gb.models) == 2


def test_histogram_pool_size_warns_loudly(capsys, monkeypatch):
    from lightgbm_tpu.utils import log
    log.reset_warn_once()   # the warning is one-shot per process now
    ds = _tiny_dataset()
    monkeypatch.delenv("LGBT_DEVICE_MEMORY_BYTES", raising=False)
    cfg = Config({"objective": "binary", "num_leaves": 255, "max_bin": 32,
                  "min_data_in_leaf": 5, "num_iterations": 1,
                  "histogram_pool_size": 0.001})
    GBDT(cfg, ds)
    err = capsys.readouterr().err
    assert "histogram_pool_size" in err
    assert "does NOT bound memory" in err


def test_histogram_pool_size_default_is_silent(capsys):
    ds = _tiny_dataset()
    cfg = Config({"objective": "binary", "num_leaves": 7, "max_bin": 32,
                  "min_data_in_leaf": 5, "num_iterations": 1})
    GBDT(cfg, ds)
    assert "histogram_pool_size" not in capsys.readouterr().err
