"""Fused Pallas forest-walk serving strategy (ops/pallas_walk.py +
``serve_walk``, docs/SERVING.md §Serving strategies).

Tier-1 CPU pins, all interpreter-mode (``pl.pallas_call(interpret=True)``
— the same kernel body a TPU runs, minus the mosaic lowering):

- fused vs gather parity ≤1e-6 across the bucket ladder (n=1..700,
  binned + raw + transformed), on constant, linear, categorical/NaN,
  DART and multiclass forests — the strategies must be swappable per
  forest with nothing downstream noticing;
- bin quantization: bf16 leaf storage activates only under the
  QUANTIZE_LEAF_ATOL bound and pins to it; past the bound the forest
  falls back to f32 and the named ``forest_quantize_fallback`` counter
  records why;
- gather byte-identity: ``serve_walk=gather`` builds/compiles ZERO
  walk-named programs (ledger delta empty) and keeps the atol=0
  ``Booster.predict`` contract bit-for-bit;
- warmup covers every dispatchable bucket: a ``max_bucket`` strictly
  between ladder rungs warms the rung ABOVE it (where bucket_for routes
  the largest admitted requests), pinned by a zero-compile ledger delta
  on the first such request — both strategies;
- the bench_regress ``--latency-threshold`` gate trips on a p99
  regression per (strategy, batch) point and skips with a note when a
  side lacks the ``latency_sweep`` block.
"""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import LightGBMError, obs
from lightgbm_tpu.serve import CompiledForest

pytestmark = pytest.mark.walk

BUCKETS = [32, 128, 512]
# crosses every rung boundary; 700 > max bucket streams chunked
SIZES = [1, 33, 129, 700]


def _train(n=800, num_class=1, seed=0, num_boost_round=4, extra=None):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, 6))
    X[:, 3] = np.round(X[:, 3] * 4) / 4       # boundary-tied values
    params = {"num_leaves": 7, "verbose": -1, "min_data_in_leaf": 20}
    if num_class > 1:
        y = np.digitize(X[:, 0], [-0.5, 0.5]).astype(np.float64)
        params.update({"objective": "multiclass", "num_class": num_class})
    else:
        y = (X[:, 0] + 0.3 * X[:, 1] > 0).astype(np.float64)
        params["objective"] = "binary"
    params.update(extra or {})
    bst = lgb.train(params, lgb.Dataset(X, label=y),
                    num_boost_round=num_boost_round)
    return bst, X


def _pair(bst, **kw):
    fused = CompiledForest.from_booster(bst, buckets=BUCKETS,
                                        serve_walk="fused", **kw)
    gather = CompiledForest.from_booster(bst, buckets=BUCKETS,
                                         serve_walk="gather")
    assert fused.walk_strategy == "fused"
    assert gather.walk_strategy == "gather"
    return fused, gather


def _assert_parity(fused, gather, X, sizes=SIZES, atol=1e-6):
    for n in sizes:
        Xn = X[:n]
        np.testing.assert_allclose(
            fused.raw_scores(Xn), gather.raw_scores(Xn),
            rtol=0, atol=atol, err_msg=f"binned raw_scores n={n}")
        fr, fo = fused._device_scores(Xn)
        gr, go = gather._device_scores(Xn)
        np.testing.assert_allclose(fr, gr, rtol=0, atol=atol,
                                   err_msg=f"raw-path margins n={n}")
        np.testing.assert_allclose(fo, go, rtol=0, atol=atol,
                                   err_msg=f"transformed n={n}")


# ---------------------------------------------------------------------------
# fused vs gather parity across the ladder


@pytest.mark.parametrize("num_class", [1, 3])
def test_fused_matches_gather_across_ladder(num_class):
    bst, X = _train(num_class=num_class)
    fused, gather = _pair(bst)
    _assert_parity(fused, gather, X)
    # and through the public surface, shaped like Booster.predict
    np.testing.assert_allclose(
        fused.predict(X[:300], raw_score=True),
        gather.predict(X[:300], raw_score=True), rtol=0, atol=1e-6)


def test_fused_matches_gather_nan_and_categorical():
    rng = np.random.RandomState(3)
    X = rng.normal(size=(1000, 6))
    X[:, 1] = rng.randint(0, 8, size=1000)    # categorical codes
    y = ((X[:, 0] > 0) ^ (X[:, 1] >= 4)).astype(np.float64)
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbose": -1, "min_data_in_leaf": 20},
                    lgb.Dataset(X, label=y, categorical_feature=[1]),
                    num_boost_round=4)
    X = X.copy()
    X[rng.rand(*X.shape) < 0.05] = np.nan     # missing values
    X[::50, 1] = 97.0                         # unseen category
    fused, gather = _pair(bst)
    _assert_parity(fused, gather, X, sizes=[1, 129, 700])


def test_fused_matches_gather_linear_forest():
    # regression target with real structure so leaves carry affine fits
    rng = np.random.RandomState(1)
    X = rng.normal(size=(800, 6))
    y = X[:, 0] * 2.0 + np.abs(X[:, 1]) + rng.normal(scale=0.1, size=800)
    bst = lgb.train({"objective": "regression", "num_leaves": 7,
                     "verbose": -1, "min_data_in_leaf": 20,
                     "linear_tree": True, "linear_lambda": 0.01},
                    lgb.Dataset(X, label=y), num_boost_round=4)
    fused, gather = _pair(bst)
    assert fused._has_linear and fused._walk_aff_dev is not None
    _assert_parity(fused, gather, X, sizes=[1, 129, 700])


def test_fused_matches_gather_dart():
    bst, X = _train(extra={"boosting": "dart", "drop_rate": 0.4,
                           "drop_seed": 5}, num_boost_round=6)
    fused, gather = _pair(bst)
    _assert_parity(fused, gather, X, sizes=[1, 700])


# ---------------------------------------------------------------------------
# bin quantization: atol pin + named fallback


def test_quantized_leaves_activate_within_atol_pin():
    rng = np.random.RandomState(2)
    X = rng.normal(size=(800, 6))
    y = (X[:, 0] + 0.2 * X[:, 1]) * 1e-4      # tiny-magnitude leaves
    bst = lgb.train({"objective": "regression", "num_leaves": 7,
                     "verbose": -1, "min_data_in_leaf": 20},
                    lgb.Dataset(X, label=y), num_boost_round=4)
    fused, gather = _pair(bst, quantize_leaves=True)
    assert fused.leaf_dtype == "bfloat16"
    assert fused.info()["leaf_dtype"] == "bfloat16"
    # the documented bound: quantized output within QUANTIZE_LEAF_ATOL
    # of the exact (gather) scores, on every path
    atol = CompiledForest.QUANTIZE_LEAF_ATOL
    for n in (1, 700):
        np.testing.assert_allclose(fused.raw_scores(X[:n]),
                                   gather.raw_scores(X[:n]),
                                   rtol=0, atol=atol)
        fr, _ = fused._device_scores(X[:n])
        gr, _ = gather._device_scores(X[:n])
        np.testing.assert_allclose(fr, gr, rtol=0, atol=atol)


def test_quantize_falls_back_to_f32_past_atol():
    rng = np.random.RandomState(4)
    X = rng.normal(size=(800, 6))
    y = (X[:, 0] + 0.2 * X[:, 1]) * 50000.0   # bf16 error >> atol
    bst = lgb.train({"objective": "regression", "num_leaves": 7,
                     "verbose": -1, "min_data_in_leaf": 20},
                    lgb.Dataset(X, label=y), num_boost_round=4)
    before = obs.snapshot()["counters"].get("forest_quantize_fallback", 0)
    fused, gather = _pair(bst, quantize_leaves=True)
    after = obs.snapshot()["counters"].get("forest_quantize_fallback", 0)
    assert after == before + 1                # the refusal is named
    assert fused.leaf_dtype == "float32"      # precision kept, not lost
    _assert_parity(fused, gather, X, sizes=[129])


# ---------------------------------------------------------------------------
# gather byte-identity: zero new programs, bit-identical output


def test_gather_builds_no_walk_programs_and_keeps_atol0_contract():
    bst, X = _train()
    before = obs.snapshot()["counters"]
    gather = CompiledForest.from_booster(bst, buckets=BUCKETS,
                                         serve_walk="gather")
    gather.warmup()
    gather.predict(X[:100], raw_score=True)
    gather.predict(X[:100], device_binning=True)
    after = obs.snapshot()["counters"]
    delta = {k for k in after if after[k] != before.get(k, 0)}
    walked = {k for k in delta if "walk" in k}
    assert walked == set(), f"gather touched walk programs: {walked}"
    assert gather._walk_dev is None           # no fused operands frozen
    # compiles landed only under the pre-strategy program names
    compiled = {k for k in delta if "compiles" in k}
    assert compiled and all(
        k.startswith(("predict_forest_compiles", "serve_forest_compiles"))
        for k in compiled), compiled
    # bit-identity: an explicit serve_walk=gather forest and a default
    # build (no strategy kwargs — every pre-existing caller) produce
    # byte-identical output on every path; the strategy layer added
    # dispatch indirection, not arithmetic
    default = CompiledForest.from_booster(bst, buckets=BUCKETS)
    assert np.array_equal(gather.raw_scores(X),
                          default.raw_scores(X))
    gr, go = gather._device_scores(X)
    dr, do = default._device_scores(X)
    assert np.array_equal(gr, dr) and np.array_equal(go, do)


# ---------------------------------------------------------------------------
# warmup: every dispatchable bucket, both strategies


@pytest.mark.parametrize("strategy", ["gather", "fused"])
def test_warmup_covers_rung_above_max_bucket(strategy):
    bst, X = _train()
    cf = CompiledForest.from_booster(bst, buckets=BUCKETS,
                                     serve_walk=strategy)
    # 200 sits strictly between rungs 128 and 512: bucket_for routes a
    # 200-row request to 512, so warmup(max_bucket=200) must compile 512
    cf.warmup(max_bucket=200)
    before = obs.snapshot()["counters"]
    cf.predict(X[:200], raw_score=True)
    cf.predict(X[:200], device_binning=True)
    after = obs.snapshot()["counters"]
    new = {k: after[k] - before.get(k, 0) for k in after
           if "compiles" in k and after[k] != before.get(k, 0)}
    assert new == {}, f"post-warmup hot-path compiles ({strategy}): {new}"


# ---------------------------------------------------------------------------
# strategy resolution + config plumbing


def test_auto_resolves_gather_off_tpu_and_info_reports():
    bst, _ = _train(num_boost_round=2)
    auto = CompiledForest.from_booster(bst, buckets=[32],
                                       serve_walk="auto")
    assert auto.serve_walk_requested == "auto"
    assert auto.walk_strategy == "gather"     # no TPU attached in tier-1
    assert auto.info()["serve_walk"] == "gather"
    assert "walk_vmem_bytes" not in auto.info()
    fused = CompiledForest.from_booster(bst, buckets=[32],
                                        serve_walk="fused")
    info = fused.info()
    assert info["serve_walk"] == "fused"
    assert info["walk_vmem_bytes"] > 0
    assert info["bin_dtype"] == "uint8"       # max_bin 255 fits u8 bins
    assert info["leaf_dtype"] == "float32"    # quantize not requested
    # off the chip an explicit fused walk is the interpreter, and says so
    assert info["walk_interpreted"] is True
    assert "walk_interpreted" not in auto.info()


def test_serve_walk_param_plumbs_from_config():
    rng = np.random.RandomState(5)
    X = rng.normal(size=(400, 4))
    y = (X[:, 0] > 0).astype(np.float64)
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbose": -1, "min_data_in_leaf": 20,
                     "serve_walk": "fused"},
                    lgb.Dataset(X, label=y), num_boost_round=2)
    cf = bst.compile(buckets=[32])
    assert cf.walk_strategy == "fused"        # config reached the freeze
    with pytest.raises(LightGBMError):
        CompiledForest.from_booster(bst, buckets=[32],
                                    serve_walk="sideways")


def test_serve_walk_config_validation():
    with pytest.raises(ValueError):
        lgb.train({"objective": "binary", "serve_walk": "sideways",
                   "verbose": -1},
                  lgb.Dataset(np.zeros((50, 2)), label=np.zeros(50)),
                  num_boost_round=1)


# ---------------------------------------------------------------------------
# bench_regress --latency-threshold gate


def _bench(value, sweep=None):
    res = {"metric": "predict_rows_per_sec", "value": value,
           "unit": "rows/s"}
    if sweep is not None:
        res["latency_sweep"] = {"active": "fused", "strategies": sweep}
    return res


def test_bench_regress_latency_threshold_gates():
    from tools.bench_regress import compare
    base = _bench(1000.0, {"gather": {"1": {"p99_ms": 2.0},
                                      "64": {"p99_ms": 5.0}},
                           "fused": {"1": {"p99_ms": 1.0}}})
    cand = _bench(1000.0, {"gather": {"1": {"p99_ms": 2.1},
                                      "64": {"p99_ms": 7.0}},  # +40%
                           "fused": {"1": {"p99_ms": 1.0},
                                     "256": {"p99_ms": 9.0}}})  # new pt
    v = compare(base, cand, 10.0, latency_threshold_pct=10.0)
    assert v["ok"] is False and v["latency_ok"] is False
    assert v["latency_delta"]["gather/64"]["ok"] is False
    assert v["latency_delta"]["gather/64"]["delta_pct"] == pytest.approx(
        40.0)
    assert v["latency_delta"]["gather/1"]["ok"] is True
    # points on one side only are not compared (no gate on new batches)
    assert "fused/256" not in v["latency_delta"]
    wide = compare(base, cand, 10.0, latency_threshold_pct=50.0)
    assert wide["ok"] is True and wide["latency_ok"] is True


def test_bench_regress_latency_gate_skips_without_block():
    from tools.bench_regress import compare
    old = _bench(1000.0)                      # pre-sweep baseline
    cand = _bench(1000.0, {"gather": {"1": {"p99_ms": 2.0}}})
    v = compare(old, cand, 10.0, latency_threshold_pct=10.0)
    assert v["ok"] is True and v["latency_ok"] is True
    assert "baseline" in v["latency_note"]
    # and without the flag the block is ignored entirely
    v2 = compare(old, cand, 10.0)
    assert "latency_ok" not in v2
