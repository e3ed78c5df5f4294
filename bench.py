"""Benchmark: boosting iterations/sec on a Higgs-like binary task.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "iters/sec", "vs_baseline": N}

Workload (mirrors the reference's recommended operating point,
examples/binary_classification/train.conf + BASELINE.json configs):
binary logloss objective, 28 features, num_leaves=63, max_bin=255,
learning_rate=0.1, min_data_in_leaf=50.  Rows default to 1M synthetic
Higgs-like events (override with BENCH_ROWS).

vs_baseline compares against the reference LightGBM CLI (v2 C++, OpenMP,
all cores) measured on THIS repo's build box on the identical synthetic
dataset and config: see CPU_REF_ITERS_PER_SEC provenance note below.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

import numpy as np

# Reference CPU baseline, measured once on the build host:
#   /root/reference built with cmake -DCMAKE_BUILD_TYPE=Release (GCC 12,
#   OpenMP; host exposes 1 core), run on the identical synthetic 1M x 28
#   dataset (make_higgs_like seed 42, CSV) with num_leaves=63 max_bin=255
#   learning_rate=0.1 min_data_in_leaf=50 num_trees=40; steady-state
#   per-iteration wall time from the CLI's "seconds elapsed" log over
#   iterations 10..40: 4.17 iters/sec.
CPU_REF_ITERS_PER_SEC = {
    1_000_000: 4.17,
}


def make_higgs_like(num_data: int, num_features: int = 28, seed: int = 42):
    """Synthetic stand-in for the Higgs dataset: a few informative
    low-level features, quadratic 'derived' features, heavy noise."""
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(num_data, num_features)).astype(np.float32)
    X[:, 7:14] = np.abs(X[:, 7:14])            # energy-like positives
    X[:, 14:21] = X[:, 0:7] * X[:, 7:14]       # derived products
    logit = (0.8 * X[:, 0] - 0.6 * X[:, 1] + 0.5 * X[:, 14]
             - 0.4 * X[:, 15] + 0.3 * X[:, 7] * X[:, 2]
             + rng.normal(scale=1.5, size=num_data))
    y = (logit > 0).astype(np.float32)
    return X.astype(np.float64), y


def make_ctr_like(num_data: int, num_features: int = 2000,
                  block_size: int = 20, seed: int = 9):
    """Wide-sparse CTR-style synthetic: one-hot-ish blocks so real
    exclusive bundles exist (docs/SPARSE.md).

    Features come in blocks of ``block_size``; each row activates at most
    ONE feature per block (a categorical one-hot) with a small integer
    level value, so features within a block are perfectly mutually
    exclusive — exactly what EFB packs — and overall sparsity lands
    around 95-97%.  The label is a logistic read-out of a sparse subset
    of (feature, level) weights plus noise."""
    rng = np.random.RandomState(seed)
    num_blocks = max(num_features // block_size, 1)
    F = num_blocks * block_size
    X = np.zeros((num_data, F))
    logit = rng.normal(scale=0.6, size=num_data)
    w = rng.normal(scale=1.0, size=F) * (rng.rand(F) < 0.15)
    idx = np.arange(num_data)
    for b in range(num_blocks):
        act = rng.rand(num_data) < 0.6          # block fires on 60% of rows
        choice = b * block_size + rng.randint(0, block_size, num_data)
        level = rng.randint(1, 5, num_data).astype(np.float64)
        rows = idx[act]
        X[rows, choice[act]] = level[act]
        logit[rows] += w[choice[act]] * level[act] * 0.25
    y = (logit > np.median(logit)).astype(np.float32)
    return X, y


def make_piecewise_linear(num_data: int, num_features: int = 10,
                          seed: int = 5):
    """Piece-wise linear regression synthetic (docs/LINEAR_TREES.md):
    axis-aligned regions whose responses are AFFINE in a few features —
    the workload linear trees are built for.  Constant-leaf trees must
    staircase each slope; an affine leaf captures it in one fit."""
    rng = np.random.RandomState(seed)
    X = rng.uniform(-3.0, 3.0, size=(num_data, num_features))
    y = np.where(X[:, 0] > 0.0,
                 2.0 * X[:, 1] - 0.7 * X[:, 2] + 1.0,
                 np.where(X[:, 1] > 0.5,
                          -1.5 * X[:, 2] + 0.4 * X[:, 3],
                          0.8 * X[:, 3] + 0.3))
    y = y + 0.05 * rng.normal(size=num_data)
    return X, y.astype(np.float64)


def bench_linear() -> None:
    """--dataset linear: piece-wise linear trees A/B benchmark.

    Trains a constant-leaf and a linear-leaf booster on the same
    piece-wise linear synthetic and reports trees-to-target (rounds the
    linear run needs to reach the constant run's best l2), per-round
    fit seconds, and the leaf-fit fallback rate.  One BENCH-style JSON
    line; ``linear`` block passed through by tools/bench_regress.py."""
    num_data = int(os.environ.get("BENCH_LINEAR_ROWS", 100_000))
    num_iters = int(os.environ.get("BENCH_LINEAR_ITERS", 60))
    max_feats = int(os.environ.get("BENCH_LINEAR_K", 4))

    import jax
    from lightgbm_tpu.utils import compile_cache
    compile_cache.setup()
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu import obs as _obs
    _obs.devprof.configure(None)

    X, y = make_piecewise_linear(num_data)
    params = {"objective": "regression", "metric": "l2",
              "num_leaves": 31, "max_bin": 255, "learning_rate": 0.1,
              "min_data_in_leaf": 50, "num_iterations": num_iters}
    ds = BinnedDataset.from_matrix(X, y, max_bin=255, min_data_in_leaf=50,
                                   keep_raw=True)

    def run(linear: bool):
        p = dict(params)
        if linear:
            p.update({"linear_tree": True, "linear_lambda": 0.01,
                      "linear_max_leaf_features": max_feats})
        booster = GBDT(Config(p), ds)
        per_round = []
        curve = []
        for _ in range(num_iters):
            t0 = time.time()
            booster.train_one_iter()
            jax.block_until_ready(booster.train_data.score)
            per_round.append(time.time() - t0)
            curve.append(float(booster.eval_metrics()
                               .get("training", {}).get("l2", np.inf)))
        return booster, per_round, curve

    fb_before = _obs.get_counter("linear_fallback_total")
    t0 = time.time()
    _, const_rounds, const_curve = run(linear=False)
    _, lin_rounds, lin_curve = run(linear=True)
    total_s = time.time() - t0
    fb_total = _obs.get_counter("linear_fallback_total") - fb_before

    target = min(const_curve)                    # constant run's best l2
    trees_to_target = next(
        (i + 1 for i, v in enumerate(lin_curve) if v <= target), None)
    num_leaves = int(params["num_leaves"])
    fit_rate = fb_total / float(num_iters * num_leaves)

    bench_json = {
        "metric": f"linear_tree_ab_piecewise{num_data // 1000}k_"
                  f"31leaves_l2",
        "value": (round(trees_to_target / float(num_iters), 4)
                  if trees_to_target else None),
        "unit": "tree_ratio_to_const_best",
        "linear": {
            "rows": num_data,
            "iterations": num_iters,
            "max_leaf_features": max_feats,
            "const_best_l2": round(target, 6),
            "linear_best_l2": round(min(lin_curve), 6),
            "trees_to_const_best": trees_to_target,
            "const_round_s_median": round(
                statistics.median(const_rounds), 4),
            "linear_round_s_median": round(
                statistics.median(lin_rounds), 4),
            "fit_s_per_round_median": round(
                statistics.median(lin_rounds)
                - statistics.median(const_rounds), 4),
            "fallback_total": int(fb_total),
            "fallback_rate": round(fit_rate, 4),
        },
        "compile_events": None,
    }
    from lightgbm_tpu.obs import compile_ledger
    bench_json["compile_events"] = compile_ledger.summary(5)
    bench_json["profile"], bench_json["device"] = _profile_blocks()
    print(json.dumps(bench_json))
    print(f"# device={jax.devices()[0].platform} total_s={total_s:.1f} "
          f"const_best={target:.6f} linear_best={min(lin_curve):.6f} "
          f"trees_to_target={trees_to_target} fallback={fb_total}",
          file=sys.stderr)


def _profile_blocks():
    """The BENCH ``profile`` + ``device`` blocks (obs/devprof.py,
    obs/devcaps.py).  Always emitted: ``profile.mode`` records whether
    device-time attribution ran (arm it with LIGHTGBM_TPU_DEVPROF), and
    ``device`` makes every archived BENCH_r*.json self-describing about
    the hardware and peak numbers that produced it."""
    import jax
    from lightgbm_tpu.obs import report
    prof = report.profile_summary()
    caps = prof["device"]
    profile = {
        "mode": prof["mode"],
        "rounds": prof["rounds"],
        "device_seconds_est_total": prof["device_seconds_est_total"],
        "samples_total": prof["samples_total"],
        "dispatches_total": prof["dispatches_total"],
        "programs": prof["programs"],
        "transfers": prof["transfers"],
    }
    device = {
        "platform": caps.get("platform"),
        "device_kind": caps.get("device_kind"),
        "device_count": jax.device_count(),
        "peak_flops": caps.get("peak_flops"),
        "peak_bytes_per_sec": caps.get("peak_bytes_per_sec"),
        "peaks_source": caps.get("source"),
        "jax_version": jax.__version__,
    }
    return profile, device


def _fleet_scaling(booster, X32: np.ndarray, concurrency: int) -> dict:
    """``--concurrency N``: threaded closed-loop clients against the
    serving fleet at every replica count 1..len(local_devices) — the
    1->K scaling curve as numbers.  Per replica count: aggregate and
    per-replica rows/sec, shed rate, client p50/p99.  On a CPU box,
    XLA_FLAGS=--xla_force_host_platform_device_count=K simulates K
    devices (docs/SERVING.md §Benchmark)."""
    import threading

    import jax
    from lightgbm_tpu.serve.batcher import default_ladder
    from lightgbm_tpu.serve.fleet import Fleet, Overloaded
    from lightgbm_tpu.serve.forest import CompiledForest

    batch = int(os.environ.get("BENCH_PREDICT_FLEET_BATCH", 1024))
    calls = int(os.environ.get("BENCH_PREDICT_FLEET_CALLS", 30))
    queue_depth = int(os.environ.get("BENCH_PREDICT_QUEUE_DEPTH", 128))
    rows = X32.shape[0]
    batch = min(batch, rows)
    # a fleet-sized ladder: every replica warms it, so keep it at the
    # client batch instead of the offline 65536 ladder
    forest = CompiledForest.from_booster(
        booster, buckets=default_ladder(16, batch))
    devs = jax.local_devices()
    out = {}
    for R in range(1, len(devs) + 1):
        fleet = Fleet.build(forest, devices=devs[:R], max_batch=batch,
                            max_delay_s=0.002, max_queue=queue_depth)
        lat: list = []
        served = [0] * concurrency
        shed = [0] * concurrency

        def client(ci: int) -> None:
            for i in range(calls):
                off = ((i * concurrency + ci) * batch) \
                    % max(rows - batch + 1, 1)
                t0 = time.time()
                try:
                    fleet.submit(X32[off:off + batch], timeout=300.0)
                except Overloaded:
                    shed[ci] += 1
                    continue
                lat.append((time.time() - t0) * 1000.0)
                served[ci] += batch

        threads = [threading.Thread(target=client, args=(ci,))
                   for ci in range(concurrency)]
        t0 = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.time() - t0
        per_replica = [
            round(rep["requests"] * batch / wall, 1)
            for rep in fleet.stats()["replicas"]]
        fleet.close()
        attempts = concurrency * calls
        out[str(R)] = {
            "rows_per_sec": round(sum(served) / wall, 1),
            "per_replica_rows_per_sec": per_replica,
            "shed_rate": round(sum(shed) / attempts, 4),
            "p50_ms": round(float(np.percentile(lat, 50)), 3) if lat
            else None,
            "p99_ms": round(float(np.percentile(lat, 99)), 3) if lat
            else None,
        }
    return out


def predict_main(concurrency: int = 0) -> None:
    """--mode predict: serving throughput/latency benchmark.

    Trains a small forest at the reference operating point (63 leaves,
    255 bins, binary), freezes it into a ``serve.CompiledForest``, warms
    every bucket, then measures the fused device-binned predict path
    (the server hot path) per batch size.  One BENCH-style JSON line:
    rows/sec at the largest batch as the headline, per-batch-size
    rows/sec + p50/p99 call latency in ``batches``.  With
    ``--concurrency N`` the JSON gains a ``fleet`` block: closed-loop
    clients against 1..K device replicas (``_fleet_scaling``)."""
    rows = int(os.environ.get("BENCH_PREDICT_ROWS", 1_000_000))
    train_rows = int(os.environ.get("BENCH_PREDICT_TRAIN_ROWS", 100_000))
    trees = int(os.environ.get("BENCH_PREDICT_TREES", 40))
    calls = int(os.environ.get("BENCH_PREDICT_CALLS", 30))
    sizes = [int(s) for s in os.environ.get(
        "BENCH_PREDICT_BATCHES", "256,2048,16384,65536").split(",")]
    sizes = [s for s in sizes if s <= rows] or [rows]

    import jax
    from lightgbm_tpu.utils import compile_cache
    compile_cache.setup()
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.serve.forest import CompiledForest
    from lightgbm_tpu import obs
    # bench drives GBDT directly (no engine.train), so arm device-time
    # attribution here: LIGHTGBM_TPU_DEVPROF=sample:N|full populates the
    # BENCH `profile` block; unset leaves it off (zero overhead)
    obs.devprof.configure(None)

    X, y = make_higgs_like(rows)
    cfg = Config({"objective": "binary", "metric": "auc",
                  "num_leaves": 63, "max_bin": 255, "learning_rate": 0.1,
                  "min_data_in_leaf": 50, "num_iterations": trees})
    t0 = time.time()
    ds = BinnedDataset.from_matrix(X[:train_rows], y[:train_rows],
                                   max_bin=255, min_data_in_leaf=50)
    booster = GBDT(cfg, ds)
    for _ in range(trees):
        booster.train_one_iter()
    t_train = time.time() - t0

    t0 = time.time()
    from lightgbm_tpu.serve.batcher import default_ladder
    # ladder capped at the largest measured size (default_ladder always
    # includes its `hi` endpoint), so warmup() covers every bucket any
    # measured batch can route to — no hidden compile in the timings
    forest = CompiledForest.from_booster(
        booster, buckets=default_ladder(16, max(sizes)))
    forest.warmup()
    t_warm = time.time() - t0

    # drift observatory riding the measured traffic: a threadless
    # collector hangs off the forest so every timed batch is also drift
    # accounting — the BENCH `drift` block reports the window PSI summary
    # and the collector's own compute seconds (docs/OBSERVABILITY.md
    # §Drift).  No fingerprint on the model = no block, nothing attached.
    from lightgbm_tpu.obs.drift import DriftCollector
    drift_col = None
    if forest.data_fingerprint is not None:
        drift_col = DriftCollector(forest.data_fingerprint, model="bench",
                                   window_s=3600.0, start_thread=False)
        forest._drift = drift_col

    X32 = X.astype(np.float32)
    batches = {}
    for size in sizes:
        # touch distinct row windows so cache effects resemble traffic
        lat = []
        done = 0
        for i in range(calls):
            off = (i * size) % max(rows - size + 1, 1)
            t0 = time.time()
            raw, out = forest.batched_fn()(X32[off:off + size])
            np.asarray(out)                      # block until materialized
            lat.append((time.time() - t0) * 1000.0)
            done += size
        total_s = sum(lat) / 1000.0
        batches[str(size)] = {
            "rows_per_sec": round(done / total_s, 1),
            "p50_ms": round(float(np.percentile(lat, 50)), 3),
            "p99_ms": round(float(np.percentile(lat, 99)), 3),
        }
    # small-batch latency sweep on BOTH walk strategies (docs/SERVING.md
    # strategy matrix): batch 1/16/64/256 is the p50/p99 regime single
    # user requests live in; tools/bench_regress.py --latency-threshold
    # gates p99 per (strategy, batch) point of this block
    sweep_sizes = [int(s) for s in os.environ.get(
        "BENCH_LATENCY_BATCHES", "1,16,64,256").split(",")]
    sweep_sizes = [s for s in sweep_sizes if s <= rows] or [1]
    sweep_calls = int(os.environ.get("BENCH_LATENCY_CALLS", 15))
    latency_sweep = {"active": forest.walk_strategy, "strategies": {}}
    for strat in ("gather", "fused"):
        if forest.walk_strategy == strat:
            f2 = forest
        else:
            f2 = CompiledForest.from_booster(
                booster, buckets=default_ladder(16, max(sizes)),
                serve_walk=strat)
            f2.warmup(max_bucket=max(sweep_sizes))
        fn = f2.batched_fn()
        pts = {}
        for size in sweep_sizes:
            lat = []
            for i in range(sweep_calls):
                off = (i * size) % max(rows - size + 1, 1)
                t0 = time.time()
                raw, out = fn(X32[off:off + size])
                np.asarray(out)                  # block until materialized
                lat.append((time.time() - t0) * 1000.0)
            pts[str(size)] = {
                "p50_ms": round(float(np.percentile(lat, 50)), 3),
                "p99_ms": round(float(np.percentile(lat, 99)), 3),
            }
        latency_sweep["strategies"][strat] = pts

    drift_block = None
    if drift_col is not None:
        forest._drift = None
        win = drift_col.flush() or {}
        st = drift_col.stats()
        feats = win.get("features") or {}
        max_psi = max((d["psi"] for d in feats.values()), default=None)
        drift_block = {
            "windows": int(st["windows"]),
            "rows": int(st["rows"]),
            "dropped": int(st["dropped"]),
            "overhead_s": round(float(st["overhead_s"]), 6),
            "max_psi": (round(float(max_psi), 6)
                        if max_psi is not None else None),
            "score_psi": (round(float(win["score_psi"]), 6)
                          if win.get("score_psi") is not None else None),
        }
        drift_col.close()

    top = batches[str(max(sizes))]
    # availability bill over the fleet run (round 9, serve/health.py):
    # hedged retries / ejections / deadline sheds as counter deltas —
    # informational BENCH keys, passed through by bench_regress
    _avail_keys = ("serve_retries_total", "serve_ejections_total",
                   "serve_deadline_expired_total", "serve_shed_total")
    avail0 = {k: obs.get_counter(k) for k in _avail_keys}
    fleet = _fleet_scaling(booster, X32, concurrency) if concurrency \
        else None
    from lightgbm_tpu.obs import compile_ledger
    result = {
        "metric": f"serve_rows_per_sec_higgslike_{trees}trees_"
                  "63leaves_255bins_binary",
        "value": top["rows_per_sec"],
        "unit": "rows/sec",
        "vs_baseline": None,
        "batches": batches,
        "latency_sweep": latency_sweep,
        "warmup_s": round(t_warm, 3),
        "compile_events": compile_ledger.summary(5),
    }
    if drift_block is not None:
        result["drift"] = drift_block
    result["profile"], result["device"] = _profile_blocks()
    if fleet is not None:
        result["concurrency"] = concurrency
        result["fleet"] = fleet
        result["availability"] = {
            k: obs.get_counter(k) - avail0[k] for k in _avail_keys}
    print(json.dumps(result))
    c = obs.snapshot()["counters"]
    tail = ""
    if fleet is not None:
        tail = (" fleet_rows_per_sec=" + ",".join(
            f"{r}:{fleet[r]['rows_per_sec']:g}" for r in sorted(
                fleet, key=int)))
    print(f"# device={jax.devices()[0].platform} train_s={t_train:.1f} "
          f"warmup_s={t_warm:.1f} calls_per_size={calls} "
          f"serve_compiles={c.get('serve_forest_compiles', 0)} "
          f"post_warmup_compiles_expected=0"
          f"{tail}", file=sys.stderr)


def main(dataset: str = "higgslike") -> None:
    num_data = int(os.environ.get("BENCH_ROWS", 1_000_000))
    num_warmup = int(os.environ.get("BENCH_WARMUP", 5))
    num_timed = int(os.environ.get("BENCH_ITERS", 30))
    # median over >=3 timed windows: a one-chip machine shares its
    # host's CPU cores, and an earlier installation measured 5.9-7.5 it/s
    # for identical code across a day (PERF.md, "Carried over"), so a
    # single window reflects box load as much as code.  Each window is
    # num_timed iterations; the reported value is the median of the
    # per-window rates.
    num_windows = max(int(os.environ.get("BENCH_WINDOWS", 3)), 1)

    import jax
    # persistent XLA compilation cache: the grow program takes minutes
    # to compile cold (PERF.md, "On the chip"); repeat runs hit the
    # cache instead — the same helper engine.train and the CLI use
    # (utils/compile_cache.py).
    from lightgbm_tpu.utils import compile_cache
    compile_cache.setup()
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu import obs as _obs_p
    # bench drives GBDT directly (no engine.train), so arm device-time
    # attribution here: LIGHTGBM_TPU_DEVPROF=sample:N|full populates the
    # BENCH `profile` block; unset leaves it off (zero overhead)
    _obs_p.devprof.configure(None)

    params = {"objective": "binary", "metric": "auc",
              "num_leaves": 63, "max_bin": 255, "learning_rate": 0.1,
              "min_data_in_leaf": 50,
              "num_iterations": num_warmup + num_windows * num_timed}
    bin_kwargs = {}
    if dataset == "ctrlike":
        # wide-sparse mode (docs/SPARSE.md §Bench recipe): ~500k x 2000
        # at ~95% sparsity with one-hot blocks, so real exclusive
        # bundles exist.  BENCH_ENABLE_BUNDLE / BENCH_SCREEN_RATIO toggle
        # the two wide-sparse optimizations for A/B BENCH runs compared
        # by tools/bench_regress.py.
        num_data = int(os.environ.get("BENCH_CTR_ROWS", 500_000))
        num_feat = int(os.environ.get("BENCH_CTR_FEATURES", 2000))
        enable_bundle = os.environ.get(
            "BENCH_ENABLE_BUNDLE", "1").lower() in ("1", "true", "yes")
        screen_ratio = float(os.environ.get("BENCH_SCREEN_RATIO", "0"))
        X, y = make_ctr_like(num_data, num_feat)
        params.update({
            "enable_bundle": enable_bundle,
            "feature_screen_ratio": screen_ratio,
            "feature_screen_warmup": int(os.environ.get(
                "BENCH_SCREEN_WARMUP", num_warmup)),
            "feature_screen_refresh": int(os.environ.get(
                "BENCH_SCREEN_REFRESH", 10)),
        })
        bin_kwargs = {"enable_bundle": enable_bundle,
                      # bound the host sample: 2000 f64 columns x 200k
                      # sampled rows would be 3.2 GB of transient RAM
                      "bin_construct_sample_cnt": int(os.environ.get(
                          "BENCH_CTR_SAMPLE", 50_000))}
        metric_name = (f"boosting_iters_per_sec_ctrlike"
                       f"{num_data // 1000}k_{X.shape[1]}f_"
                       "63leaves_255bins_binary")
    else:
        X, y = make_higgs_like(num_data)
        metric_name = (f"boosting_iters_per_sec_higgslike"
                       f"{num_data // 1000}k_63leaves_255bins_binary")
    cfg = Config(params)
    t0 = time.time()
    ds = BinnedDataset.from_matrix(X, y, max_bin=255, min_data_in_leaf=50,
                                   **bin_kwargs)
    t_bin = time.time() - t0

    booster = GBDT(cfg, ds)
    t0 = time.time()
    for _ in range(num_warmup):
        booster.train_one_iter()
    jax.block_until_ready(booster.train_data.score)
    t_warm = time.time() - t0

    rates = []
    for _ in range(num_windows):
        t0 = time.time()
        for _ in range(num_timed):
            booster.train_one_iter()
        jax.block_until_ready(booster.train_data.score)
        rates.append(num_timed / (time.time() - t0))
    # median() sorts its own copy: `rates` must stay in measurement order
    # for the stderr `windows=` diagnostic (load drift over time is the
    # signal a pre-sorted list destroys)
    iters_per_sec = statistics.median(rates)
    # the CPU reference numbers are higgslike-only: a ctrlike run whose
    # row count happens to collide must not compare across workloads
    base = (CPU_REF_ITERS_PER_SEC.get(num_data)
            if dataset == "higgslike" else None)
    vs = (iters_per_sec / base) if base else None
    auc = booster.eval_metrics().get("training", {}).get("auc")

    # cold-vs-warm warmup split: a SECOND booster over the same dataset
    # re-runs the warmup iterations.  With the shared train_step/grow
    # programs (models/gbdt.py) it must hit the in-process jit caches —
    # zero new compiles — so warm warmup measures the steady-state cost a
    # restarted-but-cache-warm run pays, while warmup_cold_s keeps the
    # first-boot compile tax.  bench_regress gates the cold number.
    from lightgbm_tpu.obs import compile_ledger
    n_cold_events = len(compile_ledger.events())
    del booster                      # free the first booster's HBM first
    t0 = time.time()
    booster = GBDT(cfg, ds)
    for _ in range(num_warmup):
        booster.train_one_iter()
    jax.block_until_ready(booster.train_data.score)
    t_warm_warm = time.time() - t0
    warm_events = compile_ledger.events()[n_cold_events:]

    bench_json = {
        "metric": metric_name,
        "value": round(iters_per_sec, 4),
        "unit": "iters/sec",
        "vs_baseline": round(vs, 4) if vs is not None else None,
        "warmup_s": round(t_warm, 3),
        "warmup_cold_s": round(t_warm, 3),
        "warmup_warm_s": round(t_warm_warm, 3),
        "warmup_warm_compiles": len(warm_events),
        "spread": [round(min(rates), 4), round(max(rates), 4)],
        "compile_events": compile_ledger.summary(5),
    }
    bench_json["profile"], bench_json["device"] = _profile_blocks()
    if auc is not None:
        bench_json["auc"] = round(float(auc), 5)
    if dataset == "ctrlike":
        # wide-sparse bill (docs/SPARSE.md): how far EFB shrank the
        # feature space and what screening kept active — informational
        # BENCH keys, passed through by bench_regress
        from lightgbm_tpu import obs as _obs2
        plan = ds.bundle_plan
        bench_json["efb"] = {
            "enabled": bool(params["enable_bundle"]),
            "num_features": int(ds.num_features),
            "columns": int(ds.num_columns),
            "bundles": len(plan.bundles) if plan is not None else 0,
            "features_bundled": (plan.features_bundled
                                 if plan is not None else 0),
            "sample_conflicts": (plan.sample_conflicts
                                 if plan is not None else 0),
        }
        bench_json["screening"] = {
            "ratio": float(params["feature_screen_ratio"]),
            "refresh": int(params["feature_screen_refresh"]),
            "warmup": int(params["feature_screen_warmup"]),
            "active_features_last": int(
                _obs2.get_gauge("screen_active_features") or 0),
            "refresh_total": int(
                _obs2.get_counter("screen_refresh_total")),
        }
    # resource bill (PR 15, utils/resource.py + utils/diskguard.py):
    # estimated vs measured peak bytes, degrade steps taken, sink write
    # errors — a throughput number from a degraded run must carry its
    # asterisk (bench_regress passes `resource` through informationally)
    from lightgbm_tpu import obs as _obs_r
    from lightgbm_tpu.obs import memwatch as _memwatch
    from lightgbm_tpu.utils.resource import DEGRADE_STEPS as _STEPS
    _mw = _memwatch.sample()
    bench_json["resource"] = {
        "estimated_peak_bytes": int(
            _obs_r.get_gauge("hbm_train_estimate_bytes") or 0),
        "measured_peak_bytes": int(
            _mw.get("device_peak_bytes",
                    _mw.get("peak_live_bytes", _mw.get("live_bytes", 0)))),
        "degrade_steps": [s for s in _STEPS if _obs_r.get_counter(
            "resource_degrade_" + s)],
        "sink_write_errors": int(
            _obs_r.get_counter("sink_write_errors_total")),
        "device_oom": int(_obs_r.get_counter("device_oom_total")),
    }
    # data-boundary bill (PR 13, io/guard.py): when a file-fed run
    # quarantined rows, say so in the BENCH JSON — a throughput number
    # from a partially-skipped dataset must carry its asterisk
    # (bench_regress passes bad_rows through informationally)
    from lightgbm_tpu import obs as _obs
    _bad_total = _obs.get_counter("bad_rows_total")
    if _bad_total:
        _counters = _obs.snapshot()["counters"]
        bench_json["bad_rows"] = {
            "total": _bad_total,
            **{k[len("bad_rows_"):]: v for k, v in sorted(
                _counters.items())
               if k.startswith("bad_rows_") and k != "bad_rows_total"},
        }
    print(json.dumps(bench_json))
    # trailing comment line only — the JSON line above is the contract.
    # LIGHTGBM_TPU_TIMETAG=1 folds the serializing per-phase breakdown in
    # so BENCH_*.json tails carry phase data; the obs counters are always
    # on (and must stay free: the acceptance gate for the telemetry layer
    # is that a disabled-telemetry run sits inside the window spread).
    from lightgbm_tpu import obs
    from lightgbm_tpu.utils import timetag
    tail = ""
    if timetag.ENABLED:
        t = timetag.get_timings()
        if t:
            tail += " phases=" + json.dumps(
                {k: round(v, 3) for k, v in sorted(t.items())},
                separators=(",", ":"))
    c = obs.snapshot()["counters"]
    tail += (f" obs_iters={c.get('iterations', 0)}"
             f" obs_trees={c.get('trees_grown', 0)}"
             f" obs_d2h={c.get('device_to_host_transfers', 0)}"
             f" obs_comm_bytes={c.get('comm_collective_bytes', 0)}")
    print(f"# device={jax.devices()[0].platform} bin_s={t_bin:.1f} "
          f"warmup_s={t_warm:.1f} warm_warmup_s={t_warm_warm:.1f} "
          f"timed_iters={num_timed} "
          f"windows={[round(r, 3) for r in rates]} "
          f"spread={min(rates):.3f}-{max(rates):.3f} "
          f"auc={auc}"
          f"{tail}",
          file=sys.stderr)


def _parse_opt(argv, name: str, default: str) -> str:
    """``--name value`` / ``--name=value`` — no argparse so the BENCH
    invocation stays copy-pasteable into constrained drivers."""
    val = default
    for i, tok in enumerate(argv):
        if tok == f"--{name}" and i + 1 < len(argv):
            val = argv[i + 1]
        elif tok.startswith(f"--{name}="):
            val = tok.split("=", 1)[1]
    return val


def _parse_mode(argv) -> str:
    return _parse_opt(argv, "mode", "train")


if __name__ == "__main__":
    if _parse_mode(sys.argv[1:]) == "predict":
        predict_main(concurrency=int(_parse_opt(
            sys.argv[1:], "concurrency",
            os.environ.get("BENCH_PREDICT_CONCURRENCY", "0"))))
    else:
        _ds = _parse_opt(sys.argv[1:], "dataset",
                         os.environ.get("BENCH_DATASET", "higgslike"))
        if _ds == "linear":
            bench_linear()
        else:
            main(dataset=_ds)
