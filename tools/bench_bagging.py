"""Measure the bagging-compaction speedup on TPU (VERDICT round-2 item 5:
bagging_fraction=0.25, bagging_freq=1 must train >= 2.5x faster trees
than full-data at 1M).

    python tools/bench_bagging.py [rows]
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, ".")


def run(num_data, bagging):
    import jax
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.models.gbdt import GBDT
    from bench import make_higgs_like

    X, y = make_higgs_like(num_data)
    params = {"objective": "binary", "metric": "auc",
              "is_training_metric": True,
              "num_leaves": 63, "max_bin": 255,
              "learning_rate": 0.1, "min_data_in_leaf": 50,
              "num_iterations": 40}
    if bagging:
        params.update({"bagging_fraction": 0.25, "bagging_freq": 1,
                       "bagging_seed": 7})
    cfg = Config(params)
    ds = BinnedDataset.from_matrix(X, y, max_bin=255, min_data_in_leaf=50)
    booster = GBDT(cfg, ds)
    warm = int(os.environ.get("BAG_WARMUP", 3))
    timed = int(os.environ.get("BAG_ITERS", 12))
    for _ in range(warm):
        booster.train_one_iter()
    jax.block_until_ready(booster.train_data.score)
    t0 = time.time()
    for _ in range(timed):
        booster.train_one_iter()
    jax.block_until_ready(booster.train_data.score)
    dt = (time.time() - t0) / timed
    auc = booster.eval_metrics().get("training", {}).get("auc")
    return dt, auc


def main():
    from lightgbm_tpu.utils import compile_cache
    compile_cache.setup()
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    dt_full, auc_full = run(rows, bagging=False)
    dt_bag, auc_bag = run(rows, bagging=True)
    print(f"full    : {dt_full * 1e3:8.1f} ms/iter")
    print(f"bag 0.25: {dt_bag * 1e3:8.1f} ms/iter  "
          f"speedup {dt_full / dt_bag:.2f}x")
    print(f"auc full={auc_full} bag={auc_bag}")


if __name__ == "__main__":
    main()
