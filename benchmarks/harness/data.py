"""Data generators, by name.  A configuration's file names one under
``data.generator``; everything is made from ``--seed``."""

from __future__ import annotations

import numpy as np


BLOCKS = 16


def higgs_like(num_data: int, num_features: int, seed: int):
    """Synthetic stand-in for the Higgs data set: a few informative
    low-level features, quadratic 'derived' features, heavy noise.

    The construction of ``bench.py make_higgs_like`` as of PR 24 (the same
    features, products and label rule), kept here because later PRs may
    change the program and may not change the yardstick.  The draws differ:
    float32 normals from ``BLOCKS`` Philox streams spawned from the seed,
    filled by a few threads, because the original's one legacy float64
    stream took 3 s a million rows of every run's set-up.  The same seed
    gives the same rows whatever the thread count.  Returns float32
    features and float32 labels in {0, 1}."""
    from concurrent.futures import ThreadPoolExecutor
    X = np.empty((num_data, num_features), np.float32)
    y = np.empty(num_data, np.float32)
    edges = np.linspace(0, num_data, BLOCKS + 1).astype(np.int64)
    seeds = np.random.SeedSequence(int(seed)).spawn(BLOCKS)

    def fill(i):
        lo, hi = int(edges[i]), int(edges[i + 1])
        rng = np.random.Generator(np.random.Philox(seeds[i]))
        x = rng.standard_normal((hi - lo, num_features), dtype=np.float32)
        x[:, 7:14] = np.abs(x[:, 7:14])            # energy-like positives
        x[:, 14:21] = x[:, 0:7] * x[:, 7:14]       # derived products
        logit = (0.8 * x[:, 0] - 0.6 * x[:, 1] + 0.5 * x[:, 14]
                 - 0.4 * x[:, 15] + 0.3 * x[:, 7] * x[:, 2]
                 + 1.5 * rng.standard_normal(hi - lo, dtype=np.float32))
        X[lo:hi] = x
        y[lo:hi] = logit > 0

    with ThreadPoolExecutor(4) as pool:
        list(pool.map(fill, range(BLOCKS)))
    return X, y


GENERATORS = {"higgs_like": higgs_like}


def make(spec: dict, rows: int, seed: int):
    gen = GENERATORS[spec["generator"]]
    return gen(rows, int(spec["num_features"]), seed)
