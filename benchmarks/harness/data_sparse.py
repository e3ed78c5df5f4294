"""One-hot encoded tables as sparse matrices, by name; everything from
``--seed``.

For a further sparse cell: add a generator here with ``allstate_like``'s
signature and return, enter it in ``GENERATORS``, and name it under
``data.generator`` in the configuration's file; ``kinds/train_sparse.py``
finds it through ``make``.  ``harness/data.py`` (the dense generators) is
the yardstick's and is not edited.

``allstate_like`` stands in for the "Allstate" table of LightGBM's
``docs/Experiments.rst`` (the Kaggle Allstate Claim Prediction Challenge's
training file, one-hot encoded: 13,184,290 rows x 4,228 columns, binary
label ``Claim_Amount > 0``).  What is kept of the source is its SHAPE,
recalled from the data set's description and not read from it (no data
set and no network here):

- 16 categorical source columns, one indicator column a level, 4,214 in
  all: three heavy-tailed ones in a hierarchy (``Blind_Make`` 75 levels,
  ``Blind_Model`` 1,300, ``Blind_Submodel`` 2,750: a submodel belongs to
  one model and a model to one make, popularity Zipf-like) and 13 small
  ones of 2 to 15 levels with skewed shares.  A missing category is a
  level of its own, so every row stores exactly one indicator a
  categorical;
- 14 numeric source columns, stored on every row: twelve continuous
  (``Var1`` to ``Var8``, ``NVVar1`` to ``NVVar4``: standardised, so never
  exactly zero), ``Calendar_Year`` (2005 to 2007) and ``Model_Year`` (1981
  to 2009, skewed to the recent);
- so a row stores exactly 30 entries, 365,528,700 at 12,184,290 rows;
- about 0.7% of the labels are positive, drawn from a logistic model
  over level effects and the numerics, so that the columns carry signal
  a tree can find;
- the table's structure (the hierarchy, the levels' shares and effects)
  is one fixed draw, ``STRUCTURE_KEY``; ``--seed`` draws the rows.

The matrix is built column by column from the 30 source columns (a
stable sort of a categorical's levels gives every indicator column's
rows), never through a dense intermediate, and is handed over as a
``RefusesDense``: a ``csc_matrix`` whose ``toarray`` / ``todense`` / ``A``
raise ``MemoryError``.  A program that makes it dense fails at once, at
any size, instead of being killed minutes into filling memory.

The same seed gives the same matrix whatever the thread count: ``BLOCKS``
Philox streams over fixed row blocks, as ``data.higgs_like``.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp

BLOCKS = 16
HEAVY = (("Blind_Make", 75), ("Blind_Model", 1300), ("Blind_Submodel", 2750))
SMALL = (2, 3, 3, 4, 4, 5, 6, 7, 8, 9, 10, 13, 15)     # Cat1..12, NVCat
CONTINUOUS = 12                 # Var1..Var8, NVVar1..NVVar4
NUM_CATEGORICAL = len(HEAVY) + len(SMALL)               # 16
NUM_NUMERIC = CONTINUOUS + 2                            # 14
NUM_INDICATORS = sum(k for _, k in HEAVY) + sum(SMALL)  # 4,214
NUM_COLUMNS = NUM_INDICATORS + NUM_NUMERIC              # 4,228
STORED_PER_ROW = NUM_CATEGORICAL + NUM_NUMERIC          # 30
POSITIVE_SHARE = 0.007
# The table's STRUCTURE (which submodel belongs to which model, every
# level's share and effect, the numerics' weights) is one fixed draw: a
# seed draws new rows of the same table, as the other cells' generators
# do.  Drawn from the seed too, the structure gave every seed a table of
# its own: 66 to 77 bundled columns and trees of other shapes, 0.36 to
# 0.40 rounds/s (PERF.md, PR 36).
STRUCTURE_KEY = 4228


class RefusesDense(sp.csc_matrix):
    """A CSC matrix that refuses to be made dense."""

    def _refuse(self, *args, **kwargs):
        n, f = self.shape
        raise MemoryError(
            f"a dense float64 copy of this {n:,} x {f:,} sparse matrix "
            f"would take {n * f * 8 / 1e9:,.0f} GB "
            f"({self.nnz:,} entries are stored): read its stored entries")

    toarray = todense = _refuse
    A = property(_refuse)


def _zipf(levels: int, exponent: float) -> np.ndarray:
    p = 1.0 / np.arange(1, levels + 1) ** exponent
    return p / p.sum()


def _structure(rng):
    """What does not depend on the rows: the hierarchy, every level's
    share and effect on the label, the numerics' weights."""
    makes, models, subs = (k for _, k in HEAVY)
    # a submodel's model and a model's make: the first of each are its
    # own, the rest fall to a parent drawn by popularity
    model_of = np.concatenate([np.arange(models), rng.choice(
        models, subs - models, p=_zipf(models, 0.7))])
    make_of = np.concatenate([np.arange(makes), rng.choice(
        makes, models - makes, p=_zipf(makes, 0.7))])
    small_p = [rng.dirichlet(np.full(k, 0.8)) for k in SMALL]
    effects = [rng.normal(0, s, k) for s, k in
               [(0.5, makes), (0.4, models), (0.3, subs)]
               + [(0.35, k) for k in SMALL]]
    # the anonymous numerics say little beside the vehicle and the
    # categories, as in a claims table
    weights = rng.normal(0, 0.08, NUM_NUMERIC)
    return model_of, make_of, small_p, effects, weights


def allstate_like(num_data: int, seed: int):
    """``(X, y)``: a ``RefusesDense`` of float32 values with int32 indices,
    ``[num_data, 4228]``, and float32 labels in {0, 1}."""
    from concurrent.futures import ThreadPoolExecutor
    s_blocks = np.random.SeedSequence(int(seed)).spawn(BLOCKS)
    model_of, make_of, small_p, effects, weights = _structure(
        np.random.Generator(np.random.Philox(STRUCTURE_KEY)))
    sub_p = _zipf(HEAVY[2][1], 1.05)
    levels = [np.empty(num_data, np.int16) for _ in range(NUM_CATEGORICAL)]
    numeric = [np.empty(num_data, np.float32) for _ in range(NUM_NUMERIC)]
    logit = np.empty(num_data, np.float32)
    coin = np.empty(num_data, np.float32)
    edges = np.linspace(0, num_data, BLOCKS + 1).astype(np.int64)

    def fill(i):
        lo, hi = int(edges[i]), int(edges[i + 1])
        n = hi - lo
        rng = np.random.Generator(np.random.Philox(s_blocks[i]))
        sub = rng.choice(len(sub_p), n, p=sub_p)
        drawn = [make_of[model_of[sub]], model_of[sub], sub] + [
            rng.choice(len(p), n, p=p) for p in small_p]
        z = np.zeros(n, np.float32)
        for j, lvl in enumerate(drawn):
            levels[j][lo:hi] = lvl
            z += effects[j][lvl].astype(np.float32)
        x = rng.standard_normal((CONTINUOUS, n), dtype=np.float32)
        # a make's vehicles share a little of the first variables
        x[:4] += (0.5 * effects[0][drawn[0]]).astype(np.float32)
        x[x == 0] = np.float32(1e-6)    # stored and never exactly zero
        year = 2005 + rng.integers(0, 3, n)
        model_year = 2009 - np.minimum(rng.geometric(0.12, n) - 1, 28)
        cols = list(x) + [year.astype(np.float32),
                          model_year.astype(np.float32)]
        for j, c in enumerate(cols):
            numeric[j][lo:hi] = c
            # the years enter the label centred
            z += np.float32(weights[j]) * (
                c if j < CONTINUOUS else (c - (2006 if j == CONTINUOUS
                                               else 2003)) / 4)
        logit[lo:hi] = z
        coin[lo:hi] = rng.random(n, dtype=np.float32)

    with ThreadPoolExecutor(4) as pool:
        list(pool.map(fill, range(BLOCKS)))

    # the intercept that gives the positive share, by bisection on a
    # fixed subsample
    sub = logit[::max(1, num_data // 200000)].astype(np.float64)
    lo_b, hi_b = -30.0, 10.0
    for _ in range(60):
        b = (lo_b + hi_b) / 2
        if (1 / (1 + np.exp(-(sub + b)))).mean() > POSITIVE_SHARE:
            hi_b = b
        else:
            lo_b = b
    y = (coin < 1 / (1 + np.exp(-(logit + np.float32(b))))).astype(np.float32)
    del logit, coin

    # ---- CSC, column by column --------------------------------------------
    nnz = num_data * STORED_PER_ROW
    indices = np.empty(nnz, np.int32)
    data = np.ones(nnz, np.float32)
    counts = [np.bincount(lvl, minlength=k) for lvl, k in zip(
        levels, [k for _, k in HEAVY] + list(SMALL))]
    indptr = np.concatenate(
        [[0], np.cumsum(np.concatenate(
            counts + [np.full(NUM_NUMERIC, num_data)]))]).astype(np.int64)
    every_row = np.arange(num_data, dtype=np.int32)

    def place(j):
        at = j * num_data           # a source column stores num_data entries
        if j < NUM_CATEGORICAL:
            # rows by level, ascending within a level: a level's column
            indices[at:at + num_data] = np.argsort(levels[j], kind="stable")
        else:
            indices[at:at + num_data] = every_row
            data[at:at + num_data] = numeric[j - NUM_CATEGORICAL]

    with ThreadPoolExecutor(4) as pool:
        list(pool.map(place, range(STORED_PER_ROW)))
    X = RefusesDense((data, indices, indptr.astype(np.int32)
                      if nnz < 2 ** 31 else indptr),
                     shape=(num_data, NUM_COLUMNS), copy=False)
    # by construction: rows ascending within a column, none twice
    X.has_sorted_indices = True
    X.has_canonical_format = True
    return X, y


GENERATORS = {"allstate_like": allstate_like}


def make(spec: dict, rows: int, seed: int):
    X, y = GENERATORS[spec["generator"]](rows, seed)
    if X.shape[1] != int(spec["num_features"]):
        raise ValueError(f"{spec['generator']} makes {X.shape[1]} columns, "
                         f"the configuration states {spec['num_features']}")
    return X, y
