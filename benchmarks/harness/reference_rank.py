"""The plain reference of a ranking cell: LambdaRank's gradients and
hessians as the published description gives them (Burges, "From RankNet
to LambdaRank to LambdaMART", MSR-TR-2010-82; LightGBM's
``rank_objective.hpp``), NDCG at the cut-offs, and the rows binned a column at
a time on the host.  It imports nothing of the program.

For a further ranking cell nothing here has to change: the cell's kind
(``kinds/train_rank.py``) builds ``RankRows`` from the run's rows, queries
and the program's bound tables, and binds ``objective`` below in
``reference.objective``'s place, so that ``reference.follow`` and
``reference.grow`` (histograms, float64 split search, the walk of a tree:
none of them edited) run on the ranking gradients.  A cell whose label
gains or cut-offs differ states them in its configuration's ``stated``.

What is computed, per query, in float32 ``jax.numpy`` (matrix products
none; ``reference.histograms`` keeps its ``highest`` precision):

- a document's rank is its position under a stable sort by falling score;
  its discount ``1 / log2(2 + rank)``; its gain ``label_gain[label]``;
- for every pair of documents whose labels differ, the higher-labelled
  one ``i`` and the other ``j``: ``delta = (gain_i - gain_j) |disc_i -
  disc_j| / maxDCG``, divided by ``0.01 + |s_i - s_j|`` when the query's
  best and worst score differ; ``p = 2 / (1 + exp(2 sigma (s_i - s_j)))``
  (the exact sigmoid; LightGBM tabulates it); ``lambda = p delta``:
  document i's gradient falls by it, j's rises by it, and both hessians
  rise by ``2 p (2 - p) delta``.  ``maxDCG`` is the DCG of the labels in
  falling order over the first ``max_position`` places;
- queries go in blocks padded to a power of two, ``2**22 / P**2`` queries a
  step, so that a [block, P, P] float32 array is 16 MB; a class is one
  ``lax.map``;
- NDCG at k: mean over the queries of DCG@k over the ideal DCG@k of the
  same k, 1 for a query without a relevant document; float64 on the host.

``low=True`` computes scores, gains, discounts and the pair arithmetic in
bfloat16: the control.  ``fault`` plants ``no_normaliser`` (the ``0.01 +
|gap|`` division left out), ``no_discount`` (the discount term dropped
from delta) or ``half_queries`` (the second half of the queries left out
of every sum).

``RankRows`` also counts, by the reference's own sort of every column
over ALL rows, what ``correct_rank.bin_table_gap`` holds a bound table
against: distinct values, values heavier than an equal-count bin, and per
bin of the table the rows and the rows of its heaviest single value.
"""

from __future__ import annotations

import functools

import numpy as np

from . import reference

# reference.histograms builds a [features, CHUNK, bins] one-hot a step:
# 2.3 GB at 136 columns and 16,384 rows.  A quarter of the rows a step.
reference.CHUNK = 4096
COLUMN_THREADS = 8            # columns sorted and binned at a time
PAIR_ELEMENTS = 1 << 22       # pairs of a lax.map step


def pad_class(n: int) -> int:
    p = 16
    while p < n:
        p *= 2
    return p


class RankRows:
    """What ``reference.Rows`` holds (``binsT`` [F, Np] uint8, labels, a
    mask of real rows, Np padded to whole chunks), with the queries and
    the column counts.  Binned and counted on the host, a column a
    thread: ``reference.Rows`` bins all columns in one call on the chip
    at 0.51 GB a million rows of 28 columns, which 3.77M rows of 136 do
    not fit, and sorting every column there with its run lengths took
    107 to 125 s of a run (my chip run, PR 34, call 3) where the host
    takes a tenth."""

    def __init__(self, X32: np.ndarray, y: np.ndarray, group: np.ndarray,
                 bounds, stated: dict, max_bin: int):
        from concurrent.futures import ThreadPoolExecutor
        import jax.numpy as jnp
        n, f = X32.shape
        self.n, self.f = n, f
        self.n_bins = max(len(b) for b in bounds)
        self.B = max(8, 1 << (self.n_bins - 1).bit_length())
        pad = (-n) % reference.CHUNK
        self.np_rows = n + pad
        table = np.full((f, self.B - 1), np.inf, np.float32)
        for i, b in enumerate(bounds):
            table[i, :len(b) - 1] = reference.floor_f32(
                np.asarray(b[:-1], np.float64))
        heavy_over = n // int(max_bin)
        bins = np.zeros((f, self.np_rows), np.uint8)
        self.bin_count = np.zeros((f, self.B), np.int64)
        self.bin_heaviest = np.zeros((f, self.B), np.int64)
        self.distinct = np.zeros(f, np.int64)
        self.heavy_values = np.zeros(f, np.int64)

        def column(j):
            """One column on the host: its bins in row order, and from
            its sort the rows of every bin, the rows of each bin's
            heaviest single value, the distinct values and those with
            more rows than an equal-count bin."""
            x = np.ascontiguousarray(X32[:, j])
            t = table[j]
            bins[j, :n] = np.searchsorted(t, x, side="left")
            srt = np.sort(x)
            at = np.searchsorted(srt, t, side="right")    # rows <= bound k
            self.bin_count[j] = np.diff(np.concatenate([[0], at, [n]]))
            starts = np.concatenate(
                [[0], np.flatnonzero(srt[1:] != srt[:-1]) + 1])
            run = np.diff(np.concatenate([starts, [n]]))
            self.distinct[j] = len(starts)
            self.heavy_values[j] = int((run > heavy_over).sum())
            run_bin = np.searchsorted(t, srt[starts], side="left")
            present, first = np.unique(run_bin, return_index=True)
            self.bin_heaviest[j, present] = np.maximum.reduceat(run, first)

        with ThreadPoolExecutor(COLUMN_THREADS) as pool:
            list(pool.map(column, range(f)))
        self.binsT = jnp.asarray(bins)
        del bins
        self.valid = jnp.arange(self.np_rows) < n
        self.y = jnp.pad(jnp.asarray(y, jnp.float32), (0, pad))
        self.y_sign = None                      # the binary objective's
        # ---- the queries ------------------------------------------------
        self.label = np.asarray(y).astype(np.int64)
        self.sizes = np.asarray(group, np.int64)
        self.qb = np.concatenate([[0], np.cumsum(self.sizes)])
        assert self.qb[-1] == n
        self.stated = stated
        self.gains = np.asarray(stated["label_gain"], np.float64)
        self.queries_kept = len(self.sizes)
        self.classes = self._classes()

    def keeping(self, queries: int):
        """These rows with only the first ``queries`` queries counted in
        any sum (the planted fault ``half_queries``): the binned columns
        are shared, the mask and the classes are the copy's own."""
        import copy
        import jax.numpy as jnp
        out = copy.copy(self)
        out.queries_kept = int(queries)
        out.valid = jnp.arange(self.np_rows) < int(self.qb[out.queries_kept])
        out.classes = out._classes()
        return out

    def classes_all(self):
        """The classes over every query, whatever a planted fault leaves
        out of the sums: NDCG is read over the whole training set."""
        if self.queries_kept == len(self.sizes):
            return self.classes
        return self.keeping(len(self.sizes)).classes

    def _classes(self):
        """Per power-of-two class: the queries' document indices padded
        to P (pad slots point at row 0 and are masked), labels, and the
        inverse ideal DCG over ``max_position`` places."""
        import jax.numpy as jnp
        k_max = int(self.stated["max_position"])
        pads = np.asarray([pad_class(int(s)) for s in self.sizes])
        out = []
        for P in sorted(set(pads.tolist())):
            q = np.flatnonzero((pads == P)
                               & (np.arange(len(pads)) < self.queries_kept))
            if not len(q):
                continue
            ok = np.arange(P) < self.sizes[q, None]
            idx = np.where(ok, self.qb[q, None] + np.arange(P), 0)
            lbl = np.where(ok, self.label[idx], -1)
            ideal = -np.sort(-lbl, axis=1)
            k = min(k_max, P)
            disc = 1.0 / np.log2(np.arange(k) + 2.0)
            max_dcg = (np.where(ideal[:, :k] >= 0,
                                self.gains[np.maximum(ideal[:, :k], 0)], 0.0)
                       * disc).sum(axis=1)
            inv = np.where(max_dcg > 0, 1.0 / np.where(max_dcg > 0, max_dcg,
                                                       1.0), 0.0)
            step = max(1, PAIR_ELEMENTS // (P * P))
            grow = (-len(q)) % step             # whole lax.map steps
            padq = lambda a, fill: np.pad(
                a, [(0, grow)] + [(0, 0)] * (a.ndim - 1),
                constant_values=fill)
            out.append({"P": P, "step": step, "queries": q, "ideal": ideal,
                        "idx": jnp.asarray(padq(idx, 0).astype(np.int32)),
                        "ok": jnp.asarray(padq(ok, False)),
                        "lbl": jnp.asarray(padq(np.maximum(lbl, 0), 0)
                                           .astype(np.int32)),
                        "inv": jnp.asarray(padq(inv, 0.0)
                                           .astype(np.float32)),
                        "host_idx": idx, "host_ok": ok, "host_lbl": lbl})
        return out


@functools.lru_cache(maxsize=None)
def _class_lambdas(P: int, step: int, sigma: float, low: bool, fault):
    """The jitted pair work of one class: (scores [Np], idx, ok, lbl, inv,
    gains) -> gradients and hessians of the class's documents [Q, P]."""
    import jax
    import jax.numpy as jnp
    dt = jnp.bfloat16 if low else jnp.float32

    def one_query(s, ok, lbl, inv, gains):
        place = jnp.arange(P, dtype=jnp.int32)
        order = jnp.argsort(jnp.where(ok, -s, jnp.inf), stable=True)
        rank = jnp.zeros(P, jnp.int32).at[order].set(place)
        disc = (1.0 / jnp.log2(rank.astype(jnp.float32) + 2.0)).astype(dt)
        gain = gains[lbl].astype(dt)
        s = s.astype(dt)
        best = jnp.max(jnp.where(ok, s, -jnp.inf))
        worst = jnp.min(jnp.where(ok, s, jnp.inf))
        pair = (lbl[:, None] > lbl[None, :]) & ok[:, None] & ok[None, :]
        gap = s[:, None] - s[None, :]                   # higher - lower
        delta = (gain[:, None] - gain[None, :]) * inv.astype(dt)
        if fault != "no_discount":
            delta = delta * jnp.abs(disc[:, None] - disc[None, :])
        if fault != "no_normaliser":
            delta = jnp.where(best != worst,
                              delta / (dt(0.01) + jnp.abs(gap)), delta)
        p = dt(2.0) / (dt(1.0) + jnp.exp(dt(2.0 * sigma) * gap))
        lam = jnp.where(pair, p * delta, dt(0.0))
        hes = jnp.where(pair, dt(2.0) * p * (dt(2.0) - p) * delta, dt(0.0))
        g = lam.sum(axis=0) - lam.sum(axis=1)
        h = hes.sum(axis=0) + hes.sum(axis=1)
        return g.astype(jnp.float32), h.astype(jnp.float32)

    @jax.jit
    def run(score, idx, ok, lbl, inv, gains):
        s = jnp.where(ok, score.astype(jnp.float32)[idx], 0.0)
        shape = (idx.shape[0] // step, step)
        blocks = (s.reshape(shape + (P,)), ok.reshape(shape + (P,)),
                  lbl.reshape(shape + (P,)), inv.reshape(shape))
        g, h = jax.lax.map(
            lambda b: jax.vmap(one_query, in_axes=(0, 0, 0, 0, None))(
                *b, gains), blocks)
        return g.reshape(-1, P), h.reshape(-1, P)
    return run


def lambdas(score, rows: RankRows, low=False, fault=None):
    """Gradients and hessians [Np] of the scores, float32 device arrays
    (rounded to bfloat16 where ``low``)."""
    import jax.numpy as jnp
    sigma = float(rows.stated["sigmoid"])
    gains = jnp.asarray(rows.gains, jnp.float32)
    g = jnp.zeros(rows.np_rows, jnp.float32)
    h = jnp.zeros(rows.np_rows, jnp.float32)
    for c in rows.classes:
        run = _class_lambdas(c["P"], c["step"], sigma, bool(low), fault)
        gc, hc = run(score, c["idx"], c["ok"], c["lbl"], c["inv"], gains)
        # a class's documents are distinct rows: pad slots add 0 to row 0
        flat = c["idx"].reshape(-1)
        ok = c["ok"].reshape(-1)
        g = g.at[flat].add(jnp.where(ok, gc.reshape(-1), 0.0))
        h = h.at[flat].add(jnp.where(ok, hc.reshape(-1), 0.0))
    if low:
        g = g.astype(jnp.bfloat16).astype(jnp.float32)
        h = h.astype(jnp.bfloat16).astype(jnp.float32)
    return g, h


def ndcg(score, rows: RankRows, cutoffs=None) -> np.ndarray:
    """NDCG at every cut-off over ALL the queries, float64 on the host."""
    cutoffs = [int(k) for k in (cutoffs or rows.stated["eval_at"])]
    s = np.asarray(score, np.float64)[:rows.n]
    total = np.zeros(len(cutoffs))
    n_queries = 0
    for c in rows.classes_all():
        ok, lbl = c["host_ok"], c["host_lbl"]
        sc = np.where(ok, s[c["host_idx"]], -np.inf)
        order = np.argsort(-sc, axis=1, kind="stable")
        got = np.take_along_axis(np.where(ok, rows.gains[np.maximum(lbl, 0)],
                                          0.0), order, axis=1)
        ideal = np.where(c["ideal"] >= 0,
                         rows.gains[np.maximum(c["ideal"], 0)], 0.0)
        disc = 1.0 / np.log2(np.arange(c["P"]) + 2.0)
        for i, k in enumerate(cutoffs):
            k = min(k, c["P"])
            top = (ideal[:, :k] * disc[:k]).sum(axis=1)
            dcg = (got[:, :k] * disc[:k]).sum(axis=1)
            total[i] += np.where(top > 0, dcg / np.where(top > 0, top, 1.0),
                                 1.0).sum()
        n_queries += len(c["queries"])
    return total / n_queries


def objective(score, rows: RankRows, sigmoid, low=False, fault=None):
    """In ``reference.objective``'s place (``follow`` and ``grow`` call it
    by that name): NDCG at the stated cut-offs where the binary reference
    returns its loss, and every row's gradient and hessian."""
    g, h = lambdas(score, rows, low=low, fault=fault)
    return ndcg(score, rows), g, h


def bind(fault=None):
    """Put the ranking objective in ``reference.objective``'s place for
    this process, with ``fault`` planted in it (None: sound)."""
    reference.objective = functools.partial(objective, fault=fault)


# the binary reference's bound tables take a sample's quantiles; for the
# runs in which the reference stands in the program's place, over
# discrete columns too: a value its own bin where a column has few
def own_bounds(X: np.ndarray, max_bin: int, seed: int, sample: int = 200000):
    """Plain bound tables of the reference's own: a column of at most
    ``max_bin`` distinct sample values gets the midpoints between them;
    any other column ``reference.quantile_bounds``' equal-count cuts."""
    rng = np.random.RandomState(seed % (2 ** 32))
    idx = rng.choice(X.shape[0], min(sample, X.shape[0]), replace=False)
    equal = reference.quantile_bounds(X, max_bin, seed, sample=sample)
    out = []
    for f in range(X.shape[1]):
        v = np.unique(X[idx, f].astype(np.float64))
        if len(v) <= max_bin:
            out.append(np.concatenate([(v[:-1] + v[1:]) / 2.0, [np.inf]]))
        else:
            out.append(equal[f])
    return out
