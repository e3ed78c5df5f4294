"""The plain reference of a sparse cell: ``reference.py``'s leaf-wise
histogram GBDT for the binary objective, working from a CSC matrix's
STORED entries.  It imports nothing of the program and knows nothing of
bundles: every histogram is in ORIGINAL feature space.

What it does itself: bins every stored value with the bound table under
test (``searchsorted`` on the float32 values, a column at a time), and
the zeros that are not stored by where 0.0 falls in the column's table;
walks every row down a tree from those bins; sums gradient, hessian and
one over a leaf's stored entries by (feature, bin) in float64
(``numpy.bincount``, a column at a time), the bin that holds zero then
gaining the leaf's total less the column's stored sums; makes a parent's
histogram by float64 addition of its leaves'; and counts, by its own
sort of every column over ALL rows, what ``correct_rank.bin_table_gap``
holds a bound table against.

What it takes from ``reference.py``, called and not copied: ``parse_tree``
(a ``dump_model`` tree as arrays), ``split_gains`` (the float64 search;
here over the features of one bin count at a time, since a dense
``[4228, 255, 3]`` a node would be forty times the stored bins),
``leaf_value``, ``floor_f32`` and ``objective`` (float32 gradients on the
device, the loss on the host).

- ``follow``: teacher-forced over the program's trees, returning what
  ``correct.numbers_from`` reads, as ``reference.follow`` does.
- ``grow``: best-first growth with its own argmax, for the control and
  the planted faults (``control_sparse.py``).  A split's smaller child is
  summed from its rows' stored entries (a CSR copy of the bins, made
  once).  ``precision="bfloat16"``: gradients, hessians, leaf values and
  scores in bfloat16 (the sums stay wide: the mildest such control).
  ``fault``: ``half_rows`` (the second half of the rows left out of every
  sum), ``zero_bin_dropped`` (the bin that holds zero never gains the
  leaf's total less the stored sums: what a bundle member's default bin
  reads when its column's total is not subtracted), ``decode_off_by_one``
  (rows partitioned by a bin read one slot too low: what a bundle member
  decoded one slot off sends right).
"""

from __future__ import annotations

import time

import numpy as np

from . import reference

COLUMN_THREADS = 8


def _threads(fn, items):
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(COLUMN_THREADS) as pool:
        return list(pool.map(fn, items))


def _longest_first(indptr):
    """Columns by stored entries, descending: fourteen numerics of 12M
    entries each at the end of the queue were the tail of every pass."""
    return np.argsort(-np.diff(indptr), kind="stable")


class SparseRows:
    """A CSC matrix binned by the reference: per stored entry its row and
    its bin; per feature its bins, the bin of zero, and the counts of
    ``bin_table_gap``; the labels as ``reference.objective`` reads
    them."""

    def __init__(self, X, y, bounds, max_bin: int):
        import jax.numpy as jnp
        X = X.tocsc()
        if not X.has_sorted_indices:
            X = X.sorted_indices()
        n, f = X.shape
        self.n, self.f = n, f
        self.indptr = np.asarray(X.indptr, np.int64)
        self.rows = np.asarray(X.indices)
        values = np.asarray(X.data, np.float32)
        self.n_bins_f = np.asarray([len(b) for b in bounds], np.int64)
        self.offset = np.concatenate([[0], np.cumsum(self.n_bins_f)])
        self.T = int(self.offset[-1])
        B = max(2, int(self.n_bins_f.max()))
        tables = [reference.floor_f32(np.asarray(b[:-1], np.float64))
                  for b in bounds]
        self.zero_bin = np.asarray(
            [np.searchsorted(t, np.float32(0), side="left") for t in tables],
            np.int64)
        self.bins = np.zeros(len(values), np.uint8)
        self.bin_count = np.zeros((f, B), np.int64)
        self.bin_heaviest = np.zeros((f, B), np.int64)
        self.distinct = np.zeros(f, np.int64)
        self.heavy_values = np.zeros(f, np.int64)
        heavy_over = n // int(max_bin)

        def column(j):
            """One column: its stored entries' bins, and from the sort of
            ALL its values (the zeros that are not stored among them) the
            rows of every bin, the rows of each bin's heaviest single
            value, the distinct values and those with more rows than an
            equal-count bin."""
            a, b = self.indptr[j], self.indptr[j + 1]
            v, t = values[a:b], tables[j]
            self.bins[a:b] = np.searchsorted(t, v, side="left")
            uniq, cnt = np.unique(v, return_counts=True)
            if n - len(v):
                at = int(np.searchsorted(uniq, 0))
                if at < len(uniq) and uniq[at] == 0:
                    cnt[at] += n - len(v)
                else:
                    uniq = np.insert(uniq, at, 0)
                    cnt = np.insert(cnt, at, n - len(v))
            self.distinct[j] = len(uniq)
            self.heavy_values[j] = int((cnt > heavy_over).sum())
            ubin = np.searchsorted(t, uniq, side="left")
            self.bin_count[j] = np.bincount(ubin, weights=cnt, minlength=B)
            present, first = np.unique(ubin, return_index=True)
            self.bin_heaviest[j, present] = np.maximum.reduceat(cnt, first)

        _threads(column, _longest_first(self.indptr))
        self.np_rows = n
        self.y = jnp.asarray(y, jnp.float32)
        self.y_sign = np.where(np.asarray(y) > 0, 1.0, -1.0).astype(
            np.float32)
        self._csr = None

    # ---- the walk -----------------------------------------------------------

    def goes_right(self, feat, thr, idx, bin_shift=0):
        """Of the ascending rows ``idx``, which hold a bin of ``feat``
        over ``thr``: a stored entry's own bin, the bin of zero where
        the row stores nothing.  Work by the smaller of the leaf and the
        column, never by the table."""
        a, b = self.indptr[feat], self.indptr[feat + 1]
        over = int(thr) + int(bin_shift)
        if b - a == self.n:             # every row stores it, in row order
            return self.bins[a:b][idx] > over
        right = np.full(len(idx), int(self.zero_bin[feat]) > over)
        if b > a and len(idx):
            r = self.rows[a:b]
            if b - a <= len(idx):       # the column's entries among idx
                at = np.minimum(np.searchsorted(idx, r), len(idx) - 1)
                hit = idx[at] == r
                right[at[hit]] = self.bins[a:b][hit] > over
            else:                       # idx among the column's entries
                at = np.minimum(np.searchsorted(r, idx), b - a - 1)
                hit = r[at] == idx
                right[hit] = self.bins[a:b][at[hit]] > over
        return right

    def split_rows(self, leaf, members, feat, thr, new, bin_shift=0):
        """Of a leaf's rows ``members`` (ascending) those whose bin of
        ``feat`` lies over ``thr`` go to leaf ``new`` (``leaf`` in place);
        returns the rows that stay and the rows that went, both
        ascending.  ``bin_shift``: the planted decode fault."""
        right = self.goes_right(feat, thr, members, bin_shift)
        went = members[right]
        leaf[went] = new
        return members[~right], went

    def route(self, S):
        leaf = np.zeros(self.n, np.int32)
        members = {0: np.arange(self.n, dtype=np.int32)}
        for k in range(len(S["feat"])):
            which = int(S["leaf_of"][k])
            members[which], members[k + 1] = self.split_rows(
                leaf, members.pop(which), int(S["feat"][k]),
                int(S["thr"][k]), k + 1)
        return leaf

    # ---- sums ---------------------------------------------------------------

    def _fix_zero_bins(self, hist, totals):
        """``hist`` [..., T, 3] of stored entries, ``totals`` [..., 3]: the
        bin that holds zero gains the total less the feature's stored
        sums."""
        stored = np.add.reduceat(hist, self.offset[:-1], axis=-2)
        hist[..., self.offset[:-1] + self.zero_bin, :] += \
            totals[..., None, :] - stored
        return hist

    def leaf_histograms(self, leaf, g, h, L: int):
        """[L, T, 3] float64: gradient, hessian and count by leaf and by
        (feature, bin), in one pass over the stored entries."""
        out = np.zeros((L, self.T, 3))
        one = np.ones(self.n)
        totals = np.stack([np.bincount(leaf, weights=w, minlength=L)
                           for w in (g, h, one)], axis=-1)

        def column(j):
            a, b = self.indptr[j], self.indptr[j + 1]
            nb, off = int(self.n_bins_f[j]), int(self.offset[j])
            if a == b:
                return
            # a column that every row stores is the rows in order
            at = slice(None) if b - a == self.n else self.rows[a:b]
            key = leaf[at].astype(np.int64) * nb + self.bins[a:b]
            for s, w in enumerate((g[at], h[at], None)):
                out[:, off:off + nb, s] = np.bincount(
                    key, weights=w, minlength=L * nb).reshape(L, nb)

        _threads(column, _longest_first(self.indptr))
        return self._fix_zero_bins(out, totals)

    def csr(self):
        """The stored entries by row: per entry the global bin code
        ``offset[feature] + bin``.  Made once, for ``grow``."""
        if self._csr is None:
            import scipy.sparse as sp
            code = (np.repeat(self.offset[:-1], np.diff(self.indptr))
                    + self.bins).astype(np.int32)
            m = sp.csc_matrix((code, self.rows, self.indptr),
                              shape=(self.n, self.f)).tocsr()
            self._csr = (np.asarray(m.indptr, np.int64), m.data)
        return self._csr

    def rows_histogram(self, idx, g, h, fix_zero=True):
        """[T, 3] float64 over the rows ``idx`` from their stored
        entries."""
        ptr, code = self.csr()
        starts, lens = ptr[idx], ptr[idx + 1] - ptr[idx]
        total = int(lens.sum())
        pos = np.repeat(starts - (np.cumsum(lens) - lens), lens) \
            + np.arange(total)
        c = code[pos]
        hist = np.stack(
            [np.bincount(c, weights=np.repeat(w[idx], lens),
                         minlength=self.T) for w in (g, h)]
            + [np.bincount(c, minlength=self.T).astype(np.float64)], axis=-1)
        if fix_zero:
            totals = np.asarray([g[idx].sum(dtype=np.float64),
                                 h[idx].sum(dtype=np.float64), len(idx)])
            hist = self._fix_zero_bins(hist, totals)
        return hist

    # ---- the float64 search, a bin count at a time --------------------------

    def split_gains(self, hist, stated, min_data):
        """``reference.split_gains`` of one node's [T, 3] histogram: the
        gains [T] of every (feature, threshold), -inf where a split is
        not allowed (a feature's last bin is no threshold)."""
        gains = np.full(self.T, -np.inf)
        for nb in np.unique(self.n_bins_f):
            if nb < 2:
                continue
            feats = np.flatnonzero(self.n_bins_f == nb)
            at = self.offset[feats][:, None] + np.arange(nb)
            gains[at] = reference.split_gains(hist[at], stated, min_data,
                                              [nb] * len(feats))
        return gains

    def best_of(self, hist, stated, min_data):
        gains = self.split_gains(hist, stated, min_data)
        at = int(np.argmax(gains))
        f = int(np.searchsorted(self.offset, at, side="right")) - 1
        return float(gains[at]), f, at - int(self.offset[f])


def _objective(score, rows, sig, low=False):
    import jax.numpy as jnp
    loss, g, h = reference.objective(jnp.asarray(score), rows, sig, low)
    return loss, np.asarray(g, np.float64), np.asarray(h, np.float64)


def follow(rows: SparseRows, trees, bounds, cfg):
    """``reference.follow`` over sparse rows: per tree what the
    comparison needs, from the reference's own gradients and scores."""
    stated = cfg["stated"]
    lr = float(cfg["learning_rate"])
    l2 = float(stated["lambda_l2"])
    sig = float(stated["sigmoid"])
    min_data = int(cfg["min_data_in_leaf"])
    score = np.full(rows.n, float(stated["init_score"]), np.float32)
    spent = {"objective": 0.0, "route": 0.0, "histograms": 0.0, "host": 0.0}
    out = []

    def timed(what, t0):
        spent[what] += time.time() - t0
        return time.time()

    t = time.time()
    _, g, h = _objective(score, rows, sig)
    t = timed("objective", t)
    for tree in trees:
        S, leaf = reference.parse_tree(tree, bounds)
        L = len(leaf["value"])
        leaf_id = rows.route(S)
        t = timed("route", t)
        per_leaf = rows.leaf_histograms(leaf_id, g, h, L)      # [L, T, 3]
        t = timed("histograms", t)
        # every feature's bins sum to the leaf's totals: feature 0's
        tot_leaf = per_leaf[:, :int(rows.n_bins_f[0])].sum(axis=1)
        ref_leaf_value = lr * reference.leaf_value(tot_leaf[:, 0],
                                                   tot_leaf[:, 1], l2)
        node = {"count": [], "value": [], "best": [], "chosen": [],
                "term": []}
        for k in range(len(S["feat"])):
            hk = per_leaf[S["leaves"][k]].sum(axis=0)
            G, H, C = hk[:int(rows.n_bins_f[0])].sum(axis=0)
            gains = rows.split_gains(hk, stated, min_data)
            node["term"].append(reference.leaf_gain(G, H, l2))
            node["count"].append(int(round(C)))
            node["value"].append(lr * reference.leaf_value(G, H, l2))
            node["best"].append(float(gains.max()))
            node["chosen"].append(float(
                gains[rows.offset[S["feat"][k]] + S["thr"][k]]))
        leaf_best = [float(rows.split_gains(per_leaf[i], stated,
                                            min_data).max())
                     for i in range(L)]
        t = timed("host", t)
        score = score + ref_leaf_value.astype(np.float32)[leaf_id]
        loss, g, h = _objective(score, rows, sig)
        t = timed("objective", t)
        out.append({"S": S, "leaf": leaf,
                    "ref_leaf_count": np.rint(tot_leaf[:, 2]).astype(np.int64),
                    "ref_leaf_value": ref_leaf_value,
                    "ref_node_count": np.asarray(node["count"], np.int64),
                    "ref_node_value": np.asarray(node["value"]),
                    "best": np.asarray(node["best"]),
                    "chosen": np.asarray(node["chosen"]),
                    "leaf_best": np.asarray(leaf_best), "loss": loss,
                    # a node's own term G^2 / (H + l2), which every gain
                    # of it is a difference of: the scale of a float32
                    # search's resolution (correct_sparse.py)
                    "node_term": np.asarray(node["term"]),
                    "leaf_term": reference.leaf_gain(
                        tot_leaf[:, 0], tot_leaf[:, 1], l2)})
    out[0]["spent"] = {k: round(v, 1) for k, v in spent.items()}
    return out


def grow(rows: SparseRows, bounds, cfg, n_trees: int, precision="float32",
         fault=None):
    """``reference.grow`` over sparse rows: best-first trees in
    ``dump_model``'s form and the loss after each (module docstring for
    ``precision`` and ``fault``)."""
    import jax.numpy as jnp
    stated = cfg["stated"]
    lr = float(cfg["learning_rate"])
    l2 = float(stated["lambda_l2"])
    sig = float(stated["sigmoid"])
    min_data = int(cfg["min_data_in_leaf"])
    max_leaves = int(cfg["num_leaves"])
    low = precision == "bfloat16"
    shift = 1 if fault == "decode_off_by_one" else 0
    fix_zero = fault != "zero_bin_dropped"
    limit = rows.n // 2 if fault == "half_rows" else rows.n

    def counted(idx):
        """Of ascending rows those that enter the sums."""
        return idx[:np.searchsorted(idx, limit)]

    def rounded(x):
        if not low:
            return x
        return np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))

    score = np.full(rows.n, float(stated["init_score"]), np.float32)
    trees, losses = [], []
    _, g, h = _objective(score, rows, sig, low)
    for _ in range(n_trees):
        leaf_id = np.zeros(rows.n, np.int32)
        routed = {0: np.arange(rows.n, dtype=np.int32)}     # a leaf's rows
        members = {0: counted(routed[0])}   # those of them that are summed
        hists = {0: rows.rows_histogram(members[0], g, h, fix_zero)}
        best = {0: rows.best_of(hists[0], stated, min_data)}
        root_holder = {"node": None}
        where = {0: (root_holder, "node")}
        n_leaves = 1
        nb0 = int(rows.n_bins_f[0])
        for k in range(max_leaves - 1):
            cand = max(best, key=lambda i: best[i][0])
            gain, f, t = best[cand]
            if not np.isfinite(gain):
                break
            new = n_leaves
            routed[cand], routed[new] = rows.split_rows(
                leaf_id, routed.pop(cand), f, t, new, bin_shift=shift)
            hp = hists.pop(cand)
            members[cand], members[new] = (counted(routed[cand]),
                                           counted(routed[new]))
            small = cand if len(members[cand]) <= len(members[new]) else new
            hs = rows.rows_histogram(members[small], g, h, fix_zero)
            hl, hr = (hs, hp - hs) if small == cand else (hp - hs, hs)
            G, H, C = hp[:nb0].sum(axis=0)
            node = {"split_index": k, "split_feature": f,
                    "split_gain": gain, "threshold": float(bounds[f][t]),
                    "decision_type": "no_greater",
                    "internal_value": reference.leaf_value(G, H, l2)
                    if k else 0.0,
                    "internal_count": int(round(C)),
                    "left_child": None, "right_child": None}
            holder, key = where.pop(cand)
            holder[key] = node
            where[cand] = (node, "left_child")
            where[new] = (node, "right_child")
            hists[cand], hists[new] = hl, hr
            best[cand] = rows.best_of(hl, stated, min_data)
            best[new] = rows.best_of(hr, stated, min_data)
            n_leaves += 1
        values = np.zeros(n_leaves)
        counts = np.zeros(n_leaves, np.int64)
        for i in range(n_leaves):
            G, H, C = hists[i][:nb0].sum(axis=0)
            values[i] = float(rounded(np.float32(
                lr * reference.leaf_value(G, H, l2))))
            counts[i] = int(round(C))
        for i in range(n_leaves):
            holder, key = where[i]
            holder[key] = {"leaf_index": i, "leaf_value": float(values[i]),
                           "leaf_count": int(counts[i])}
        trees.append({"num_leaves": n_leaves, "shrinkage": lr,
                      "tree_structure": root_holder["node"]})
        score = rounded(score + values.astype(np.float32)[leaf_id])
        loss, g, h = _objective(score, rows, sig, low)
        losses.append(loss)
    return trees, losses


def own_bounds(X, max_bin: int, seed: int, sample: int = 200000):
    """Plain bound tables of the reference's own, for the runs in which
    it stands in the program's place: from a row sample's values (the
    zeros that are not stored among them), a column of at most
    ``max_bin`` distinct values gets the midpoints between them, any
    other ``reference.quantile_bounds``' equal-count cuts."""
    rng = np.random.RandomState(seed % (2 ** 32))
    n = X.shape[0]
    idx = np.sort(rng.choice(n, min(sample, n), replace=False))
    S = X.tocsr()[idx].tocsc()
    out = []
    for j in range(X.shape[1]):
        v = np.asarray(S.data[S.indptr[j]:S.indptr[j + 1]], np.float64)
        v = np.concatenate([v, np.zeros(len(idx) - len(v))])
        u = np.unique(v)
        if len(u) <= max_bin:
            out.append(np.concatenate([(u[:-1] + u[1:]) / 2.0, [np.inf]]))
        else:
            out.append(reference.quantile_bounds(v[:, None], max_bin, seed,
                                                 sample=len(v))[0])
    return out
