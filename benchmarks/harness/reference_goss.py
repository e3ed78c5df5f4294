"""The plain reference of a GOSS cell: ``reference_sparse``'s sparse rows,
walk, sums and float64 search, called and not copied, with a draw of
its own.  It imports nothing of the program.

- ``draw``: one round's sample from float32 gradients.  ``|g * h|``; the
  ``int(top_rate * N)`` rows of the largest by a stable ``argsort`` of its
  negation (equal scores by row index); every other row kept where the
  stateless draw of (``bagging_seed``, iteration, row) falls under
  ``other_rate / (1 - top_rate)`` (docs/Parameters.md, ``other_rate``: MurmurHash3's
  32-bit finaliser, written out here in numpy), its gradient and hessian
  then amplified by ``(1 - top_rate) / other_rate``.  A row's status: 0
  left out, 1 of the top set, 2 drawn and amplified.
- ``follow``: the program's first ``goss.warmup`` trees walked with the
  program's own leaf values (the scores the program had after them, to
  the float32 bit where the walk agrees), then each later tree followed
  as ``reference_sparse.follow`` follows one, over the KEPT rows: its own
  gradients and draw, the kept rows' amplified gradients, hessians and
  counts summed in float64 by (feature, bin) (a row left out is summed
  into a leaf no tree has and dropped), the float64 search.  A round's
  loss is that of the scores before it plus its own leaf values; the
  scores the next round ranks by take the program's leaf values, so that
  each round's draw is held against the rows the program ranked, not
  against its own rounding of the leaf values.
- ``GossRows``: ``reference_sparse.SparseRows`` whose histograms of a
  leaf's rows count only the rows of the current sample, for
  ``control_goss.py``, where the reference grows in the program's place
  through ``reference_sparse.grow``.
"""

from __future__ import annotations

import time

import numpy as np

from . import reference, reference_sparse

GOLDEN = 0x9E3779B9
DRAW_BITS = 24


def fmix32(x):
    """MurmurHash3's 32-bit finaliser, on uint32 arrays."""
    x = np.asarray(x, np.uint32).copy()
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x *= np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    return x


def uniform_bits(seed: int, iteration: int, n: int):
    """The draw of rows ``0 .. n - 1`` in ``iteration`` under ``seed``."""
    key = fmix32(np.asarray([seed & 0xFFFFFFFF], np.uint32)
                 ^ fmix32(np.asarray([iteration], np.uint32)
                          + np.uint32(GOLDEN)))
    return fmix32(np.arange(n, dtype=np.uint32) * np.uint32(GOLDEN)
                  + key) >> np.uint32(32 - DRAW_BITS)


def draw(g32, h32, goss: dict, iteration: int, fault=None):
    """``(kept, factor, status)`` of one round, every array ``[n]``.
    ``fault`` (``control_goss.py``): ``ties_overshoot`` keeps every row
    whose score reaches the ``top_cnt``-th (a ``>=`` threshold);
    ``no_amplification`` leaves the drawn rows' gradients as they are;
    ``wrong_iteration`` keys the draw by the next iteration."""
    n = len(g32)
    a, b = float(goss["top_rate"]), float(goss["other_rate"])
    top_cnt = int(a * n)
    score = np.abs(np.asarray(g32, np.float32) * np.asarray(h32, np.float32))
    order = np.argsort(-score, kind="stable")
    top = np.zeros(n, bool)
    top[order[:top_cnt]] = True
    if fault == "ties_overshoot" and top_cnt:
        top = score >= score[order[top_cnt - 1]]
    keep_thr = round(b / (1.0 - a) * (1 << DRAW_BITS))
    other = ~top & (uniform_bits(
        int(goss["bagging_seed"]),
        iteration + (1 if fault == "wrong_iteration" else 0), n) < keep_thr)
    amp = np.float32(1.0 if fault == "no_amplification"
                     else (1.0 - a) / b)
    factor = np.where(other, amp, np.float32(1.0)).astype(np.float32)
    status = top.astype(np.int8) + 2 * other.astype(np.int8)
    return top | other, factor, status


def follow(rows, trees, bounds, cfg):
    """Per followed tree what ``correct.numbers_from`` reads, as
    ``reference_sparse.follow`` returns it, and the reference's status of
    every row in each followed round."""
    goss, stated = cfg["goss"], cfg["stated"]
    warmup = int(goss["warmup"])
    lr = float(cfg["learning_rate"])
    l2 = float(stated["lambda_l2"])
    sig = float(stated["sigmoid"])
    min_data = int(cfg["min_data_in_leaf"])
    score = np.full(rows.n, float(stated["init_score"]), np.float32)
    spent = {"walk": 0.0, "objective": 0.0, "draw": 0.0, "route": 0.0,
             "histograms": 0.0, "host": 0.0}
    out, statuses = [], []

    def timed(what, t0):
        spent[what] += time.time() - t0
        return time.time()

    t = time.time()
    for tree in trees[:warmup]:
        S, leaf = reference.parse_tree(tree, bounds)
        score = score + leaf["value"].astype(np.float32)[rows.route(S)]
    t = timed("walk", t)
    _, g, h = reference_sparse._objective(score, rows, sig)
    t = timed("objective", t)
    for it, tree in enumerate(trees[warmup:], start=warmup):
        kept, factor, status = draw(g, h, goss, it)
        statuses.append(status)
        gk = np.where(kept, g * factor, 0.0)
        hk = np.where(kept, h * factor, 0.0)
        t = timed("draw", t)
        S, leaf = reference.parse_tree(tree, bounds)
        L = len(leaf["value"])
        leaf_id = rows.route(S)
        t = timed("route", t)
        per_leaf = rows.leaf_histograms(np.where(kept, leaf_id, L), gk, hk,
                                        L + 1)[:L]
        t = timed("histograms", t)
        tot_leaf = per_leaf[:, :int(rows.n_bins_f[0])].sum(axis=1)
        ref_leaf_value = lr * reference.leaf_value(tot_leaf[:, 0],
                                                   tot_leaf[:, 1], l2)
        node = {"count": [], "value": [], "best": [], "chosen": [],
                "term": []}
        for k in range(len(S["feat"])):
            hn = per_leaf[S["leaves"][k]].sum(axis=0)
            G, H, C = hn[:int(rows.n_bins_f[0])].sum(axis=0)
            gains = rows.split_gains(hn, stated, min_data)
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
        loss, _, _ = reference_sparse._objective(
            score + ref_leaf_value.astype(np.float32)[leaf_id], rows, sig)
        score = score + leaf["value"].astype(np.float32)[leaf_id]
        _, g, h = reference_sparse._objective(score, rows, sig)
        t = timed("objective", t)
        out.append({"S": S, "leaf": leaf,
                    "ref_leaf_count": np.rint(tot_leaf[:, 2]).astype(np.int64),
                    "ref_leaf_value": ref_leaf_value,
                    "ref_node_count": np.asarray(node["count"], np.int64),
                    "ref_node_value": np.asarray(node["value"]),
                    "best": np.asarray(node["best"]),
                    "chosen": np.asarray(node["chosen"]),
                    "leaf_best": np.asarray(leaf_best), "loss": loss,
                    "node_term": np.asarray(node["term"]),
                    "leaf_term": reference.leaf_gain(
                        tot_leaf[:, 0], tot_leaf[:, 1], l2)})
    if out:
        out[0]["spent"] = {k: round(v, 1) for k, v in spent.items()}
    return out, statuses


class GossRows:
    """``reference_sparse.SparseRows`` whose ``rows_histogram`` sums the
    rows of the current sample only (``kept``; None: every row)."""

    def __init__(self, rows):
        self._rows = rows
        self.kept = None

    def __getattr__(self, name):
        return getattr(self._rows, name)

    def rows_histogram(self, idx, g, h, fix_zero=True):
        if self.kept is not None:
            idx = idx[self.kept[idx]]
        return self._rows.rows_histogram(idx, g, h, fix_zero)
