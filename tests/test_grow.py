"""Tree-grower correctness: against a brute-force host-side oracle that
re-states the reference's leaf-wise algorithm (histogram + right-to-left
scan + best-leaf argmax) in plain NumPy."""

import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops.grow import GrowParams, grow_tree
from lightgbm_tpu.ops.split import SplitParams, find_best_split
from lightgbm_tpu.ops.histogram import build_root_histogram


def _np_hist(bins, g, h, w, B):
    F, N = bins.shape
    out = np.zeros((F, B, 3))
    for f in range(F):
        for i in range(N):
            b = bins[f, i]
            out[f, b, 0] += g[i]
            out[f, b, 1] += h[i]
            out[f, b, 2] += w[i]
    return out


def _np_best_split(hist, tg, th, tc, num_bin, is_cat, p: SplitParams,
                   feat_mask=None):
    """Reference scan transcription (feature_histogram.hpp:75-187)."""
    F, B, _ = hist.shape
    best = dict(gain=-np.inf, feat=-1, t=-1, lg=0.0, lh=0.0, lc=0.0)
    gain_shift = _gain(tg, th, p)
    for f in range(F):
        nb = num_bin[f]
        if nb <= 1 or (feat_mask is not None and not feat_mask[f]):
            continue
        if is_cat[f]:
            cands = [(t, hist[f, t, 0], hist[f, t, 1], hist[f, t, 2])
                     for t in range(nb - 1, -1, -1)]
        else:
            cum = np.cumsum(hist[f, :, :], axis=0)
            cands = [(t, cum[t, 0], cum[t, 1], cum[t, 2])
                     for t in range(nb - 2, -1, -1)]
        for t, lg, lh, lc in cands:
            rg, rh, rc = tg - lg, th - lh, tc - lc
            if lc < p.min_data_in_leaf or rc < p.min_data_in_leaf:
                continue
            if lh < p.min_sum_hessian_in_leaf or rh < p.min_sum_hessian_in_leaf:
                continue
            cur = _gain(lg, lh, p) + _gain(rg, rh, p)
            if cur <= gain_shift + p.min_gain_to_split:
                continue
            if cur > best["gain"] + gain_shift or (
                    np.isclose(cur - gain_shift, best["gain"]) and f < best["feat"]):
                # strictly-greater within a feature handled by scan order
                if cur - gain_shift > best["gain"]:
                    best = dict(gain=cur - gain_shift, feat=f, t=t,
                                lg=lg, lh=lh, lc=lc)
    return best


def _gain(G, H, p):
    reg = max(abs(G) - p.lambda_l1, 0.0)
    return reg * reg / (H + p.lambda_l2)


def _make_data(seed=0, n=400, f=5, B=16):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, B, size=(f, n)).astype(np.int32)
    g = rng.normal(size=n).astype(np.float32)
    h = np.abs(rng.normal(size=n)).astype(np.float32) + 0.1
    return bins, g, h


def test_histogram_matches_numpy():
    bins, g, h = _make_data()
    w = np.ones_like(g)
    hist = np.asarray(build_root_histogram(jnp.asarray(bins), jnp.asarray(g),
                                           jnp.asarray(h), jnp.asarray(w), 16))
    expected = _np_hist(bins, g, h, w, 16)
    np.testing.assert_allclose(hist, expected, rtol=1e-4, atol=1e-4)


def _one_leaf_case(seed, l1, l2, min_data, min_hess):
    """One leaf over all rows: [F, B, 3], scalar totals."""
    bins, g, h = _make_data(seed=seed, B=16)
    F = bins.shape[0]
    hist = _np_hist(bins, g, h, np.ones_like(g), 16)
    p = SplitParams(min_data_in_leaf=min_data,
                    min_sum_hessian_in_leaf=min_hess,
                    lambda_l1=l1, lambda_l2=l2, min_gain_to_split=0.0)
    return dict(hist=hist, totals=np.asarray([g.sum(), h.sum(), len(g)]),
                num_bin=np.full(F, 16, np.int32), is_cat=np.zeros(F, bool),
                feat_mask=np.ones(F, bool), can=np.asarray(True), p=p)


def _two_child_case(seed, p, n=700, f=6, B=21, n_cat=2, masked_from=None,
                    can=(True, True), splittable=True):
    """Both children of a split leaf as the growers hand them over: a
    [2, F, B, 3] histogram with [2] totals and guards, bagged-out rows,
    rows of a third leaf, features with fewer bins than B, two
    categorical features."""
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, B, size=(f, n))
    g = rng.normal(size=n).astype(np.float32)
    h = rng.uniform(0.2, 1.5, size=n).astype(np.float32)
    w = (rng.uniform(size=n) > 0.25).astype(np.float32)
    leaf = rng.randint(0, 3, size=n)
    num_bin = rng.randint(2, B + 1, size=f).astype(np.int32)
    is_cat = np.arange(f) < n_cat
    feat_mask = np.ones(f, bool)
    if masked_from is not None:
        feat_mask[masked_from:] = False
    hist, totals = [], []
    for child in (0, 1):
        m = (leaf == child) * w
        hist.append(_np_hist(bins, g * m, h * m, m, B))
        totals.append([(g * m).sum(), (h * m).sum(), m.sum()])
    return dict(hist=np.stack(hist), totals=np.asarray(totals).T,
                num_bin=num_bin, is_cat=is_cat, feat_mask=feat_mask,
                can=np.asarray(can), p=p, splittable=splittable)


_LOOSE = SplitParams(min_data_in_leaf=5, min_sum_hessian_in_leaf=1e-3)
_SPLIT_CASES = {
    **{f"leaf-{l1}-{l2}-{md}-{mh}-{seed}":
       (_one_leaf_case, (seed, l1, l2, md, mh))
       for (l1, l2, md, mh) in [(0.0, 0.0, 5, 1e-3), (0.5, 1.0, 10, 0.5)]
       for seed in range(4)},
    **{f"two_child-{seed}": (_two_child_case, (seed, _LOOSE))
       for seed in range(3)},
    "l1_and_min_gain": (_two_child_case, (3, SplitParams(
        min_data_in_leaf=10, min_sum_hessian_in_leaf=0.5, lambda_l1=0.3,
        lambda_l2=0.7, min_gain_to_split=0.05), 900, 6, 17)),
    # min_data_in_leaf near the leaf size (about 100 bagged rows a
    # child): most candidates invalid, the valid frontier decides
    "min_data_edge": (_two_child_case, (4, SplitParams(
        min_data_in_leaf=40, min_sum_hessian_in_leaf=10.0), 400)),
    # impossible constraints: gain -inf, feature -1, threshold 0
    "all_unsplittable": (_two_child_case, (5, SplitParams(
        min_data_in_leaf=10_000), 300, 6, 21, 2, None, (True, True),
        False)),
    # only features 0 and 1 usable, and the right child may not split
    "mask_and_can_split": (_two_child_case, (6, _LOOSE, 700, 6, 21, 2, 2,
                                              (True, False))),
}


@pytest.mark.parametrize("case", list(_SPLIT_CASES))
def test_find_best_split_matches_oracle(case):
    make, args = _SPLIT_CASES[case]
    c = make(*args)
    p, hist, (tg, th, tc) = c["p"], c["hist"], c["totals"]
    got = find_best_split(jnp.asarray(hist, jnp.float32), jnp.float32(tg),
                          jnp.float32(th), jnp.float32(tc),
                          jnp.asarray(c["num_bin"]), jnp.asarray(c["is_cat"]),
                          jnp.asarray(c["feat_mask"]), jnp.asarray(c["can"]),
                          p)
    # one oracle call a leaf: leading dims of the batched input, if any
    leaves = [()] if hist.ndim == 3 else [(0,), (1,)]
    finite = []
    for ix in leaves:
        oracle = _np_best_split(hist[ix], tg[ix], th[ix], tc[ix],
                                c["num_bin"], c["is_cat"], p,
                                c["feat_mask"])
        if not c["can"][ix]:
            oracle = dict(gain=-np.inf, feat=-1)
        rec = [np.asarray(f)[ix] for f in got]
        assert int(rec[1]) == oracle["feat"]
        if oracle["feat"] < 0:
            assert rec[0] == -np.inf and int(rec[2]) == 0
            continue
        finite.append(ix)
        assert int(rec[2]) == oracle["t"]
        np.testing.assert_allclose(rec[0], oracle["gain"], rtol=1e-4)
        np.testing.assert_allclose(rec[3], oracle["lg"], rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(rec[4], oracle["lh"], rtol=1e-5)
        np.testing.assert_allclose(rec[5], oracle["lc"], rtol=1e-5)
        assert c["feat_mask"][oracle["feat"]]
    assert bool(finite) == c.get("splittable", True), "degenerate scenario"


@pytest.mark.parametrize("case", ["noisy_category_5",
                                  "category_0_has_the_negative_mass"])
def test_find_best_split_categorical(case):
    rng = np.random.RandomState(3)
    p = SplitParams(min_data_in_leaf=5, min_sum_hessian_in_leaf=1e-3)
    if case == "noisy_category_5":
        n, B, want = 600, 8, (0, 5)
        bins = rng.randint(0, B, size=(1, n)).astype(np.int32)
        g = np.where(bins[0] == 5, -2.0, 0.5).astype(np.float32) \
            + rng.normal(scale=0.1, size=n).astype(np.float32)
        num_bin, is_cat = [B], [True]
    else:
        # known answer beside a numerical feature that says nothing:
        # one-vs-rest, "category 0 goes left"
        n, B, want = 512, 8, (0, 0)
        cats = rng.randint(0, 4, size=n)
        bins = np.stack([cats, rng.randint(0, B, size=n)]).astype(np.int32)
        g = np.where(cats == 0, -2.0, 1.0).astype(np.float32)
        num_bin, is_cat = [4, B], [True, False]
    h = np.ones(n, np.float32)
    hist = _np_hist(bins, g, h, np.ones(n), B)
    got = find_best_split(jnp.asarray(hist, jnp.float32),
                          jnp.float32(g.sum()), jnp.float32(h.sum()),
                          jnp.float32(n), jnp.asarray(num_bin, np.int32),
                          jnp.asarray(is_cat), jnp.ones(len(is_cat), bool),
                          jnp.asarray(True), p)
    assert (int(got.feature), int(got.threshold)) == want


def test_grow_tree_structure_and_fit():
    # single clean split on feature 0 at bin <= 7
    rng = np.random.RandomState(0)
    n = 1000
    bins = np.stack([rng.randint(0, 16, n), rng.randint(0, 16, n)]).astype(np.int32)
    target = np.where(bins[0] <= 7, 2.0, -1.0)
    score = np.zeros(n)
    g = (score - target).astype(np.float32)  # L2 gradients
    h = np.ones(n, np.float32)
    params = GrowParams(num_leaves=2, max_bin=16, min_data_in_leaf=5,
                        min_sum_hessian_in_leaf=1e-3)
    tree, leaf_id, delta = grow_tree(
        jnp.asarray(bins), jnp.asarray([16, 16], np.int32),
        jnp.zeros(2, bool), jnp.ones(2, bool),
        jnp.asarray(g), jnp.asarray(h), jnp.ones(n, jnp.float32),
        jnp.float32(1.0), params)
    assert int(tree.num_leaves) == 2
    assert int(tree.split_feature[0]) == 0
    assert int(tree.split_bin[0]) == 7
    # leaf outputs approximate targets (lr=1, L2 loss, one split)
    lv = np.asarray(tree.leaf_value)
    assert abs(lv[0] - 2.0) < 1e-3 and abs(lv[1] + 1.0) < 1e-3
    # partition + delta agree
    np.testing.assert_array_equal(np.asarray(leaf_id), np.where(bins[0] <= 7, 0, 1))
    np.testing.assert_allclose(np.asarray(delta), lv[np.asarray(leaf_id)], rtol=1e-6)


def test_grow_tree_depth_guard():
    bins, g, h = _make_data(n=2000, f=4, B=32)
    params = GrowParams(num_leaves=31, max_bin=32, min_data_in_leaf=5,
                        min_sum_hessian_in_leaf=1e-3, max_depth=2)
    tree, _, _ = grow_tree(
        jnp.asarray(bins), jnp.full(4, 32, np.int32),
        jnp.zeros(4, bool), jnp.ones(4, bool),
        jnp.asarray(g), jnp.asarray(h), jnp.ones(2000, jnp.float32),
        jnp.float32(0.1), params)
    # max_depth=2 means at most 4 leaves
    assert int(tree.num_leaves) <= 4
    depths = np.asarray(tree.leaf_depth)[:int(tree.num_leaves)]
    assert depths.max() <= 2


def test_grow_tree_stops_without_gain():
    # constant gradients and huge min_gain: no split possible
    n = 300
    bins = np.zeros((2, n), dtype=np.int32)  # all same bin -> no candidates
    g = np.ones(n, np.float32)
    h = np.ones(n, np.float32)
    params = GrowParams(num_leaves=15, max_bin=8, min_data_in_leaf=5,
                        min_sum_hessian_in_leaf=1e-3)
    tree, leaf_id, delta = grow_tree(
        jnp.asarray(bins), jnp.asarray([8, 8], np.int32),
        jnp.zeros(2, bool), jnp.ones(2, bool),
        jnp.asarray(g), jnp.asarray(h), jnp.ones(n, jnp.float32),
        jnp.float32(1.0), params)
    assert int(tree.num_leaves) == 1
    np.testing.assert_array_equal(np.asarray(leaf_id), 0)


def test_grow_tree_matches_oracle_sequence():
    """Full leaf-wise growth vs a host oracle that replays the same policy."""
    rng = np.random.RandomState(7)
    n, F, B, L = 800, 3, 8, 6
    bins = rng.randint(0, B, size=(F, n)).astype(np.int32)
    g = rng.normal(size=n).astype(np.float32)
    h = np.ones(n, np.float32)
    p = SplitParams(min_data_in_leaf=10, min_sum_hessian_in_leaf=1e-3)
    params = GrowParams(num_leaves=L, max_bin=B, min_data_in_leaf=10,
                        min_sum_hessian_in_leaf=1e-3)

    tree, leaf_id, _ = grow_tree(
        jnp.asarray(bins), jnp.full(F, B, np.int32), jnp.zeros(F, bool),
        jnp.ones(F, bool), jnp.asarray(g), jnp.asarray(h),
        jnp.ones(n, jnp.float32), jnp.float32(1.0), params)

    # Oracle: leaf-wise growth with per-leaf exhaustive search.
    leaf = np.zeros(n, dtype=np.int64)
    num_leaves = 1
    num_bin = np.full(F, B, np.int32)
    is_cat = np.zeros(F, bool)
    splits = []
    for _step in range(L - 1):
        best = None
        for l in range(num_leaves):
            m = leaf == l
            if m.sum() == 0:
                continue
            hist = _np_hist(bins[:, m], g[m], h[m], np.ones(m.sum()), B)
            cand = _np_best_split(hist, g[m].sum(), h[m].sum(), m.sum(),
                                  num_bin, is_cat, p)
            if cand["feat"] >= 0 and (best is None or cand["gain"] > best[1]["gain"]):
                best = (l, cand)
        if best is None:
            break
        l, cand = best
        splits.append((l, cand["feat"], cand["t"]))
        m = (leaf == l) & (bins[cand["feat"]] > cand["t"])
        leaf[m] = num_leaves
        num_leaves += 1

    assert int(tree.num_leaves) == num_leaves
    got_splits = [(int(f), int(t)) for f, t in
                  zip(np.asarray(tree.split_feature)[:num_leaves - 1],
                      np.asarray(tree.split_bin)[:num_leaves - 1])]
    assert got_splits == [(f, t) for _, f, t in splits]
    np.testing.assert_array_equal(np.asarray(leaf_id), leaf)


# ---- the integer search (ops/split.py find_best_split_sums, PR 36) --------

def _leaf_sums(rng, rows, feats, bins, shards=1):
    """int32 digit sums [shards, feats, 9, bins] of ``rows`` rows a shard
    as a histogram's are: every feature holds every row once."""
    dig = rng.randint(-128, 128, (shards, rows, 9))
    dig[..., 3:] = np.abs(dig[..., 3:])         # hessians, weights
    sums = np.zeros((shards, feats, 9, bins), np.int64)
    for s in range(shards):
        for f in range(feats):
            np.add.at(sums[s, f], (slice(None), rng.randint(0, bins, rows)),
                      dig[s].T)
    return sums


@pytest.mark.parametrize("bins,scale", [(255, 1), (255, 60000), (256, 60000),
                                        (500, 40000)])
def test_prefix_sums_are_the_exact_integers(bins, scale):
    """At sums of a 16M-row leaf (``scale``: every row counted that many
    times) as at small ones, and past 256 bins, where the form changes."""
    from lightgbm_tpu.ops.split import prefix_sums
    rng = np.random.RandomState(bins + scale)
    sums = _leaf_sums(rng, 250, 3, bins)[0] * scale
    want = np.cumsum(sums, axis=-1)
    assert np.abs(want).max() < 2 ** 31 and (scale == 1
                                             or np.abs(want).max() > 2 ** 24)
    got = np.asarray(prefix_sums(jnp.asarray(sums.astype(np.int32))))
    np.testing.assert_array_equal(got, want)


def test_search_on_halves_is_the_search_on_whole_sums():
    """Four shards' sums added as 16-bit halves (parallel/comm.py
    ``HistExchange``) give the record of the whole int32 sums, bit for
    bit: the mesh's trees are the serial learner's."""
    from lightgbm_tpu.ops import leafhist
    from lightgbm_tpu.ops.split import find_best_split_sums
    rng = np.random.RandomState(3)
    shards = _leaf_sums(rng, 4000, 5, 64, shards=4) * 700
    assert np.abs(shards.sum(0)).max() < 2 ** 31
    halves = sum(leafhist.split_halves(jnp.asarray(s.astype(np.int32)))
                 for s in shards)
    whole = jnp.asarray(shards.sum(0).astype(np.int32))
    scales = jnp.asarray([3e-2, 2e-3, 1.0], jnp.float32)
    args = (scales, jnp.full((5,), 64, jnp.int32), jnp.zeros((5,), bool),
            jnp.ones((5,), bool), jnp.asarray(True),
            SplitParams(min_data_in_leaf=0, min_sum_hessian_in_leaf=1e-3))
    a = find_best_split_sums(halves, *args)
    b = find_best_split_sums(whole, *args)
    assert int(b.feature) >= 0
    for field in b._fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, field)),
                                      np.asarray(getattr(b, field)), field)
