"""Vectorized best-split search over feature histograms.

Replaces the reference's sequential right-to-left per-feature bin scan
(feature_histogram.hpp:75-237) with one fused cumulative-sum + masked-argmax
over the whole [num_features, max_bin] histogram — the shape XLA tiles well
on TPU.  The gain math is kept exactly (feature_histogram.hpp:270-289):

    gain(G, H)  = max(|G| - lambda_l1, 0)^2 / (H + lambda_l2)
    output(G,H) = -sign(G) * max(|G| - lambda_l1, 0) / (H + lambda_l2)

Semantics preserved from the reference scan:
  * threshold t means "bin <= t goes left" for numerical features; the scan
    candidates are t in [0, num_bin-2],
  * categorical is one-vs-rest: "bin == t goes left" (hpp:144-237),
  * constraint masking is equivalent to the reference's continue/break
    ordering because left counts/hessians are monotone in scan order,
  * tie-breaking: equal gains pick the LARGEST threshold (the reference scans
    right-to-left keeping strictly-greater) and the SMALLEST feature index
    (SplitInfo::operator>, split_info.hpp:100-105).

Two entries, one search.  ``find_best_split_sums`` takes a leaf's int32
digit sums (ops/leafhist.py), which every grower with a histogram cache
holds: a candidate's left side is an integer prefix sum, its right side
the leaf's integer total less it, and each becomes a float once, at the
end.  ``find_best_split`` takes a float histogram, which is all that the
full-pass strategy and the learners that all-reduce float histograms
have (ops/grow.py ``leaf_cache=False``, parallel/comm.py): there the
right side is the float total less the float left side, which is sound
while the sides are of a size and loses the small side where a one-hot
split peels 500 rows off 12 million (its leaf value read to 1e-2:
PERF.md, PR 36).  Gains, constraints and ties are one code
(``best_thresholds``, ``_pick``).  A ``BestSplit`` carries BOTH sides'
sums, and the growers take the children's totals from it.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .leafhist import canonical_halves, combine_digit_streams

K_EPSILON = 1e-15
K_MIN_SCORE = -jnp.inf


class SplitParams(NamedTuple):
    """Static split constraints (TreeConfig subset, config.h:172-192)."""
    min_data_in_leaf: int = 100
    min_sum_hessian_in_leaf: float = 10.0
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_gain_to_split: float = 0.0


class BestSplit(NamedTuple):
    """Per-leaf best split record (SplitInfo, split_info.hpp)."""
    gain: jax.Array        # f32, -inf when unsplittable
    feature: jax.Array     # i32 inner feature index
    threshold: jax.Array   # i32 bin threshold
    left_sum_g: jax.Array  # f32
    left_sum_h: jax.Array  # f32
    left_count: jax.Array  # f32 (bagging-weighted row count)
    right_sum_g: jax.Array  # f32: the other side's own sums, not the
    right_sum_h: jax.Array  # parent's less the left's (module docstring)
    right_count: jax.Array


def leaf_split_gain(sum_g, sum_h, l1: float, l2: float):
    """GetLeafSplitGain (feature_histogram.hpp:270-276)."""
    reg = jnp.maximum(jnp.abs(sum_g) - l1, 0.0)
    return (reg * reg) / (sum_h + l2)


def leaf_output(sum_g, sum_h, l1: float, l2: float):
    """CalculateSplittedLeafOutput (feature_histogram.hpp:284-289)."""
    reg = jnp.maximum(jnp.abs(sum_g) - l1, 0.0)
    return -jnp.sign(sum_g) * reg / (sum_h + l2)


def per_feature_scan(hist, total_g, total_h, total_c, num_bin, is_cat,
                     feat_mask, p: SplitParams):
    """The cumulative-scan half of split finding on a FLOAT histogram:
    per-feature best candidate.

    Returns (feat_best_gain [..., F] with the parent gain_shift NOT yet
    subtracted and invalid candidates at -inf, feat_best_t [..., F] i32,
    left_g/left_h/left_c [..., F, B]).  Exposed separately so the voting
    learner can elect features by local gain (GlobalVoting,
    voting_parallel_tree_learner.cpp:157-186) before the global reduce.
    """
    F, B = hist.shape[-3], hist.shape[-2]
    tg = total_g[..., None, None]
    th = total_h[..., None, None]
    tc = total_c[..., None, None]

    # [F, B] broadcasts over any leading dims
    bins = jax.lax.broadcasted_iota(jnp.int32, (F, B), 1)

    # ---- numerical: left = cumsum over bins <= t --------------------------
    cum = jnp.cumsum(hist, axis=-2)
    left_g_n, left_h_n, left_c_n = cum[..., 0], cum[..., 1], cum[..., 2]
    # ---- categorical: left = the single bin t (one-vs-rest) ---------------
    left_g_c, left_h_c, left_c_c = hist[..., 0], hist[..., 1], hist[..., 2]

    cat = is_cat[:, None]
    left_g = jnp.where(cat, left_g_c, left_g_n)
    left_h = jnp.where(cat, left_h_c, left_h_n)
    left_c = jnp.where(cat, left_c_c, left_c_n)
    right_g = tg - left_g
    right_h = th - left_h
    right_c = tc - left_c
    feat_best_gain, feat_best_t = best_thresholds(
        (left_g, left_h, left_c), (right_g, right_h, right_c), total_g,
        total_h, bins, num_bin, is_cat, feat_mask, p)
    return feat_best_gain, feat_best_t, left_g, left_h, left_c


def prefix_sums(sums):
    """Exact prefix sums along the bins (last axis) of int32 digit sums,
    as products with a triangle of ones: the four bytes of every sum
    (0 to 255, the top one signed) are exact in bfloat16, as the ones
    are, and at most 256 products of a byte accumulate in float32 far
    under 2^24, so every partial sum is the exact integer on any backend;
    the bytes' prefix sums go back together in int32 (which wraps as the
    sums themselves would).  ``jnp.cumsum`` is a ``reduce-window`` on a
    TPU: 187 to 284 us a step as int32 at the cells' shapes, 333 to 560
    as two float32 cumsums of 16-bit halves, where these four products
    take 10 to 19 (PERF.md, PR 36)."""
    bins = sums.shape[-1]
    if bins > 256:
        # uint16 bins (the cached grower; no cell runs them): the
        # triangle grows with the square of the bins
        return jnp.cumsum(sums, axis=-1)
    upto = (jnp.arange(bins)[:, None] <= jnp.arange(bins)[None, :])
    out = 0
    for k in range(4):
        byte = sums >> 8 * k if k == 3 else (sums >> 8 * k) & 0xFF
        part = jnp.einsum("...b,bt->...t", byte.astype(jnp.bfloat16),
                          upto.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
        out = out + (part.astype(jnp.int32) << 8 * k)
    return out


def sums_totals(sums, scales):
    """(g, h, count) f32 [...] of the leaves whose digit sums are
    ``sums`` [..., F, S, B]: every feature (every EFB column) holds every
    row of the leaf once, so the first one's integer total is the
    leaf's."""
    total = jnp.sum(sums[..., 0, :, :], axis=-1, keepdims=True)
    return tuple(x[..., 0] for x in combine_digit_streams(
        canonical_halves(total), scales))


def both_sides(left, sums, scales):
    """((g, h, count) of the left side, of the right side), f32
    [..., K, T], of candidates whose left sides' integer digit sums are
    ``left`` [..., K, S, T], in leaves whose digit sums are ``sums``
    [..., F, S, B].  The right side is the leaf's integer total less the
    left side BEFORE either becomes a float."""
    total = jnp.sum(sums[..., :1, :, :], axis=-1, keepdims=True)
    return tuple(combine_digit_streams(canonical_halves(x), scales)
                 for x in (left, total - left))


def best_thresholds(left, right, total_g, total_h, bins, num_bin, is_cat,
                    feat_mask, p: SplitParams):
    """Each feature's best candidate from both sides' sums: ``left`` and
    ``right`` are ``(g, h, count)`` of [..., F, B] per (feature,
    threshold), ``bins`` the [F, B] threshold index.  Returns
    (feat_best_gain [..., F], the parent's gain_shift NOT yet subtracted,
    invalid candidates at -inf; feat_best_t [..., F] i32).  The half of
    the search that does not care where the sums came from."""
    gain = candidate_gains(left, right, total_g, total_h, bins, num_bin,
                           is_cat, feat_mask, p)

    # Per-feature best threshold; ties pick the largest t (reference scans
    # right-to-left with strict improvement).
    feat_best_gain = jnp.max(gain, axis=-1)
    is_best_t = gain == feat_best_gain[..., None]
    feat_best_t = jnp.max(jnp.where(is_best_t, bins, -1), axis=-1)
    feat_best_gain = jnp.where(jnp.isfinite(feat_best_gain), feat_best_gain,
                               K_MIN_SCORE)
    return feat_best_gain, feat_best_t


def candidate_gains(left, right, total_g, total_h, bins, num_bin, is_cat,
                    feat_mask, p: SplitParams):
    """[..., F, B] gain of every (feature, threshold), the parent's
    gain_shift NOT subtracted, -inf where the candidate is not allowed.
    ``feat_mask`` [F], or [F, B] where every threshold has a mask of its
    own (ops/bundle.py: a slot of a bundle's column is a feature)."""
    left_g, left_h, left_c = left
    right_g, right_h, right_c = right
    gain_shift = leaf_split_gain(total_g, total_h, p.lambda_l1, p.lambda_l2)
    min_gain_shift = gain_shift + p.min_gain_to_split

    gain = (leaf_split_gain(left_g, left_h, p.lambda_l1, p.lambda_l2)
            + leaf_split_gain(right_g, right_h, p.lambda_l1, p.lambda_l2))

    # Candidate validity: numerical t in [0, num_bin-2]; categorical
    # t in [0, num_bin-1].
    t_limit = jnp.where(is_cat, num_bin, num_bin - 1)
    valid = bins < t_limit[:, None]
    valid &= left_c >= p.min_data_in_leaf
    valid &= right_c >= p.min_data_in_leaf
    valid &= left_h >= p.min_sum_hessian_in_leaf
    valid &= right_h >= p.min_sum_hessian_in_leaf
    valid &= gain > min_gain_shift[..., None, None]
    valid &= feat_mask[:, None] if feat_mask.ndim == 1 else feat_mask
    valid &= num_bin[:, None] > 1

    return jnp.where(valid, gain, K_MIN_SCORE)


def _at(arr, index):
    """``arr[..., index]`` with ``index`` [...] along the last axis."""
    return jnp.take_along_axis(arr, index[..., None], axis=-1)[..., 0]


def pick_split(best_gain, feature, threshold, sides, total_g, total_h,
               can_split, p: SplitParams) -> BestSplit:
    """The record of the chosen candidate: ``best_gain`` [...] still with
    the parent's gain_shift in it, ``sides`` its six sums (left g, h,
    count, right g, h, count)."""
    gain_shift = leaf_split_gain(total_g, total_h, p.lambda_l1, p.lambda_l2)
    splittable = jnp.isfinite(best_gain) & can_split
    return BestSplit(
        jnp.where(splittable, best_gain - gain_shift,
                  K_MIN_SCORE).astype(jnp.float32),
        jnp.where(splittable, feature, -1).astype(jnp.int32),
        jnp.where(splittable, threshold, 0).astype(jnp.int32),
        *(x.astype(jnp.float32) for x in sides))


def _best_feature(feat_best_gain, feat_best_t, left, right, total_g, total_h,
                  can_split, p: SplitParams) -> BestSplit:
    """Across features: max gain, ties to the smallest feature index
    (argmax returns the first occurrence); ``left`` / ``right`` are
    (g, h, count) of [..., F, B]."""
    best_f = jnp.argmax(feat_best_gain, axis=-1).astype(jnp.int32)
    sides = tuple(_at(_at(x, feat_best_t), best_f) for x in left + right)
    return pick_split(_at(feat_best_gain, best_f), best_f,
                      _at(feat_best_t, best_f), sides, total_g, total_h,
                      can_split, p)


def find_best_split(hist, total_g, total_h, total_c, num_bin, is_cat,
                    feat_mask, can_split, p: SplitParams) -> BestSplit:
    """Best split for one leaf (or a batch of leaves via leading dims)
    from a FLOAT histogram.

    Args:
      hist: [..., F, B, 3] per-feature histograms (sum_g, sum_h, count).
      total_g/total_h/total_c: [...] leaf totals.
      num_bin: [F] i32 bins in use per feature.
      is_cat: [F] bool categorical flag per feature.
      feat_mask: [F] bool usable features this tree (feature_fraction).
      can_split: [...] bool depth/validity guard for the leaf.
      p: static constraints.
    Returns BestSplit with fields shaped [...].
    """
    feat_best_gain, feat_best_t, left_g, left_h, left_c = per_feature_scan(
        hist, total_g, total_h, total_c, num_bin, is_cat, feat_mask, p)
    left = (left_g, left_h, left_c)
    right = tuple(t[..., None, None] - x
                  for t, x in zip((total_g, total_h, total_c), left))
    return _best_feature(feat_best_gain, feat_best_t, left, right, total_g,
                         total_h, can_split, p)


def find_best_split_sums(sums, scales, num_bin, is_cat, feat_mask, can_split,
                         p: SplitParams) -> BestSplit:
    """Best split of the leaves whose int32 digit sums are ``sums``
    [..., F, S, B] (S = 9, or 18 for ``split_halves`` sums;
    ops/leafhist.py), both sides of every candidate exact integers until
    the last step (module docstring).  The leaves' totals are the sums'
    own (``sums_totals``); the other arguments as ``find_best_split``."""
    F, B = sums.shape[-3], sums.shape[-1]
    bins = jax.lax.broadcasted_iota(jnp.int32, (F, B), 1)
    left_int = jnp.where(is_cat[:, None, None], sums, prefix_sums(sums))
    left, right = both_sides(left_int, sums, scales)
    total_g, total_h, _ = sums_totals(sums, scales)
    feat_best_gain, feat_best_t = best_thresholds(
        left, right, total_g, total_h, bins, num_bin, is_cat, feat_mask, p)
    return _best_feature(feat_best_gain, feat_best_t, left, right, total_g,
                         total_h, can_split, p)


def better_split(a: BestSplit, b: BestSplit) -> BestSplit:
    """Elementwise pick of the better of two split records.

    SplitInfo::operator> semantics (split_info.hpp:100-105): larger gain
    wins; equal gains break the tie toward the smaller feature index.  This
    is the structured-dtype replacement for the reference's raw-byte
    SplitInfo::MaxReducer network callback (split_info.hpp:58-74)."""
    a_wins = jnp.logical_or(
        a.gain > b.gain,
        jnp.logical_and(a.gain == b.gain, a.feature <= b.feature))
    return jax.tree.map(lambda x, y: jnp.where(a_wins, x, y), a, b)


def combine_gathered_splits(gathered: BestSplit, num_shards: int) -> BestSplit:
    """Reduce an all_gather'ed BestSplit (leading axis = shard) to the global
    winner — the Allreduce(SplitInfo::MaxReducer) of the parallel learners
    (feature_parallel_tree_learner.cpp:47-69; data_parallel 219-242)."""
    shards = [jax.tree.map(lambda f, i=i: f[i], gathered)
              for i in range(num_shards)]
    return functools.reduce(better_split, shards)
