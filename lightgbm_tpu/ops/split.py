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
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

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
    """The cumulative-scan half of split finding: per-feature best candidate.

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
    valid &= feat_mask[:, None]
    valid &= num_bin[:, None] > 1

    gain = jnp.where(valid, gain, K_MIN_SCORE)

    # Per-feature best threshold; ties pick the largest t (reference scans
    # right-to-left with strict improvement).
    feat_best_gain = jnp.max(gain, axis=-1)
    is_best_t = gain == feat_best_gain[..., None]
    feat_best_t = jnp.max(jnp.where(is_best_t, bins, -1), axis=-1)
    feat_best_gain = jnp.where(jnp.isfinite(feat_best_gain), feat_best_gain,
                               K_MIN_SCORE)
    return feat_best_gain, feat_best_t, left_g, left_h, left_c


def find_best_split(hist, total_g, total_h, total_c, num_bin, is_cat,
                    feat_mask, can_split, p: SplitParams) -> BestSplit:
    """Best split for one leaf (or a batch of leaves via leading dims).

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
    t = feat_best_t[..., None]

    def _at_t(arr):
        # left sums at each feature's own best threshold: [..., F]
        return jnp.take_along_axis(arr, t, axis=-1)[..., 0]

    left_g, left_h, left_c = _at_t(left_g), _at_t(left_h), _at_t(left_c)
    # across features: max gain, ties to the smallest feature index
    # (argmax returns the first occurrence), then the parent gain_shift
    # subtraction and the can_split mask
    gain_shift = leaf_split_gain(total_g, total_h, p.lambda_l1, p.lambda_l2)
    best_f = jnp.argmax(feat_best_gain, axis=-1).astype(jnp.int32)

    def _at_f(arr):
        return jnp.take_along_axis(arr, best_f[..., None], axis=-1)[..., 0]

    best_gain = _at_f(feat_best_gain)
    best_t = _at_f(feat_best_t).astype(jnp.int32)
    splittable = jnp.isfinite(best_gain) & can_split
    best_gain_out = jnp.where(splittable, best_gain - gain_shift, K_MIN_SCORE)
    return BestSplit(
        gain=best_gain_out.astype(jnp.float32),
        feature=jnp.where(splittable, best_f, -1).astype(jnp.int32),
        threshold=jnp.where(splittable, best_t, 0).astype(jnp.int32),
        left_sum_g=_at_f(left_g).astype(jnp.float32),
        left_sum_h=_at_f(left_h).astype(jnp.float32),
        left_count=_at_f(left_c).astype(jnp.float32),
    )


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
