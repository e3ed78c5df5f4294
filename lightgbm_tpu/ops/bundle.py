"""Device-side half of exclusive feature bundling (EFB).

The host planner (``io/bundling.py``) packs mutually-exclusive sparse
features into shared *columns* with offset-encoded bin sub-ranges, so the
device bin matrix — and every histogram pass over it — shrinks from
``[F, N]`` to ``[C, N]`` with ``C`` = bundled column count.  Split
finding, however, must stay in ORIGINAL feature space: a contiguous
``bin <= t`` range of a bundled column is *not* an original-feature
partition (rows of members after the split member would route by bundle
position, not by their own value).  The reference resolves this the same
way (FeatureGroup histograms + per-feature OffsetBin slices +
FixHistogram for the default bin): build histograms per column, then
*expand* them back to per-original-feature histograms before the scan.

This module owns that expansion plus the per-split bin decode:

- :class:`BundleDecode` — per-original-feature gather tables, passed as
  runtime device arrays (pytree) so toggling datasets never retraces.
- :func:`expand_digit_sums` — int32 digit-sum expansion for the cached
  serial learner (ops/leafhist.py).  Pure integer gathers + an exact
  integer reconstruction of each feature's default bin
  (``total - sum(non-default)``), so a zero-conflict bundled run is
  BIT-IDENTICAL to the unbundled run (pinned in tests/test_bundling.py).
- :func:`expand_histogram` — the f32 equivalent for the full-pass /
  distributed strategies (deterministic; the default-bin reconstruction
  re-associates one f32 sum, the same last-bit wiggle any accumulation
  order change causes).
- :func:`decode_feature_bins` — raw column bin -> original feature bin,
  used by the growers' partition step and the binned tree walk.
- :func:`find_best_split_columns` — the leaf-ordered grower's split
  search (ops/ordered_grow.py): ``ops/split.py find_best_split_sums``
  over ORIGINAL features, computed where the features lie in the
  columns.  The expansion gathers ``[F, 9, B]`` a child; on a one-hot
  table of thousands of two-bin features that is a hundred times the
  ``[C, B]`` the columns hold.  Three kinds of feature, each read in
  place:

  * an identity column IS its feature's histogram: its integer prefix
    sums are the left sides;
  * a two-bin member of a bundle has one candidate, "bin 0 goes left":
    the right side is the member's one slot, the left the leaf's total
    less it (every slot of every bundle column is scanned at once,
    ``[C * B]`` candidates);
  * a bundled member of more than two bins (``multi``) is expanded as
    before, it alone (none on a one-hot table).

  Sides, gains, constraints and the order of ties are ``ops/split.py``'s
  own (``both_sides``, ``best_thresholds``, ``pick_split``), so the
  result is ``find_best_split_sums`` over ``expand_digit_sums`` to the
  bit, ties included (tests/test_bundling.py), and a zero-conflict
  bundled run grows the unbundled run's trees.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from .split import (BestSplit, SplitParams, best_thresholds, both_sides,
                    candidate_gains, pick_split, prefix_sums, sums_totals)


class BundleDecode(NamedTuple):
    """Per-original-used-feature decode tables (runtime device arrays).

    col:         [F] i32  column holding feature f.
    off:         [F] i32  column slot of f's local bin 1 (0 = feature is
                          stored identity-encoded: its column IS its own
                          original bin codes).
    width:       [F] i32  non-default slot count (num_bin_f - 1) for
                          offset-encoded features; ignored when off == 0.
    slot_map:    [F, B] i32  histogram gather map: column bin-slot for
                          (feature, original bin).  The feature's default
                          bin and any bin >= num_bin_f point at the
                          ZERO slot (index B) of the slot-padded column.
    default_bin: [F] i32  original bin reconstructed as
                          total - sum(non-default).

    The column-space search's tables (``find_best_split_columns``; of
    the FULL column layout, so a screener's compacted view drops them):

    col_feat:    [C] i32  feature of an identity column, -1 for a
                          bundle's column.
    slot_feat:   [C, B] i32  the two-bin member whose non-default bin
                          is this slot, -1 where there is none.
    multi:       [M] i32  bundled members of more than two bins.
    """
    col: jax.Array
    off: jax.Array
    width: jax.Array
    slot_map: jax.Array
    default_bin: jax.Array
    col_feat: Any = None
    slot_feat: Any = None
    multi: Any = None

    @classmethod
    def from_tables(cls, tables: dict) -> "BundleDecode":
        """From ``io/bundling.py BundlePlan.decode_arrays``' numpy
        tables."""
        return cls(**{k: jnp.asarray(v) for k, v in tables.items()})


def _slot_indices(dec: BundleDecode, lead_shape, tail: int):
    """slot_map broadcast to ``lead_shape + (B, tail)`` for
    take_along_axis over a slot-padded bin axis."""
    F, B = dec.slot_map.shape
    idx = dec.slot_map.reshape((1,) * (len(lead_shape) - 1) + (F, B, 1))
    return jnp.broadcast_to(idx, tuple(lead_shape) + (B, tail))


def _default_mask(dec: BundleDecode):
    """[F, B] bool: True at each feature's default bin."""
    F, B = dec.slot_map.shape
    bins = jax.lax.broadcasted_iota(jnp.int32, (F, B), 1)
    return bins == dec.default_bin[:, None]


def expand_histogram(hist, dec: BundleDecode):
    """[..., C, B, 3] f32 column histograms -> [..., F, B, 3] per-original-
    feature histograms.

    ``hist`` may carry one extra trailing column (the all-zero pad the
    feature-parallel learner appends for non-owned features); ``dec.col``
    indexes whatever column count arrives."""
    F, B = dec.slot_map.shape
    h = jnp.take(hist, dec.col, axis=-3)              # [..., F, B, 3]
    tot = jnp.sum(h, axis=-2)                         # [..., F, 3]
    zero = jnp.zeros(h.shape[:-2] + (1, h.shape[-1]), h.dtype)
    hp = jnp.concatenate([h, zero], axis=-2)          # [..., F, B+1, 3]
    idx = _slot_indices(dec, h.shape[:-2], h.shape[-1])
    e = jnp.take_along_axis(hp, idx, axis=-2)         # [..., F, B, 3]
    # default bin = column total minus the feature's non-default slots
    # (FixHistogram, dataset.cpp:451-471) — the default slot gathered 0
    # above, so the subtraction is not double-counted.
    body = jnp.sum(e, axis=-2)                        # [..., F, 3]
    recon = tot - body
    mask = _default_mask(dec)                         # [F, B]
    mask = mask.reshape((1,) * (e.ndim - 3) + mask.shape + (1,))
    return jnp.where(mask, recon[..., None, :], e)


def expand_digit_sums(sums, dec: BundleDecode):
    """[..., C, 9, B] int32 digit sums -> [..., F, 9, B].

    All-integer gathers and subtraction: the expansion is EXACT, so the
    cached serial learner's splits over a zero-conflict bundled dataset
    bit-match the unbundled run."""
    F, B = dec.slot_map.shape
    s = jnp.take(sums, dec.col, axis=-3)              # [..., F, 9, B]
    tot = jnp.sum(s, axis=-1)                         # [..., F, 9]
    zero = jnp.zeros(s.shape[:-1] + (1,), s.dtype)
    sp = jnp.concatenate([s, zero], axis=-1)          # [..., F, 9, B+1]
    idx = dec.slot_map.reshape(
        (1,) * (s.ndim - 3) + (F, 1, B))
    idx = jnp.broadcast_to(idx, s.shape[:-2] + (s.shape[-2], B))
    e = jnp.take_along_axis(sp, idx, axis=-1)         # [..., F, 9, B]
    body = jnp.sum(e, axis=-1)                        # [..., F, 9]
    recon = tot - body                                # exact int32
    mask = _default_mask(dec)                         # [F, B]
    mask = mask.reshape((1,) * (e.ndim - 3) + (F, 1, B))
    return jnp.where(mask, recon[..., None], e)


def decode_feature_bins(bins, feat, dec: BundleDecode):
    """Original-feature bin codes of (rows x) ``feat`` from the bundled
    column matrix.

    Args:
      bins: [C, N] column bin codes.
      feat: scalar i32 (grower partition) or [N] i32 (tree walk) original
        feature index; negative values are clamped to 0 (callers mask).
      dec: decode tables.
    Returns [N] i32 original-feature bin codes.
    """
    feat = jnp.maximum(feat, 0)
    col = dec.col[feat]
    if col.ndim == 0:
        raw = jnp.take(bins, col, axis=0).astype(jnp.int32)
    else:
        raw = jnp.take_along_axis(bins, col[None, :],
                                  axis=0)[0].astype(jnp.int32)
    o = dec.off[feat]
    w = dec.width[feat]
    in_range = (raw >= o) & (raw < o + w)
    decoded = jnp.where(in_range, raw - o + 1, 0)
    # off == 0 marks identity-encoded features (their column stores the
    # original bin codes directly)
    return jnp.where(o > 0, decoded, raw)


class ColumnSearch(NamedTuple):
    """What ``find_best_split_columns`` needs of a tree that does not
    change from split to split: per candidate feature of the three kinds
    (identity columns, slots of bundle columns, multi members), the
    feature it stands for (``F`` where it stands for none, which never
    wins a tie) and that feature's ``num_bin`` / ``is_cat`` / mask.
    Built once a tree by ``column_search``, outside the grow loop."""
    feat: jax.Array          # [C + C * B + M] i32
    num_bin: tuple           # per kind: [C], [C] (twos), [M]
    is_cat: tuple
    mask: tuple              # [C], [C, B] (a slot's own), [M]
    multi: Any               # BundleDecode rows of the multi members


def column_search(dec: BundleDecode, num_bin, is_cat,
                  feat_mask) -> ColumnSearch:
    F = dec.col.shape[0]
    C = dec.col_feat.shape[0]
    kinds = (dec.col_feat, dec.slot_feat, dec.multi)
    feat = jnp.concatenate([jnp.where(k >= 0, k, F).reshape(-1)
                            for k in kinds])
    at = [jnp.maximum(k, 0) for k in kinds]
    # the expansion's five tables, the multi members' rows of each
    multi = BundleDecode(*(t[dec.multi] for t in dec[:5]))
    return ColumnSearch(
        feat=feat.astype(jnp.int32),
        num_bin=(num_bin[at[0]], jnp.full((C,), 2, num_bin.dtype),
                 num_bin[at[2]]),
        is_cat=(is_cat[at[0]], jnp.zeros((C,), bool), is_cat[at[2]]),
        mask=tuple(feat_mask[a] & (k >= 0) for a, k in zip(at, kinds)),
        multi=multi)


def find_best_split_columns(sums, scales, can_split, p: SplitParams,
                            cs: ColumnSearch) -> BestSplit:
    """The best split over ORIGINAL features of the leaves whose
    column-space digit sums are ``sums`` (module docstring):
    ``ops/split.py find_best_split_sums`` over ``expand_digit_sums``,
    computed where the features lie.

    Args:
      sums: [..., C, 9, B] int32 digit sums in COLUMN space.
      scales: the digits' scales (ops/leafhist.py).
      can_split: [...] as ``find_best_split``.
      cs: ``column_search``'s tables for this tree.
    """
    lead = sums.shape[:-3]
    C, B = sums.shape[-3], sums.shape[-1]
    total_g, total_h, _ = sums_totals(sums, scales)
    gains, ts, sides = [], [], [[] for _ in range(6)]

    def scanned(kind, left_int):
        """Features with a histogram of their own: each one's best
        threshold."""
        left, right = both_sides(left_int, sums, scales)
        bins = jax.lax.broadcasted_iota(jnp.int32, left_int.shape[-3::2], 1)
        gain, t = best_thresholds(left, right, total_g, total_h, bins,
                                  cs.num_bin[kind], cs.is_cat[kind],
                                  cs.mask[kind], p)
        gains.append(gain)
        ts.append(t)
        for out, x in zip(sides, left + right):
            out.append(jnp.take_along_axis(x, t[..., None], axis=-1)[..., 0])

    # identity columns: the column is the feature's histogram
    scanned(0, jnp.where(cs.is_cat[0][:, None, None], sums,
                         prefix_sums(sums)))
    # a two-bin member at slot s of a bundle's column: its one other bin
    # is the slot (the right side), bin 0 the rest of the leaf
    # (FixHistogram, dataset.cpp:451-471, in exact integers).  Every slot
    # of every column at once, where it lies: a candidate a slot,
    # threshold 0
    total = jnp.sum(sums[..., :1, :, :], axis=-1, keepdims=True)
    left, right = both_sides(total - sums, sums, scales)
    gains.append(candidate_gains(
        left, right, total_g, total_h, jnp.zeros((C, B), jnp.int32),
        cs.num_bin[1], cs.is_cat[1], cs.mask[1], p).reshape(lead + (C * B,)))
    ts.append(jnp.zeros(lead + (C * B,), jnp.int32))
    for out, x in zip(sides, left + right):
        out.append(x.reshape(lead + (C * B,)))
    if cs.multi.col.shape[0]:
        scanned(2, prefix_sums(expand_digit_sums(sums, cs.multi)))
    gain = jnp.concatenate(gains, axis=-1)                  # [..., K]
    feat = cs.feat

    # across features: the largest gain, ties to the smallest ORIGINAL
    # feature (find_best_split's argmax over features in index order); a
    # feature is one candidate, its threshold ties already settled
    best_gain = jnp.max(gain, axis=-1)
    best_f = jnp.min(jnp.where(gain == best_gain[..., None], feat,
                               jnp.iinfo(jnp.int32).max), axis=-1)
    k = jnp.argmax((gain == best_gain[..., None]) & (feat == best_f[..., None]),
                   axis=-1)

    def _at_k(arrs):
        return jnp.take_along_axis(jnp.concatenate(arrs, axis=-1),
                                   k[..., None], axis=-1)[..., 0]

    return pick_split(best_gain, best_f, _at_k(ts),
                      tuple(_at_k(x) for x in sides), total_g, total_h,
                      can_split, p)
