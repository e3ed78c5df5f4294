"""Histogram construction: the hottest op in GBDT training.

Replaces the reference's per-leaf gather + 4-way-unrolled scalar
accumulation loop (dense_bin.hpp:65-133) with TPU-shaped formulations over
the dense feature-major bin matrix:

  * ``scatter`` (CPU path): one fused scatter-add keyed by (child,
    feature, bin) — a single XLA scatter over all rows.  Because the pass
    is over the full row set with masking, building BOTH children of a
    split in one pass costs the same as building one, so the reference's
    smaller-child + histogram-subtraction dance (serial_tree_learner.cpp:
    398-453) and the LRU HistogramPool (feature_histogram.hpp:299-455) are
    unnecessary: no per-leaf histogram state is kept at all.
  * Pallas MXU kernel (TPU path): see pallas_histogram.py; selected by the
    ``children_histograms`` / ``root_histogram`` dispatchers below.

Values accumulated per (feature, bin): (sum_gradients, sum_hessians, count)
— HistogramBinEntry (bin.h:22-51).  Counts are bagging-mask sums.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..utils import device


def children_histograms(bins, grad, hess, weight, leaf_id,
                        parent_leaf, right_leaf, max_bin: int):
    """Platform dispatcher: Pallas MXU kernel on TPU (14x the XLA
    scatter there), scatter-add elsewhere (CPU tests, small data)."""
    if device.on_tpu():
        from .pallas_histogram import children_histograms_pallas
        return children_histograms_pallas(bins, grad, hess, weight, leaf_id,
                                          parent_leaf, right_leaf, max_bin)
    return build_children_histograms(bins, grad, hess, weight, leaf_id,
                                     parent_leaf, right_leaf, max_bin)


def root_histogram(bins, grad, hess, weight, max_bin: int):
    """Platform dispatcher for the root (all-rows) histogram."""
    if device.on_tpu():
        from .pallas_histogram import root_histogram_pallas
        return root_histogram_pallas(bins, grad, hess, weight, max_bin)
    return build_root_histogram(bins, grad, hess, weight, max_bin)


def histogram_scatter(bins, seg, num_seg: int, grad, hess, weight):
    """Scatter-add histogram.

    Args:
      bins: [F, N] integer bin codes.
      seg:  [F, N] i32 flat segment ids in [0, num_seg) (rows to drop may
            point at a dump slot == num_seg).
      num_seg: static number of live segments.
      grad/hess/weight: [N] f32.
    Returns [num_seg, 3] f32.
    """
    del bins  # already encoded in seg
    vals = jnp.stack([grad, hess, weight], axis=-1)          # [N, 3]
    F = seg.shape[0]
    vals = jnp.broadcast_to(vals[None], (F,) + vals.shape)   # [F, N, 3]
    out = jnp.zeros((num_seg + 1, 3), dtype=jnp.float32)
    out = out.at[seg.reshape(-1)].add(vals.reshape(-1, 3), mode="drop")
    return out[:num_seg]


def build_children_histograms(bins, grad, hess, weight, leaf_id,
                              parent_leaf, right_leaf, max_bin: int):
    """Histograms of both children of a just-split leaf in ONE pass.

    After the partition update, rows of the left child carry leaf_id ==
    parent_leaf and rows of the right child carry leaf_id == right_leaf.

    Args:
      bins: [F, N] bin codes (any int dtype).
      grad/hess/weight: [N] f32 (weight = bagging mask; 0 drops the row).
      leaf_id: [N] i32 current leaf of each row.
      parent_leaf, right_leaf: scalar i32.
      max_bin: static B.
    Returns [2, F, B, 3] f32: [0]=left child, [1]=right child.
    """
    F, N = bins.shape
    B = max_bin
    is_left = leaf_id == parent_leaf
    is_right = leaf_id == right_leaf
    in_leaf = is_left | is_right
    child = jnp.where(is_right, 1, 0).astype(jnp.int32)      # [N]
    feat = jnp.arange(F, dtype=jnp.int32)[:, None]           # [F, 1]
    seg = (child[None, :] * (F * B) + feat * B + bins.astype(jnp.int32))
    seg = jnp.where(in_leaf[None, :], seg, 2 * F * B)        # dump slot
    flat = histogram_scatter(bins, seg, 2 * F * B, grad, hess, weight)
    return flat.reshape(2, F, B, 3)


def build_root_histogram(bins, grad, hess, weight, max_bin: int):
    """Histogram of all rows (the root leaf). Returns [F, B, 3] f32."""
    F, N = bins.shape
    B = max_bin
    feat = jnp.arange(F, dtype=jnp.int32)[:, None]
    seg = feat * B + bins.astype(jnp.int32)
    flat = histogram_scatter(bins, seg, F * B, grad, hess, weight)
    return flat.reshape(F, B, 3)
