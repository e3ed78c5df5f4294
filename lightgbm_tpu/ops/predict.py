"""Vectorized tree traversal on binned data.

Replaces the reference's per-row pointer walk (tree.h:197-227,
Tree::AddPredictionToScore tree.cpp:102-160) with a data-parallel absorbing
node walk: every row advances one level per step; rows that reach a leaf
(negative child code) stay put.  Comparisons are integer bin comparisons,
exactly equivalent to raw-value comparisons because thresholds are bin
upper bounds (see models/tree.py docstring).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..obs.compile_ledger import instrumented_jit


def predict_binned_tree(split_feature, split_bin, is_cat_node, left_child,
                        right_child, leaf_value, bins, max_steps: int,
                        bundle=None):
    """Predict one tree on binned rows.

    Args:
      split_feature: [L-1] i32; split_bin: [L-1] i32; is_cat_node: [L-1] bool.
      left_child/right_child: [L-1] i32 (~leaf or node index).
      leaf_value: [L] f32.
      bins: [F, N] bin codes ([C, N] EFB column codes when ``bundle`` is
        given — split features/thresholds stay in original feature space
        and each step decodes the split feature's column on the fly).
      max_steps: static depth bound (num_leaves is always enough).
      bundle: optional ops.bundle.BundleDecode for EFB-bundled ``bins``.
    Returns ([N] f32 leaf values, [N] i32 leaf indices).
    """
    N = bins.shape[1]

    F = bins.shape[0]

    def step(_, node):
        live = node >= 0
        idx = jnp.maximum(node, 0)
        feat = split_feature[idx]
        if bundle is not None:
            from .bundle import decode_feature_bins
            fbin = decode_feature_bins(bins, feat, bundle)
        elif F <= 64:
            # per-row feature pick as a select chain: XLA TPU lowers the
            # take_along_axis gather per index (~14 ns/row/level: PERF.md,
            # "Carried over") — F sequential [N] selects are
            # 5-10x cheaper for the narrow feature counts GBDTs run at
            fbin = bins[0].astype(jnp.int32)
            for f in range(1, F):
                fbin = jnp.where(feat == f, bins[f].astype(jnp.int32), fbin)
        else:
            fbin = jnp.take_along_axis(bins, feat[None, :],
                                       axis=0)[0].astype(jnp.int32)
        tbin = split_bin[idx]
        go_left = jnp.where(is_cat_node[idx], fbin == tbin, fbin <= tbin)
        nxt = jnp.where(go_left, left_child[idx], right_child[idx])
        return jnp.where(live, nxt, node)

    node0 = jnp.zeros(N, dtype=jnp.int32)
    # a 1-leaf tree has no nodes: every row is leaf 0
    has_split = leaf_value.shape[0] > 1 and split_feature.shape[0] > 0
    if not has_split:
        leaf = node0
    else:
        # while (not fori): cost tracks the tree's actual depth, which is
        # what the out-of-bag score walk under bagging compaction pays
        # per tree (max_steps stays the hard bound)
        def cond(carry):
            k, node = carry
            return (k < max_steps) & jnp.any(node >= 0)

        def body(carry):
            k, node = carry
            return k + 1, step(k, node)

        _, node = jax.lax.while_loop(cond, body,
                                     (jnp.asarray(0, jnp.int32), node0))
        leaf = jnp.where(node < 0, ~node, 0)
    return leaf_value[leaf], leaf


# ledgered one level up: every offline caller goes through the
# process-wide CountingJit wrapper (models/gbdt.py _counting_forest_jit,
# program "predict_forest"); serve/forest.py inlines this jit into its
# own instrumented programs.  Wrapping here too would double-count each
# compile in the ledger.
@functools.partial(jax.jit, static_argnames=("max_steps",))  # graftcheck: disable=jit-raw
def predict_binned_forest(split_feature, split_bin, is_cat_node, left_child,
                          right_child, leaf_value, bins, max_steps: int):
    """Sum of tree predictions.

    Tree arrays carry a leading [T] axis.  Returns [T_groups?]: here the sum
    over all T trees, [N] f32.  For multiclass, call per class with that
    class's tree stack.
    """
    def body(carry, tree):
        acc, comp = carry
        sf, sb, ic, lc, rc, lv = tree
        val, _ = predict_binned_tree(sf, sb, ic, lc, rc, lv, bins, max_steps)
        # Kahan-compensated sum: TPUs run f32; the compensation keeps the
        # forest total within ~1 ulp of the host's f64 accumulation
        y = val - comp
        t = acc + y
        comp = (t - acc) - y
        return (t, comp), None

    N = bins.shape[1]
    init = (jnp.zeros(N, dtype=jnp.float32), jnp.zeros(N, dtype=jnp.float32))
    (out, _), _ = jax.lax.scan(body, init,
                               (split_feature, split_bin, is_cat_node,
                                left_child, right_child, leaf_value))
    return out


# ledgered one level up, exactly like predict_binned_forest (the
# linear-forest callers wrap this in their own CountingJit programs)
@functools.partial(jax.jit, static_argnames=("max_steps",))  # graftcheck: disable=jit-raw
def predict_binned_forest_linear(split_feature, split_bin, is_cat_node,
                                 left_child, right_child, leaf_value,
                                 leaf_coeff, leaf_feat, bins, raw,
                                 max_steps: int):
    """Sum of PIECE-WISE LINEAR tree predictions (docs/LINEAR_TREES.md).

    Like :func:`predict_binned_forest` plus the per-leaf dot-product
    epilogue: each tree contributes
    ``leaf_value[leaf] + sum_k leaf_coeff[leaf, k] * raw[leaf_feat[leaf, k]]``.

    Extra args: ``leaf_coeff`` [T, L, K] f32, ``leaf_feat`` [T, L, K]
    i32 rows into ``raw`` (-1 = unused pad slot), ``raw`` [F, N] f32 raw
    feature values with NaN pre-imputed to 0.0.  A separate entry point
    (rather than optional args) keeps the constant-leaf program's trace
    — and its compile-ledger identity — untouched.
    """
    N = bins.shape[1]
    rows = jnp.arange(N)[:, None]

    def body(carry, tree):
        acc, comp = carry
        sf, sb, ic, lc, rc, lv, lcf, lft = tree
        val, leaf = predict_binned_tree(sf, sb, ic, lc, rc, lv, bins,
                                        max_steps)
        f_row = lft[leaf]                              # [N, K]
        vals = raw[jnp.maximum(f_row, 0), rows]
        vals = jnp.where(f_row >= 0, vals, 0.0)
        val = val + (lcf[leaf] * vals).sum(axis=1)
        y = val - comp
        t = acc + y
        comp = (t - acc) - y
        return (t, comp), None

    init = (jnp.zeros(N, dtype=jnp.float32), jnp.zeros(N, dtype=jnp.float32))
    (out, _), _ = jax.lax.scan(body, init,
                               (split_feature, split_bin, is_cat_node,
                                left_child, right_child, leaf_value,
                                leaf_coeff, leaf_feat))
    return out


@instrumented_jit(program="predict_leaves",
                  static_argnames=("max_steps",))
def predict_leaf_indices_forest(split_feature, split_bin, is_cat_node,
                                left_child, right_child, leaf_value, bins,
                                max_steps: int):
    """[T, N] i32 leaf index per tree (PredictLeafIndex, gbdt.cpp:817-826)."""
    def body(_, tree):
        sf, sb, ic, lc, rc, lv = tree
        _, leaf = predict_binned_tree(sf, sb, ic, lc, rc, lv, bins, max_steps)
        return None, leaf

    _, leaves = jax.lax.scan(body, None,
                             (split_feature, split_bin, is_cat_node,
                              left_child, right_child, leaf_value))
    return leaves
