"""Jitted leaf-wise (best-first) tree growth.

The reference grows a tree with a data-dependent Python-style loop
(SerialTreeLearner::Train, serial_tree_learner.cpp:167-224): pick the leaf
with the best split, partition its rows, build child histograms, find child
splits, repeat num_leaves-1 times, breaking early when no leaf has positive
gain.  On TPU the whole loop runs inside one jitted ``lax.fori_loop`` with
fixed trip count: the early break becomes a masked no-op (observationally
identical because once no leaf can split, no new splits ever appear).

Fixed-shape state replaces the reference's dynamic structures:
  * DataPartition's shuffled index array (data_partition.hpp) -> a per-row
    ``leaf_id`` vector updated with ``where``,
  * the LRU histogram pool -> nothing: both children's histograms are built
    in one masked scatter pass per split (see ops/histogram.py),
  * SplitInfo per leaf -> struct-of-arrays over [num_leaves].

Node/leaf indexing matches Tree::Split (tree.cpp:52-95): step k creates
internal node k; the left child keeps the parent's leaf index, the right
child becomes leaf k+1; children encoded as ~leaf in the child arrays.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..obs.compile_ledger import instrumented_jit

from .bundle import decode_feature_bins, expand_digit_sums, expand_histogram
from .histogram import children_histograms, root_histogram
from .split import (BestSplit, SplitParams, find_best_split,
                    find_best_split_sums, leaf_output, sums_totals,
                    K_MIN_SCORE)


class _SerialPrep(NamedTuple):
    """Per-tree device state for the cached serial learner."""
    bins_rm: jax.Array     # [N, F] row-major bins
    digits: jax.Array      # [N, 9] int8 fixed-point g/h/w digits
    scales: jax.Array      # [3] f32 quantization scales


class _StepInfo(NamedTuple):
    """Everything the partition step already knows about the split being
    applied, handed to the comm so it never re-derives masks."""
    leaf_id: jax.Array     # [N] AFTER the partition update
    in_leaf: jax.Array     # [N] bool, rows of the split leaf (pre-update)
    go_right: jax.Array    # [N] bool, rows moving to the right child
    parent_leaf: jax.Array  # scalar i32 (left child keeps this slot)
    right_leaf: jax.Array   # scalar i32
    do_split: jax.Array     # scalar bool


class SerialComm(NamedTuple):
    """Single-device communication strategy: no collectives.

    grow_tree is parameterized by a static ``comm`` object so the
    distributed learners (lightgbm_tpu/parallel/comm.py) can swap the
    reference's network calls (data_parallel_tree_learner.cpp ReduceScatter/
    Allreduce, feature_parallel Allreduce-max, voting Allgather+elect) into
    the same growth loop without duplicating it.  Interface:

      reduce_sums((g, h, c))          -> globally-reduced leaf totals
      prepare(...)                    -> opaque per-tree state (closure data)
      root_split(...)                 -> (BestSplit, histogram cache pytree,
                                          the root's (g, h, count) as the
                                          strategy's histograms hold them)
      children_splits(...)            -> (BestSplit [2], updated cache)

    With ``leaf_cache=True`` (the default) the serial learner reproduces the
    reference's core cost structure (serial_tree_learner.cpp:398-453): keep
    every live leaf's histogram cached, build only the SMALLER child of each
    split over only that child's rows, and derive the sibling by
    subtraction.  The cache holds int32 fixed-point digit sums
    (ops/leafhist.py), so the subtraction is exact — stronger than the
    reference's f64 accumulators (bin.h:25-27) — and the split search
    reads them as integers (ops/split.py ``find_best_split_sums``, the
    leaf-ordered grower's search: the two grow the same trees to the
    bit).  ``leaf_cache=False`` keeps
    the one-full-pass-per-split strategy (used by tests needing bit-parity
    with the distributed learners, which share that code path).
    """
    leaf_cache: bool = True

    def reduce_sums(self, sums):
        return sums

    def traffic_per_tree(self, num_features: int, max_bin: int,
                         num_leaves: int):
        """Collective-traffic account (obs layer): serial growth issues no
        collectives.  Same interface as the distributed strategies in
        lightgbm_tpu/parallel/comm.py."""
        return {}

    # -- per-tree preparation -------------------------------------------
    def prepare(self, bins, bins_rm, g, h, w, params: "GrowParams"):
        if not self.leaf_cache:
            return None
        from . import leafhist
        if bins_rm is None:
            bins_rm = bins.T
        scales = leafhist.compute_scales(g, h, w)
        digits = leafhist.quantize_digits(g, h, w, scales)
        return _SerialPrep(bins_rm, digits, scales)

    def root_split(self, prep, bins, g, h, w, root_g, root_h, root_c,
                   num_bin, is_cat, feat_mask, max_bin: int,
                   sp: SplitParams, num_leaves: int, bundle=None):
        if not self.leaf_cache:
            hist = root_histogram(bins, g, h, w, max_bin)
            if bundle is not None:
                hist = expand_histogram(hist, bundle)
            split = find_best_split(hist, root_g, root_h, root_c, num_bin,
                                    is_cat, feat_mask, jnp.asarray(True), sp)
            return split, (), (root_g, root_h, root_c)
        from . import leafhist
        F = bins.shape[0]
        sums = leafhist.digit_histogram(prep.bins_rm, prep.digits, max_bin)
        # EFB: digit sums are built (and cached) in COLUMN space — the
        # shrunk shape is where the histogram savings live — and expanded
        # to original feature space only for the scan.  The expansion is
        # all-integer, so a zero-conflict bundled run bit-matches the
        # unbundled one (tests/test_bundling.py).
        scan_sums = (expand_digit_sums(sums, bundle)
                     if bundle is not None else sums)
        split = find_best_split_sums(scan_sums, prep.scales, num_bin, is_cat,
                                     feat_mask, jnp.asarray(True), sp)
        cache = jnp.zeros((num_leaves, F, 9, max_bin), jnp.int32)
        cache = cache.at[0].set(sums)
        return split, cache, sums_totals(sums, prep.scales)

    def children_splits(self, prep, cache, bins, g, h, w, step: _StepInfo,
                        totals_g, totals_h, totals_c, can,
                        num_bin, is_cat, feat_mask, max_bin: int,
                        sp: SplitParams, bundle=None):
        if not self.leaf_cache:
            hists = children_histograms(bins, g, h, w, step.leaf_id,
                                        step.parent_leaf, step.right_leaf,
                                        max_bin)
            if bundle is not None:
                hists = expand_histogram(hists, bundle)
            split = find_best_split(hists, totals_g, totals_h, totals_c,
                                    num_bin, is_cat, feat_mask, can, sp)
            return split, cache
        from . import leafhist
        N = step.leaf_id.shape[0]
        classes = leafhist.size_classes(N)

        # TIMETAG phase names (serial_tree_learner.cpp:10-37) as trace
        # annotations: jax.profiler device traces group ops by these.
        with jax.named_scope("hist"):
            # Raw (unweighted) row counts decide which child is smaller,
            # like the reference's data-count rule
            # (serial_tree_learner.cpp:404-420).
            cnt_r = jnp.sum((step.in_leaf & step.go_right).astype(jnp.int32))
            cnt_in = jnp.sum(step.in_leaf.astype(jnp.int32))
            cnt_l = cnt_in - cnt_r
            small_is_left = cnt_l <= cnt_r
            mask_small = step.in_leaf & jnp.where(small_is_left,
                                                  ~step.go_right,
                                                  step.go_right)
            small_cnt = jnp.minimum(cnt_l, cnt_r)

            sums_small = leafhist.leaf_histogram(prep.bins_rm, prep.digits,
                                                 mask_small, small_cnt,
                                                 max_bin, classes)
            sums_parent = cache[step.parent_leaf]      # [F, 9, B] i32
            sums_large = sums_parent - sums_small      # EXACT sibling
            sums_left = jnp.where(small_is_left, sums_small, sums_large)
            sums_right = jnp.where(small_is_left, sums_large, sums_small)

            keep = step.do_split
            cache = cache.at[step.parent_leaf].set(
                jnp.where(keep, sums_left, sums_parent))
            cache = cache.at[step.right_leaf].set(
                jnp.where(keep, sums_right, cache[step.right_leaf]),
                mode="drop")

        with jax.named_scope("find_split"):
            scan_sums = jnp.stack([sums_left, sums_right])
            if bundle is not None:
                scan_sums = expand_digit_sums(scan_sums, bundle)
            split = find_best_split_sums(scan_sums, prep.scales, num_bin,
                                         is_cat, feat_mask, can, sp)
        return split, cache


class GrowParams(NamedTuple):
    """Static tree-growth configuration."""
    num_leaves: int = 31
    max_bin: int = 255
    min_data_in_leaf: int = 100
    min_sum_hessian_in_leaf: float = 10.0
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_gain_to_split: float = 0.0
    max_depth: int = -1
    # bagging/GOSS: physically move zero-weight rows behind the active
    # segment once per tree so every window/partition/histogram cost
    # tracks the SUBSAMPLE, not N (gbdt.cpp:271-278's smaller-dataset
    # switch); their score deltas come from routing them through the tree
    # like the reference's out-of-bag AddPredictionToScore.  Only the
    # leaf-ordered grower honors it.
    compact_inactive: bool = False

    def split_params(self) -> SplitParams:
        return SplitParams(self.min_data_in_leaf, self.min_sum_hessian_in_leaf,
                           self.lambda_l1, self.lambda_l2,
                           self.min_gain_to_split)


class TreeArrays(NamedTuple):
    """Flat tree tensors (device-side Tree, mirrors tree.h:17-194).

    Leaf values are already scaled by learning_rate (Shrinkage applied at
    the end of growth like GBDT::TrainOneIter, gbdt.cpp:357)."""
    num_leaves: jax.Array          # scalar i32: leaves actually grown
    split_feature: jax.Array       # [L-1] i32 inner feature index
    split_bin: jax.Array           # [L-1] i32 bin threshold
    split_gain: jax.Array          # [L-1] f32
    left_child: jax.Array          # [L-1] i32 (~leaf or node)
    right_child: jax.Array         # [L-1] i32
    internal_value: jax.Array      # [L-1] f32 (unshrunk, like reference)
    internal_count: jax.Array      # [L-1] i32
    leaf_value: jax.Array          # [L] f32 (shrunk)
    leaf_count: jax.Array          # [L] i32
    leaf_parent: jax.Array         # [L] i32
    leaf_depth: jax.Array          # [L] i32


def pack_tree_arrays(ta: "TreeArrays"):
    """Pack TreeArrays into (ints, floats) vectors so a host fetch is TWO
    transfers instead of 13 (each device->host round-trip stalls the
    pipelined host path; see GBDT._flush_pending)."""
    ints = jnp.concatenate([
        ta.num_leaves.reshape(1), ta.split_feature, ta.split_bin,
        ta.left_child, ta.right_child, ta.internal_count,
        ta.leaf_count, ta.leaf_parent, ta.leaf_depth])
    flts = jnp.concatenate([ta.split_gain, ta.internal_value, ta.leaf_value])
    return ints, flts


def unpack_tree_arrays(ints, flts, num_leaves: int) -> "TreeArrays":
    """Inverse of pack_tree_arrays, on host numpy arrays."""
    L, n = num_leaves, num_leaves - 1
    io, fo = 1, 0
    out_i = []
    for k in (n, n, n, n, n, L, L, L):
        out_i.append(ints[io:io + k])
        io += k
    out_f = []
    for k in (n, n, L):
        out_f.append(flts[fo:fo + k])
        fo += k
    sf, sb, lc, rc, icnt, leaf_cnt, leaf_par, leaf_dep = out_i
    sg, ival, lval = out_f
    return TreeArrays(num_leaves=ints[0], split_feature=sf, split_bin=sb,
                      split_gain=sg, left_child=lc, right_child=rc,
                      internal_value=ival, internal_count=icnt,
                      leaf_value=lval, leaf_count=leaf_cnt,
                      leaf_parent=leaf_par, leaf_depth=leaf_dep)


class _GrowState(NamedTuple):
    leaf_id: jax.Array             # [N] i32
    num_leaves: jax.Array          # scalar i32
    stopped: jax.Array             # scalar bool
    # per-leaf best-split SoA [L]
    best_gain: jax.Array
    best_feat: jax.Array
    best_bin: jax.Array
    best_left_g: jax.Array
    best_left_h: jax.Array
    best_left_c: jax.Array
    best_right_g: jax.Array
    best_right_h: jax.Array
    best_right_c: jax.Array
    # per-leaf totals [L]
    total_g: jax.Array
    total_h: jax.Array
    total_c: jax.Array
    cur_value: jax.Array           # [L] leaf output at creation (unshrunk)
    leaf_parent: jax.Array         # [L]
    leaf_depth: jax.Array          # [L]
    # node arrays [L-1]
    split_feature: jax.Array
    split_bin: jax.Array
    split_gain: jax.Array
    left_child: jax.Array
    right_child: jax.Array
    internal_value: jax.Array
    internal_count: jax.Array


def _store_leaf_split(state: _GrowState, leaf, split: BestSplit) -> _GrowState:
    return state._replace(
        best_gain=state.best_gain.at[leaf].set(split.gain),
        best_feat=state.best_feat.at[leaf].set(split.feature),
        best_bin=state.best_bin.at[leaf].set(split.threshold),
        best_left_g=state.best_left_g.at[leaf].set(split.left_sum_g),
        best_left_h=state.best_left_h.at[leaf].set(split.left_sum_h),
        best_left_c=state.best_left_c.at[leaf].set(split.left_count),
        best_right_g=state.best_right_g.at[leaf].set(split.right_sum_g),
        best_right_h=state.best_right_h.at[leaf].set(split.right_sum_h),
        best_right_c=state.best_right_c.at[leaf].set(split.right_count),
    )


@instrumented_jit(program="grow_tree", static_argnames=("params", "comm"))
def grow_tree(bins, num_bin, is_cat, feat_mask, grad, hess, row_weight,
              learning_rate, params: GrowParams, comm=None, bins_rm=None,
              bundle=None):
    """Grow one tree.  All inputs are device arrays.

    Args:
      bins: [C, N] column-major bin codes (C == F unless ``bundle``; F
        and N are the *local* shard shapes when called under shard_map
        with a distributed comm).
      num_bin: [F] i32; is_cat: [F] bool; feat_mask: [F] bool — always
        ORIGINAL feature space.
      grad, hess: [N] f32 raw gradients/hessians.
      row_weight: [N] f32 bagging/GOSS weight (0 excludes a row from
        training; weights also scale grad/hess like the reference's
        gradient amplification).
      comm: static communication strategy (SerialComm by default; see
        lightgbm_tpu/parallel/comm.py for the distributed learners).
      bins_rm: optional [N, C] row-major copy of bins for the cached serial
        learner's gathers (derived by transposition when omitted).
      bundle: optional ops.bundle.BundleDecode — EFB column layout of
        ``bins``; histograms expand back to feature space for the scan
        and the partition decodes column bins per split.
    Returns (TreeArrays, leaf_id [N] i32, output_delta [N] f32) where
      output_delta = shrunk leaf value per row (the train-score update,
      serial_tree_learner AddPredictionToScore semantics).
    """
    return _grow_tree_impl(bins, num_bin, is_cat, feat_mask, grad, hess,
                           row_weight, learning_rate, params,
                           SerialComm() if comm is None else comm, bins_rm,
                           bundle)


def _grow_tree_impl(bins, num_bin, is_cat, feat_mask, grad, hess, row_weight,
                    learning_rate, params: GrowParams, comm, bins_rm=None,
                    bundle=None):
    """Unjitted growth loop — callable inside shard_map."""
    L = params.num_leaves
    B = params.max_bin
    F, N = bins.shape
    sp = params.split_params()

    g = grad * row_weight
    h = hess * row_weight

    root_g, root_h, root_c = comm.reduce_sums(
        (jnp.sum(g), jnp.sum(h), jnp.sum(row_weight)))

    prep = comm.prepare(bins, bins_rm, g, h, row_weight, params)
    root_split, cache0, (root_g, root_h, root_c) = comm.root_split(
        prep, bins, g, h, row_weight, root_g, root_h, root_c, num_bin,
        is_cat, feat_mask, B, sp, L, bundle=bundle)

    neg_inf = jnp.full((L,), K_MIN_SCORE, dtype=jnp.float32)
    state = _GrowState(
        leaf_id=jnp.zeros((N,), dtype=jnp.int32),
        num_leaves=jnp.asarray(1, jnp.int32),
        stopped=jnp.asarray(False),
        best_gain=neg_inf.at[0].set(root_split.gain),
        best_feat=jnp.zeros((L,), jnp.int32).at[0].set(root_split.feature),
        best_bin=jnp.zeros((L,), jnp.int32).at[0].set(root_split.threshold),
        best_left_g=jnp.zeros((L,), jnp.float32).at[0].set(root_split.left_sum_g),
        best_left_h=jnp.zeros((L,), jnp.float32).at[0].set(root_split.left_sum_h),
        best_left_c=jnp.zeros((L,), jnp.float32).at[0].set(root_split.left_count),
        best_right_g=jnp.zeros((L,), jnp.float32).at[0].set(root_split.right_sum_g),
        best_right_h=jnp.zeros((L,), jnp.float32).at[0].set(root_split.right_sum_h),
        best_right_c=jnp.zeros((L,), jnp.float32).at[0].set(root_split.right_count),
        total_g=jnp.zeros((L,), jnp.float32).at[0].set(root_g),
        total_h=jnp.zeros((L,), jnp.float32).at[0].set(root_h),
        total_c=jnp.zeros((L,), jnp.float32).at[0].set(root_c),
        cur_value=jnp.zeros((L,), jnp.float32),
        leaf_parent=jnp.full((L,), -1, jnp.int32),
        leaf_depth=jnp.zeros((L,), jnp.int32),
        split_feature=jnp.full((L - 1,), -1, jnp.int32),
        split_bin=jnp.zeros((L - 1,), jnp.int32),
        split_gain=jnp.zeros((L - 1,), jnp.float32),
        left_child=jnp.zeros((L - 1,), jnp.int32),
        right_child=jnp.zeros((L - 1,), jnp.int32),
        internal_value=jnp.zeros((L - 1,), jnp.float32),
        internal_count=jnp.zeros((L - 1,), jnp.int32),
    )

    def step(k, carry):
        state, cache = carry
        # Best leaf by gain; ties -> first (smallest leaf idx), matching
        # ArrayArgs::ArgMax over SplitInfo (serial_tree_learner.cpp:204).
        best_leaf = jnp.argmax(state.best_gain).astype(jnp.int32)
        gain = state.best_gain[best_leaf]
        do_split = jnp.logical_and(~state.stopped, gain > 0.0)
        stopped = ~do_split

        feat = state.best_feat[best_leaf]
        tbin = state.best_bin[best_leaf]
        right_leaf = state.num_leaves  # new leaf index (tree.cpp:89)

        # --- partition: rows of best_leaf with bin > t (numerical) or
        # bin != t (categorical) move to the right child -------------------
        with jax.named_scope("split"):
            if bundle is None:
                fbin = jnp.take(bins, jnp.maximum(feat, 0),
                                axis=0).astype(jnp.int32)
            else:
                # EFB: the split feature lives in a shared column —
                # decode that column's bins back to the feature's own
                # bin space before the threshold compare
                fbin = decode_feature_bins(bins, feat, bundle)
            go_right = jnp.where(is_cat[jnp.maximum(feat, 0)],
                                 fbin != tbin, fbin > tbin)
            in_leaf = state.leaf_id == best_leaf
            new_leaf_id = jnp.where(do_split & in_leaf & go_right,
                                    right_leaf, state.leaf_id)

        # --- split sums: both sides as the search held them (ops/split.py:
        # the parent's total less the left side where the histogram is
        # floats, the right side's own integers where it is digit sums) ----
        parent_g = state.total_g[best_leaf]
        parent_h = state.total_h[best_leaf]
        parent_c = state.total_c[best_leaf]
        left_g = state.best_left_g[best_leaf]
        left_h = state.best_left_h[best_leaf]
        left_c = state.best_left_c[best_leaf]
        right_g = state.best_right_g[best_leaf]
        right_h = state.best_right_h[best_leaf]
        right_c = state.best_right_c[best_leaf]
        left_val = leaf_output(left_g, left_h, sp.lambda_l1, sp.lambda_l2)
        right_val = leaf_output(right_g, right_h, sp.lambda_l1, sp.lambda_l2)

        # --- tree structure updates (Tree::Split, tree.cpp:52-95) ---------
        node = k  # node index == split step while not stopped
        parent_node = state.leaf_parent[best_leaf]
        p_safe = jnp.maximum(parent_node, 0)
        was_left = state.left_child[p_safe] == ~best_leaf
        upd_parent = do_split & (parent_node >= 0)
        left_child = state.left_child.at[p_safe].set(
            jnp.where(upd_parent & was_left, node, state.left_child[p_safe]))
        right_child = state.right_child.at[p_safe].set(
            jnp.where(upd_parent & ~was_left, node, state.right_child[p_safe]))

        def upd(arr, value):
            return arr.at[node].set(jnp.where(do_split, value, arr[node]))

        depth = state.leaf_depth[best_leaf]
        new_state = state._replace(
            leaf_id=new_leaf_id,
            num_leaves=state.num_leaves + jnp.where(do_split, 1, 0),
            stopped=stopped,
            split_feature=upd(state.split_feature, feat),
            split_bin=upd(state.split_bin, tbin),
            split_gain=upd(state.split_gain, gain),
            left_child=upd(left_child, ~best_leaf),
            right_child=upd(right_child, ~right_leaf),
            internal_value=upd(state.internal_value,
                               state.cur_value[best_leaf]),
            internal_count=upd(state.internal_count,
                               parent_c.astype(jnp.int32)),
            total_g=state.total_g.at[best_leaf].set(
                jnp.where(do_split, left_g, parent_g))
                .at[right_leaf].set(jnp.where(do_split, right_g, 0.0)),
            total_h=state.total_h.at[best_leaf].set(
                jnp.where(do_split, left_h, parent_h))
                .at[right_leaf].set(jnp.where(do_split, right_h, 0.0)),
            total_c=state.total_c.at[best_leaf].set(
                jnp.where(do_split, left_c, parent_c))
                .at[right_leaf].set(jnp.where(do_split, right_c, 0.0)),
            cur_value=state.cur_value.at[best_leaf].set(
                jnp.where(do_split, left_val, state.cur_value[best_leaf]))
                .at[right_leaf].set(jnp.where(do_split, right_val, 0.0)),
            leaf_parent=state.leaf_parent.at[best_leaf].set(
                jnp.where(do_split, node, parent_node))
                .at[right_leaf].set(jnp.where(do_split, node, -1)),
            leaf_depth=state.leaf_depth.at[best_leaf].set(
                jnp.where(do_split, depth + 1, depth))
                .at[right_leaf].set(jnp.where(do_split, depth + 1, 0)),
        )

        # --- child histograms + child best splits -------------------------
        child_depth_ok = jnp.logical_or(params.max_depth <= 0,
                                        depth + 1 < params.max_depth)
        totals_g = jnp.stack([left_g, right_g])
        totals_h = jnp.stack([left_h, right_h])
        totals_c = jnp.stack([left_c, right_c])
        can = jnp.stack([do_split & child_depth_ok] * 2)
        info = _StepInfo(leaf_id=new_state.leaf_id, in_leaf=in_leaf,
                         go_right=go_right, parent_leaf=best_leaf,
                         right_leaf=right_leaf, do_split=do_split)
        child_split, cache = comm.children_splits(
            prep, cache, bins, g, h, row_weight, info,
            totals_g, totals_h, totals_c, can, num_bin, is_cat, feat_mask,
            B, sp, bundle=bundle)

        # Invalidate the split leaf's old record, then store children.
        new_state = new_state._replace(
            best_gain=new_state.best_gain.at[best_leaf].set(
                jnp.where(do_split, K_MIN_SCORE, new_state.best_gain[best_leaf])))
        left_rec = jax.tree.map(lambda a: a[0], child_split)
        right_rec = jax.tree.map(lambda a: a[1], child_split)
        store_left = jax.tree.map(
            lambda cur, new: jnp.where(do_split, new, cur),
            BestSplit(new_state.best_gain[best_leaf],
                      new_state.best_feat[best_leaf],
                      new_state.best_bin[best_leaf],
                      new_state.best_left_g[best_leaf],
                      new_state.best_left_h[best_leaf],
                      new_state.best_left_c[best_leaf],
                      new_state.best_right_g[best_leaf],
                      new_state.best_right_h[best_leaf],
                      new_state.best_right_c[best_leaf]),
            left_rec)
        new_state = _store_leaf_split(new_state, best_leaf, store_left)
        store_right = jax.tree.map(
            lambda cur, new: jnp.where(do_split, new, cur),
            BestSplit(new_state.best_gain[right_leaf],
                      new_state.best_feat[right_leaf],
                      new_state.best_bin[right_leaf],
                      new_state.best_left_g[right_leaf],
                      new_state.best_left_h[right_leaf],
                      new_state.best_left_c[right_leaf],
                      new_state.best_right_g[right_leaf],
                      new_state.best_right_h[right_leaf],
                      new_state.best_right_c[right_leaf]),
            right_rec)
        new_state = _store_leaf_split(new_state, right_leaf, store_right)
        return new_state, cache

    state, _ = jax.lax.fori_loop(0, L - 1, step, (state, cache0))

    shrunk = state.cur_value * learning_rate
    tree = TreeArrays(
        num_leaves=state.num_leaves,
        split_feature=state.split_feature,
        split_bin=state.split_bin,
        split_gain=state.split_gain,
        left_child=state.left_child,
        right_child=state.right_child,
        internal_value=state.internal_value,
        internal_count=state.internal_count,
        leaf_value=shrunk,
        leaf_count=state.total_c.astype(jnp.int32),
        leaf_parent=state.leaf_parent,
        leaf_depth=state.leaf_depth,
    )
    output_delta = shrunk[state.leaf_id]
    return tree, state.leaf_id, output_delta
