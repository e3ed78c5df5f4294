"""Distributed communication strategies for the tree-growth loop.

The reference implements three parallel tree learners over a hand-rolled
socket/MPI collective stack (src/network/):

  * feature-parallel (feature_parallel_tree_learner.cpp): every machine
    holds ALL data; split *finding* is sharded by feature; the only
    communication is Allreduce(SplitInfo::MaxReducer).
  * data-parallel (data_parallel_tree_learner.cpp): rows are sharded;
    local histograms are ReduceScatter'ed so each machine owns the fully
    reduced histograms of a feature block (142-160); best split on owned
    features; Allreduce(MaxReducer) of the 2 candidate SplitInfos (219-242).
  * voting-parallel / PV-tree (voting_parallel_tree_learner.cpp): data-
    parallel with communication cut to O(2*top_k*max_bin): local per-feature
    best splits -> local top-k -> Allgather of candidates (332) ->
    GlobalVoting (157-186) -> reduce only elected features' histograms
    (188-244, 354-356) -> full-precision split on elected features.

Here each strategy is a static NamedTuple plugged into
ops.grow._grow_tree_impl under ``jax.shard_map``; the byte-level reducers
become XLA collectives on structured values: psum for
HistogramBinEntry sums, and an all_gather + tournament
(ops.split.combine_gathered_splits) for the SplitInfo max-reduce.
Data-parallel over uint8 unbundled bins does not go through
``_grow_tree_impl`` at all: ``HistExchange`` is what crosses chips when
every shard grows its own leaf-ordered row block (ops/ordered_grow.py).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import leafhist
from ..ops.bundle import expand_histogram
from ..ops.histogram import children_histograms, root_histogram
from ..ops.split import (BestSplit, SplitParams, combine_gathered_splits,
                         find_best_split, leaf_split_gain, per_feature_scan)


def _psum_tree(x, axis_name):
    return jax.tree.map(lambda a: lax.psum(a, axis_name), x)


# ---------------------------------------------------------------------------
# Collective-traffic accounting (lightgbm_tpu/obs/).
#
# Every strategy below also implements ``traffic_per_tree(F, B, L)``: the
# collective calls and payload bytes ONE tree's growth issues, computed
# statically from shapes — the jitted path is never touched.  This is
# exact, not a bound: grow_tree runs a fixed-trip-count fori_loop (L-1
# steps; saturated steps are masked no-ops that still execute their
# collectives), so the per-tree comm volume is a pure function of
# (num_features, max_bin, num_leaves, strategy).
#
# "bytes" counts the device-local logical payload handed to each
# collective call (for all_gather: the local shard's contribution, not
# the k-times-larger gathered result).  BestSplit is 6 scalar fields
# (gain/feature/threshold/left_sum_g/left_sum_h/left_count), each its own
# pytree leaf and hence its own collective call.
# ---------------------------------------------------------------------------

_SPLITINFO_FIELDS = 6
_HIST_ITEM = 3 * 4          # <sum_g, sum_h, count> f32 per bin


def _traffic(**kinds):
    """Assemble a {kind: {"calls", "bytes"}} dict, dropping empty kinds."""
    return {k: {"calls": int(c), "bytes": int(b)}
            for k, (c, b) in kinds.items() if c}


def traffic_totals(traffic):
    """(total_calls, total_bytes) over a traffic_per_tree dict."""
    if not traffic:
        return 0, 0
    return (sum(v["calls"] for v in traffic.values()),
            sum(v["bytes"] for v in traffic.values()))


def observe_traffic(traffic, trees: int = 1) -> None:
    """Feed ``trees`` tree growths' static collective account into the
    metrics pipeline (obs/): one ``comm_bytes_<kind>`` histogram sample
    per tree per collective kind (the per-tree payload that kind moved),
    plus the aggregate ``comm_bytes`` series.  Host-side arithmetic on
    the already-static account — the jitted path stays untouched, which
    is the whole design of the traffic model (module header).  Merged
    across hosts via ``registry.merge``, the per-rank distributions are
    what makes stragglers and asymmetric meshes visible."""
    if not traffic or trees <= 0:
        return
    from .. import obs
    total = sum(v["bytes"] for v in traffic.values())
    for _ in range(trees):
        for kind, v in traffic.items():
            obs.observe(f"comm_bytes_{kind}", float(v["bytes"]),
                        buckets=obs.DEFAULT_BYTE_BUCKETS)
        obs.observe("comm_bytes", float(total),
                    buckets=obs.DEFAULT_BYTE_BUCKETS)


# ---------------------------------------------------------------------------
# Host-side (out-of-jit) collectives.
#
# The fault-tolerance layer needs a handful of tiny cross-process
# exchanges that run on the HOST between rounds — resume consensus over
# snapshot iterations (snapshot.coordinated_resume), desync-digest
# comparison and state re-broadcast (models/gbdt.py) — not inside the
# jitted growers.  They live here, next to the in-jit strategies, so the
# comm layer owns every byte that crosses processes; tests monkeypatch
# these two names to simulate multi-rank gathers in one process.
# ---------------------------------------------------------------------------

def allgather_host_array(x):
    """All-gather one small replicated host array: every process
    contributes its local value and receives the ``[P, ...]`` stack
    (identity reshape-to-[1, ...] when single-process)."""
    import numpy as np
    from jax.experimental import multihost_utils
    return np.asarray(multihost_utils.process_allgather(np.asarray(x)))


def broadcast_host_bytes(payload, is_source: bool) -> bytes:
    """Broadcast an arbitrary byte string from the source rank to every
    process: lengths first (a tiny allgather, so every rank pads to the
    same word count), then ONE ``broadcast_one_to_all`` of the payload
    viewed as int32 words.  The word view keeps the wire/host cost at
    1x the payload (an astype would 4x it), and a true broadcast — not
    an allgather — keeps a resync payload (full booster state, possibly
    hundreds of MB) from materializing a [P, n] gather on every rank."""
    import numpy as np
    from jax.experimental import multihost_utils
    n = int(len(payload)) if is_source else 0
    # single-process process_allgather returns the value unstacked;
    # normalize to the [P] view max() expects
    lens = np.atleast_1d(allgather_host_array(np.int64(n)))
    size = int(lens.max())
    buf = np.zeros(size + (-size) % 4, np.uint8)
    if is_source:
        buf[:size] = np.frombuffer(payload, np.uint8)
    out = multihost_utils.broadcast_one_to_all(buf.view(np.int32),
                                               is_source=is_source)
    return np.ascontiguousarray(out).view(np.uint8)[:size].tobytes()


def _allgather_combine(split: BestSplit, axis_name: str,
                       num_shards: int) -> BestSplit:
    """Allreduce(SplitInfo::MaxReducer): tiny all_gather + tournament."""
    gathered = jax.tree.map(
        lambda f: lax.all_gather(f, axis_name, axis=0), split)
    return combine_gathered_splits(gathered, num_shards)


def _offset_features(split: BestSplit, offset) -> BestSplit:
    """Map a shard-local feature index to the global index."""
    return split._replace(
        feature=jnp.where(split.feature >= 0, split.feature + offset,
                          split.feature))


class HistExchange(NamedTuple):
    """What crosses chips when every shard grows its own leaf-ordered
    row block (ops/ordered_grow.py ``grow_tree_ordered(exchange=...)``
    under ``jax.shard_map``): the root's scales and sums once a tree,
    and one digit-sum histogram a split.  Integer sums are exact and
    order-free, so the summed histograms are the serial learner's on the
    same rows to the bit, and every total the grower holds is taken from
    them (ops/split.py ``sums_totals``).  Segments, row lanes, sorts and
    the score update never leave a shard.

    Each call sits under its own leaf phase (obs/phases.py
    ``exchange/root``, ``exchange/hist``): a collective's device time
    includes the wait for the slowest shard.  Call ``hist`` OUTSIDE any
    ``lax.cond``: each shard is in its own size class, and a collective
    inside a branch would deadlock."""
    axis_name: str = "data"
    num_shards: int = 1

    def root(self, scales, shard_rows):
        """Global quantisation scales (a max over shards, so all shards
        cut the same digits) and the root's rows as an integer, from each
        shard's float count: float32 holds a count exactly only up to
        2^24 rows, which one shard keeps under (ops/leafhist.py) and
        four together do not."""
        with jax.named_scope("exchange/root"):
            rows = jnp.round(shard_rows).astype(jnp.int32)
            return (lax.pmax(scales, self.axis_name),
                    lax.psum(rows, self.axis_name))

    def hist(self, sums_i32, root: bool = False):
        """A shard's ``[F, 9, B]`` int32 digit sums summed over the
        shards, the root's (``exchange/root``) or a split step's, as
        ``[F, 18, B]`` halves (ops/leafhist.py ``split_halves``): a digit
        is up to 128, so whole int32 sums over all shards would wrap
        once one (feature, bin) held 2^31 / 128 = 16.7M rows, which a
        three-valued column of a 42M-row table does."""
        # two literal scopes: tools/lint_phase_scopes.py reads them
        if root:
            with jax.named_scope("exchange/root"):
                return lax.psum(leafhist.split_halves(sums_i32),
                                self.axis_name)
        with jax.named_scope("exchange/hist"):
            return lax.psum(leafhist.split_halves(sums_i32), self.axis_name)

    def traffic_per_tree(self, num_features: int, max_bin: int,
                         num_leaves: int):
        """Static per-tree account: one all-reduce of digit-sum halves
        for the root and one a split step (``num_leaves`` in all; the
        loop's trip count is fixed, so saturated steps still exchange),
        plus the root's 3 scales and its integer row count."""
        hist_b = num_features * 2 * leafhist.NUM_STREAMS * max_bin * 4
        return _traffic(pmax=(1, 3 * 4),
                        psum=(1 + num_leaves, 4 + hist_b * num_leaves))


class DataParallelComm(NamedTuple):
    """Rows sharded over ``axis_name``; histograms globally reduced.

    The strategy ops/grow.py runs for data-parallel learning where the
    leaf-ordered grower cannot (uint16 bins, EFB columns): the
    [*, F, B, 3] float histogram is all-reduced and every shard finds
    the split on it, replicated.  The reference's reduce-scatter by
    feature block with a SplitInfo tournament was a second form of the
    same sum; the chip's compiler served it as this all-reduce plus a
    slice (PERF.md, PR 24), so the one form stays.  uint8 unbundled data
    goes through ``HistExchange`` on the leaf-ordered grower instead.
    """
    axis_name: str = "data"
    num_shards: int = 1

    def reduce_sums(self, sums):
        # Root Allreduce of <count, sum_g, sum_h> (data_parallel:112-139).
        return _psum_tree(sums, self.axis_name)

    def traffic_per_tree(self, num_features: int, max_bin: int,
                         num_leaves: int):
        """Static per-tree collective account (see module header): the
        three root scalars, the root's histogram and both children's at
        every split."""
        steps = max(num_leaves - 1, 0)
        hist_b = num_features * max_bin * _HIST_ITEM
        return _traffic(psum=(3 + 1 + steps,
                              3 * 4 + hist_b * (1 + 2 * steps)))

    def _split_from_hist(self, hist, totals_g, totals_h, totals_c, can,
                         num_bin, is_cat, feat_mask, sp, bundle=None):
        # under EFB the wire payload is the (much smaller) COLUMN
        # histogram, expanded to feature space after the sum
        hist = lax.psum(hist, self.axis_name)
        if bundle is not None:
            hist = expand_histogram(hist, bundle)
        return find_best_split(hist, totals_g, totals_h, totals_c,
                               num_bin, is_cat, feat_mask, can, sp)

    def prepare(self, bins, bins_rm, g, h, w, params):
        return None

    def root_split(self, prep, bins, g, h, w, root_g, root_h, root_c,
                   num_bin, is_cat, feat_mask, max_bin: int, sp: SplitParams,
                   num_leaves: int, bundle=None):
        hist = root_histogram(bins, g, h, w, max_bin)
        return self._split_from_hist(hist, root_g, root_h, root_c,
                                     jnp.asarray(True), num_bin, is_cat,
                                     feat_mask, sp, bundle=bundle), (), \
            (root_g, root_h, root_c)

    def children_splits(self, prep, cache, bins, g, h, w, step,
                        totals_g, totals_h, totals_c, can,
                        num_bin, is_cat, feat_mask, max_bin: int,
                        sp: SplitParams, bundle=None):
        hists = children_histograms(bins, g, h, w, step.leaf_id,
                                    step.parent_leaf, step.right_leaf,
                                    max_bin)
        return self._split_from_hist(hists, totals_g, totals_h, totals_c,
                                     can, num_bin, is_cat, feat_mask,
                                     sp, bundle=bundle), cache


class FeatureParallelComm(NamedTuple):
    """All data replicated; split finding sharded by feature block.

    Mirrors FeatureParallelTreeLearner: each shard scans only its feature
    block (the reference's bin-count-balanced assignment,
    feature_parallel_tree_learner.cpp:26-45, becomes a uniform block — XLA
    wants equal shapes), then Allreduce(MaxReducer) over shards (47-69).
    All shards then apply the winning split to their (full) row set
    identically — no data exchange.

    f_block: static features-per-shard (ceil(F / num_shards); the caller
    pads feature metadata to num_shards * f_block).
    """
    axis_name: str = "feature"
    num_shards: int = 1
    f_block: int = 1

    def reduce_sums(self, sums):
        return sums  # every shard already holds all rows

    def traffic_per_tree(self, num_features: int, max_bin: int,
                         num_leaves: int):
        """Static per-tree collective account: feature-parallel ships ONLY
        SplitInfos (the Allreduce-max tournament) — zero histogram bytes,
        the whole point of the strategy."""
        steps = max(num_leaves - 1, 0)
        return _traffic(
            all_gather=(_SPLITINFO_FIELDS * (1 + steps),
                        _SPLITINFO_FIELDS * 4 * (1 + 2 * steps)))

    def _local_meta(self, num_bin, is_cat, feat_mask):
        shard = lax.axis_index(self.axis_name)
        offset = shard * self.f_block
        nb = lax.dynamic_slice_in_dim(num_bin, offset, self.f_block)
        ic = lax.dynamic_slice_in_dim(is_cat, offset, self.f_block)
        fm = lax.dynamic_slice_in_dim(feat_mask, offset, self.f_block)
        return offset, nb, ic, fm

    def prepare(self, bins, bins_rm, g, h, w, params):
        return None

    def _expand_block(self, hist_blk, bundle, offset):
        """EFB: expand this shard's COLUMN block back to the full
        original-feature space.  Columns owned by other shards read a
        zero pad column; their features come back as garbage and are
        masked out of the scan (the split finder only trusts features
        whose column this shard owns)."""
        fb = self.f_block
        owned = (bundle.col >= offset) & (bundle.col < offset + fb)
        widths = [(0, 0)] * hist_blk.ndim
        widths[hist_blk.ndim - 3] = (0, 1)
        hist_pad = jnp.pad(hist_blk, widths)
        local = bundle._replace(
            col=jnp.where(owned, bundle.col - offset, fb))
        return expand_histogram(hist_pad, local), owned

    def root_split(self, prep, bins, g, h, w, root_g, root_h, root_c,
                   num_bin, is_cat, feat_mask, max_bin: int, sp: SplitParams,
                   num_leaves: int, bundle=None):
        if bundle is not None:
            shard = lax.axis_index(self.axis_name)
            offset = shard * self.f_block
            bins_blk = lax.dynamic_slice_in_dim(bins, offset, self.f_block,
                                                axis=0)
            hist = root_histogram(bins_blk, g, h, w, max_bin)
            hist, owned = self._expand_block(hist, bundle, offset)
            local = find_best_split(hist, root_g, root_h, root_c, num_bin,
                                    is_cat, feat_mask & owned,
                                    jnp.asarray(True), sp)
            return _allgather_combine(local, self.axis_name,
                                      self.num_shards), (), \
                (root_g, root_h, root_c)
        offset, nb, ic, fm = self._local_meta(num_bin, is_cat, feat_mask)
        bins_blk = lax.dynamic_slice_in_dim(bins, offset, self.f_block, axis=0)
        hist = root_histogram(bins_blk, g, h, w, max_bin)
        local = find_best_split(hist, root_g, root_h, root_c, nb, ic, fm,
                                jnp.asarray(True), sp)
        local = _offset_features(local, offset)
        return _allgather_combine(local, self.axis_name, self.num_shards), \
            (), (root_g, root_h, root_c)

    def children_splits(self, prep, cache, bins, g, h, w, step,
                        totals_g, totals_h, totals_c, can,
                        num_bin, is_cat, feat_mask, max_bin: int,
                        sp: SplitParams, bundle=None):
        if bundle is not None:
            shard = lax.axis_index(self.axis_name)
            offset = shard * self.f_block
            bins_blk = lax.dynamic_slice_in_dim(bins, offset, self.f_block,
                                                axis=0)
            hists = children_histograms(bins_blk, g, h, w, step.leaf_id,
                                        step.parent_leaf, step.right_leaf,
                                        max_bin)
            hists, owned = self._expand_block(hists, bundle, offset)
            local = find_best_split(hists, totals_g, totals_h, totals_c,
                                    num_bin, is_cat, feat_mask & owned,
                                    can, sp)
            return (_allgather_combine(local, self.axis_name,
                                       self.num_shards), cache)
        offset, nb, ic, fm = self._local_meta(num_bin, is_cat, feat_mask)
        bins_blk = lax.dynamic_slice_in_dim(bins, offset, self.f_block, axis=0)
        hists = children_histograms(bins_blk, g, h, w, step.leaf_id,
                                    step.parent_leaf, step.right_leaf,
                                    max_bin)
        local = find_best_split(hists, totals_g, totals_h, totals_c,
                                nb, ic, fm, can, sp)
        local = _offset_features(local, offset)
        return (_allgather_combine(local, self.axis_name, self.num_shards),
                cache)


class VotingParallelComm(NamedTuple):
    """PV-tree: data-parallel with top-k feature election.

    Per leaf: local per-feature best gains (per_feature_scan on the LOCAL
    histogram with locally derived totals and 1/num_shards-scaled
    constraints, voting_parallel_tree_learner.cpp:52-54) -> local top-k
    feature ids by unweighted local gain -> all_gather candidates ->
    election by per-feature MAX of count-weighted local gain (GlobalVoting,
    157-186) -> psum of only the elected features' histograms
    (CopyLocalHistogram + ReduceScatter, 188-244) -> exact split on elected
    features against GLOBAL totals -> winner (already replicated, no final
    reduce needed).
    """
    axis_name: str = "data"
    num_shards: int = 1
    top_k: int = 20

    def reduce_sums(self, sums):
        return _psum_tree(sums, self.axis_name)

    def traffic_per_tree(self, num_features: int, max_bin: int,
                         num_leaves: int):
        """Static per-tree collective account: the PV-tree promise made
        measurable — per elect call, 2 all_gathers of the [C, K] proposal
        lists plus a psum of only the K elected features' histograms
        (O(2*top_k*max_bin) instead of O(F*max_bin))."""
        steps = max(num_leaves - 1, 0)
        K = min(self.top_k, num_features)
        hist_b = K * max_bin * _HIST_ITEM        # one candidate leaf's psum
        # root elect has C=1 candidate leaf, each child elect C=2
        return _traffic(
            psum=(3 + 1 + steps,
                  3 * 4 + hist_b * (1 + 2 * steps)),
            all_gather=(2 * (1 + steps),
                        2 * K * 4 * (1 + 2 * steps)))

    def _local_sp(self, sp: SplitParams) -> SplitParams:
        # local_tree_config_.min_data_in_leaf /= num_machines_ is C++ INTEGER
        # division (voting_parallel_tree_learner.cpp:52-54): floor, not a
        # float scale; the hessian constraint is double and divides exactly.
        k = self.num_shards
        return sp._replace(min_data_in_leaf=int(sp.min_data_in_leaf) // k,
                           min_sum_hessian_in_leaf=(
                               sp.min_sum_hessian_in_leaf / k))

    def _elect_and_split(self, hist, totals_g, totals_h, totals_c, can,
                         num_bin, is_cat, feat_mask, sp):
        """hist: [C, F, B, 3] local histograms of C candidate leaves."""
        C, F = hist.shape[0], hist.shape[1]
        K = min(self.top_k, F)
        # Local leaf totals derive from the local histogram itself.
        loc = jnp.sum(hist, axis=2)                        # [C, F, 3]
        loc_g = jnp.max(loc[..., 0], axis=1)               # any feature's
        loc_h = jnp.max(loc[..., 1], axis=1)               # sums are equal;
        loc_c = jnp.max(loc[..., 2], axis=1)               # max is cheap
        local_sp = self._local_sp(sp)
        feat_gain, _, _, _, _ = per_feature_scan(
            hist, loc_g, loc_h, loc_c, num_bin, is_cat, feat_mask,
            local_sp)                                      # [C, F]
        # Local proposals: top-k features by the true local split gain
        # (parent shift subtracted), UNWEIGHTED — exactly the per-machine
        # MaxK over FindBestThreshold outputs
        # (voting_parallel_tree_learner.cpp:322-326).
        shift = leaf_split_gain(loc_g, loc_h, local_sp.lambda_l1,
                                local_sp.lambda_l2)        # [C]
        gain_local = jnp.where(jnp.isfinite(feat_gain),
                               feat_gain - shift[:, None],
                               -jnp.inf)                   # [C, F]
        top_gain, top_ids = lax.top_k(gain_local, K)       # [C, K]
        # GlobalVoting's vote weight is gain * (left_count + right_count)
        # / mean_num_data (voting_parallel_tree_learner.cpp:157-173);
        # left+right is the proposing machine's LOCAL leaf count.
        mean_cnt = jnp.maximum(totals_c / self.num_shards, 1.0)  # [C]
        top_w = jnp.where(jnp.isfinite(top_gain),
                          top_gain * loc_c[:, None] / mean_cnt[:, None],
                          -jnp.inf)

        # ---- GlobalVoting: per-feature MAX of weighted local gains over
        # machines, then top-k (NOT a sum: cpp:168-173 keeps the best
        # weighted proposal per feature).
        w_all = lax.all_gather(top_w, self.axis_name)          # [S, C, K]
        ids_all = lax.all_gather(top_ids, self.axis_name)      # [S, C, K]
        votes = jnp.full((C, F), -jnp.inf, jnp.float32)
        flat_ids = ids_all.transpose(1, 0, 2).reshape(C, -1)   # [C, S*K]
        flat_w = w_all.transpose(1, 0, 2).reshape(C, -1)
        votes = jax.vmap(lambda v, i, s: v.at[i].max(s))(
            votes, flat_ids, flat_w)
        vote_val, elected = lax.top_k(votes, K)            # [C, K] global ids
        # GlobalVoting drops entries nobody proposed (gain == kMinScore or
        # feature == -1, cpp:177-185): with fewer than K genuine proposals
        # top_k pads with arbitrary -inf-vote features — mask them out of
        # the exact scan instead of electing them.
        voted = jnp.isfinite(vote_val)                     # [C, K]
        # Ascending feature order keeps the final argmax tie-break identical
        # to the serial scan (smallest feature index wins).
        order = jnp.argsort(jnp.where(voted, elected, jnp.int32(1 << 30)),
                            axis=-1)
        elected = jnp.take_along_axis(elected, order, axis=-1)
        voted = jnp.take_along_axis(voted, order, axis=-1)

        # ---- reduce only the elected features' histograms ----------------
        hist_el = jax.vmap(lambda hc, ids: hc[ids])(hist, elected)
        hist_el = lax.psum(hist_el, self.axis_name)        # [C, K, B, 3]
        nb_el = num_bin[elected]
        ic_el = is_cat[elected]
        fm_el = feat_mask[elected] & voted

        def _one(hist_c, tg, th, tc, cn, nb, ic, fm):
            return find_best_split(hist_c, tg, th, tc, nb, ic, fm, cn, sp)

        local_best = jax.vmap(_one)(hist_el, totals_g, totals_h, totals_c,
                                    can, nb_el, ic_el, fm_el)
        # Map elected-set index back to the global feature index.
        real_feat = jax.vmap(lambda ids, f: ids[jnp.maximum(f, 0)])(
            elected, local_best.feature)
        return local_best._replace(
            feature=jnp.where(local_best.feature >= 0, real_feat,
                              local_best.feature))

    def prepare(self, bins, bins_rm, g, h, w, params):
        return None

    def root_split(self, prep, bins, g, h, w, root_g, root_h, root_c,
                   num_bin, is_cat, feat_mask, max_bin: int, sp: SplitParams,
                   num_leaves: int, bundle=None):
        hist = root_histogram(bins, g, h, w, max_bin)
        if bundle is not None:
            # EFB: the election, votes and elected-feature psum all run
            # in ORIGINAL feature space; only the local histogram pass
            # ran over the shrunk columns — bundling multiplies with the
            # voting learner's top-k comm reduction.
            hist = expand_histogram(hist, bundle)
        best = self._elect_and_split(
            hist[None], jnp.asarray([root_g]), jnp.asarray([root_h]),
            jnp.asarray([root_c]), jnp.asarray([True]),
            num_bin, is_cat, feat_mask, sp)
        return jax.tree.map(lambda f: f[0], best), (), \
            (root_g, root_h, root_c)

    def children_splits(self, prep, cache, bins, g, h, w, step,
                        totals_g, totals_h, totals_c, can,
                        num_bin, is_cat, feat_mask, max_bin: int,
                        sp: SplitParams, bundle=None):
        hists = children_histograms(bins, g, h, w, step.leaf_id,
                                    step.parent_leaf, step.right_leaf,
                                    max_bin)
        if bundle is not None:
            hists = expand_histogram(hists, bundle)
        return self._elect_and_split(hists, totals_g, totals_h, totals_c,
                                     can, num_bin, is_cat, feat_mask,
                                     sp), cache
