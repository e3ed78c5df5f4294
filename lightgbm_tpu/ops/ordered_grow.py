"""Leaf-ordered (DataPartition-style) serial tree growth.

The cached learner in ops/grow.py keeps rows in original order and pays a
FULL-N stable sort per split to compact the smaller child's rows — an O(N)
term per split that dominates at large N.  This grower instead maintains
the reference's DataPartition invariant (data_partition.hpp: one index
array where every leaf's rows are CONTIGUOUS) — applied to the DATA
ITSELF: binned rows and gradient digits live physically grouped by leaf.
Splitting leaf ``l`` then only touches its own segment:

  * the split feature column is a contiguous dynamic slice (no gather),
  * the stable left/right partition is segment-local: on a TPU the
    counting kernel of ops/partition.py, two passes over the window and
    O(P); elsewhere the stable sort it replaced (the tests' oracle).  Its
    cost tracks the PARENT segment (padded to a power-of-two class), not
    N — sum over a tree ~ O(N * depth) instead of O(N * leaves),
  * the smaller child's histogram kernel reads a contiguous slice of
    the partitioned window,
  * the sibling histogram comes from the exact int32 parent-cache
    subtraction (ops/leafhist.py).

A step picks its size class through a chain of two-way ``lax.cond``s that
thread the row lanes, NOT through one ``lax.switch``, and nothing reads a
lane after its write-back: handed to an N-way conditional (or read again
by a nested one) every carried lane was copied whole, per branch and per
step, by the chip's compiler — 447 ms of a 2,073 ms round at 10.5M rows
(PERF.md, PR 29; tests/test_tpu_compile.py holds the property).  The
carried histogram cache has the same trap by another way in: a step reads
one row of it (the parent's sums) and writes two (the children's), and a
plain slice is free to be fused into every consumer.  The compiler fused
it a second time into the second row write, so the OLD cache was read
after the first write: it was copied whole before that write and the
second write's result copied back into the carry, 495 ms of a 1,444 ms
round at 255 leaves x 136 features (a 318 MB cache; PERF.md, PR 35).
``subtract`` therefore materialises the parent's row once, behind an
``optimization_barrier``, before either write.

Row payloads travel through the partition as WORD-MAJOR i32 lanes (7
words of bins + 3 words of digits + original row id at 28 features, each
a separate 1-D array, so every slice, operand and write-back is
contiguous).  The partition is two-way on one bit, "goes left": the
window's suffix beyond the segment and every row of a rejected split are
"other" rows, and a stable two-way partition leaves both where they were
(the suffix IS the tail of the window).  The lane packing assumes uint8
bins (max_bin <= 256): ``accepts`` is that rule, and whoever chooses a
grower (models/gbdt.py, parallel/grow.py) asks it here.  EFB-bundled
columns (``bundle``) ride the same lanes: histograms, the cache and the
sibling subtraction stay in COLUMN space, ``[L, C, 9, B]``, which is
where bundling's saving lives; the split member's byte is decoded by its
offset before the threshold compare (``split/decode``), and the search
runs over original features where they lie in the columns
(``find_split/columns``, ops/bundle.py ``find_best_split_columns``).
Bundled or not, the search reads the integer sums (ops/split.py
``find_best_split_sums``) and the children's totals are its record's
two sides, so this grower, its shards under a mesh and the cached
grower of ops/grow.py grow the same trees to the bit.  The layout
itself is this module's business too: others take the lane pad of a
row count from ``lane_pad`` and the padded bin word lanes of a dataset, whole or one block a shard, from
``pack_word_lanes``.

Alternatives measured and rejected on TPU (tools/probe_partition.py;
PERF.md, "Carried over", dead ends): XLA row gathers run ~12-200 ns/row
(lowered per-index), so permutation-only layouts that gather payloads on
demand are 2x SLOWER end-to-end; the
12-operand sort is a comparison sort (11.7 ns a row slot at 16M rows,
half of a round at 10.5M: PERF.md, PR 31) and stays where nothing
measures it: the once-a-tree bagging compaction below.

Per-step bookkeeping (SplitInfo/LeafSplits, serial_tree_learner.cpp:
167-224) lives in three PACKED buffers so a step issues ~12 indexed
device ops instead of ~40 scalar SoA updates (the round-2 ablation's
~36 ms/tree dispatch floor):

  leaf_f32 [L, 8]: best_gain, best_left_g/h/c, total_g/h/c, cur_value
  leaf_i32 [L, 8]: best_feat, best_bin, parent, depth, seg_start, seg_cnt
  node_i32 [L-1, 8]: feature, bin, gain(bits), left, right, value(bits),
                     count  (f32 fields stored bitcast — storage only)

The per-row leaf assignment is NOT maintained per step (the round-2
implementation paid a full-[N] select per split): leaf segments are
contiguous, so it is reconstructed once per tree from (seg_start,
seg_cnt): ``leaf_delta`` selects every position's leaf by comparing the
position with the sorted segment starts, scatters it back to original row
order once and selects the row's value by its leaf (no search, no N-row
table gather).

Outputs are identical to ops/grow.py's serial learner: the same splits,
the same TreeArrays (int histogram sums are order-invariant).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..obs.compile_ledger import instrumented_jit
from ..utils import compile_cache, device
from . import leafhist, partition
from .bundle import column_search, find_best_split_columns
from .grow import GrowParams, TreeArrays
from .split import (K_MIN_SCORE, find_best_split_sums, leaf_output,
                    sums_totals)

# Column layout of the packed per-leaf / per-node state buffers.
_LF = dict(best_gain=0, best_left_g=1, best_left_h=2, best_left_c=3,
           total_g=4, total_h=5, total_c=6, cur_value=7,
           best_right_g=8, best_right_h=9, best_right_c=10)
_LFW = 16                               # the float table's row, padded
_LI = dict(best_feat=0, best_bin=1, parent=2, depth=3, start=4, cnt=5,
           rows=6, best_left_rows=7)    # the last two: sharded growth only
_ND = dict(feature=0, bin=1, gain=2, left=3, right=4, value=5, count=6)


def _size_classes(n: int, smallest: int = 8192):
    """Power-of-two window classes covering [1, n].

    A x4-spaced ladder was tried for compile time and REVERTED: it saved
    no measurable warmup on the installation of the time and cost ~5%
    throughput in window padding (PERF.md, "Carried over").  Warm runs
    hide the compile behind the persistent compilation cache
    (utils/compile_cache.py, applied by every entry point).

    Callers pass the row-BUCKETED N (utils/compile_cache.py
    bucket_rows via models/gbdt.py), so the classes — and with them the
    whole grow program — are shared across nearby dataset sizes."""
    out = []
    s = smallest
    while s < n:
        out.append(s)
        s *= 2
    out.append(s)
    return tuple(out)


def accepts(bins_dtype, column_decode: bool = False) -> bool:
    """Whether the leaf-ordered grower can grow this data: uint8 bins
    (the i32 lanes hold four bin codes a word) without a column decode
    that the lanes do not carry.  The dataset's own EFB layout they do
    carry (``bundle``); a screener's compacted view, which changes from
    period to period, and EFB columns under a learner's mesh (the
    exchange is in feature space) they do not.  Everything else grows on
    ops/grow.py."""
    return bins_dtype == jnp.uint8 and not column_decode


# Mantissa bits an EFB layout's count of bin WORDS keeps on its way up
# the shared ladder (utils/compile_cache.py bucket_rows): whole words up
# to 8, even counts to 16, multiples of 4 to 32 (64 to 128 columns in
# steps of 16), of 8 to 64.
BUNDLE_WORD_BITS = 3


def bundled_shape(columns: int, features: int):
    """``(columns, features)`` of an EFB layout on the device: the
    plan's own, rounded up a ladder.  How many columns a plan makes, and
    how many features keep more than one bin, follow from the rows that
    were drawn: the one-hot cell's table gives 71 to 74 columns from
    seed to seed (PERF.md, PR 36), a fold or a day's refresh of a user's
    table likewise, and every count was a ``train_step`` of its own, 260
    to 310 s of compile where the cache serves a known shape in 35.  So
    the layout is padded as the rows are (``bucket_rows``): columns by
    the bin word, ``BUNDLE_WORD_BITS`` bits kept, features by the rows'
    own rule.  A pad column holds bin 0 on every row and no feature, a
    pad feature lies in no slot and is masked: neither can be split on,
    and the sums of the real ones are untouched.  What it costs is the
    pad words' share of the histogram and partition lanes (PERF.md
    section 6, PR 36: the round at 18 and at 20 words)."""
    words = compile_cache.bucket_rows(-(-columns // 4), BUNDLE_WORD_BITS)
    return 4 * words, compile_cache.bucket_rows(features)


def lane_pad(n: int) -> int:
    """Slots behind the ``n`` rows of every lane: the largest window
    class, so a window that starts at the last row still fits."""
    return _size_classes(max(n, 1))[-1]


def pack_u8_words(x_u8):
    """[N, C] u8 -> tuple of ceil(C/4) [N] i32 word arrays (bit-packed)."""
    n, c = x_u8.shape
    w = -(-c // 4)
    pad = w * 4 - c
    if pad:
        x_u8 = jnp.pad(x_u8, ((0, 0), (0, pad)))
    words = jax.lax.bitcast_convert_type(
        x_u8.reshape(n, w, 4), jnp.int32)               # [N, w]
    return tuple(words[:, i] for i in range(w))


def _word_lanes(bins):
    """[C, N] u8 columns -> ceil(C/4) padded [N + lane_pad] i32 lanes,
    column 4w + b the word's byte b (low byte first: ``pack_u8_words``'
    bitcast of four bytes).  From the COLUMNS, a word four shifted rows
    of them ORed: from the row-major matrix the lanes were strided
    slices of its relayout, 37 MB of code and 70 s of compile at 80
    columns x 12.6M rows for what is 3.5 MB and 3 s here (sandbox
    compile, PR 36; PERF.md section 6)."""
    c, n = bins.shape
    pad = lane_pad(n)
    lanes = []
    for w in range(-(-c // 4)):
        word = bins[4 * w].astype(jnp.uint32)
        for b in range(1, min(4, c - 4 * w)):
            word = word | (bins[4 * w + b].astype(jnp.uint32) << (8 * b))
        lanes.append(jnp.pad(
            jax.lax.bitcast_convert_type(word, jnp.int32), (0, pad)))
    return tuple(lanes)


# module-level, so that boosters over the same shapes share ONE compiled
# program.  (Both programs keep the names earlier versions compiled them
# under: a name is part of the persistent compile cache's key.)
@instrumented_jit(program="pack_words")
def _pack_words_padded(bins):
    return _word_lanes(bins)


def pack_word_lanes(bins, mesh=None):
    """The padded bin word lanes ``grow_tree_ordered`` takes as
    ``bins_words``, from the [C, N] column-major uint8 matrix, once a
    dataset.  With ``mesh`` (rows of ``bins`` in one block a device of
    its first axis) each device packs its own rows and pads them by its
    own ``lane_pad``, so block ``i`` of every returned
    ``[k * (N/k + PAD)]`` lane is what shard ``i`` grows from.  That
    program is this call's own and is released with it (PERF.md, PR 32)."""
    if mesh is None:
        return _pack_words_padded(bins)
    axis = mesh.axis_names[0]

    def pack(cm):
        return jax.shard_map(_word_lanes, mesh=mesh, in_specs=P(None, axis),
                             out_specs=P(axis))(cm)
    return instrumented_jit(pack, program="pack_words")(bins)


def _unpack_words(cols, c: int):
    """tuple of W [P] i32 -> [P, c] u8."""
    stacked = jnp.stack(cols, axis=1)                    # [P, W]
    u8 = jax.lax.bitcast_convert_type(stacked, jnp.uint8)
    return u8.reshape(stacked.shape[0], -1)[:, :c]


def _f2i(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _i2f(x):
    return jax.lax.bitcast_convert_type(x, jnp.float32)


def _row(buf, i, w: int):
    """One row of a packed [R, w] buffer as a [w] vector."""
    return jax.lax.dynamic_slice(buf, (i, 0), (1, w))[0]


def _put_row(buf, i, vec):
    return jax.lax.dynamic_update_slice(buf, vec[None, :], (i, 0))


def sorted_segments(start, cnt, num_leaves, n: int):
    """The live leaves' segments in position order: ``lo[k] <= p <
    hi[k]`` holds for exactly one k at every position p, and
    ``leaf_sorted[k]`` is that segment's leaf.  A leaf that is not grown
    or holds no row sorts to the end with ``lo == hi == n``; the last
    live segment runs to ``n`` (positions past its rows are the
    zero-weight rows of a compacted bag, rerouted by the caller)."""
    leaf_iota = jnp.arange(start.shape[0], dtype=jnp.int32)
    live = (leaf_iota < num_leaves) & (cnt > 0)
    sv = jnp.where(live, start, jnp.int32(n))
    lo, leaf_sorted = jax.lax.sort((sv, leaf_iota), num_keys=1,
                                   is_stable=True)
    hi = jnp.concatenate([lo[1:], jnp.full((1,), n, jnp.int32)])
    return lo, hi, leaf_sorted


def leaf_delta(start, cnt, num_leaves, shrunk, row_ord, n: int):
    """Per-row leaf id and leaf value from the contiguous segments:
    ``(leaf_id, output_delta)``, both ``[n]`` in ORIGINAL row order.

    ``start``, ``cnt`` ``[L]`` int32 are the leaves' segments in position
    space, ``shrunk`` ``[L]`` float32 their values and ``row_ord`` the
    row held at each position.  The segment starts are sorted and the
    positions an iota, so a position's leaf is SELECTED by two compares
    against L numbers that sit in registers (a one-hot over the segments,
    summed), scattered to row order once, and a row's value selected the
    same way by its leaf: the one-hot picks the value's BITS, summed as
    integers with one non-zero term, so every float comes out as it went
    in, ``-0.0`` and infinities too.  Nothing searches and nothing
    gathers n elements from a table: a binary search is a gather of all
    n positions a step, 8.2 ns a row each from a table of 256 entries on
    a v5e (1,186 of a 2,313 ms round at 12.6M rows), where both selects
    together are under 1 ns a row and the scatter 6 to 7 (PERF.md, PR 37;
    tools/probe_leaf_delta.py times the forms).  The ``[L, n]`` compares
    are fused into their reductions and never materialised."""
    with jax.named_scope("leaf_delta"):
        lo, hi, leaf_sorted = sorted_segments(start, cnt, num_leaves, n)
        pos = jnp.arange(n, dtype=jnp.int32)[None, :]
        inside = (lo[:, None] <= pos) & (pos < hi[:, None])
        leaf_of_pos = jnp.sum(
            jnp.where(inside, leaf_sorted[:, None], 0), axis=0)
        # back to ORIGINAL row order: one scatter per tree
        leaf_id = jnp.zeros(n, jnp.int32).at[row_ord[:n]].set(
            leaf_of_pos, unique_indices=True)
        bits = jax.lax.bitcast_convert_type(shrunk, jnp.int32)
        leaves = jnp.arange(shrunk.shape[0], dtype=jnp.int32)
        output_delta = jax.lax.bitcast_convert_type(
            jnp.sum(jnp.where(leaf_id[None, :] == leaves[:, None],
                              bits[:, None], 0), axis=0), jnp.float32)
    return leaf_id, output_delta


@instrumented_jit(program="grow_tree_ordered",
                  static_argnames=("params", "exchange"))
def grow_tree_ordered(bins, num_bin, is_cat, feat_mask, grad, hess,
                      row_weight, learning_rate, params: GrowParams,
                      bins_rm=None, bins_words=None, exchange=None,
                      bundle=None):
    """Drop-in replacement for ops.grow.grow_tree.

    Args/returns: see grow_tree.  ``bins_words`` (tuple of ceil(F/4) [N]
    i32 arrays from pack_u8_words, shared across trees) seeds the physical
    layout, which every histogram reads, the root's too; it is derived
    from ``bins_rm`` ([N, F] row-major) or ``bins`` when omitted.

    N here may be the row-BUCKET shape (models/gbdt.py pads every row
    array up the shared ladder): pad rows carry bin 0, zero digits and
    zero ``row_weight``, so they ride the window partitions inside
    segments without touching any histogram sum or weighted count —
    exactly like bagged-out rows — and ``compact_inactive`` moves them
    behind the active segment together with the bagging zeros.

    ``exchange`` (static; parallel/comm.py ``HistExchange``) makes this
    the data-parallel learner's per-shard program under
    ``jax.shard_map``: every array above is the shard's own row block,
    and only histogram sums and scalars cross chips.  The quantisation
    scales and the root's sums are global; each shard histograms the
    smaller child of ITS OWN segment (never more than half a window,
    whatever the skew between shards), keeps a second, local cache to
    derive its sibling, and hands its left child's sums to one
    all-reduce a split, outside the chain of conds (each shard is in
    its own size class).  The global sums come back in 16-bit halves
    (ops/leafhist.py ``split_halves``: whole int32 sums over all shards
    could wrap); the global cache, the sibling subtraction and the
    split search then run replicated on them, so gains,
    thresholds, counts and ``min_data_in_leaf`` are the serial
    learner's; segment starts and counts, lanes, partitions and the leaf
    reconstruction stay local.  With ``exchange=None`` nothing below
    differs from the serial program.

    ``bundle`` (ops/bundle.py ``BundleDecode`` with its column-space
    tables): ``bins`` is the EFB column matrix ``[C, N]`` while
    ``num_bin``, ``is_cat``, ``feat_mask`` and the tree stay in original
    feature space.  Serial growth only."""
    L = params.num_leaves
    B = params.max_bin
    F, N = bins.shape           # F: COLUMNS (the features, unbundled)
    sp = params.split_params()
    assert bundle is None or exchange is None, \
        "EFB columns under a mesh grow on ops/grow.py"
    if bundle is not None:
        with jax.named_scope("find_split/columns"):
            # what the search needs of this tree, once, outside the loop
            searched = column_search(bundle, num_bin, is_cat, feat_mask)

    # Every operation below sits under exactly one leaf phase of
    # obs/phases.py ROUND_PHASES (the innermost scope wins): the scopes
    # are metadata only, and obs/compile_ledger.py joins them to the
    # chip's device events through the compiled text.
    with jax.named_scope("layout"):
        if bins_words is None:
            bins_words = pack_u8_words(bins.T if bins_rm is None
                                       else bins_rm)

    with jax.named_scope("gradients"):
        g = grad * row_weight
        h = hess * row_weight

    classes = _size_classes(N)
    PAD = classes[-1]          # windows may overrun the last segment
    W = len(bins_words)

    with jax.named_scope("layout"):
        scales = leafhist.compute_scales(g, h, row_weight)
    if exchange is not None:
        with jax.named_scope("gradients"):
            shard_rows = jnp.sum(row_weight)
        scales, root_rows = exchange.root(scales, shard_rows)
    with jax.named_scope("layout"):
        digits = leafhist.quantize_digits(g, h, row_weight,
                                          scales)       # [N, 9] i8

        # callers hand over pack_word_lanes' padded lanes, packed once
        # per dataset; pad here only when handed bare [N] words
        bins_w = tuple(bw if bw.shape[0] >= N + PAD
                       else jnp.pad(bw, (0, N + PAD - bw.shape[0]))
                       for bw in bins_words)
        root_cnt = jnp.int32(N)
        dig_w = tuple(jnp.pad(dw, (0, PAD)) for dw in pack_u8_words(
            jax.lax.bitcast_convert_type(digits, jnp.uint8)))
        DW = len(dig_w)
        if exchange is None and bundle is None:
            row_ord = jnp.pad(jnp.arange(N, dtype=jnp.int32), (0, PAD))
        else:
            # the same lane with the pad numbered on (nothing reads a row
            # id past N): the chip's compiler folds the padded iota into
            # a constant of the lane's size, 109 MB of the executable at
            # 10.5M rows (sandbox compile, PR 30) and 117 of 286 at the
            # one-hot cell's 12.6M (PR 36), more than the machine's
            # compile cache keeps; ROADMAP S5 has the plain serial
            # program's, whose text is left as it is
            row_ord = jnp.arange(N + PAD, dtype=jnp.int32)

    if params.compact_inactive:
        # one stable sort per tree (over the REAL N rows only — the
        # window pad stays put) moves zero-weight rows behind the active
        # segment: every later window, partition, and histogram then
        # costs O(subsample), not O(N) — the reference's bag-subset
        # dataset switch (gbdt.cpp:271-278)
        with jax.named_scope("layout"):
            bag_key = (row_weight <= 0.0).astype(jnp.uint8)
            ops0 = (bag_key,) + tuple(w[:N] for w in bins_w) \
                + tuple(w[:N] for w in dig_w) + (row_ord[:N],)
            sorted0 = jax.lax.sort(ops0, num_keys=1, is_stable=True)

            def _splice(full, head):
                return jax.lax.dynamic_update_slice(full, head, (0,))
            bins_w = tuple(_splice(f, h)
                           for f, h in zip(bins_w, sorted0[1:1 + W]))
            dig_w = tuple(_splice(f, h)
                          for f, h in zip(dig_w,
                                          sorted0[1 + W:1 + W + DW]))
            row_ord = _splice(row_ord, sorted0[-1])
            root_cnt = jnp.sum((row_weight > 0.0).astype(jnp.int32))

    def hist_window(bw_tuple, dw_tuple, off, scnt, Psz: int):
        """[F, 9, B] digit sums over the scnt rows from ``off`` of the
        packed layout, read as ONE Psz-row window with every other row's
        digit streams masked to zero.  The window starts at ``off``, or
        ends with the arrays where ``off + Psz`` would overrun them (a
        partitioned split window; the full lanes carry PAD spare rows).  The
        ONE histogram formulation every call site shares (the root, the
        compacted root and the per-split child windows).  On a TPU the
        kernel takes the window's word lanes as they lie and masks the
        rows itself; nothing row-major is built for it (PERF.md, PR 33)."""
        with jax.named_scope("hist/window"):
            start = jnp.minimum(off, bw_tuple[0].shape[0] - Psz)
            first = off - start
            win_b = tuple(jax.lax.dynamic_slice(bw, (start,), (Psz,))
                          for bw in bw_tuple)
            win_d = tuple(jax.lax.dynamic_slice(dw, (start,), (Psz,))
                          for dw in dw_tuple)
        # the kernels scope themselves (hist/kernel, ops/leafhist.py)
        if device.on_tpu():
            return leafhist.digit_histogram_lanes(win_b, win_d, first, scnt,
                                                  F, B)
        # off the TPU the rows are unpacked for the scatter: the oracle
        with jax.named_scope("hist/window"):
            ch_bins = _unpack_words(win_b, F)
            ch_dig = jax.lax.bitcast_convert_type(
                _unpack_words(win_d, leafhist.NUM_STREAMS), jnp.int8)
            row = jnp.arange(Psz, dtype=jnp.int32)[:, None]
            ch_dig = jnp.where((row >= first) & (row < first + scnt),
                               ch_dig, 0)
        return leafhist.digit_histogram_scatter(ch_bins, ch_dig, B)

    def windowed_hist(off, scnt):
        """hist_window at the size class covering scnt (used by the
        compacted root pass)."""
        hbs = [(lambda P: (lambda args: hist_window(
            bins_w, dig_w, args[0], args[1], P)))(P) for P in classes]
        cls = jnp.minimum(jnp.sum(scnt > jnp.asarray(classes, jnp.int32))
                          .astype(jnp.int32), len(hbs) - 1)
        return jax.lax.switch(cls, hbs, (off, scnt))

    with jax.named_scope("hist/root"):
        if params.compact_inactive:
            # root histogram over the compacted ACTIVE prefix: cost
            # tracks the subsample (inactive rows have zero digits
            # either way)
            sums_root = windowed_hist(jnp.int32(0), root_cnt)
        else:
            # root histogram over the initial (original-order) layout:
            # all N rows as one window of whole kernel steps
            step = leafhist.STEP_ROWS
            sums_root = hist_window(bins_w, dig_w, jnp.int32(0), root_cnt,
                                    -(-N // step) * step)
    if exchange is not None:
        sums_root_local = sums_root
        sums_root = exchange.hist(sums_root, root=True)
    def left_rows(sums, feature, threshold):
        """The split's left count as an integer, from the exchanged sums'
        weight stream (a float32 running count is exact only to 2^24
        rows).  Sharded growth only, where a node holds all shards' rows."""
        f = jnp.maximum(feature, 0)
        col = leafhist.digit_row_counts(jax.lax.dynamic_index_in_dim(
            sums, f, 0, keepdims=False))
        b = jnp.arange(B, dtype=jnp.int32)
        left = jnp.where(is_cat[f], b == threshold, b <= threshold)
        return jnp.sum(jnp.where(left, col, 0))

    def find_split(sums, can):
        """ops/split.py's search from integer sums, over the features as
        they lie: plain columns, or an EFB layout's (ops/bundle.py)."""
        if bundle is None:
            return find_best_split_sums(sums, scales, num_bin, is_cat,
                                        feat_mask, can, sp)
        with jax.named_scope("find_split/columns"):
            return find_best_split_columns(sums, scales, can, sp, searched)

    with jax.named_scope("find_split"):
        # the root's totals from its own integer sums, as every child's
        # are its parent's split record's: a float32 sum of 12M
        # gradients, and a parent's total less a child's, lose what a
        # one-hot split's small side is made of (ops/split.py)
        root_g, root_h, root_c = sums_totals(sums_root, scales)
        root_split = find_split(sums_root, jnp.asarray(True))
        if exchange is not None:
            root_left_rows = left_rows(sums_root, root_split.feature,
                                       root_split.threshold)
    with jax.named_scope("hist/root"):
        cache = jnp.zeros((L,) + sums_root.shape, jnp.int32) \
            .at[0].set(sums_root)
        # a shard's own sums by leaf, beside the global ones (in halves)
        caches = (cache,) if exchange is None else (
            cache, jnp.zeros((L, F, 9, B), jnp.int32)
            .at[0].set(sums_root_local))

    with jax.named_scope("leaf_table"):
        def leaf_floats(split, ci, tot_g, tot_h, tot_c, val):
            """A leaf's row of the float table: child ``ci`` of
            ``split``'s batch (``None``: the root's, unbatched)."""
            at = (lambda x: x) if ci is None else (lambda x: x[ci])
            return jnp.stack(
                [at(split.gain), at(split.left_sum_g), at(split.left_sum_h),
                 at(split.left_count), tot_g, tot_h, tot_c, val,
                 at(split.right_sum_g), at(split.right_sum_h),
                 at(split.right_count)]
                + [jnp.float32(0.0)] * (_LFW - len(_LF)))

        leaf_f32 = jnp.full((L, _LFW), K_MIN_SCORE, jnp.float32) \
            .at[:, 1:].set(0.0).at[0].set(leaf_floats(
                root_split, None, root_g, root_h, root_c, jnp.float32(0.0)))
        root_i32 = jnp.array([0, 0, -1, 0, 0, 0, 0, 0], jnp.int32) \
            .at[_LI["best_feat"]].set(root_split.feature) \
            .at[_LI["best_bin"]].set(root_split.threshold) \
            .at[_LI["cnt"]].set(root_cnt)
        if exchange is not None:
            root_i32 = root_i32.at[_LI["rows"]].set(root_rows) \
                .at[_LI["best_left_rows"]].set(root_left_rows)
        leaf_i32 = jnp.zeros((L, 8), jnp.int32) \
            .at[:, _LI["parent"]].set(-1).at[0].set(root_i32)
        empty_node = jnp.zeros((8,), jnp.int32).at[_ND["feature"]].set(-1)
        node_i32 = jnp.broadcast_to(empty_node, (L - 1, 8))

    def make_branch(P: int):
        def branch(ops):
            # ``feat``: the split feature's COLUMN; ``decode``: its offset
            # and width there, of a bundle's member (else empty)
            (bins_w, dig_w, row_ord, s, c, feat, tbin, cat, do_split,
             *decode) = ops
            # the reference's split phase (serial_tree_learner.cpp:
            # 10-37), divided where its device time divides
            with jax.named_scope("split/window_read"):
                win_b = tuple(jax.lax.dynamic_slice(bw, (s,), (P,))
                              for bw in bins_w)
                win_d = tuple(jax.lax.dynamic_slice(dw, (s,), (P,))
                              for dw in dig_w)
                win_r = jax.lax.dynamic_slice(row_ord, (s,), (P,))

            with jax.named_scope("split/key"):
                word = feat // 4
                byte = feat % 4
                # dynamic word pick as a select chain (a lax.switch here
                # costs 7 branch bodies x 8 size classes of compile time)
                col32 = win_b[0]
                for i in range(1, W):
                    col32 = jnp.where(word == i, win_b[i], col32)
                fcol = (col32 >> (8 * byte)) & 0xFF
            if decode:
                # the member's own bin from its column's byte
                # (ops/bundle.py decode_feature_bins): slots off to
                # off + width - 1 are its bins 1 to width, every other
                # slot its bin 0; off 0 marks an identity column
                with jax.named_scope("split/decode"):
                    off, width = decode
                    mine = (fcol >= off) & (fcol < off + width)
                    fcol = jnp.where(
                        off > 0, jnp.where(mine, fcol - off + 1, 0), fcol)
            with jax.named_scope("split/key"):
                go_r = jnp.where(cat, fcol != tbin, fcol > tbin)
                iota = jnp.arange(P, dtype=jnp.int32)
                inseg = iota < c
                # every other row keeps its order behind the left ones:
                # the segment's right rows, then the suffix (other
                # segments / tail pad), which so stays where it was; a
                # rejected split has no left row (identity permutation)
                is_left = do_split & inseg & ~go_r

            with jax.named_scope("split/sort"):
                parts = partition.stable_partition(
                    win_b + win_d + (win_r,), is_left)
                sb = parts[:W]
                sd = parts[W:W + DW]
                sr = parts[-1]

            with jax.named_scope("split/window_write"):
                bins_w = tuple(jax.lax.dynamic_update_slice(bw, nb, (s,))
                               for bw, nb in zip(bins_w, sb))
                dig_w = tuple(jax.lax.dynamic_update_slice(dw, nd, (s,))
                              for dw, nd in zip(dig_w, sd))
                row_ord = jax.lax.dynamic_update_slice(row_ord, sr, (s,))

            # smaller child's histogram from its CONTIGUOUS slice; pad to
            # P/8 when the child is small enough (splits are often very
            # unbalanced — a fixed P/2 pad wastes up to 4x kernel work).
            # Measured dead ends (PERF.md, "Carried over"): a dynamic-grid
            # packed-word kernel runs 3x slower per row (Mosaic keeps all
            # one-hot temporaries live under a dynamic grid, forcing tiny
            # blocks), so the static size-class structure stays.
            with jax.named_scope("split/key"):
                cnt_r = jnp.sum((go_r & inseg).astype(jnp.int32))
                cnt_l = c - cnt_r
                small_left = cnt_l <= cnt_r
                off = jnp.where(small_left, 0, cnt_l)
                scnt = jnp.minimum(cnt_l, cnt_r)

            def hist_at(Psz):
                # reads the PARTITIONED WINDOW, not the lanes just written:
                # a lane that this nested cond read after its write-back
                # was copied whole around the write by the chip's compiler
                return lambda win: hist_window(*win, off, scnt, Psz)

            P2 = max(P // 2, classes[0] // 2, 4096)
            P8 = max(P // 8, 4096)
            with jax.named_scope("hist/window"):
                if P8 < P2:
                    sums_small = jax.lax.cond(scnt <= P8, hist_at(P8),
                                              hist_at(P2), (sb, sd))
                else:
                    sums_small = hist_at(P2)((sb, sd))
            return bins_w, dig_w, row_ord, cnt_l, small_left, sums_small
        return branch

    branches = [make_branch(P) for P in classes]
    with jax.named_scope("grow_loop"):
        sizes_arr = jnp.asarray(classes, jnp.int32)

    def step(k, carry):
        (num_leaves, stopped, leaf_f32, leaf_i32, node_i32, caches,
         bins_w, dig_w, row_ord) = carry
        gains = leaf_f32[:, _LF["best_gain"]]
        best_leaf = jnp.argmax(gains).astype(jnp.int32)
        gain = gains[best_leaf]
        do_split = jnp.logical_and(~stopped, gain > 0.0)
        stopped = ~do_split
        right_leaf = num_leaves

        rb_f = _row(leaf_f32, best_leaf, _LFW)
        rb_i = _row(leaf_i32, best_leaf, 8)
        rr_f = _row(leaf_f32, right_leaf, _LFW)
        rr_i = _row(leaf_i32, right_leaf, 8)

        feat = jnp.maximum(rb_i[_LI["best_feat"]], 0)
        tbin = rb_i[_LI["best_bin"]]
        s = rb_i[_LI["start"]]
        c = rb_i[_LI["cnt"]]
        depth = rb_i[_LI["depth"]]
        parent_node = rb_i[_LI["parent"]]

        cls = jnp.minimum(jnp.sum(c > sizes_arr).astype(jnp.int32),
                          len(branches) - 1)
        scalars = (s, c, feat, tbin, is_cat[feat], do_split)
        if bundle is not None:
            with jax.named_scope("split/decode"):
                scalars = (s, c, bundle.col[feat], tbin, is_cat[feat],
                           do_split, bundle.off[feat], bundle.width[feat])
        # NOT a lax.switch: under an N-way conditional the chip's compiler
        # copies every lane whole, per branch and per step (module
        # docstring); a chain of two-way conds writes the lanes in place
        st = (bins_w, dig_w, row_ord, jnp.int32(0), jnp.asarray(False),
              jnp.zeros((F, 9, B), jnp.int32))
        for i, branch in enumerate(branches):
            st = jax.lax.cond(
                cls == i,
                lambda st, branch=branch: branch(st[:3] + scalars),
                lambda st: st, st)
        bins_w, dig_w, row_ord, cnt_l, small_left, sums_small = st
        # (what no scope names up to here is the loop's own: grow_loop,
        # entered around the fori_loop below)

        with jax.named_scope("leaf_table"):
            # --- split sums: both sides as the search held them --------
            parent_c = rb_f[_LF["total_c"]]
            left_g = rb_f[_LF["best_left_g"]]
            left_h = rb_f[_LF["best_left_h"]]
            left_c = rb_f[_LF["best_left_c"]]
            right_g = rb_f[_LF["best_right_g"]]
            right_h = rb_f[_LF["best_right_h"]]
            right_c = rb_f[_LF["best_right_c"]]
            left_val = leaf_output(left_g, left_h, sp.lambda_l1,
                                   sp.lambda_l2)
            right_val = leaf_output(right_g, right_h, sp.lambda_l1,
                                    sp.lambda_l2)

            # --- node record + parent child-pointer fixup ---------------
            node = k
            p_safe = jnp.maximum(parent_node, 0)
            rp = _row(node_i32, p_safe, 8)
            was_left = rp[_ND["left"]] == ~best_leaf
            upd_parent = do_split & (parent_node >= 0)
            rp = rp.at[_ND["left"]].set(
                jnp.where(upd_parent & was_left, node, rp[_ND["left"]]))
            rp = rp.at[_ND["right"]].set(
                jnp.where(upd_parent & ~was_left, node, rp[_ND["right"]]))
            node_i32 = _put_row(node_i32, p_safe, rp)
            if exchange is None:
                parent_rows = parent_c.astype(jnp.int32)
                rows_lr = (jnp.int32(0), jnp.int32(0))
            else:
                parent_rows = rb_i[_LI["rows"]]
                rows_lr = (rb_i[_LI["best_left_rows"]],
                           parent_rows - rb_i[_LI["best_left_rows"]])
            new_node = jnp.stack([
                rb_i[_LI["best_feat"]], tbin, _f2i(gain), ~best_leaf,
                ~right_leaf, _f2i(rb_f[_LF["cur_value"]]),
                parent_rows, jnp.int32(0)])
            node_i32 = _put_row(node_i32, node,
                                jnp.where(do_split, new_node, empty_node))

        # --- child histograms via exact sibling subtraction -------------
        def subtract(cache, sums_one, one_is_left):
            """Both children's sums from one child's and the cached
            parent's; the cache rows of the two leaves rewritten.  The
            parent's row is read ONCE, into a buffer of its own, before
            either write: fused again into the second write it kept the
            old cache alive past the first (module docstring)."""
            with jax.named_scope("hist/subtract"):
                sums_parent = jax.lax.optimization_barrier(cache[best_leaf])
                sums_other = sums_parent - sums_one
                sums_left = jnp.where(one_is_left, sums_one, sums_other)
                sums_right = jnp.where(one_is_left, sums_other, sums_one)
                cache = cache.at[best_leaf].set(
                    jnp.where(do_split, sums_left, sums_parent))
                cache = cache.at[right_leaf].set(
                    jnp.where(do_split, sums_right, cache[right_leaf]),
                    mode="drop")
            return cache, sums_left, sums_right

        if exchange is None:
            cache, sums_left, sums_right = subtract(caches[0], sums_small,
                                                    small_left)
            caches = (cache,)
        else:
            # the shard's own children first (small_left is the SHARD's
            # smaller child), then its left child into the one all-reduce
            # of the step; the right one is the global subtraction
            local, own_left, _ = subtract(caches[1], sums_small, small_left)
            cache, sums_left, sums_right = subtract(
                caches[0], exchange.hist(own_left), jnp.asarray(True))
            caches = (cache, local)

        with jax.named_scope("find_split"):
            child_depth_ok = jnp.logical_or(params.max_depth <= 0,
                                            depth + 1 < params.max_depth)
            child_split = find_split(
                jnp.stack([sums_left, sums_right]),
                jnp.stack([do_split & child_depth_ok] * 2))
            best_rows = (jnp.int32(0),) * 2 if exchange is None else tuple(
                left_rows(sums, child_split.feature[ci],
                          child_split.threshold[ci])
                for ci, sums in enumerate((sums_left, sums_right)))

        with jax.named_scope("leaf_table"):
            def leaf_rows(ci, tot_g, tot_h, tot_c, val, seg_s, seg_c):
                f32 = leaf_floats(child_split, ci, tot_g, tot_h, tot_c, val)
                i32 = jnp.stack([
                    child_split.feature[ci], child_split.threshold[ci],
                    node, depth + 1, seg_s, seg_c, rows_lr[ci],
                    best_rows[ci]])
                return f32, i32

            lf, li = leaf_rows(0, left_g, left_h, left_c, left_val, s, cnt_l)
            rf, ri = leaf_rows(1, right_g, right_h, right_c, right_val,
                               s + cnt_l, c - cnt_l)
            leaf_f32 = _put_row(leaf_f32, best_leaf,
                                jnp.where(do_split, lf, rb_f))
            leaf_i32 = _put_row(leaf_i32, best_leaf,
                                jnp.where(do_split, li, rb_i))
            leaf_f32 = _put_row(leaf_f32, right_leaf,
                                jnp.where(do_split, rf, rr_f))
            leaf_i32 = _put_row(leaf_i32, right_leaf,
                                jnp.where(do_split, ri, rr_i))
            num_leaves = num_leaves + jnp.where(do_split, 1, 0)
        return (num_leaves, stopped, leaf_f32, leaf_i32, node_i32, caches,
                bins_w, dig_w, row_ord)

    with jax.named_scope("grow_loop"):
        carry = (jnp.asarray(1, jnp.int32), jnp.asarray(False),
                 leaf_f32, leaf_i32, node_i32, caches, bins_w, dig_w,
                 row_ord)
    with jax.named_scope("grow_loop"):
        (num_leaves, _, leaf_f32, leaf_i32, node_i32, _, _, _, row_ord) = \
            jax.lax.fori_loop(0, L - 1, step, carry)

    with jax.named_scope("leaf_table"):
        shrunk = leaf_f32[:, _LF["cur_value"]] * learning_rate
        tree = TreeArrays(
            num_leaves=num_leaves,
            split_feature=node_i32[:, _ND["feature"]],
            split_bin=node_i32[:, _ND["bin"]],
            split_gain=_i2f(node_i32[:, _ND["gain"]]),
            left_child=node_i32[:, _ND["left"]],
            right_child=node_i32[:, _ND["right"]],
            internal_value=_i2f(node_i32[:, _ND["value"]]),
            internal_count=node_i32[:, _ND["count"]],
            leaf_value=shrunk,
            leaf_count=(leaf_f32[:, _LF["total_c"]].astype(jnp.int32)
                        if exchange is None else leaf_i32[:, _LI["rows"]]),
            leaf_parent=leaf_i32[:, _LI["parent"]],
            leaf_depth=leaf_i32[:, _LI["depth"]],
        )
        seg_start, seg_cnt = leaf_i32[:, _LI["start"]], leaf_i32[:, _LI["cnt"]]

    leaf_id, output_delta = leaf_delta(seg_start, seg_cnt, num_leaves,
                                       shrunk, row_ord, N)

    with jax.named_scope("leaf_delta"):
        if params.compact_inactive:
            # zero-weight rows never entered a segment: route them through
            # the tree like the reference's out-of-bag AddPredictionToScore
            # (gbdt.cpp UpdateScore; cost ~ actual tree depth via the
            # while walk in ops/predict.py)
            from .predict import predict_binned_tree
            pval, pleaf = predict_binned_tree(
                tree.split_feature, tree.split_bin,
                is_cat[jnp.maximum(tree.split_feature, 0)],
                tree.left_child, tree.right_child, shrunk, bins, L,
                bundle=bundle)
            active = row_weight > 0.0
            leaf_id = jnp.where(active, leaf_id, pleaf)
            output_delta = jnp.where(active, output_delta, pval)
    return tree, leaf_id, output_delta
