"""Leaf-proportional histogram construction with exact integer accumulation.

This is the TPU replacement for the reference's two core histogram tricks
(serial_tree_learner.cpp:398-453): build the histogram of only the *smaller*
child of each split over only that child's rows, and derive the sibling by
subtracting from the cached parent histogram (FeatureHistogram::Subtract,
feature_histogram.hpp:62-68; cache = HistogramPool, :299-455).  Histogram
cost per tree becomes O(N * depth) instead of the O(N * num_leaves) of a
full-data pass per split.

TPU-shaped design, three pieces:

1. **Fixed-point quantization** (`quantize_digits`): per-tree scales map
   gradient / hessian / weight to 24-bit fixed point, decomposed into three
   balanced radix-256 int8 digits.  The histogram kernel then accumulates
   int8 x int8(one-hot) products into int32 — *exact* integer arithmetic,
   so the parent-minus-child subtraction is exact at any data scale.  This
   replaces the reference's double-precision HistogramBinEntry accumulators
   (bin.h:25-27): where f64 merely shrinks subtraction error, int32 sums
   eliminate it.  Quantization error (half a step of scale * 2^-22 per row)
   is of the same order as f32 input rounding.  Digit sums stay exact while
   128 * rows_per_shard < 2^31, i.e. ~16M rows per device shard.

2. **MXU one-hot kernels**, one for each layout the growers hold rows
   in; which one runs follows from the grower (models/gbdt.py
   ``_choose_grower``), never from an option.

   `_lanes_hist_kernel` (`digit_histogram_lanes`; the leaf-ordered
   grower, ops/ordered_grow.py): the window's WORD lanes as they lie,
   four bin codes or four digits an int32, each lane ``[P]`` read as
   ``[P / 128, 128]`` (a bitcast).  Rows run along lanes and bins along
   sublanes: the one-hot of the four features of a word comes from one
   XOR of the word, broadcast along sublanes, with the bin index in every
   byte, an exact zero-byte test, and the int32 block read as int8
   ``[4 B, T]``; it is contracted with the nine digit planes over the
   rows (``A x B^T`` in int8, int32 sums), so nothing is transposed and
   nothing row-major is built in HBM.  The segment comes as two
   prefetched scalars: a grid step that holds none of its rows does no
   work, the steps at its two ends mask the rest.  2.6 ns a row slot at 28
   features and 255 bins on a v5e (PERF.md, PR 33), in a loop whose code
   does not grow with the window.

   `_digit_hist_kernel` (`digit_histogram_pallas`; the gathered rows of
   the cached grower below, row-major by construction): bins stream from
   HBM in ROW-major uint8, the one-hot is a compare against a lane iota
   per feature, 7.5 ns a row.  Until PR 33 the leaf-ordered grower fed it
   too, through an XLA relayout of its lanes at every split (4.3 ns a
   row slot more).  An earlier note here said that a feature-major
   layout "forces a lane->sublane relayout that dominates runtime": that
   was measured on a kernel whose BINS lie along lanes; with the bins
   along sublanes the broadcast is the cheap one and no relayout is left.

3. **Compaction + size-class dispatch** (`compact_rows`, `leaf_histogram`;
   the `cached` grower of ops/grow.py only): the smaller child's row
   indices are compacted with one stable key/payload sort (selected rows
   first — see compact_rows for why sort beats scatter on TPU), its rows
   gathered, and the row-major kernel run at a power-of-two padded size
   chosen by `lax.switch` over static size classes — fixed shapes for
   XLA, work proportional to the leaf.

The scatter-add fallback (`digit_histogram_scatter`) keeps every piece
runnable (and testable) on CPU with identical integer semantics.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import device

# 24-bit fixed point: values quantized to round(x / scale * 2^QBITS),
# |q| <= 2^QBITS, decomposed into 3 balanced radix-256 int8 digits.
QBITS = 22
_DIGIT_W = (65536.0, 256.0, 1.0)
NUM_STREAMS = 9  # 3 values (g, h, w) x 3 digits


def compute_scales(g, h, w):
    """Per-tree quantization scales [3] f32 (max |value| per stream)."""
    return jnp.stack([
        jnp.maximum(jnp.max(jnp.abs(g)), 1e-30),
        jnp.maximum(jnp.max(jnp.abs(h)), 1e-30),
        jnp.maximum(jnp.max(jnp.abs(w)), 1e-30),
    ])


def quantize_digits(g, h, w, scales):
    """[N, 9] int8 balanced radix-256 digits of the 24-bit fixed-point
    g/h/w.  Digit order: (g2, g1, g0, h2, h1, h0, w2, w1, w0) with weights
    (65536, 256, 1); value = digits . weights * scale / 2^QBITS."""
    vals = jnp.stack([g, h, w])                       # [3, N]
    q = jnp.round(vals / scales[:, None]
                  * float(1 << QBITS)).astype(jnp.int32)
    d0 = ((q + 128) % 256) - 128                      # balanced low digit
    q1 = (q - d0) // 256
    d1 = ((q1 + 128) % 256) - 128
    d2 = (q1 - d1) // 256                             # |d2| <= 65
    digits = jnp.stack([d2, d1, d0], axis=1)          # [3, 3, N]
    return digits.reshape(9, -1).T.astype(jnp.int8)   # [N, 9]


def split_halves(sums_i32):
    """int32 digit sums [..., 9, B] -> [..., 18, B]: the high 16 bits of
    every sum (signed) in streams 0..8, the low 16 bits in 9..17.  A sum
    of halves over any number of shards stays far inside int32 where the
    sum of whole int32 sums would wrap (2^31 / 128 rows of one bin), and
    ``value = high * 65536 + low`` stays linear, so the cached parent's
    halves minus a child's are the sibling's."""
    return jnp.concatenate([sums_i32 >> 16, sums_i32 & 0xFFFF], axis=-2)


def digit_row_counts(halves_i32):
    """Whole rows per bin, int32 [..., B], from the weight stream of
    ``split_halves`` sums whose row weights are 0 or 1: a row of weight 1
    at scale 1 is 2^QBITS, all of it in the high digit (64).  float32
    holds a count only to 2^24 rows; this one is exact to 2^31."""
    unit = 1 << (QBITS - 16)
    return (halves_i32[..., 6, :] * (65536 // unit)
            + halves_i32[..., NUM_STREAMS + 6, :] // unit)


def canonical_halves(halves_i32):
    """``split_halves`` sums [..., 18, B] with every low half carried
    back into [0, 65536) (whole sums [..., 9, B] pass through).  Sums
    and differences of halves leave the low ones anywhere in int32, and
    ``combine_digit_sums``' one rounding holds only while they stay
    under 2^24: the difference of two sets of halves does, a prefix sum
    over 255 bins of four shards' does not."""
    if halves_i32.shape[-2] != 2 * NUM_STREAMS:
        return halves_i32
    high = halves_i32[..., :NUM_STREAMS, :]
    low = halves_i32[..., NUM_STREAMS:, :]
    return jnp.concatenate([high + (low >> 16), low & 0xFFFF], axis=-2)


def combine_digit_streams(sums_i32, scales):
    """int32 digit sums [..., 9, B], or their ``split_halves``
    [..., 18, B], -> the three f32 streams (g, h, count), each [..., B].

    Exact up to one f32 rounding per entry: the digit sums themselves are
    exact integers (of halves, both terms are exact floats and their sum
    rounds once, to what the whole integer would)."""
    s = sums_i32.astype(jnp.float32)
    if sums_i32.shape[-2] == 2 * NUM_STREAMS:
        s = s[..., :NUM_STREAMS, :] * 65536.0 + s[..., NUM_STREAMS:, :]
    out = []
    for v in range(3):
        acc = (s[..., 3 * v, :] * _DIGIT_W[0]
               + s[..., 3 * v + 1, :] * _DIGIT_W[1]
               + s[..., 3 * v + 2, :] * _DIGIT_W[2])
        out.append(acc * (scales[v] / float(1 << QBITS)))
    return tuple(out)


def combine_digit_sums(sums_i32, scales):
    """``combine_digit_streams`` as one f32 histogram [..., B, 3]."""
    return jnp.stack(combine_digit_streams(sums_i32, scales), axis=-1)


# ---------------------------------------------------------------------------
# Pallas kernel: int8 digit histogram over row-major bins
# ---------------------------------------------------------------------------

def _digit_hist_kernel(bins_ref, dig_ref, out_ref, acc_ref, *, nb, f_blk, bb):
    """Grid (row_blocks,): acc[f] += digits_blk^T-contracted one-hot.

    bins_ref: [nb, f_blk] uint8/uint16 row-major block.
    dig_ref:  [nb, 9] int8.
    out/acc:  [f_blk, 9, bb] int32.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    dig = dig_ref[:, :]                                    # [nb, 9] i8
    iota = jax.lax.broadcasted_iota(jnp.int32, (nb, bb), 1)
    for f in range(f_blk):
        b_f = bins_ref[:, f].astype(jnp.int32)[:, None]    # [nb, 1]
        onehot = (b_f == iota).astype(jnp.int8)            # [nb, bb]
        # [9, bb] int32 = exact int8 x int8 MXU contraction over rows
        part = jax.lax.dot_general(
            dig, onehot, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        acc_ref[f] += part

    @pl.when(i == pl.num_programs(0) - 1)
    def _flush():
        out_ref[:] = acc_ref[:]


def digit_histogram_pallas(bins_rm, digits, max_bin: int, n_blk: int = 8192,
                           interpret: bool = False):
    """[F, 9, B] int32 digit sums over ALL rows of bins_rm.

    bins_rm: [S, F] uint8/uint16 row-major (S must be a multiple of n_blk
    after internal padding); digits: [S, 9] int8 (pad rows must be zero).
    """
    S, F = bins_rm.shape
    B = -(-max_bin // 128) * 128
    nb = min(n_blk, S) if S % n_blk else n_blk
    with jax.named_scope("hist/kernel"):
        if S % nb:
            pad = (-S) % nb
            bins_rm = jnp.pad(bins_rm, ((0, pad), (0, 0)))
            digits = jnp.pad(digits, ((0, pad), (0, 0)))
            S += pad
        out = pl.pallas_call(
            functools.partial(_digit_hist_kernel, nb=nb, f_blk=F, bb=B),
            grid=(S // nb,),
            in_specs=[pl.BlockSpec((nb, F), lambda i: (i, 0)),
                      pl.BlockSpec((nb, 9), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((F, 9, B), lambda i: (0, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((F, 9, B), jnp.int32),
            scratch_shapes=[pltpu.VMEM((F, 9, B), jnp.int32)],
            interpret=interpret,
            # a device event of the kernel reads %digit_histogram.N
            # whatever function or lax.switch branch traced the call
            # (obs/devtrace.py; benchmarks hist_ms_per_round)
            name="digit_histogram",
        )(bins_rm, digits)
        return out[:, :, :max_bin]


# ---------------------------------------------------------------------------
# Pallas kernel: the same sums over the leaf-ordered layout's word lanes
# ---------------------------------------------------------------------------

LANE = 128              # rows of a sub-block: one row of a [P / 128, 128] lane
SUB_BLOCKS = 8          # sub-blocks side by side in one contraction
STEP_ROWS = 8192        # rows of a grid step
PLANES = 16             # the 9 digit planes in whole int32 sublane tiles


def _lanes_hist_kernel(seg_ref, *refs, w, bb, nsub):
    """Grid (row blocks,): out[word] += planes x onehot(word)^T, a group
    of ``SUB_BLOCKS`` sub-blocks of 128 rows at a time.

    seg_ref   SMEM [2]            first row of the segment, its row count
    w bin lane refs [nsub, 128]   four bin codes a word, as the lane lies
    3 digit lane refs [nsub, 128] the nine int8 digits in three words
    out_ref   [w, 16, 4 * bb]     int32, resident across the grid: column
                                  ``4 * bin + k`` of word lane ``i`` is bin
                                  ``bin`` of feature ``4 * i + k``
    """
    bin_refs, dig_refs, out_ref = refs[:w], refs[w:w + 3], refs[w + 3]
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    first = seg_ref[0]
    stop = first + seg_ref[1]
    rows = nsub * LANE

    # a step that holds no row of the segment does no work (its blocks
    # are still fetched: the index maps stay plain, since a map that
    # computes is lowered once a lane); one body, the mask below trims
    # the steps at the segment's two ends
    @pl.when((i * rows < stop) & ((i + 1) * rows > first))
    def _step():
        k = SUB_BLOCKS * LANE
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
        plane = jax.lax.broadcasted_iota(jnp.int32, (PLANES, k), 0)
        # the bin index in every byte of a word, bins along sublanes
        bins4 = jax.lax.broadcasted_iota(jnp.int32, (bb, k), 0) * 0x01010101

        def rows_of(ref, j0):
            # rows along lanes: row r of the step is element (r // 128,
            # r % 128) of a lane's block, so a sub-block is one row of it
            return jnp.concatenate(
                [ref[pl.ds(j0 + u, 1), :] for u in range(SUB_BLOCKS)], axis=1)

        def group(g, carry):
            j0 = g * SUB_BLOCKS
            row = (i * nsub + j0) * LANE + lane
            inside = (row >= first) & (row < stop)
            d = [jnp.where(inside, rows_of(ref, j0), 0) for ref in dig_refs]
            # digit k is byte k % 4 of word k // 4; the truncation to int8
            # gives the signed digit back.  Planes 12 to 15 repeat word 2
            # and planes 9 to 11 are its empty bytes: their sums are never
            # read
            word = jnp.where(plane < 4, d[0], jnp.where(plane < 8, d[1], d[2]))
            planes = (word >> ((plane & 3) * 8)).astype(jnp.int8)  # [16, k]
            for wi, ref in enumerate(bin_refs):
                # four features' one-hots at once: a byte of ``t`` is zero
                # where that feature's bin is the sublane's, and the exact
                # zero-byte test leaves 0x01 there and 0x00 elsewhere (no
                # carry crosses a byte: 0x7F + 0x7F < 0x100)
                t = bins4 ^ rows_of(ref, j0)                        # [bb, k]
                y = (t & 0x7F7F7F7F) + 0x7F7F7F7F
                y = ~(y | t | 0x7F7F7F7F)
                onehot = pltpu.bitcast(jax.lax.shift_right_logical(y, 7),
                                       jnp.int8)                # [4 bb, k]
                # row 4 * bin + byte of the one-hot is byte ``byte`` of
                # the word in row ``bin``; contracted over the lanes of
                # both operands (A x B^T), so nothing is transposed
                out_ref[wi] += jax.lax.dot_general(
                    planes, onehot, dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.int32)           # [16, 4 bb]
            return carry
        # a loop, not an unrolled step: the code of an instance does not
        # grow with its rows (the grow program holds two dozen instances)
        jax.lax.fori_loop(0, nsub // SUB_BLOCKS, group, 0)


def digit_histogram_lanes(bin_lanes, dig_lanes, first, scnt, num_features: int,
                          max_bin: int, step_rows: int = STEP_ROWS,
                          interpret: bool = False):
    """[F, 9, B] int32 digit sums over rows ``[first, first + scnt)`` of
    a window of the leaf-ordered layout, read as its word lanes lie.

    bin_lanes: ceil(F / 4) [P] int32 lanes, four bin codes a word
    (ops/ordered_grow.py ``pack_u8_words``); dig_lanes: the 3 [P] int32
    lanes of the nine int8 digits.  P a multiple of ``step_rows``, or a
    smaller multiple of 1,024.  Only the grid steps that hold a row of
    the segment do work, and they zero the digits of every row outside
    it, so the time goes with the segment rounded up to whole steps (the
    other steps still fetch their blocks) and the sums with the
    segment."""
    w = len(bin_lanes)
    rows = bin_lanes[0].shape[0]
    nsub = min(step_rows, rows) // LANE
    assert w == -(-num_features // 4) and len(dig_lanes) == 3 \
        and rows % (nsub * LANE) == 0 and nsub % SUB_BLOCKS == 0, \
        (w, num_features, rows, nsub)
    B = -(-max_bin // 128) * 128
    seg = jnp.stack([first, scnt]).astype(jnp.int32)
    with jax.named_scope("hist/kernel"):
        out = pl.pallas_call(
            functools.partial(_lanes_hist_kernel, w=w, bb=B, nsub=nsub),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(rows // (nsub * LANE),),
                in_specs=[pl.BlockSpec((nsub, LANE),
                                       lambda i, seg: (i, 0))] * (w + 3),
                out_specs=pl.BlockSpec((w, PLANES, 4 * B),
                                       lambda i, seg: (0, 0, 0))),
            out_shape=jax.ShapeDtypeStruct((w, PLANES, 4 * B), jnp.int32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            # the benchmark's hist_ms_per_round and hist_roofline find
            # both histogram kernels by this name and by nothing else
            name="digit_histogram",
        )(seg, *(lane.reshape(rows // LANE, LANE)
                 for lane in tuple(bin_lanes) + tuple(dig_lanes)))
        out = out.reshape(w, PLANES, B, 4).transpose(0, 3, 1, 2) \
            .reshape(4 * w, PLANES, B)
        return out[:num_features, :NUM_STREAMS, :max_bin]


def digit_histogram_scatter(bins_rm, digits, max_bin: int):
    """CPU fallback with identical integer semantics: one scatter-add keyed
    by (feature, bin) accumulating the 9 digit streams in int32."""
    S, F = bins_rm.shape
    B = max_bin
    with jax.named_scope("hist/kernel"):
        feat = jnp.arange(F, dtype=jnp.int32)[None, :]         # [1, F]
        seg = feat * B + bins_rm.astype(jnp.int32)             # [S, F]
        out = jnp.zeros((F * B, 9), jnp.int32)
        vals = jnp.broadcast_to(digits.astype(jnp.int32)[:, None, :],
                                (S, F, 9)).reshape(-1, 9)
        out = out.at[seg.reshape(-1)].add(vals, mode="drop")
        return out.reshape(F, B, 9).transpose(0, 2, 1)         # [F, 9, B]


def digit_histogram(bins_rm, digits, max_bin: int):
    """Platform dispatcher for the all-rows digit histogram."""
    if device.on_tpu():
        return digit_histogram_pallas(bins_rm, digits, max_bin)
    return digit_histogram_scatter(bins_rm, digits, max_bin)


# ---------------------------------------------------------------------------
# Compaction + size-class dispatch
# ---------------------------------------------------------------------------

def size_classes(num_data: int, min_size: int = 8192) -> Sequence[int]:
    """Static power-of-two compaction sizes covering [1, ceil(N/2)]."""
    top = max(num_data + 1, 2) // 2
    smax = 1
    while smax < top:
        smax *= 2
    sizes = []
    s = min(min_size, smax)
    while s < smax:
        sizes.append(s)
        s *= 2
    sizes.append(smax)
    return tuple(sizes)


def compact_rows(mask, size: int):
    """Indices of the up-to-`size` True rows of mask, padded arbitrarily.

    Returns (idx [size] i32, valid [size] bool).  Implemented as a stable
    key/payload sort (selected rows first): XLA's TPU sort runs this ~4x
    faster than the equivalent 1M-update scatter, which lowers to a
    serialized loop (measured 1.7ms vs 6.3ms per call at N=1M in the grow
    loop — the scatter was the single largest cost of the cached learner)."""
    n = mask.shape[0]
    cnt = jnp.sum(mask.astype(jnp.int32))
    key = (~mask).astype(jnp.uint8)
    _, idx_sorted = jax.lax.sort(
        (key, jnp.arange(n, dtype=jnp.int32)), num_keys=1, is_stable=True)
    idx = jax.lax.slice(idx_sorted, (0,), (size,))
    valid = jnp.arange(size, dtype=jnp.int32) < cnt
    return idx, valid


def leaf_histogram(bins_rm, digits, mask, count, max_bin: int,
                   classes: Sequence[int]):
    """[F, 9, B] int32 digit sums over the rows selected by `mask`,
    dispatched over static size classes so cost tracks the leaf size.

    `count` must equal sum(mask) (precomputed by the caller, which already
    has it from the partition step)."""
    B = max_bin
    F = bins_rm.shape[1]

    def make_branch(size):
        def branch(operands):
            bins_rm, digits, mask = operands
            idx, valid = compact_rows(mask, size)
            gathered_bins = jnp.take(bins_rm, idx, axis=0)      # [size, F]
            gathered_dig = jnp.take(digits, idx, axis=0)        # [size, 9]
            gathered_dig = jnp.where(valid[:, None], gathered_dig, 0)
            if device.on_tpu():
                return digit_histogram_pallas(gathered_bins, gathered_dig, B)
            return digit_histogram_scatter(gathered_bins, gathered_dig, B)
        return branch

    branches = [make_branch(s) for s in classes]
    if len(branches) == 1:
        return branches[0]((bins_rm, digits, mask))
    sizes_arr = jnp.asarray(classes, jnp.int32)
    cls = jnp.sum(count > sizes_arr).astype(jnp.int32)
    cls = jnp.minimum(cls, len(branches) - 1)
    return jax.lax.switch(cls, branches, (bins_rm, digits, mask))
