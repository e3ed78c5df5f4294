"""Pallas TPU kernels for histogram construction and fused split gain.

The reference's hottest loop is the per-leaf gather + scalar accumulate
(dense_bin.hpp:65-133).  XLA's scatter-add lowers to a serial loop on TPU
(~300ms per pass at 1M x 28 x 256) and the XLA one-hot einsum materializes
the one-hot in HBM (~110ms).  These kernels generate the one-hot comparison
matrix *in VMEM* (never touching HBM) and feed the MXU directly:

  for each (row-block, feature):
      onehot = (bins[f, blk] == iota(B))            # VMEM, exact 0/1
      acc[f] += vals^T @ onehot                     # [6, B] MXU dot

HBM traffic per pass is just bins (int8) + grad/hess/leaf_id — about
35 bytes/row at F=28 — instead of the 4*F*B-byte one-hot.

vals packs BOTH children of the split leaf (left g/h/count, right
g/h/count), so one pass yields the two histograms the growth step needs
— the reference's smaller-child + subtraction dance is not needed.

Two epilogues share that accumulation:

- ``children_histograms_pallas`` writes the [2, F, B, 3] histograms out
  (the round-5 behavior), for callers that need the tensors themselves
  (the leaf-cache subtraction dance, distributed histogram reduces).
- ``fused_children_split_candidates_pallas`` runs the per-feature
  split-gain scan (ops/split.py ``per_feature_scan`` — the SAME code,
  traced inside the kernel) over the accumulator while it is still in
  VMEM and emits only the [2, F, 8] per-feature ``BestSplit`` candidates.
  The [2, F, B, 3] histogram never exists in HBM, and the downstream
  program shrinks to the across-features argmax
  (split.py ``combine_feature_candidates``).

Row padding rides the shared shape ladder (utils/compile_cache.py
``bucket_rows``) instead of the bare ``(-N) % n_blk`` round-up, so every
distinct row count no longer compiles a fresh kernel — nearby N share
one padded shape, in-process and across runs via the persistent compile
cache.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.compile_ledger import instrumented_jit
from ..utils.compile_cache import bucket_rows
from .split import SplitParams, per_feature_scan


# What the TPU compiler (jax 0.9.0 Pallas lowering) says to
# ``fused_children_split_candidates_pallas``: the ``jnp.cumsum`` of
# split.py's ``per_feature_scan``, traced inside the kernel, has no
# lowering.  GBDT refuses ``serial_grow=fused`` on a TPU with these words
# (models/gbdt.py); tests/test_tpu_compile.py pins that they stay true.
FUSED_GAIN_TPU_REFUSAL = ("Unimplemented primitive in Pallas TPU lowering "
                          "for KernelType.TC: cumsum")


def _padded_rows(n: int, n_blk: int) -> int:
    """Rows padded up the SHARED bucket ladder, then to a whole number
    of kernel blocks — so the padded shape is common to every row count
    in the bucket, not unique to this N.

    Deliberately independent of the ``row_buckets`` config param: that
    switch governs the TRAINING-STATE shapes callers see; this pad is
    kernel-internal (outputs are cropped, always correct) and replaces
    the old ``(-N) % n_blk`` round-up that made every distinct row
    count a fresh kernel compile.  Cost vs the old round-up is at most
    the ladder's pad bound on top of block rounding."""
    return -(-max(bucket_rows(n), 1) // n_blk) * n_blk


def _accumulate_block(parent_ref, right_ref, bins_ref, g_ref, h_ref, w_ref,
                      leaf_ref, acc_ref, *, max_bin, f_blk, n_blk):
    """One grid step of the shared histogram accumulation: fold this row
    block's per-feature one-hot MXU products into acc ([F, 6, B] VMEM)."""
    parent = parent_ref[0]
    right = right_ref[0]
    leaf = leaf_ref[0, :]                                   # [n_blk] i32
    is_l = (leaf == parent).astype(jnp.float32)
    is_r = (leaf == right).astype(jnp.float32)
    g = g_ref[0, :]
    h = h_ref[0, :]
    w = w_ref[0, :]
    # [6, n_blk]: left g/h/w then right g/h/w
    vals = jnp.stack([g * is_l, h * is_l, w * is_l,
                      g * is_r, h * is_r, w * is_r])

    bins_blk = bins_ref[:, :]                               # [f_blk, n_blk]
    iota = jax.lax.broadcasted_iota(jnp.int32, (n_blk, max_bin), 1)
    for f in range(f_blk):
        b_f = jax.lax.broadcast_in_dim(bins_blk[f], (n_blk, max_bin), (0,))
        onehot = (b_f == iota).astype(jnp.float32)
        # HIGHEST keeps the MXU pass in f32: bf16 rounding of gradients
        # would leak ~1e-2 relative error into split gains.
        part = jax.lax.dot_general(
            vals, onehot, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)            # [6, B]
        acc_ref[f] += part


def _hist_kernel(parent_ref, right_ref, bins_ref, g_ref, h_ref, w_ref,
                 leaf_ref, out_ref, acc_ref, *, max_bin, f_blk, n_blk):
    """Grid: (row_blocks,).  Accumulates [2, F, B, 3] into acc (VMEM)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    _accumulate_block(parent_ref, right_ref, bins_ref, g_ref, h_ref, w_ref,
                      leaf_ref, acc_ref, max_bin=max_bin, f_blk=f_blk,
                      n_blk=n_blk)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        out_ref[:] = acc_ref[:]


def _fused_split_kernel(parent_ref, right_ref, totals_ref, bins_ref, g_ref,
                        h_ref, w_ref, leaf_ref, nb_ref, cat_ref, fm_ref,
                        out_ref, acc_ref, *, max_bin, crop, f_blk, n_blk,
                        sp: SplitParams):
    """Same accumulation as ``_hist_kernel``; the FINAL ``pl.when``
    epilogue feeds the still-in-VMEM accumulator straight into the
    per-feature split-gain scan and writes only [2, F, 8] candidates
    (gain, threshold, left_g, left_h, left_c, 3 pad lanes)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    _accumulate_block(parent_ref, right_ref, bins_ref, g_ref, h_ref, w_ref,
                      leaf_ref, acc_ref, max_bin=max_bin, f_blk=f_blk,
                      n_blk=n_blk)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        acc = acc_ref[:]                                    # [F, 6, B]
        num_bin = nb_ref[0, :]                              # [F] i32
        is_cat = cat_ref[0, :] != 0
        feat_mask = fm_ref[0, :] != 0
        iota_b = jax.lax.broadcasted_iota(jnp.int32, (f_blk, crop), 1)
        for c in (0, 1):
            # CROP to the real bin count before the scan — the histogram
            # path scans [.., max_bin, 3] too, and XLA's cumsum may
            # associate differently for a different length, which would
            # cost the bit-parity with find_best_split
            hist = jnp.stack([acc[:, 3 * c + 0, :crop],
                              acc[:, 3 * c + 1, :crop],
                              acc[:, 3 * c + 2, :crop]], axis=-1)
            tg = totals_ref[c, 0]
            th = totals_ref[c, 1]
            tc = totals_ref[c, 2]
            # the EXACT per_feature_scan from ops/split.py, traced in
            # kernel: bit-parity with find_best_split by construction
            fbg, fbt, lg, lh, lc = per_feature_scan(
                hist, tg, th, tc, num_bin, is_cat, feat_mask, sp)

            sel = iota_b == fbt[:, None]

            def pick(arr):
                # single-element masked sum == gather at fbt (exact: one
                # nonzero addend among true zeros)
                return jnp.sum(jnp.where(sel, arr, 0.0), axis=-1)

            zeros = jnp.zeros_like(fbg)
            out_ref[c] = jnp.stack(
                [fbg, fbt.astype(jnp.float32), pick(lg), pick(lh), pick(lc),
                 zeros, zeros, zeros], axis=-1)              # [F, 8]


def _pad_row_inputs(bins, grad, hess, weight, leaf_id, n_blk: int):
    """Shared row padding for both kernels: bucket-laddered shapes."""
    F, N = bins.shape
    pad = _padded_rows(N, n_blk) - N
    if pad:
        bins = jnp.pad(bins, ((0, 0), (0, pad)))
        grad = jnp.pad(grad, (0, pad))
        hess = jnp.pad(hess, (0, pad))
        weight = jnp.pad(weight, (0, pad))
        leaf_id = jnp.pad(leaf_id, (0, pad), constant_values=-1)
    return bins, grad, hess, weight, leaf_id, N + pad


@instrumented_jit(program="pallas_children_hist",
                  static_argnames=("max_bin", "n_blk", "interpret"))
def children_histograms_pallas(bins, grad, hess, weight, leaf_id,
                               parent_leaf, right_leaf, max_bin: int,
                               n_blk: int = 2048, interpret: bool = False):
    """[2, F, B, 3] child histograms via the Pallas MXU kernel.

    Args mirror ops.histogram.build_children_histograms; bins may be any
    int dtype (converted to int32 lanes for the VMEM compare).
    ``interpret=True`` runs the kernel in the Pallas interpreter so the
    TPU path is testable on CPU.
    """
    F, N = bins.shape
    B = -(-max_bin // 128) * 128  # pad bins to a full lane multiple
    bins, grad, hess, weight, leaf_id, Np = _pad_row_inputs(
        bins, grad, hess, weight, leaf_id, n_blk)
    nblocks = Np // n_blk

    bins = bins.astype(jnp.int32)
    grid = (nblocks,)
    out = pl.pallas_call(
        functools.partial(_hist_kernel, max_bin=B, f_blk=F, n_blk=n_blk),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),          # parent
            pl.BlockSpec(memory_space=pltpu.SMEM),          # right
            pl.BlockSpec((F, n_blk), lambda i: (0, i)),     # bins
            pl.BlockSpec((1, n_blk), lambda i: (0, i)),     # g
            pl.BlockSpec((1, n_blk), lambda i: (0, i)),     # h
            pl.BlockSpec((1, n_blk), lambda i: (0, i)),     # w
            pl.BlockSpec((1, n_blk), lambda i: (0, i)),     # leaf
        ],
        out_specs=pl.BlockSpec((F, 6, B), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((F, 6, B), jnp.float32),
        scratch_shapes=[pltpu.VMEM((F, 6, B), jnp.float32)],
        interpret=interpret,
        # a device event of the kernel reads %children_histograms.N
        # whatever function traced the call (obs/devtrace.py)
        name="children_histograms",
    )(jnp.asarray([parent_leaf], jnp.int32),
      jnp.asarray([right_leaf], jnp.int32),
      bins, grad[None], hess[None], weight[None],
      leaf_id.astype(jnp.int32)[None])

    # [F, 6, B] -> [2, F, B, 3], cropped back to max_bin
    out = out.reshape(F, 2, 3, B)
    return out.transpose(1, 0, 3, 2)[:, :, :max_bin, :]


@instrumented_jit(program="pallas_fused_gain",
                  static_argnames=("max_bin", "params", "n_blk",
                                   "interpret"))
def fused_children_split_candidates_pallas(
        bins, grad, hess, weight, leaf_id, parent_leaf, right_leaf,
        totals, num_bin, is_cat, feat_mask, max_bin: int,
        params: SplitParams, n_blk: int = 2048, interpret: bool = False):
    """Fused histogram -> per-feature split gain, one kernel.

    Args as ``children_histograms_pallas`` plus:
      totals: [2, 3] f32 — (sum_g, sum_h, count) of the left and right
        child (the globally-reduced leaf totals, NOT re-derived from the
        histogram, matching find_best_split's contract).
      num_bin/is_cat/feat_mask: [F] per-feature metadata.
      params: static SplitParams (constraint scalars baked into the
        kernel).
    Returns raw [2, F, 8] f32 candidates: lanes 0..4 are (gain,
    threshold, left_g, left_h, left_c); see ``split.FeatureCandidates``.
    """
    F, N = bins.shape
    B = -(-max_bin // 128) * 128
    bins, grad, hess, weight, leaf_id, Np = _pad_row_inputs(
        bins, grad, hess, weight, leaf_id, n_blk)
    nblocks = Np // n_blk

    bins = bins.astype(jnp.int32)
    out = pl.pallas_call(
        functools.partial(_fused_split_kernel, max_bin=B, crop=max_bin,
                          f_blk=F, n_blk=n_blk, sp=params),
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),          # parent
            pl.BlockSpec(memory_space=pltpu.SMEM),          # right
            pl.BlockSpec(memory_space=pltpu.SMEM),          # totals [2,3]
            pl.BlockSpec((F, n_blk), lambda i: (0, i)),     # bins
            pl.BlockSpec((1, n_blk), lambda i: (0, i)),     # g
            pl.BlockSpec((1, n_blk), lambda i: (0, i)),     # h
            pl.BlockSpec((1, n_blk), lambda i: (0, i)),     # w
            pl.BlockSpec((1, n_blk), lambda i: (0, i)),     # leaf
            pl.BlockSpec((1, F), lambda i: (0, 0)),         # num_bin
            pl.BlockSpec((1, F), lambda i: (0, 0)),         # is_cat
            pl.BlockSpec((1, F), lambda i: (0, 0)),         # feat_mask
        ],
        out_specs=pl.BlockSpec((2, F, 8), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((2, F, 8), jnp.float32),
        scratch_shapes=[pltpu.VMEM((F, 6, B), jnp.float32)],
        interpret=interpret,
        # a device event of the kernel reads %children_histograms.N
        # whatever function traced the call (obs/devtrace.py)
        name="children_histograms",
    )(jnp.asarray([parent_leaf], jnp.int32),
      jnp.asarray([right_leaf], jnp.int32),
      jnp.asarray(totals, jnp.float32),
      bins, grad[None], hess[None], weight[None],
      leaf_id.astype(jnp.int32)[None],
      jnp.asarray(num_bin, jnp.int32)[None],
      jnp.asarray(is_cat, jnp.int32)[None],
      jnp.asarray(feat_mask, jnp.int32)[None])
    return out


@instrumented_jit(program="pallas_root_hist",
                  static_argnames=("max_bin", "n_blk", "interpret"))
def root_histogram_pallas(bins, grad, hess, weight, max_bin: int,
                          n_blk: int = 2048, interpret: bool = False):
    """[F, B, 3] root histogram: reuse the children kernel with every row
    in the 'left' child (leaf_id == 0)."""
    N = bins.shape[1]
    leaf = jnp.zeros((N,), jnp.int32)
    both = children_histograms_pallas(bins, grad, hess, weight, leaf,
                                      0, -2, max_bin, n_blk, interpret)
    return both[0]
