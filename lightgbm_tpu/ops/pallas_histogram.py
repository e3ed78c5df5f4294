"""Pallas TPU kernels for full-pass histogram construction.

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

``children_histograms_pallas`` writes the [2, F, B, 3] histograms out;
``root_histogram_pallas`` is the same kernel with every row in the left
child.  They are the full-pass learner's histogram kernels on a TPU
(ops/grow.py without a leaf cache: ``feature``, ``voting``, ``data`` over
uint16 or EFB columns, the ``hist_cache`` degrade step).

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


def _hist_kernel(parent_ref, right_ref, bins_ref, g_ref, h_ref, w_ref,
                 leaf_ref, out_ref, acc_ref, *, max_bin, f_blk, n_blk):
    """Grid: (row_blocks,).  Each step folds its row block's per-feature
    one-hot MXU products into acc ([F, 6, B] VMEM); the last writes it
    out."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

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

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        out_ref[:] = acc_ref[:]


def _pad_row_inputs(bins, grad, hess, weight, leaf_id, n_blk: int):
    """Row padding up the bucket ladder."""
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
