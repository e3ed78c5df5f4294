"""GOSS's row draw as a stage of the fused round, and its exact top-a
select as one Pallas kernel, ``goss_sample``.

What a sampled round keeps (models/goss.py; the reference's goss.hpp:
79-124): exactly ``top_cnt = int(top_rate * N)`` real rows by ``|g * h|``
summed over classes, the lower row index first among equal scores (the
order ``argsort(-score, stable=True)`` gives); every other real row with
probability ``other_rate / (1 - top_rate)``, its gradient and hessian
then amplified by ``(1 - top_rate) / other_rate``; no pad row, ever.

**The draw is stateless** (docs/Parameters.md, ``other_rate``): the random part of
row ``r`` in iteration ``i`` under ``bagging_seed`` ``s`` is

    key = fmix32(s ^ fmix32(i + 0x9E3779B9))
    u   = fmix32(r * 0x9E3779B9 + key) >> 8          (24 bits)
    kept outside the top set  iff  u < round(other_rate / (1 - top_rate) * 2^24)

in 32-bit unsigned arithmetic, ``fmix32`` MurmurHash3's finaliser.  The
same rows come out under any row padding or row sharding, a plain
reference reproduces the draw in a few lines of numpy, and a snapshot
needs no generator state to resume.

**The select** finds the ``top_cnt``-th largest score without sorting.
Scores are non-negative floats, so their bits order as int32; pad rows
get the key -1.  Eight passes over the keys count, among the rows whose
higher bits equal the prefix found so far, how many fall in each of the
16 values of the next four bits, and keep the bucket where the count from
the top reaches what is still needed: after them the prefix IS the
threshold ``T`` and ``need`` the number of rows of key ``T`` to keep.
Then as many passes over the row INDEX of those tied rows find the cut
``I`` from the bottom.  A row is in the top set iff ``key > T`` or
``key == T and index <= I``: exactly ``top_cnt`` rows (all real rows
where ``top_cnt`` reaches them, none where it is 0).  The last pass
writes the kept mask and the factor.

The kernel is one ``pallas_call`` over a grid ``(passes, blocks)``, both
sequential: a block of keys is read each pass, its 16 counts added into
``[16, 8, 128]`` vector accumulators, and at a pass's last block the
counts are summed and the bucket picked into SMEM.  The outputs' block
index stays at 0 until the last pass, so nothing is written back before
it.  Off the TPU, and under a learner's mesh (a Mosaic kernel is not
partitioned automatically), ``select_jnp`` does the same passes with
``jax.numpy`` compares: the same rows to the bit, no sort either way.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import device

LANE = 128
BLOCK_ROWS = 1024         # sublane rows of a block: 131,072 keys
DIGIT_BITS = 4
BUCKETS = 1 << DIGIT_BITS
VALUE_PASSES = 32 // DIGIT_BITS
DRAW_BITS = 24

_GOLDEN = 0x9E3779B9
_M1, _M2 = 0x85EBCA6B, 0xC2B2AE35


class GossParams(NamedTuple):
    """Static part of a GOSS round (a ``train_step`` key): the two rates,
    and whether the rows lie across a learner's mesh."""
    top_rate: float
    other_rate: float
    over_devices: bool = False

    def keep_threshold(self) -> int:
        """The draw's 24-bit threshold of the rows outside the top set."""
        p = self.other_rate / max(1e-12, 1.0 - self.top_rate)
        return int(min(max(round(p * (1 << DRAW_BITS)), 0), 1 << DRAW_BITS))

    def amplification(self) -> float:
        return (1.0 - self.top_rate) / max(self.other_rate, 1e-12)


def _i32(c: int):
    return jnp.int32(c - (1 << 32) if c >= 1 << 31 else c)


def _fmix32(x):
    srl = jax.lax.shift_right_logical
    x = x ^ srl(x, jnp.int32(16))
    x = x * _i32(_M1)
    x = x ^ srl(x, jnp.int32(13))
    x = x * _i32(_M2)
    return x ^ srl(x, jnp.int32(16))


def draw_key(seed, iteration):
    """The scalar key of one iteration's draw (int32 bits)."""
    return _fmix32(jnp.asarray(seed, jnp.int32)
                   ^ _fmix32(jnp.asarray(iteration, jnp.int32) + _i32(_GOLDEN)))


def row_draw(key, rows):
    """The 24-bit draw of the rows ``rows`` (int32) under ``key``."""
    return jax.lax.shift_right_logical(
        _fmix32(rows * _i32(_GOLDEN) + key), jnp.int32(32 - DRAW_BITS))


def index_passes(n: int) -> int:
    """Passes over the row index that cover indices below ``n``."""
    return max(1, -(-max(n - 1, 1).bit_length() // DIGIT_BITS))


def _pass_digit(p, vpasses: int, ipasses: int):
    """Shift of pass ``p``'s four bits, and the mask of the bits above
    them, in the value or the index phase."""
    in_value = p < vpasses
    shift = jnp.where(in_value, 32 - DIGIT_BITS * (p + 1),
                      DIGIT_BITS * (vpasses + ipasses - 1 - p))
    hi = shift + DIGIT_BITS
    above = jnp.where(hi >= 32, jnp.int32(0),
                      -jax.lax.shift_left(jnp.int32(1), jnp.minimum(hi, 31)))
    return in_value, shift, above


def _pick(counts, need, from_top):
    """The bucket where ``need`` is reached, counting from the top or the
    bottom, and what is still needed inside it."""
    chosen, rem = jnp.int32(-1), need
    for b in range(BUCKETS):
        b = jnp.where(from_top, BUCKETS - 1 - b, b)
        c = sum(jnp.where(b == k, counts[k], 0) for k in range(BUCKETS))
        searching = chosen < 0
        hit = searching & (rem <= c)
        chosen = jnp.where(hit, b, chosen)
        rem = jnp.where(searching & ~hit, rem - c, rem)
    return chosen, rem


def _outputs(key, idx, T, I, any_top, draw, keep_thr: int, amp: float):
    real = key >= 0
    top = real & any_top & ((key > T) | ((key == T) & (idx <= I)))
    other = real & ~top & (row_draw(draw, idx) < keep_thr)
    return (jnp.where(top | other, 1.0, 0.0).astype(jnp.float32),
            jnp.where(other, jnp.float32(amp), jnp.float32(1.0)))


# SMEM state of the kernel
_NEED, _PREFIX, _IPREFIX, _ANY = range(4)


def _select_kernel(sc_ref, key_ref, mask_ref, factor_ref, acc_ref, st_ref, *,
                   vpasses, ipasses, keep_thr, amp):
    """Grid step ``(pass, block)``: sc_ref SMEM [2] (top_cnt, draw key);
    key_ref [R, 128] int32 keys; acc_ref [16, 8, 128] the pass's counts;
    st_ref SMEM [4] need, value prefix, index prefix, any top."""
    p, t = pl.program_id(0), pl.program_id(1)
    last = vpasses + ipasses
    rows = key_ref.shape[0]
    key = key_ref[...]
    idx = t * (rows * LANE) \
        + jax.lax.broadcasted_iota(jnp.int32, (rows, LANE), 0) * LANE \
        + jax.lax.broadcasted_iota(jnp.int32, (rows, LANE), 1)

    @pl.when((p == 0) & (t == 0))
    def _init():
        for i in range(4):
            st_ref[i] = jnp.int32(0)

    @pl.when((t == 0) & (p < last))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(p < last)
    def _count():
        in_value, shift, above = _pass_digit(p, vpasses, ipasses)
        src = jnp.where(in_value, key, idx)
        prefix = jnp.where(in_value, st_ref[_PREFIX], st_ref[_IPREFIX])
        # the value phase's rows: every real one; the index phase's: the
        # rows of the threshold's key (bounds, not a select of booleans)
        lo = jnp.where(in_value, 0, st_ref[_PREFIX])
        hi = jnp.where(in_value, jnp.iinfo(jnp.int32).max, st_ref[_PREFIX])
        cand = (key >= lo) & (key <= hi) & ((src & above) == prefix)
        digit = jax.lax.shift_right_arithmetic(src, shift) & (BUCKETS - 1)
        for b in range(BUCKETS):
            hit = (cand & (digit == b)).astype(jnp.int32)
            acc_ref[b] += hit.reshape(rows // 8, 8, LANE).sum(axis=0)

        @pl.when(t == pl.num_programs(1) - 1)
        def _choose():
            counts = [jnp.sum(acc_ref[b]) for b in range(BUCKETS)]
            total = sum(counts)
            top_cnt = sc_ref[0]
            need = jnp.where(p == 0,
                             jnp.clip(top_cnt, 1, jnp.maximum(total, 1)),
                             st_ref[_NEED])

            @pl.when(p == 0)
            def _():
                st_ref[_ANY] = ((top_cnt > 0) & (total > 0)).astype(jnp.int32)
            chosen, rem = _pick(counts, need, in_value)
            st_ref[_NEED] = rem
            put = jax.lax.shift_left(chosen, shift)

            @pl.when(in_value)
            def _():
                st_ref[_PREFIX] = st_ref[_PREFIX] | put

            @pl.when(~in_value)
            def _():
                st_ref[_IPREFIX] = st_ref[_IPREFIX] | put

    @pl.when(p == last)
    def _write():
        mask, factor = _outputs(key, idx, st_ref[_PREFIX], st_ref[_IPREFIX],
                                st_ref[_ANY] > 0, sc_ref[1], keep_thr, amp)
        mask_ref[...] = mask
        factor_ref[...] = factor


def goss_sample(keys, top_cnt, draw, *, keep_thr: int, amp: float,
                interpret: bool = False):
    """The kernel: ``(mask, factor)``, both ``[N]`` float32, of the keys
    ``keys`` ([N] int32, the score's bits, -1 on pad rows) under
    ``top_cnt`` and the draw key ``draw`` (int32 scalars)."""
    n = keys.shape[0]
    rows = min(BLOCK_ROWS, -(-n // (8 * LANE)) * 8)
    blk = rows * LANE
    npad = -(-n // blk) * blk
    vp, ip = VALUE_PASSES, index_passes(npad)
    last = vp + ip
    keys = jnp.pad(keys, (0, npad - n), constant_values=-1)
    out_spec = pl.BlockSpec(
        (rows, LANE), lambda p, t, sc: (jnp.where(p == last, t, 0), 0))
    mask, factor = pl.pallas_call(
        functools.partial(_select_kernel, vpasses=vp, ipasses=ip,
                          keep_thr=keep_thr, amp=amp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(last + 1, npad // blk),
            in_specs=[pl.BlockSpec((rows, LANE), lambda p, t, sc: (t, 0))],
            out_specs=[out_spec, out_spec],
            scratch_shapes=[pltpu.VMEM((BUCKETS, 8, LANE), jnp.int32),
                            pltpu.SMEM((4,), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((npad // LANE, LANE),
                                        jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        # a device event of the kernel reads %goss_sample.N (benchmarks
        # goss_sample_ms_per_round, goss_sample_roofline)
        name="goss_sample",
    )(jnp.stack([jnp.asarray(top_cnt, jnp.int32),
                 jnp.asarray(draw, jnp.int32)]),
      keys.reshape(npad // LANE, LANE))
    return mask.reshape(npad)[:n], factor.reshape(npad)[:n]


def select_jnp(keys, top_cnt, draw, *, keep_thr: int, amp: float):
    """``goss_sample``'s passes in ``jax.numpy``: off the TPU and under a
    learner's mesh, and the kernel's oracle in the tests."""
    n = keys.shape[0]
    vp, ip = VALUE_PASSES, index_passes(n)
    idx = jnp.arange(n, dtype=jnp.int32)
    buckets = jnp.arange(BUCKETS, dtype=jnp.int32)[:, None]
    real = keys >= 0
    prefix = iprefix = jnp.int32(0)
    need = any_top = None
    for p in range(vp + ip):
        in_value, shift, above = _pass_digit(jnp.int32(p), vp, ip)
        src = keys if p < vp else idx
        cand = (real if p < vp else keys == prefix) \
            & ((src & above) == (prefix if p < vp else iprefix))
        digit = jax.lax.shift_right_arithmetic(src, shift) & (BUCKETS - 1)
        counts = jnp.sum((cand[None, :] & (digit[None, :] == buckets))
                         .astype(jnp.int32), axis=1)
        if p == 0:
            total = jnp.sum(counts)
            top_cnt = jnp.asarray(top_cnt, jnp.int32)
            any_top = (top_cnt > 0) & (total > 0)
            need = jnp.clip(top_cnt, 1, jnp.maximum(total, 1))
        chosen, need = _pick([counts[k] for k in range(BUCKETS)], need,
                             p < vp)
        if p < vp:
            prefix = prefix | jax.lax.shift_left(chosen, shift)
        else:
            iprefix = iprefix | jax.lax.shift_left(chosen, shift)
    return _outputs(keys, idx, prefix, iprefix, any_top,
                    jnp.asarray(draw, jnp.int32), keep_thr, amp)


def sample(grad, hess, real_weight, state, params: GossParams):
    """This round's ``(row_weight, factor)``, both ``[N]`` float32.

    ``grad``, ``hess`` ``[K, N]``; ``real_weight`` 1 on real rows and 0
    on pad rows; ``state`` int32 ``[4]``: iteration, ``bagging_seed``'s
    bits, whether this round samples (0 in the first ``1 /
    learning_rate`` rounds, goss.hpp:129), ``top_cnt``.  A round that
    does not sample keeps every real row at factor 1: one program serves
    both kinds of round."""
    with jax.named_scope("goss/sample"):
        score = jnp.sum(jnp.abs(grad * hess), axis=0)
        keys = jnp.where(real_weight > 0,
                         jax.lax.bitcast_convert_type(score, jnp.int32), -1)
        draw = draw_key(state[1], state[0])
        kw = dict(keep_thr=params.keep_threshold(),
                  amp=params.amplification())
        if device.on_tpu() and not params.over_devices:
            mask, factor = goss_sample(keys, state[3], draw, **kw)
        else:
            mask, factor = select_jnp(keys, state[3], draw, **kw)
        sampled = state[2] > 0
        return (jnp.where(sampled, mask, real_weight),
                jnp.where(sampled, factor, jnp.float32(1.0)))
