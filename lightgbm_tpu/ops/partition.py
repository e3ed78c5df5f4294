"""Stable two-way partition of a split step's window, as a counting kernel.

A split leaf's window of P rows (ops/ordered_grow.py; P the power-of-two
size class over the segment) has to come out with the rows that go left
first, in their order, then every other row, in its order.  Until PR 31
that was ``jax.lax.sort`` on a three-valued key with a dozen operands: a
comparison sort, n log^2 n on the chip, half of a round at 10.5M rows.
It is a permutation whose destinations are two running counts, so here
it is O(P): a fixed two passes over the window, every row placed once.

The kernel (``segment_partition``).  Each lane of the window comes in as
it lies, ``[P]`` read as ``[P / 128, 128]`` (the same bytes: the compiled
text copies nothing on the way in); a grid step lays its T rows of every
lane along the lanes of one ``[lanes, T]`` block in VMEM.  Grid ``(2, P /
T)``, both axes sequential: pass 0 places the left rows, pass 1 the
others, into ONE output through a ring of output tiles ``[lanes, b]``
that lives across the grid, with the stream's position in SMEM.  The
ranks of a step's rows within the pass come from one triangular
contraction for the whole step, the sub-blocks' starts from scalar sums
taken before anything is placed.  A sub-block of b rows is then placed
by a one-hot: ``perm[d, r] = (d == fill + rank[r])`` for d in ``[0,
2b)`` (the destinations along sublanes, the ranks broadcast along them,
so nothing is transposed), and the lanes' words, split into byte planes
``[4 * lanes, b]``, are contracted with it over r on the MXU in int8
with int32 sums.  Each sum is one byte or zero, so it is exact (``&
0xFF``: a byte of 128 and over comes back sign-extended); its two halves
are added into the tile being filled and the one after it.  The
sub-blocks are a loop of ``UNROLL`` placements a turn: no branch stands
between those, so the compiler overlaps them, and a step's code does
not grow with T (unrolled whole, twelve size classes of it cost every
process 14 s of lowering and the cold compile 170 s: PERF.md, PR 31).
The tiles a step has filled to the brim then go out, one DMA each to
``out[:, tile * b : (tile + 1) * b]``.  The left pass's last partial
tile stays in the ring and the other rows continue behind it, so the
join at ``n_left`` needs no merge and exactly ``P / b`` tiles are
written.

What the records forbid stays out (PERF.md, "Carried over"): no row
gather, no XLA scatter, no dynamic grid, no DMA at an unaligned offset,
no ``[n, 1]`` columns concatenated along lanes.  ``pltpu.roll`` by the
fill, in place of the half of the one-hot that it saves, compiles today
and was slower on the chip (PERF.md, PR 31).

``stable_partition`` is the one entry: the kernel on a TPU, the stable
sort it replaced elsewhere (the tests' oracle).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import device

SUB_BLOCK = 128           # b: rows placed by one one-hot contraction
SUB_BLOCKS_PER_STEP = 64  # T / b: sub-blocks of one grid step
UNROLL = 8                # placements between two turns of the loop
OUT_SLOTS = 8             # tiles on their way out at a time


def _partition_kernel(mask_ref, *refs, b, nsub, n):
    """One grid step ``(pass, tile)``.

    mask_ref   [nsub, b] i32    1 where the row goes left, a sub-block a row
    n lane refs [T / 128, 128]  the step's rows of each lane, as they lie
    out_ref    [lanes, P]       in HBM, written by DMA alone
    tri_ref    [b, 2b] bf16     exclusive and inclusive prefix masks
    rank_ref   [nsub, 2b] f32   ranks of the step's rows, a sub-block a row
    x_ref      [lanes, T] i32   the step's rows along lanes (``lanes``: n
                                rounded up to whole sublane tiles, the rest 0)
    ring_ref   [RING, lanes, b] output tiles being filled, tile q in q % RING
    outbuf_ref [SLOTS, lanes, b]  tiles on their way out
    state_ref  SMEM [1]         rows placed so far: the stream's position
    starts_ref SMEM [nsub]      where each sub-block's rows start in the stream
    """
    lane_refs = refs[:n]
    (out_ref, tri_ref, rank_ref, x_ref, ring_ref, outbuf_ref, state_ref,
     starts_ref, sem) = refs[n:]
    p = pl.program_id(0)
    t = pl.program_id(1)
    lanes = x_ref.shape[0]
    ring, slots = ring_ref.shape[0], outbuf_ref.shape[0]

    @pl.when(jnp.logical_and(p == 0, t == 0))
    def _init():
        state_ref[0] = 0
        ring_ref[...] = jnp.zeros_like(ring_ref)
        x_ref[...] = jnp.zeros_like(x_ref)
        r = jax.lax.broadcasted_iota(jnp.int32, (b, 2 * b), 0)
        i = jax.lax.broadcasted_iota(jnp.int32, (b, 2 * b), 1)
        before = ((i < b) & (r < i)) | ((i >= b) & (r <= i - b))
        tri_ref[...] = before.astype(jnp.float32).astype(jnp.bfloat16)

    for lane, ref in enumerate(lane_refs):
        x_ref[lane:lane + 1, :] = ref[...].reshape(1, nsub * b)

    # rows of this pass: the left ones, then the others
    m = mask_ref[...]
    sel = jnp.where(p == 0, m, 1 - m)                          # [nsub, b]
    # the sub-blocks' starts: scalars, all taken before the placements so
    # that nothing below waits for one
    first = start = state_ref[0]
    for j in range(nsub):
        starts_ref[j] = start
        start = start + jnp.sum(sel[j:j + 1, :])
    state_ref[0] = start
    # rank_ref[j, i]: selected rows of sub-block j before row i (i < b),
    # and up to and with row i - b (i >= b); 0/1 times 0/1 summed in
    # float32 over at most b terms: exact
    rank_ref[...] = jnp.dot(sel.astype(jnp.float32).astype(jnp.bfloat16),
                            tri_ref[...], preferred_element_type=jnp.float32)

    dest = jax.lax.broadcasted_iota(jnp.int32, (2 * b, b), 0)

    def place(j):
        tile, fill = starts_ref[j] // b, starts_ref[j] % b
        rank = rank_ref[pl.ds(j, 1), :]                        # [1, 2b]
        before = rank[:, 0:b].astype(jnp.int32)
        chosen = rank[:, b:2 * b].astype(jnp.int32) > before
        local = jnp.where(chosen, before + fill, -1)           # [1, b]
        perm = (dest == local).astype(jnp.int8)                # [2b, b]
        x = x_ref[:, pl.ds(pl.multiple_of(j * b, b), b)]       # [lanes, b]
        planes = jnp.concatenate(
            [x, x >> 8, x >> 16, x >> 24], axis=0).astype(jnp.int8)
        got = jax.lax.dot_general(
            planes, perm, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)                  # [4 lanes, 2b]
        words = (got[0:lanes] & 0xFF) \
            | ((got[lanes:2 * lanes] & 0xFF) << 8) \
            | ((got[2 * lanes:3 * lanes] & 0xFF) << 16) \
            | (got[3 * lanes:4 * lanes] << 24)
        # a row lands in the tile being filled or in the one after it
        ring_ref[tile % ring] += words[:, 0:b]
        ring_ref[(tile + 1) % ring] += words[:, b:2 * b]

    def turn(k, carry):
        for u in range(UNROLL):
            place(k * UNROLL + u)
        return carry
    jax.lax.fori_loop(0, nsub // UNROLL, turn, 0)

    def out_copy(tile):
        off = pl.multiple_of(tile * b, b)
        return pltpu.make_async_copy(
            outbuf_ref.at[tile % slots], out_ref.at[:, pl.ds(off, b)],
            sem.at[tile % slots])

    def emit(tile, carry):
        @pl.when(tile >= slots)
        def _():
            out_copy(tile - slots).wait()
        outbuf_ref[tile % slots] = ring_ref[tile % ring]
        ring_ref[tile % ring] = jnp.zeros((lanes, b), jnp.int32)
        out_copy(tile).start()
        return carry
    # the tiles this step filled to the brim
    jax.lax.fori_loop(first // b, start // b, emit, 0)

    @pl.when(jnp.logical_and(p == pl.num_programs(0) - 1,
                             t == pl.num_programs(1) - 1))
    def _drain():
        # every row was placed in one of the passes: P / b tiles went out,
        # the last ``slots`` of them are still on their way
        for k in range(slots):
            out_copy(start // b - slots + k).wait()


def segment_partition(lanes, is_left, *, sub_block: int = SUB_BLOCK,
                      sub_blocks_per_step: int = SUB_BLOCKS_PER_STEP,
                      interpret: bool = False):
    """The kernel: ``lanes`` (a tuple of [P] i32 arrays) with the rows
    whose ``is_left`` ([P] bool) is set first, in their order, then the
    others, in theirs.  P a power of two, at least ``OUT_SLOTS`` tiles."""
    b = sub_block
    n = len(lanes)
    rows = lanes[0].shape[0]
    nsub = min(sub_blocks_per_step, rows // b)
    step = nsub * b
    assert rows % step == 0 and rows // b >= OUT_SLOTS \
        and nsub % UNROLL == 0 and b % 128 == 0, (rows, b, nsub)
    lp = -(-n // 8) * 8                      # whole sublane tiles
    out = pl.pallas_call(
        functools.partial(_partition_kernel, b=b, nsub=nsub, n=n),
        grid=(2, rows // step),
        in_specs=[pl.BlockSpec((nsub, b), lambda p, t: (t, 0))]
        + [pl.BlockSpec((step // 128, 128), lambda p, t: (t, 0))] * n,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((lp, rows), jnp.int32),
        scratch_shapes=[pltpu.VMEM((b, 2 * b), jnp.bfloat16),
                        pltpu.VMEM((nsub, 2 * b), jnp.float32),
                        pltpu.VMEM((lp, step), jnp.int32),
                        # a step touches nsub + 2 tiles
                        pltpu.VMEM((2 * nsub, lp, b), jnp.int32),
                        pltpu.VMEM((OUT_SLOTS, lp, b), jnp.int32),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.SMEM((nsub,), jnp.int32),
                        pltpu.SemaphoreType.DMA((OUT_SLOTS,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        # a device event of the kernel reads %segment_partition.N
        # (obs/devtrace.py; benchmarks partition_kernel_ms_per_round)
        name="segment_partition",
    )(is_left.astype(jnp.int32).reshape(rows // b, b),
      *(lane.reshape(rows // 128, 128) for lane in lanes))
    return tuple(out[i] for i in range(n))


def sort_partition(lanes, is_left):
    """The same partition by ``jax.lax.sort``: the path off the TPU and
    the tests' oracle."""
    key = jnp.where(is_left, jnp.uint8(0), jnp.uint8(1))
    return tuple(jax.lax.sort((key,) + tuple(lanes), num_keys=1,
                              is_stable=True)[1:])


def stable_partition(lanes, is_left):
    """Platform dispatcher (``hist_window`` makes the same choice for the
    histogram kernel)."""
    if device.on_tpu():
        return segment_partition(lanes, is_left)
    return sort_partition(lanes, is_left)
