"""Leaf reconstruction on the live TPU: ``ops/ordered_grow.py leaf_delta``
alone, at the four cells' (rows, leaves), beside the forms it was chosen
from and the binary search it replaced.  Every form runs inside a
``fori_loop`` whose segments alternate between two trees and whose leaf
values carry the previous step's output, so nothing is hoisted out of the
loop and every timing ends behind ``block_until_ready``; each is first
held to the parent's form bit for bit on the chip itself.  Reports ms a
call and ns a row for the whole phase as the fused round uses it
(``delta``: the leaf id dropped), as the per-stage path uses it (``both``)
and, where a form has one, for its position-space half without the
scatter (``positions``).  A timing compiles for about 10 s at 11M rows
and more (the sort the compiler makes of the scatter).

    chiprun -- python tools/probe_leaf_delta.py [--forms a,b] [rows:leaves ...]

Forms: ``search`` the parent's (sort, ``searchsorted``'s scan, a table
gather, the scatter, a second table gather); ``compare_all``
``searchsorted(method='compare_all')`` for the id with the same two
gathers; ``leaf_delta`` the committed one (two compares a segment, the
one-hot summed over the segments for the leaf, ONE scatter, the value
selected in row space by the leaf); the rest select leaf and value in
position space and scatter both, so the per-stage path pays two
scatters: ``dense`` the committed compares; ``dense_nl`` the same with
the segments on the minor axis; ``steps`` one compare a segment and a sum
of the sorted values' differences (integers wrap, so the bits come out
exact); ``loop8`` / ``loop16`` a ``fori_loop`` over the segments in
groups, accumulating into the two N-vectors; ``kernel`` a Pallas kernel
over blocks of 16,384 positions with the segments' tables prefetched as
scalars, a block looping over the segments that meet it.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from lightgbm_tpu.ops.ordered_grow import leaf_delta, sorted_segments  # noqa: E402

CELLS = ((12_582_912, 255), (3_801_088, 255), (11_010_048, 63),
         (10_502_144, 63))
REPS = 6


def _bits(x):
    return lax.bitcast_convert_type(x, jnp.int32)


def _floats(x):
    return lax.bitcast_convert_type(x, jnp.float32)


def search_positions(method):
    def positions(start, cnt, num_leaves, shrunk, n):
        lo, _, leaf_sorted = sorted_segments(start, cnt, num_leaves, n)
        pos = jnp.arange(n, dtype=jnp.int32)
        seg = jnp.searchsorted(lo, pos, side="right", method=method) - 1
        return leaf_sorted[seg], None
    return positions


def dense_positions(segments_minor):
    def positions(start, cnt, num_leaves, shrunk, n):
        lo, hi, leaf_sorted = sorted_segments(start, cnt, num_leaves, n)
        bits = _bits(shrunk[leaf_sorted])
        pos = jnp.arange(n, dtype=jnp.int32)
        if segments_minor:
            inside = (lo[None, :] <= pos[:, None]) & (pos[:, None]
                                                      < hi[None, :])
            pick = lambda t: jnp.sum(jnp.where(inside, t[None, :], 0), axis=1)
        else:
            inside = (lo[:, None] <= pos[None, :]) & (pos[None, :]
                                                      < hi[:, None])
            pick = lambda t: jnp.sum(jnp.where(inside, t[:, None], 0), axis=0)
        return pick(leaf_sorted), _floats(pick(bits))
    return positions


def steps_positions(start, cnt, num_leaves, shrunk, n):
    """One compare a segment: the value at p is the sum of the sorted
    values' differences over the starts at or under p.  int32 sums wrap,
    so the telescoped bits are exact; a dead entry's start is n, never
    under a position, whatever its difference."""
    lo, _, leaf_sorted = sorted_segments(start, cnt, num_leaves, n)
    pos = jnp.arange(n, dtype=jnp.int32)[None, :]
    under = lo[:, None] <= pos

    def pick(t):
        d = t - jnp.concatenate([jnp.zeros(1, t.dtype), t[:-1]])
        return jnp.sum(jnp.where(under, d[:, None], 0), axis=0)
    return pick(leaf_sorted), _floats(pick(_bits(shrunk[leaf_sorted])))


def loop_positions(group):
    def positions(start, cnt, num_leaves, shrunk, n):
        lo, hi, leaf_sorted = sorted_segments(start, cnt, num_leaves, n)
        bits = _bits(shrunk[leaf_sorted])
        size = -(-lo.shape[0] // group) * group
        pad = lambda t, v: jnp.concatenate(
            [t, jnp.full(size - t.shape[0], v, jnp.int32)])
        lo, hi, leaf_sorted, bits = (pad(lo, n), pad(hi, n),
                                     pad(leaf_sorted, 0), pad(bits, 0))
        pos = jnp.arange(n, dtype=jnp.int32)

        def body(g, acc):
            for j in range(group):
                k = g * group + j
                inside = (lo[k] <= pos) & (pos < hi[k])
                acc = (jnp.where(inside, leaf_sorted[k], acc[0]),
                       jnp.where(inside, bits[k], acc[1]))
            return acc
        zero = jnp.zeros(n, jnp.int32)
        leaf, val = lax.fori_loop(0, size // group, body, (zero, zero))
        return leaf, _floats(val)
    return positions


BLOCK = (16, 1024)      # positions a grid step: 16 vregs a carried vector


def _select_kernel(first, last, lo, hi, leaf, bits, leaf_out, bits_out):
    rows, lanes = BLOCK
    pos = pl.program_id(0) * (rows * lanes) \
        + lax.broadcasted_iota(jnp.int32, BLOCK, 0) * lanes \
        + lax.broadcasted_iota(jnp.int32, BLOCK, 1)

    def body(k, acc):
        inside = (lo[k] <= pos) & (pos < hi[k])
        return (jnp.where(inside, leaf[k], acc[0]),
                jnp.where(inside, bits[k], acc[1]))
    b = pl.program_id(0)
    zero = jnp.zeros(BLOCK, jnp.int32)
    leaf_out[...], bits_out[...] = lax.fori_loop(first[b], last[b] + 1,
                                                 body, (zero, zero))


def kernel_positions(start, cnt, num_leaves, shrunk, n):
    lo, hi, leaf_sorted = sorted_segments(start, cnt, num_leaves, n)
    bits = _bits(shrunk[leaf_sorted])
    rows, lanes = BLOCK
    blocks = -(-n // (rows * lanes))
    # the segments that meet a block: from the last start at or under its
    # first position to the last start at or under its last
    base = jnp.arange(blocks, dtype=jnp.int32) * (rows * lanes)
    under = lambda p: jnp.sum(lo[None, :] <= p[:, None], axis=1,
                              dtype=jnp.int32) - 1
    first, last = under(base), under(base + (rows * lanes - 1))
    shape = jax.ShapeDtypeStruct((blocks * rows, lanes), jnp.int32)
    tile = pl.BlockSpec(BLOCK, lambda i, *_: (i, 0))
    leaf, val = pl.pallas_call(
        _select_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(blocks,), in_specs=[],
            out_specs=[tile, tile]),
        out_shape=[shape, shape],
        interpret=jax.default_backend() != "tpu",
        name="leaf_select",
    )(first, last, lo, hi, leaf_sorted, bits)
    return leaf.reshape(-1)[:n], _floats(val.reshape(-1)[:n])


def from_positions(positions):
    """The whole phase of a form that is given by its position-space
    half; a form that returns no value (the parent's) gathers it from the
    table by the scattered leaf id, as the parent did."""
    def whole(start, cnt, num_leaves, shrunk, row_ord, n):
        leaf_of_pos, val_of_pos = positions(start, cnt, num_leaves, shrunk,
                                            n)
        to_rows = lambda x: jnp.zeros(n, x.dtype).at[row_ord[:n]].set(
            x, unique_indices=True)
        leaf_id = to_rows(leaf_of_pos)
        return leaf_id, (shrunk[leaf_id] if val_of_pos is None
                         else to_rows(val_of_pos))
    return whole


FORMS = {
    "search": search_positions("scan"),
    "compare_all": search_positions("compare_all"),
    "leaf_delta": None,                    # the committed whole phase
    "dense": dense_positions(False),
    "dense_nl": dense_positions(True),
    "steps": steps_positions,
    "loop8": loop_positions(8),
    "loop16": loop_positions(16),
    "kernel": kernel_positions,
}


def random_tree(rng, n, leaves):
    """A grown tree's segments: every leaf live, the cuts anywhere."""
    cuts = np.sort(rng.choice(np.arange(1, n), leaves - 1, replace=False))
    start = np.concatenate([[0], cuts]).astype(np.int32)
    cnt = np.diff(np.concatenate([start, [n]])).astype(np.int32)
    order = rng.permutation(leaves)
    return start[order], cnt[order]


def timed(name, n, fn, trees, shrunk, row_ord, keep):
    """``fn`` -> (leaf_id or None, delta); ``keep`` names what the loop
    carries on: "delta", "both" or "positions".  The loop carries a
    score and adds the delta to it, as the round does, and shifts the
    next step's leaf values by the carried score's sum: an element-wise
    consumer (a reduction of the delta itself is merged into the
    select's own reduction and read 8 ms slower at 12.6M rows, and a
    slice would let the compiler compute one row)."""
    @jax.jit
    def loop(trees, shrunk, row_ord):
        def body(i, carry):
            score, ids = carry
            start, cnt = jax.tree.map(
                lambda a, b: jnp.where(i % 2 == 0, a, b), *trees)
            shift = (jnp.sum(score) + jnp.sum(ids)) * 1e-12
            leaf, delta = fn(start, cnt, jnp.int32(start.shape[0]),
                             shrunk + shift, row_ord)
            return (score + delta,
                    ids if leaf is None else ids + leaf.astype(jnp.float32))
        zero = jnp.zeros(n, jnp.float32)
        return lax.fori_loop(0, REPS, body, (zero, zero))
    t0 = time.time()
    jax.block_until_ready(loop(trees, shrunk, row_ord))   # compile + warm
    cold = time.time() - t0
    t0 = time.time()
    jax.block_until_ready(loop(trees, shrunk + 1.0, row_ord))
    dt = (time.time() - t0) / REPS
    print(f"rows {n:9d} leaves {shrunk.shape[0]:4d}  {name:12s} {keep:9s} "
          f"{dt * 1e3:9.3f} ms  {dt / n * 1e9:7.3f} ns/row   "
          f"(first call {cold:.1f} s)", flush=True)
    return dt


def main():
    args = sys.argv[1:]
    names = list(FORMS)
    if "--forms" in args:
        at = args.index("--forms")
        names = args[at + 1].split(",")
        del args[at:at + 2]
    cells = [tuple(int(v) for v in a.split(":")) for a in args] or CELLS
    print(jax.devices()[0].device_kind, flush=True)
    rng = np.random.RandomState(0)
    for n, leaves in cells:
        trees = tuple(random_tree(rng, n, leaves) for _ in range(2))
        trees = jax.tree.map(jnp.asarray, trees)
        shrunk = jnp.asarray(rng.randn(leaves).astype(np.float32))
        row_ord = jnp.asarray(rng.permutation(n).astype(np.int32))
        want = jax.jit(from_positions(FORMS["search"]),
                       static_argnums=5)(*trees[0], jnp.int32(leaves),
                                         shrunk, row_ord, n)
        for name in names:
            positions = FORMS[name]
            whole = leaf_delta if positions is None \
                else from_positions(positions)
            got = jax.jit(whole, static_argnums=5)(
                *trees[0], jnp.int32(leaves), shrunk, row_ord, n)
            same = all(np.array_equal(np.asarray(g).view(np.int32),
                                      np.asarray(w).view(np.int32))
                       for g, w in zip(got, want))
            print(f"rows {n:9d} leaves {leaves:4d}  {name:12s} "
                  + ("equal to the search bit for bit" if same
                     else "DIFFERS"), flush=True)
            both = lambda s, c, nl, v, ro, whole=whole: whole(s, c, nl, v,
                                                              ro, n)
            timed(name, n, lambda *a: (None, both(*a)[1]), trees, shrunk,
                  row_ord, "delta")
            timed(name, n, both, trees, shrunk, row_ord, "both")
            if name not in ("search", "compare_all", "leaf_delta"):
                timed(name, n,
                      lambda s, c, nl, v, ro, positions=positions:
                      positions(s, c, nl, v, n),
                      trees, shrunk, row_ord, "positions")


if __name__ == "__main__":
    main()
