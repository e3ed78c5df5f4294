"""LambdaRank's pair work as one Pallas kernel over query slabs.

A query's documents are contiguous rows (``query_boundaries``), so the
score vector read as ``[rows / 128, 128]`` holds a query in a few whole
rows of 128 documents: its SLAB, rows ``start // 128`` to ``(start + n -
1) // 128``.  The objective (objective/__init__.py ``LambdarankNDCG``)
reads one slab a query out of the scores (one slice a query, never one
element a document), hands the slabs of a size class to ``rank_lambda``
and adds the slabs of gradients and hessians back by query start.  The
slab is used AS IT LIES: a query that starts at lane 37 keeps its
documents at lanes 37 and up, the neighbours' documents in the slab's
first and last row are masked (label -1), and nothing is realigned.
That costs pair slots (a query of n documents computes ``128 ceil((o + n)
/ 128)`` squared, o its first lane: 2.4e9 a round on MSLR-WEB30K's sizes
against sum n^2 = 0.9e9) and saves every gather, sort and scatter the
``jax.numpy`` form makes a document at a time.

The kernel (``name="rank_lambda"``; the benchmark finds it by that name).
Grid over blocks of ``QUERIES_PER_STEP`` queries of one class (R rows a
slab); a query's row count and inverse maximum DCG come as prefetched
scalars.  Everything of a query is in VMEM; nothing of size P x P
exists anywhere: the pairs are visited as ``[128, 128]`` tiles (document
i along sublanes, j along lanes), i's values brought across the lanes
by one transpose of a broadcast row a tile row.  Two passes over the
tiles of a query:

A. ranks.  ``rank_j = #{i: s_i > s_j, or s_i == s_j and i < j}``: the
   position ``jnp.argsort(-s)`` (stable) gives document j, from the pair
   comparisons themselves, summed over sublanes by plain vector adds.
   No sort and no unsort.  Then the discount ``1 / log2(2 + rank)``.
B. lambdas.  For the tile's pairs: the one of higher label is the pair's
   high document; score gap high - low, gain gap, ``|discount gap|``,
   the inverse maximum DCG, the ``0.01 + |gap|`` normaliser when the
   query's best score differs from its worst, ``p = 2 / (1 + exp(2 sigma
   gap))``, ``lambda = p delta`` and ``hessian = p (2 - p) 2 delta``:
   rank_objective.hpp:83-137 term for term, all label-differing pairs,
   float32.  Document j receives ``-lambda`` where it is the high one
   and ``+lambda`` where it is the low one, so every tile is summed over
   i (sublanes) alone and no sum runs across lanes.

Against the ``jax.numpy`` oracle the sums run in another order (by
position, not by rank) and the discount is computed, not looked up: the
two agree to float32 rounding of a sum of up to 1,250 terms (tests).
"""

from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128               # documents a slab row
SUB = 32                  # sublanes of a tile worked at a time
QUERIES_PER_STEP = 8
# slab rows of the size classes: a query of n documents from lane o spans
# ceil((o + n) / 128) rows and joins the smallest class that holds them
CLASS_ROWS = (1, 2, 3, 5, 9, 17, 33, 65, 129)
LN2 = math.log(2.0)


def _fold(x):
    """[SUB, 128] -> [8, 128]: whole-vreg adds, no sublane shuffle."""
    out = x[0:8]
    for r in range(8, SUB, 8):
        out = out + x[r:r + 8]
    return out


def _rank_lambda_kernel(nr_ref, inv_ref, s_ref, lab_ref, gain_ref,
                        g_ref, h_ref, sm_ref, disc_ref, scol_ref, col_ref,
                        rank_ref, accg_ref, acch_ref, *, sigma):
    """One grid step: ``QUERIES_PER_STEP`` queries of R slab rows.

    nr_ref, inv_ref  SMEM [Q]      rows a query spans; inverse max DCG
    s/lab/gain_ref   [QPS, R, 128] scores; labels (-1: not this query's);
                                   label gains
    g/h_ref          [QPS, R, 128] out
    sm_ref, disc_ref [R, 128]      masked scores; discounts
    scol_ref         [R, 128, 128] s_i across the lanes, a tile row each
    col_ref          [3, 128, 128] label, gain, discount of the tile row
    rank/accg/acch   [R, 8, 128]   sums over i, folded to 8 sublanes
    """
    step = pl.program_id(0)
    tile = (LANES, LANES)
    # i before j within the slab: 128 ic + r < 128 jc + c
    r_minus_c = (jax.lax.broadcasted_iota(jnp.int32, tile, 0)
                 - jax.lax.broadcasted_iota(jnp.int32, tile, 1))

    def across(row):
        """[1, 128] of documents -> [128, 128]: document r's value along
        the lanes of sublane r."""
        return jnp.transpose(jnp.broadcast_to(row, tile))

    def one_query(qi, carry):
        q = step * QUERIES_PER_STEP + qi
        nr = nr_ref[q]
        inv = inv_ref[q]
        lab = lab_ref[qi]
        valid = lab >= 0.0
        s = s_ref[qi]
        sm_ref[...] = jnp.where(valid, s, -jnp.inf)
        best = jnp.max(sm_ref[...])
        worst = jnp.min(jnp.where(valid, s, jnp.inf))
        spread = best != worst
        rank_ref[...] = jnp.zeros_like(rank_ref)
        accg_ref[...] = jnp.zeros_like(accg_ref)
        acch_ref[...] = jnp.zeros_like(acch_ref)

        # ---- A: ranks from the pair comparisons ------------------------
        def rank_row(ic, c):
            scol_ref[ic] = across(sm_ref[pl.ds(ic, 1), :])

            def rank_tile(jc, c):
                sj = sm_ref[pl.ds(jc, 1), :]
                before = r_minus_c < (jc - ic) * LANES
                for r0 in range(0, LANES, SUB):
                    si = scol_ref[ic, r0:r0 + SUB, :]
                    ahead = (si > sj) | ((si == sj)
                                         & before[r0:r0 + SUB])
                    rank_ref[jc] += _fold(ahead.astype(jnp.float32))
                return c
            return jax.lax.fori_loop(0, nr, rank_tile, c)
        jax.lax.fori_loop(0, nr, rank_row, 0)
        rank = jnp.sum(rank_ref[...], axis=1)                   # [R, 128]
        disc_ref[...] = LN2 / jnp.log(rank + 2.0)

        # ---- B: lambdas and hessians of every label-differing pair -----
        def pair_row(ic, c):
            col_ref[0] = across(lab_ref[qi, pl.ds(ic, 1), :])
            col_ref[1] = across(gain_ref[qi, pl.ds(ic, 1), :])
            col_ref[2] = across(disc_ref[pl.ds(ic, 1), :])

            def pair_tile(jc, c):
                sj = sm_ref[pl.ds(jc, 1), :]
                lj = lab_ref[qi, pl.ds(jc, 1), :]
                gj = gain_ref[qi, pl.ds(jc, 1), :]
                dj = disc_ref[pl.ds(jc, 1), :]
                for r0 in range(0, LANES, SUB):
                    rows = slice(r0, r0 + SUB)
                    si = scol_ref[ic, rows, :]
                    li = col_ref[0, rows, :]
                    gi = col_ref[1, rows, :]
                    di = col_ref[2, rows, :]
                    j_high = lj > li
                    ok = (lj != li) & (lj >= 0.0) & (li >= 0.0)
                    delta = jnp.where(j_high, sj - si, si - sj)
                    dcg_gap = jnp.where(j_high, gj - gi, gi - gj)
                    delta_ndcg = dcg_gap * jnp.abs(di - dj) * inv
                    norm = jnp.where(spread, 0.01 + jnp.abs(delta), 1.0)
                    delta_ndcg = delta_ndcg / norm
                    p = 2.0 / (1.0 + jnp.exp(2.0 * delta * sigma))
                    lam = p * delta_ndcg
                    hes = p * (2.0 - p) * 2.0 * delta_ndcg
                    # the high document gets -lambda, the low one +lambda
                    lam = jnp.where(j_high, -lam, lam)
                    accg_ref[jc] += _fold(jnp.where(ok, lam, 0.0))
                    acch_ref[jc] += _fold(jnp.where(ok, hes, 0.0))
                return c
            return jax.lax.fori_loop(0, nr, pair_tile, c)
        jax.lax.fori_loop(0, nr, pair_row, 0)
        g_ref[qi] = jnp.sum(accg_ref[...], axis=1)
        h_ref[qi] = jnp.sum(acch_ref[...], axis=1)
        return carry

    jax.lax.fori_loop(0, QUERIES_PER_STEP, one_query, 0)


def rank_lambda(nr, inv_max_dcg, s, lab, gain, *, sigma: float,
                interpret: bool = False):
    """The kernel over the slabs of one size class.  ``nr`` [Q] int32 and
    ``inv_max_dcg`` [Q] float32; ``s``, ``lab``, ``gain`` [Q, R, 128]
    float32 (``lab`` -1 where the slot is not the query's own document);
    Q a multiple of ``QUERIES_PER_STEP``.  Returns the slabs of gradients
    and hessians, zero in every slot that is not the query's own."""
    Q, R, _ = s.shape
    assert Q % QUERIES_PER_STEP == 0 and s.shape[2] == LANES, s.shape
    block = pl.BlockSpec((QUERIES_PER_STEP, R, LANES),
                         lambda b, nr, inv: (b, 0, 0))
    slab = jax.ShapeDtypeStruct((Q, R, LANES), jnp.float32)
    return pl.pallas_call(
        functools.partial(_rank_lambda_kernel, sigma=float(sigma)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(Q // QUERIES_PER_STEP,),
            in_specs=[block, block, block],
            out_specs=[block, block],
            scratch_shapes=[pltpu.VMEM((R, LANES), jnp.float32),
                            pltpu.VMEM((R, LANES), jnp.float32),
                            pltpu.VMEM((R, LANES, LANES), jnp.float32),
                            pltpu.VMEM((3, LANES, LANES), jnp.float32),
                            pltpu.VMEM((R, 8, LANES), jnp.float32),
                            pltpu.VMEM((R, 8, LANES), jnp.float32),
                            pltpu.VMEM((R, 8, LANES), jnp.float32)]),
        out_shape=[slab, slab],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # a device event of the kernel reads %rank_lambda.N
        # (obs/devtrace.py; benchmarks rank_lambda_ms_per_round)
        name="rank_lambda",
    )(nr, inv_max_dcg, s, lab, gain)


# ---- the slab frame: host tables, made once a dataset --------------------

def _padded_queries(n: int) -> int:
    """Queries of a class with its pad: whole kernel steps, and up a
    ladder of 2^k and 3 * 2^(k-1) steps, so that data sets whose classes
    differ by a few queries (another seed, another fold) share the shapes
    of one compiled program.  A pad query has no rows and costs nothing
    but its slot."""
    steps = -(-max(n, 1) // QUERIES_PER_STEP)
    rung = 1 << (steps - 1).bit_length()
    if rung >= 4 and steps <= rung * 3 // 4:
        rung = rung * 3 // 4
    return rung * QUERIES_PER_STEP


def slab_tables(query_boundaries, label, label_gain, inv_max_dcg):
    """Per size class, the arrays ``rank_lambda`` and its feed need, made
    once at ``LambdarankNDCG.init`` by array operations a class: ``row0``
    [Q] (first slab row in the scores read as [rows / 128, 128]), ``nr``
    [Q], ``inv_max_dcg`` [Q], ``lab`` and ``gain`` [Q, R, 128].  Q is
    padded with queries of no rows (``_padded_queries``).  Also the pair
    slots the classes compute."""
    qb = np.asarray(query_boundaries, np.int64)
    start, cnt = qb[:-1], np.diff(qb)
    first_lane = start % LANES
    nr = (first_lane + cnt + LANES - 1) // LANES
    assert int(nr.max()) <= CLASS_ROWS[-1], "a query of over 16,384 documents"
    rows_of = np.asarray(CLASS_ROWS)[np.searchsorted(CLASS_ROWS, nr)]
    label = np.asarray(label).astype(np.int64)
    gains = np.asarray(label_gain, np.float32)
    lane = np.arange(LANES, dtype=np.int64)
    classes, slots = [], 0
    for R in sorted(set(rows_of.tolist())):
        q = np.flatnonzero(rows_of == R)
        pad = _padded_queries(len(q)) - len(q)
        row0 = start[q] // LANES
        pos = ((row0[:, None] + np.arange(R))[:, :, None] * LANES
               + lane)                                         # [Q, R, 128]
        own = (pos >= start[q, None, None]) \
            & (pos < (start[q] + cnt[q])[:, None, None])
        lab = label[np.where(own, pos, 0)]
        grow = lambda a, fill=0: np.pad(
            a, [(0, pad)] + [(0, 0)] * (a.ndim - 1), constant_values=fill)
        classes.append({
            # the pad's queries have no rows: any slab start in range does
            "row0": jnp.asarray(grow(row0, row0[-1]).astype(np.int32)),
            "nr": jnp.asarray(grow(nr[q]).astype(np.int32)),
            "inv_max_dcg": jnp.asarray(
                grow(np.asarray(inv_max_dcg)[q]).astype(np.float32)),
            "lab": jnp.asarray(grow(np.where(own, lab, -1), -1)
                               .astype(np.float32)),
            "gain": jnp.asarray(grow(np.where(own, gains[lab], 0.0))
                                .astype(np.float32)),
        })
        slots += int((nr[q] ** 2).sum()) * LANES * LANES
    return tuple(classes), slots


def slab_gradients(classes, s, *, sigma: float, interpret: bool = False):
    """Gradients and hessians [N] of the scores ``s`` [N] over the slab
    tables: a slice a query in, the kernel a class, a slab add a query
    out.  Every row is some query's, so every slot is written."""
    n = s.shape[0]
    r_max = max(c["lab"].shape[1] for c in classes)
    rows = -(-n // LANES) + r_max             # a last slab may run past N
    with jax.named_scope("gradients/rank_slab"):
        s2 = jnp.pad(s, (0, rows * LANES - n)).reshape(rows, LANES)
        g2 = jnp.zeros((rows, LANES), jnp.float32)
        h2 = jnp.zeros((rows, LANES), jnp.float32)
    for c in classes:
        R = c["lab"].shape[1]
        with jax.named_scope("gradients/rank_slab"):
            # whole rows of 128 documents by row number: R a query
            at = c["row0"][:, None] + jnp.arange(R, dtype=jnp.int32)
            slabs = jnp.take(s2, at, axis=0)
        with jax.named_scope("gradients/rank_lambda"):
            g, h = rank_lambda(c["nr"], c["inv_max_dcg"], slabs, c["lab"],
                               c["gain"], sigma=sigma, interpret=interpret)
        with jax.named_scope("gradients/rank_slab"):
            # neighbours share a row: their slabs' sums add up in it
            g2 = g2.at[at].add(g)
            h2 = h2.at[at].add(h)
    with jax.named_scope("gradients/rank_slab"):
        return g2.reshape(-1)[:n], h2.reshape(-1)[:n]
