"""Fused Pallas forest-walk serving kernel (ROADMAP item 2).

The gather-walk serving programs (``ops/predict.py``) advance every row
one tree LEVEL per step, and every step is an HBM gather of the node
arrays — exactly the anti-pattern the GBDT-inference accelerators
(Booster, He et al., arXiv:2011.02022; Mitchell & Frank,
arXiv:1806.11248) replace with node tables pinned next to compute.
This kernel pins the whole per-class SoA forest in VMEM and walks ALL
trees for a row block in one pass, accumulating leaf outputs
in-register; the only HBM traffic per grid step is the row block itself
and the [K, n_blk] output.

The walk is recast as a *path-consistency matmul* so it runs on the MXU
instead of as serial gathers (Mosaic has no cheap dynamic gather):

- ``fsel`` [KT*Mp, F] one-hot split-feature rows (Mp = the M nodes plus
  one dummy, padded to whole 8-row tiles) turn the row block's
  bins [F, n] into every node's comparison operand in one exact f32
  matmul (``fbin = fsel @ bins``; bin codes < 2^24 are exact in f32).
- each node compares once (``fbin <= thr`` numeric, ``== thr``
  categorical) giving c = ±1 for all nodes simultaneously.
- ``paths`` [KT, Lp, Mp] holds each leaf's ancestor signs (+1 = left
  edge on the leaf's path, -1 = right) with column M = -depth against a
  constant dummy node whose comparison is always +1.  For the leaf a row
  actually reaches, every ancestor comparison agrees with its sign, so
  ``(paths @ c)[leaf] == 0``; any disagreement makes the sum strictly
  negative, and unreachable/padded leaves carry a +1 bias that keeps
  them never-selected.  All sums are small exact integers in f32.
- the leaf value is a one-nonzero masked dot ``lv_row @ sel`` — exact,
  so the per-tree contribution is bit-identical to the gather walk's
  ``leaf_value[leaf]`` — and trees fold into the class total with the
  SAME Kahan-compensation order as ``predict_binned_forest``.

Linear forests (docs/LINEAR_TREES.md) fold the per-leaf affine epilogue
into the same pass: ``aff`` [KT, Lp, F] is the dense per-leaf coefficient
matrix, the epilogue is ``sum_l sel[l] * (aff_t @ xt)[l]`` (ROADMAP item
7(c) — no second program, no second HBM round trip).

Bin-space quantization rides the same layout: thresholds are cut-table
INDICES (``thr``, small exact integers held as f32 — a ``[.., 1]`` column
pads to a full lane tile in VMEM whatever its dtype, and the chip's
compiler has no unsigned-narrow -> f32 cast), binned inputs arrive in
the narrowest unsigned dtype that fits ``nan_bin`` and widen through
int32 in the kernel, and raw inputs bucketize ONCE per row block inside
the kernel against the VMEM-resident cut tables — the same
``searchsorted(side='left')`` predicate as the XLA raw program, f32
compares and all.  Leaves may be stored bf16 (``serve_quantize_leaves``)
— the accumulation stays f32 Kahan either way.

``interpret=True`` runs the kernel in the Pallas interpreter, which is
how CPU tier-1 pins fused == gather parity (like ``pallas_histogram``);
what the chip's compiler accepts is pinned by tests/test_tpu_compile.py
(every dynamic row offset here is a whole number of 8-row tiles, no
slice starts at a dynamic lane, no select is between boolean vectors).
Entry points are deliberately UN-jitted: serve/forest.py traces them
inside its own bucket-keyed CountingJit programs
(``predict_forest_walk`` / ``serve_forest_walk``), exactly like
ops/predict.py's forest walks — jitting here would double-count the
ledger.
"""

from __future__ import annotations

import functools

import numpy as np

from ..utils.log import LightGBMError


# f32 tile height on the chip: every dynamic row offset the kernel takes
# is a multiple of this (see build_walk_tables)
_SUBLANE = 8

# ---------------------------------------------------------------------------
# host-side operand builders (freeze-time, numpy)

def _leaf_paths(lc, rc, M: int, P: np.ndarray) -> None:
    """Fill one tree's [L, M+1] path matrix from its child arrays.

    Column M is the dummy-node column: -depth for reachable leaves, +1
    (never-selected bias) for unreachable ones.  ``lc == rc`` edges
    (the absorbing ``left=right=~0`` encoding of 1-leaf/padded trees)
    are unconditioned: both branches land on the same leaf, so the node
    is simply not recorded on the path."""
    P[:, M] = 1.0
    # (child code, [(node, sign), ...]) work stack; a tree with M splits
    # pushes at most 2M edges, so the guard only trips on corrupt arrays
    stack = [(0, [])] if M > 0 else []
    budget = 4 * M + 4
    while stack:
        budget -= 1
        if budget < 0:
            raise LightGBMError(
                "cyclic child links while building walk path matrix")
        code, path = stack.pop()
        if code < 0:
            leaf = ~code
            P[leaf, :] = 0.0
            for node, sign in path:
                P[leaf, node] = sign
            P[leaf, M] = -float(len(path))
            continue
        left, right = int(lc[code]), int(rc[code])
        if left == right:
            stack.append((left, path))
            continue
        stack.append((left, path + [(code, 1.0)]))
        stack.append((right, path + [(code, -1.0)]))
    if M == 0:
        P[0, :] = 0.0   # degenerate stack: leaf 0 at depth 0


def bin_index_dtype(nan_bin: int):
    """The narrowest unsigned dtype that holds every cut-bin code
    (including ``nan_bin``, the largest) — the forest's quantized
    threshold/bin domain."""
    if nan_bin <= np.iinfo(np.uint8).max:
        return np.uint8
    if nan_bin <= np.iinfo(np.uint16).max:
        return np.uint16
    return np.int32


def _round_up(x: int, m: int) -> int:
    return -(-int(x) // m) * m


def build_walk_tables(sf, sb, ic, lc, rc, lv, num_features: int):
    """Stacked [K, T, M] / [K, T, L] SoA forest -> fused-walk operands.

    The node axis (M splits + 1 dummy) and the leaf axis are padded to
    whole 8-row sublane tiles (``Mp``, ``Lp``): the kernel slices one
    tree's rows at the dynamic offset ``tree * Mp``, and the chip's
    compiler accepts a dynamic sublane offset only when it is provably
    tile-aligned.  Pad nodes are all-zero rows (they compare +1 against a
    zero path column), pad leaves carry the never-selected +1 bias.

    Returns ``(fsel, thr, icat, paths, lv_flat)``:
      fsel  [KT*Mp, F]    f32 one-hot split features (dummy/pad rows = 0)
      thr   [KT*Mp, 1]    f32 cut-bin threshold indices (exact small ints)
      icat  [KT*Mp, 1]    f32 categorical-node flags
      paths [KT, Lp, Mp]  f32 per-leaf ancestor signs / -depth column
      lv    [KT, 1, Lp]   f32 leaf values, class-major tree order
    """
    K, T, M = sf.shape
    L = M + 1
    Lp = _round_up(L, _SUBLANE)
    Mp = _round_up(M + 1, _SUBLANE)
    KT = K * T
    fsel = np.zeros((KT * Mp, num_features), np.float32)
    thr = np.zeros((KT * Mp, 1), np.float32)
    icat = np.zeros((KT * Mp, 1), np.float32)
    paths = np.zeros((KT, Lp, Mp), np.float32)
    lvf = np.zeros((KT, 1, Lp), np.float32)
    for k in range(K):
        for t in range(T):
            tt = k * T + t
            base = tt * Mp
            fsel[base + np.arange(M), sf[k, t]] = 1.0
            thr[base:base + M, 0] = sb[k, t]
            icat[base:base + M, 0] = ic[k, t]
            _leaf_paths(lc[k, t], rc[k, t], M, paths[tt])
            lvf[tt, 0, :L] = lv[k, t]
    return fsel, thr, icat, paths, lvf


def build_affine_tables(lcf, lft, num_features: int) -> np.ndarray:
    """[K, T, L, Kf] sparse leaf coeff/feat stacks -> dense [KT, Lp, F]
    per-leaf affine matrices (duplicate feature slots sum, matching the
    gather epilogue's ``(lcf * vals).sum``; the leaf axis is padded to
    ``Lp`` with zero rows like ``build_walk_tables``)."""
    K, T, L, Kf = lcf.shape
    F = num_features
    A = np.zeros((K * T * L, F), np.float32)
    rows = np.repeat(np.arange(K * T * L), Kf)
    feats = lft.reshape(-1)
    coefs = lcf.reshape(-1).astype(np.float32)
    valid = feats >= 0
    np.add.at(A, (rows[valid], feats[valid]), coefs[valid])
    A = A.reshape(K * T, L, F)
    return np.pad(A, ((0, 0), (0, _round_up(L, _SUBLANE) - L), (0, 0)))


def walk_vmem_bytes(num_class: int, trees_per_class: int, num_leaves: int,
                    num_features: int, max_cuts: int, linear: bool,
                    n_blk: int = 128) -> int:
    """Estimated VMEM residency of the fused walk's pinned operands plus
    per-block transients, with every trailing dim lane-padded to 128 —
    the ``serve_walk=auto`` sizing rule (docs/SERVING.md)."""
    lane = 128

    def pad(x: int) -> int:
        return _round_up(max(int(x), 1), lane)

    K, T = max(num_class, 1), max(trees_per_class, 1)
    L = max(num_leaves, 2)
    # L leaves, and (L - 1) nodes + 1 dummy: both tile-padded to the same
    Lp = Mp = _round_up(L, _SUBLANE)
    F, C = num_features, _round_up(max_cuts, _SUBLANE)
    KT = K * T
    b = 0
    b += 4 * KT * Mp * pad(F)            # fsel
    b += 2 * 4 * KT * Mp * lane          # thr + icat ([.., 1] lanes pad)
    b += 4 * KT * Lp * pad(Mp)           # paths
    b += 4 * KT * _SUBLANE * pad(Lp)     # lv ([1, Lp] slab = one tile row)
    b += 4 * 2 * C * pad(F)              # bnd + cats (raw variant, [C, F])
    b += 4 * F * lane                    # is_cat column
    if linear:
        b += 4 * KT * Lp * pad(F)        # aff
    # per-block transients: bins/x row block, fbin/cmp, sel/S, epilogue,
    # and the raw variant's [C, n] per-feature compare matrix
    b += 4 * pad(n_blk) * (4 * F + 4 * Mp + 4 * Lp + 2 * C)
    return int(b)


# ---------------------------------------------------------------------------
# the kernel

def _class_walk(fsel_ref, thr_ref, icat_ref, paths_ref, lv_ref, aff_ref,
                bins_f, xt, out_ref, *, K: int, T: int, Mp: int,
                n_blk: int):
    """Per-class Kahan scan over trees: the compensation order mirrors
    ``predict_binned_forest`` exactly, so per-tree contributions (which
    are bit-exact vs the gather walk) fold bit-identically too."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    dot = functools.partial(
        jax.lax.dot_general,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)

    for k in range(K):
        def tree_body(t, carry, k=k):
            acc, comp = carry
            tt = k * T + t
            # Mp is a whole number of sublane tiles, so the offset is
            # provably aligned — the only dynamic row slice Mosaic takes
            base = pl.multiple_of(tt * Mp, _SUBLANE)
            fsel_t = fsel_ref[pl.ds(base, Mp), :]          # [Mp, F]
            fbin = dot(fsel_t, bins_f)                     # [Mp, n] exact
            thr_t = thr_ref[pl.ds(base, Mp), :]            # [Mp, 1]
            icat_t = icat_ref[pl.ds(base, Mp), :]
            # numeric (<=) vs categorical (==) as +-1 f32 arithmetic:
            # the chip's compiler has no select between boolean vectors
            le = jnp.where(fbin <= thr_t, 1.0, -1.0).astype(jnp.float32)
            eq = jnp.where(fbin == thr_t, 1.0, -1.0).astype(jnp.float32)
            cmp = le + icat_t * (eq - le)
            p_t = paths_ref[tt]                            # [Lp, Mp]
            s = dot(p_t, cmp)                              # [Lp, n] exact
            sel = jnp.where(s == 0.0, 1.0, 0.0).astype(jnp.float32)
            lv_t = lv_ref[tt].astype(jnp.float32)          # [1, Lp]
            val = dot(lv_t, sel)                           # [1, n]
            if aff_ref is not None:
                z = dot(aff_ref[tt], xt)                   # [Lp, n]
                val = val + jnp.sum(sel * z, axis=0, keepdims=True)
            y = val - comp
            tot = acc + y
            comp = (tot - acc) - y
            return tot, comp

        zero = jnp.zeros((1, n_blk), jnp.float32)
        acc, _ = jax.lax.fori_loop(0, T, tree_body, (zero, zero))
        out_ref[k:k + 1, :] = acc


def _walk_kernel(*refs, K: int, T: int, Mp: int, n_blk: int,
                 raw: bool, linear: bool, nan_bin: int):
    """Grid: (row_blocks,).  Forest operands use constant index maps, so
    they stay VMEM-resident across the whole grid; only the row block
    and output move per step."""
    import jax.numpy as jnp

    it = iter(refs)
    fsel_ref, thr_ref, icat_ref, paths_ref, lv_ref = (
        next(it), next(it), next(it), next(it), next(it))
    if raw:
        bnd_ref, cats_ref, iscol_ref, x_ref = (
            next(it), next(it), next(it), next(it))
    else:
        bins_ref = next(it)
        x_ref = next(it) if linear else None
    aff_ref = next(it) if linear else None
    out_ref = next(it)
    bins_scr = next(it) if raw else None

    if raw:
        # bucketize ONCE per row block against the VMEM cut tables: the
        # same f32 searchsorted(side='left') predicate as the XLA raw
        # program (count of cuts strictly below the value), NaN -> the
        # nan bin, categorical miss -> the nan bin (routes identically
        # to the gather path's -1: neither ever equals a threshold).
        # One feature per (static) step: its cuts are a [C, 1] column of
        # the transposed tables against the feature's [1, n] row, so no
        # slice ever starts at a dynamic lane.
        x = x_ref[:, :]
        isnan = x != x
        safe = jnp.where(isnan, 0.0, x)
        iv = safe.astype(jnp.int32)
        bnd_t = bnd_ref[:, :]                              # [C, F] f32
        cats_t = cats_ref[:, :]                            # [C, F] i32
        nanb = jnp.float32(nan_bin)

        def count(mask):
            return jnp.sum(jnp.where(mask, 1.0, 0.0).astype(jnp.float32),
                           axis=0, keepdims=True)          # [1, n]

        for f in range(x.shape[0]):
            xr, ivr = safe[f:f + 1, :], iv[f:f + 1, :]
            cv = cats_t[:, f:f + 1]
            nbin = count(bnd_t[:, f:f + 1] < xr)
            cbin = jnp.where(count(cv == ivr) > 0, count(cv < ivr), nanb)
            # f32 select on the [1, 1] column flag (not a boolean select)
            isc = iscol_ref[f:f + 1, :]
            bins_scr[f:f + 1, :] = nbin + isc * (cbin - nbin)
        bins_f = jnp.where(isnan, nanb, bins_scr[:, :])
        xt = safe if linear else None
    else:
        # u8/u16 bin codes widen through int32: the chip's compiler has
        # no direct unsigned-narrow -> f32 cast
        bins_f = bins_ref[:, :].astype(jnp.int32).astype(jnp.float32)
        xt = x_ref[:, :] if linear else None

    _class_walk(fsel_ref, thr_ref, icat_ref, paths_ref, lv_ref, aff_ref,
                bins_f, xt, out_ref, K=K, T=T, Mp=Mp, n_blk=n_blk)


def _pad_cols(a, width: int):
    import jax.numpy as jnp
    pad = width - a.shape[-1]
    return jnp.pad(a, ((0, 0), (0, pad))) if pad else a


def _run_walk(tables, grid_args, grid_dtypes, const_args, *,
              num_class: int, raw: bool, nan_bin: int,
              aff=None, n_blk: int, interpret: bool):
    """Shared pallas_call assembly for both variants.  ``tables`` are
    the pinned forest operands, ``grid_args`` the per-row-block inputs
    ([F, B], last axis gridded and padded to whole blocks) and
    ``const_args`` extra VMEM-resident operands (the raw variant's cut
    tables)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    fsel, thr, icat, paths, lv = tables
    KT, _, Mp = paths.shape
    K = num_class
    if KT % K:
        raise LightGBMError(
            f"walk tables carry {KT} trees, not a multiple of "
            f"num_class={K}")
    T = KT // K
    F, B = grid_args[0].shape
    Bp = _round_up(max(B, 1), n_blk)
    grid_args = [_pad_cols(jnp.asarray(a, dt), Bp)
                 for a, dt in zip(grid_args, grid_dtypes)]

    def const(a):
        dims = tuple(a.shape)
        return pl.BlockSpec(dims, lambda i: (0,) * len(dims))

    in_specs = [const(a) for a in (fsel, thr, icat, paths, lv)]
    operands = [fsel, thr, icat, paths, lv]
    for a in const_args:
        in_specs.append(const(a))
        operands.append(a)
    for a in grid_args:
        in_specs.append(pl.BlockSpec((a.shape[0], n_blk),
                                     lambda i: (0, i)))
        operands.append(a)
    linear = aff is not None
    if linear:
        in_specs.append(const(aff))
        operands.append(aff)

    out = pl.pallas_call(
        functools.partial(_walk_kernel, K=K, T=T, Mp=Mp, n_blk=n_blk,
                          raw=raw, linear=linear, nan_bin=nan_bin),
        grid=(Bp // n_blk,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((K, n_blk), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((K, Bp), jnp.float32),
        # the raw variant assembles its [F, n] bin block row by row
        scratch_shapes=([pltpu.VMEM((F, n_blk), jnp.float32)]
                        if raw else []),
        interpret=interpret,
        # a device event of the kernel reads %forest_walk.N whatever
        # function traced the call (obs/devtrace.py)
        name="forest_walk",
    )(*operands)
    return out[:, :B]


def forest_walk(fsel, thr, icat, paths, lv, bins, *, num_class: int,
                nan_bin: int, aff=None, xt=None, n_blk: int = 128,
                interpret: bool = False):
    """Fused all-trees walk on pre-binned rows.

    ``bins`` [F, B] cut-bin codes in the forest's quantized bin domain
    (u8/u16/i32; categorical misses already remapped to ``nan_bin``).
    Linear forests pass ``aff`` [KT, Lp, F] and ``xt`` [F, B] f32
    NaN-imputed covariates.  Returns [num_class, B] f32 raw scores."""
    grid_args, grid_dtypes = [bins], [bins.dtype]
    if aff is not None:
        import jax.numpy as jnp
        grid_args.append(xt)
        grid_dtypes.append(jnp.float32)
    return _run_walk((fsel, thr, icat, paths, lv), grid_args, grid_dtypes,
                     (), num_class=num_class, raw=False, nan_bin=nan_bin,
                     aff=aff, n_blk=n_blk, interpret=interpret)


def forest_walk_raw(fsel, thr, icat, paths, lv, bnd, cats, is_cat_col, X,
                    *, num_class: int, nan_bin: int,
                    aff=None, n_blk: int = 128, interpret: bool = False):
    """Fused bucketize-and-walk on raw floats (the serving hot path).

    ``X`` [F, B] f32 raw features (NaN allowed), ``bnd`` [F, C] f32
    numeric cut values (+inf pad), ``cats`` [F, C] i32 category codes
    (sentinel pad), ``is_cat_col`` [F, 1] f32 flags.  Rows bucketize
    once per row block inside the kernel, which reads the cut tables
    transposed ([C, F], cut axis padded to whole sublane tiles with the
    same never-counted pad values).  Returns [num_class, B] f32 raw
    scores."""
    import jax.numpy as jnp
    bnd = jnp.asarray(bnd, jnp.float32)
    cats = jnp.asarray(cats, jnp.int32)
    pad = _round_up(bnd.shape[1], _SUBLANE) - bnd.shape[1]
    bnd_t = jnp.pad(bnd.T, ((0, pad), (0, 0)), constant_values=jnp.inf)
    cats_t = jnp.pad(cats.T, ((0, pad), (0, 0)),
                     constant_values=np.iinfo(np.int32).max)
    return _run_walk((fsel, thr, icat, paths, lv), [X], [jnp.float32],
                     (bnd_t, cats_t, jnp.asarray(is_cat_col, jnp.float32)),
                     num_class=num_class, raw=True, nan_bin=nan_bin,
                     aff=aff, n_blk=n_blk, interpret=interpret)
