"""Freeze a trained booster into an immutable, device-resident forest.

``Booster.predict`` historically walked trees one at a time through
per-tree Python loops (host walk) or re-jitted ``ops/predict.py`` forest
programs that specialize on every new batch shape.  Inference throughput
on accelerators comes from the opposite shape (XGBoost: Mitchell &
Frank, arXiv:1806.11248; Booster: He et al., arXiv:2011.02022): a frozen
structure-of-arrays forest traversed data-parallel in one fused program.

``CompiledForest`` is that artifact:

- every tree is padded to a common leaf count and stacked into
  ``[num_class, T, L]`` SoA tensors (1-leaf trees use the absorbing
  ``left=right=~0`` encoding so the same walk handles them);
- feature *cut tables* are derived from the forest's own split
  thresholds (sorted unique thresholds per feature), NOT from the
  training bin mappers — so loaded model files compile too, and the
  tables are as small as the forest actually needs.  ``value <= t`` is
  exactly ``searchsorted(cuts, value, 'left') <= index(t)`` for sorted
  unique cuts, so integer bin compares reproduce the host walk's double
  compares bit-for-bit when binning runs on the host in f64;
- one fused jit does raw-float -> cut lookup, the all-tree absorbing
  walk, and the objective's output transform (sigmoid / softmax /
  identity) in a single compile per bucket size (the serving hot path;
  its on-device binning compares in f32 — rows closer to a threshold
  than f32 resolution may route differently from the f64 host compare,
  the standard fp32-inference trade documented in docs/SERVING.md);
- batch shapes are bucketed through ``serve/batcher.py``'s ladder, and
  ``warmup()`` pre-compiles every bucket so arbitrary request sizes
  never hit XLA on the hot path.  Per-bucket compile counters land in
  the obs registry (``serve_forest_compiles_bucket_<B>`` /
  ``predict_forest_compiles_bucket_<B>``);
- two WALK STRATEGIES serve the same artifact (``serve_walk`` param,
  docs/SERVING.md): ``gather`` is the XLA per-level gather walk above;
  ``fused`` routes through ``ops/pallas_walk.py``'s Pallas kernel that
  pins the SoA forest in VMEM and walks all trees per row block in one
  pass (programs ``predict_forest_walk`` / ``serve_forest_walk``).
  ``auto`` picks fused on TPU when the forest's estimated VMEM
  footprint fits.  Every predict entry point routes through
  ``_dispatch_binned`` / ``_dispatch_raw`` (enforced by graftcheck rule
  ``serve-strategy-parity``), so replicas, warmup, fleet dispatch and
  hedging gate the strategy with zero extra plumbing — and
  ``serve_walk=gather`` keeps programs and outputs byte-identical to
  the pre-strategy artifact.

``Booster.compile()`` / the large-array fast path in
``Booster._predict_array`` feed host-binned (f64-exact) bins to the same
stacked walk, so offline batch predict and the serving path share one
artifact and one compiled program universe.
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from .. import obs
from ..utils import device, log
from ..utils.log import LightGBMError
from .batcher import BucketLadder, CountingJit, pad_rows

_I32_SENTINEL = np.iinfo(np.int32).max


def _tree_class_lists(models, num_class: int, n_models: int):
    """Class-major model rows -> per-class tree lists (row i is class
    i % num_class, like the reference's class-major model vector)."""
    return [[models[i] for i in range(n_models) if i % num_class == k]
            for k in range(num_class)]


def build_cut_tables(trees) -> Tuple[Dict[int, np.ndarray],
                                     Dict[int, np.ndarray]]:
    """Per-feature sorted unique split thresholds across the forest.

    Returns ``(numerical, categorical)`` keyed by real feature index;
    numerical tables are f64 threshold values, categorical tables are
    the int64 category codes the host walk compares with
    (``int64(value) == int64(threshold)``)."""
    num: Dict[int, set] = {}
    cat: Dict[int, set] = {}
    for tree in trees:
        n = tree.num_leaves - 1
        for i in range(n):
            f = int(tree.split_feature[i])
            if int(tree.decision_type[i]) == 1:
                cat.setdefault(f, set()).add(int(np.int64(tree.threshold[i])))
            else:
                num.setdefault(f, set()).add(float(tree.threshold[i]))
    both = set(num) & set(cat)
    if both:
        raise LightGBMError(
            f"features {sorted(both)} carry both numerical and categorical "
            f"splits; cannot build a single cut table per feature")
    return ({f: np.asarray(sorted(v), np.float64) for f, v in num.items()},
            {f: np.asarray(sorted(v), np.int64) for f, v in cat.items()})


def stack_class_trees(trees, num_leaves: int, cuts_num, cuts_cat):
    """Stack one class's trees into SoA arrays ``[T, L-1]`` / ``[T, L]``.

    ``split_bin`` holds each node's threshold INDEX in its feature's cut
    table; 1-leaf trees get the absorbing ``left=right=~0`` node so the
    shared walk terminates them at leaf 0."""
    T = len(trees)
    L = max(num_leaves, 2)
    M = L - 1
    sf = np.zeros((T, M), np.int32)
    sb = np.zeros((T, M), np.int32)
    ic = np.zeros((T, M), bool)
    lc = np.full((T, M), ~0, np.int32)
    rc = np.full((T, M), ~0, np.int32)
    lv = np.zeros((T, L), np.float32)
    for t, tree in enumerate(trees):
        k = tree.num_leaves - 1
        if k <= 0:
            lv[t, 0] = tree.leaf_value[0] if tree.num_leaves else 0.0
            continue
        sf[t, :k] = tree.split_feature[:k]
        ic[t, :k] = tree.decision_type[:k] == 1
        for i in range(k):
            f = int(tree.split_feature[i])
            if ic[t, i]:
                sb[t, i] = int(np.searchsorted(
                    cuts_cat[f], np.int64(tree.threshold[i])))
            else:
                sb[t, i] = int(np.searchsorted(
                    cuts_num[f], np.float64(tree.threshold[i])))
        lc[t, :k] = tree.left_child[:k]
        rc[t, :k] = tree.right_child[:k]
        lv[t, :tree.num_leaves] = tree.leaf_value[:tree.num_leaves]
    return sf, sb, ic, lc, rc, lv


def stack_class_linear(trees, num_leaves: int, linear_k: int):
    """Stack one class's per-leaf affine tables into ``[T, L, Kf]``
    coeff (f32) / feat (i32 REAL feature indices, -1 pad) arrays
    (docs/LINEAR_TREES.md).  Constant trees contribute all-zero rows, so
    the shared epilogue is a no-op for them."""
    T = len(trees)
    L = max(num_leaves, 2)
    kf = max(linear_k, 1)
    lcf = np.zeros((T, L, kf), np.float32)
    lft = np.full((T, L, kf), -1, np.int32)
    for t, tree in enumerate(trees):
        if not tree.has_linear():
            continue
        nl, tk = tree.leaf_coeff.shape
        lcf[t, :nl, :tk] = tree.leaf_coeff
        lft[t, :nl, :tk] = tree.leaf_feat
    return lcf, lft


class CompiledForest:
    """Immutable inference artifact: stacked SoA forest + cut tables +
    shape-bucketed compiled programs.  Build with :meth:`from_booster`."""

    # class-level defaults so pickled/pre-drift instances behave:
    # data_fingerprint is the training-data summary riding the artifact,
    # _drift the (shared) serve-side DriftCollector hook — None = off;
    # pre-strategy pickles serve via the gather walk with f32 leaves
    data_fingerprint = None
    _drift = None
    walk_strategy = "gather"
    leaf_dtype = "float32"
    _walk_dev = None
    _walk_aff_dev = None

    #: documented bound on the fused walk's quantized-leaf output error
    #: (docs/SERVING.md): ``serve_quantize_leaves`` only sticks when the
    #: worst-case bf16 leaf-rounding perturbation stays within it
    QUANTIZE_LEAF_ATOL = 1e-3

    def __init__(self):
        raise TypeError("use CompiledForest.from_booster()")

    @classmethod
    def from_booster(cls, booster, num_iteration: int = -1,
                     buckets: Optional[Sequence[int]] = None,
                     serve_walk: Optional[str] = None,
                     quantize_leaves: Optional[bool] = None
                     ) -> "CompiledForest":
        """Freeze ``booster`` (a ``Booster`` or a ``models/gbdt.py``
        engine) into a CompiledForest.  ``num_iteration`` limits the
        forest like ``Booster.predict``; ``buckets`` overrides the batch
        bucket ladder (default: powers of two, 16..65536).

        ``serve_walk`` picks the walk strategy (``auto``/``fused``/
        ``gather``; None reads the booster's config, defaulting to
        ``auto``) and ``quantize_leaves`` opts fused leaf tables into
        bf16 storage behind the :data:`QUANTIZE_LEAF_ATOL` pin
        (docs/SERVING.md)."""
        import jax.numpy as jnp

        b = getattr(booster, "_booster", booster)
        models = list(b.models)
        K = max(int(b.num_class), 1)
        n_models = len(models)
        if num_iteration > 0:
            n_models = min(n_models, num_iteration * K)
        models = models[:n_models]
        self = object.__new__(cls)
        self.num_class = K
        self.num_features = int(b.max_feature_idx) + 1
        self.num_trees = n_models
        self.num_leaves = max([t.num_leaves for t in models] + [2])
        self.sigmoid = float(getattr(b, "sigmoid", -1.0) or -1.0)
        self.transform = ("softmax" if K > 1
                          else "sigmoid" if self.sigmoid > 0 else "identity")
        self.ladder = BucketLadder(buckets)

        # -- piece-wise linear forest? (docs/LINEAR_TREES.md)  Kept as a
        # build-time property: constant forests keep the exact pre-linear
        # program signatures (and compile-ledger identity).
        self._has_linear = any(t.has_linear() for t in models)
        self.linear_k = (max([t.leaf_feat.shape[1] for t in models
                              if t.has_linear()] or [1])
                         if self._has_linear else 0)

        # -- cut tables (host f64/int64 exact + device f32/int32 copies)
        self._cuts_num, self._cuts_cat = build_cut_tables(models)
        F = self.num_features
        for f in list(self._cuts_num) + list(self._cuts_cat):
            if f >= F:       # loaded model with max_feature_idx unset/low
                F = self.num_features = f + 1
        for t in models:     # affine covariates widen the matrix too
            if t.has_linear() and int(t.leaf_feat.max(initial=-1)) >= F:
                F = self.num_features = int(t.leaf_feat.max()) + 1
        self.max_cuts = max(
            [len(v) for v in self._cuts_num.values()]
            + [len(v) for v in self._cuts_cat.values()] + [1])
        self._nan_bin = np.int32(self.max_cuts + 1)   # > any threshold index
        bnd = np.full((F, self.max_cuts), np.inf, np.float32)
        cats = np.full((F, self.max_cuts), _I32_SENTINEL, np.int32)
        is_cat = np.zeros(F, bool)
        for f, v in self._cuts_num.items():
            bnd[f, :len(v)] = v.astype(np.float32)
        for f, v in self._cuts_cat.items():
            cats[f, :len(v)] = np.clip(v, -2**31, _I32_SENTINEL - 1)
            is_cat[f] = True
        self._bnd_dev = jnp.asarray(bnd)
        self._cats_dev = jnp.asarray(cats)
        self._is_cat_dev = jnp.asarray(is_cat)
        self._is_cat_feat = is_cat

        # -- stacked SoA trees: [K, T, L-1] / [K, T, L]
        per_class = _tree_class_lists(models, K, n_models)
        T = max([len(ts) for ts in per_class] + [0])
        zero = _zero_tree(self.num_leaves)
        stacks = []
        for ts in per_class:
            arrs = stack_class_trees(ts, self.num_leaves,
                                     self._cuts_num, self._cuts_cat)
            if len(ts) < T:    # ragged tail: pad with absorbing 0-trees
                arrs = tuple(
                    np.concatenate([a, np.repeat(z, T - len(ts), axis=0)],
                                   axis=0)
                    for a, z in zip(arrs, zero))
            stacks.append(arrs)
        self.trees_per_class = T
        stacked = tuple(np.stack([s[i] for s in stacks], axis=0)
                        for i in range(6))
        self._tree_dev = tuple(jnp.asarray(a) for a in stacked)
        self._lin_dev = None
        lin_stacked = None
        if self._has_linear:
            lin_stacks = []
            for ts in per_class:
                lcf, lft = stack_class_linear(ts, self.num_leaves,
                                              self.linear_k)
                if len(ts) < T:   # ragged tail: all-zero epilogue rows
                    pad = T - len(ts)
                    lcf = np.concatenate(
                        [lcf, np.zeros((pad,) + lcf.shape[1:],
                                       np.float32)], axis=0)
                    lft = np.concatenate(
                        [lft, np.full((pad,) + lft.shape[1:], -1,
                                      np.int32)], axis=0)
                lin_stacks.append((lcf, lft))
            lin_stacked = tuple(np.stack([s[i] for s in lin_stacks],
                                         axis=0) for i in range(2))
            self._lin_dev = tuple(jnp.asarray(a) for a in lin_stacked)
        # default placement (first local device); serve/fleet.py pins
        # per-replica copies with to_device()
        self.device = None
        obs.devprof.transfer(
            "h2d", "forest",
            int(bnd.nbytes) + int(cats.nbytes) + int(is_cat.nbytes)
            + sum(int(a.nbytes) for a in self._tree_dev)
            + sum(int(a.nbytes) for a in (self._lin_dev or ())),
            transfers=3 + len(self._tree_dev)
            + len(self._lin_dev or ()))
        obs.inc("forest_compile_artifacts")
        obs.set_gauge("forest_trees", int(n_models))
        obs.set_gauge("forest_leaves_padded", int(self.num_leaves))

        # drift observatory (obs/drift.py): the training fingerprint
        # rides from the booster's artifact; ``_drift`` is the serve
        # collector hook — None (drift=off) keeps the predict path at
        # exactly one attribute read and zero new programs
        self.data_fingerprint = getattr(b, "data_fingerprint", None)
        # pre-publication: from_booster owns the instance exclusively
        self._drift = None   # graftcheck: disable=lock-shared-attr

        # -- fused programs (one compile per bucket size)
        self._binned_jit = CountingJit(self._make_binned_fn(),
                                       "predict_forest")
        self._raw_jit = CountingJit(self._make_raw_fn(), "serve_forest")

        # -- walk strategy (docs/SERVING.md): gather keeps everything
        # above byte-identical (no new arrays, jits, or programs); fused
        # additionally builds the Pallas walk operands + its own
        # bucket-keyed programs
        cfg = getattr(booster, "config", None)
        if serve_walk is None:
            serve_walk = str(getattr(cfg, "serve_walk", "auto") or "auto")
        if quantize_leaves is None:
            quantize_leaves = bool(getattr(cfg, "serve_quantize_leaves",
                                           False))
        if serve_walk not in ("auto", "fused", "gather"):
            raise LightGBMError(
                f"serve_walk must be auto, fused or gather "
                f"(got {serve_walk!r})")
        self.serve_walk_requested = serve_walk
        self._quantize_requested = bool(quantize_leaves)
        self.walk_strategy = self._resolve_walk_strategy()
        if self.walk_strategy == "fused":
            self._build_fused_walk(stacked, lin_stacked)
        return self

    # ------------------------------------------------------------------
    # fused walk strategy (ops/pallas_walk.py)
    def walk_vmem_bytes(self) -> int:
        """Estimated VMEM residency of the fused walk's operands — the
        ``serve_walk=auto`` sizing input (docs/SERVING.md)."""
        from ..ops.pallas_walk import walk_vmem_bytes
        return walk_vmem_bytes(self.num_class, self.trees_per_class,
                               self.num_leaves, self.num_features,
                               self.max_cuts, self._has_linear)

    def _resolve_walk_strategy(self) -> str:
        """``fused``/``gather`` from the requested mode: ``auto`` takes
        the kernel only on TPU and only when the pinned operands fit the
        VMEM budget (``LIGHTGBM_TPU_WALK_VMEM_BYTES``, default 8 MiB of
        the ~16 MiB/core)."""
        req = self.serve_walk_requested
        if req != "auto":
            return req
        if not device.on_tpu():
            return "gather"
        budget = int(os.environ.get("LIGHTGBM_TPU_WALK_VMEM_BYTES",
                                    8 << 20))
        return "fused" if self.walk_vmem_bytes() <= budget else "gather"

    def _build_fused_walk(self, stacked, lin_stacked) -> None:
        """Freeze-time fused-walk operands + per-strategy programs."""
        import jax.numpy as jnp
        from ..ops.pallas_walk import (bin_index_dtype, build_affine_tables,
                                       build_walk_tables)

        sf, sb, ic, lc, rc, lv = stacked
        fsel, thr, icat, paths, lvf = build_walk_tables(
            sf, sb, ic, lc, rc, lv, self.num_features)
        self._bin_dtype = bin_index_dtype(int(self._nan_bin))
        self.leaf_dtype = "float32"
        lv_dtype = jnp.float32
        if self._quantize_requested:
            # atol pin: every row takes exactly ONE leaf per tree, so
            # the bf16-storage output perturbation is bounded by the
            # per-class sum over trees of the max per-leaf rounding
            # error.  Past QUANTIZE_LEAF_ATOL the forest stays f32 and
            # the named fallback counter records why.
            lv_q = np.asarray(jnp.asarray(lvf, jnp.bfloat16)
                              .astype(jnp.float32))
            per_tree = np.abs(lv_q - lvf).max(axis=(1, 2))
            bound = float(per_tree.reshape(
                self.num_class, self.trees_per_class).sum(axis=1).max()
                if per_tree.size else 0.0)
            if bound <= self.QUANTIZE_LEAF_ATOL:
                self.leaf_dtype = "bfloat16"
                lv_dtype = jnp.bfloat16
            else:
                obs.inc("forest_quantize_fallback")
        self._walk_dev = (jnp.asarray(fsel), jnp.asarray(thr),
                          jnp.asarray(icat), jnp.asarray(paths),
                          jnp.asarray(lvf, lv_dtype))
        self._walk_aff_dev = None
        if self._has_linear:
            lcf, lft = lin_stacked
            aff = build_affine_tables(lcf, lft, self.num_features)
            self._walk_aff_dev = jnp.asarray(aff)
        self._is_cat_col_dev = jnp.asarray(
            self._is_cat_feat.astype(np.float32)[:, None])
        # off the chip an explicit serve_walk=fused runs the kernel in
        # the Pallas interpreter (how the CPU tests pin parity) — said
        # once and reported by info(), never a silent stand-in
        self._walk_interpret = not device.on_tpu()
        if self._walk_interpret:
            log.warn_once(
                "serve_walk_interpreted",
                "serve_walk=fused without a TPU: the walk kernel runs in "
                "the Pallas interpreter (correctness only, not a serving "
                "speed)")
        obs.devprof.transfer(
            "h2d", "forest",
            sum(int(a.nbytes) for a in self._walk_dev)
            + int(self._is_cat_col_dev.nbytes)
            + (int(self._walk_aff_dev.nbytes)
               if self._walk_aff_dev is not None else 0),
            transfers=len(self._walk_dev) + 1
            + (1 if self._walk_aff_dev is not None else 0))
        obs.inc("forest_walk_fused_builds")
        self._walk_binned_jit = CountingJit(self._make_walk_binned_fn(),
                                            "predict_forest_walk")
        self._walk_raw_jit = CountingJit(self._make_walk_raw_fn(),
                                         "serve_forest_walk")

    def _make_walk_binned_fn(self):
        import jax
        import jax.numpy as jnp
        from ..ops.pallas_walk import forest_walk

        nan_bin = int(self._nan_bin)
        K = self.num_class
        interp = self._walk_interpret

        if self._has_linear:
            def walk_lin_fn(walk_dev, aff, bins, mask, xt):
                fsel, thr, icat, paths, lv = walk_dev
                raw = forest_walk(fsel, thr, icat, paths, lv, bins,
                                  num_class=K, nan_bin=nan_bin, aff=aff,
                                  xt=xt, interpret=interp)
                return jnp.where(mask[None, :], raw, 0.0)
            # ledgered by the CountingJit wrapper (predict_forest_walk)
            return jax.jit(walk_lin_fn)  # graftcheck: disable=jit-raw

        def walk_fn(walk_dev, bins, mask):
            fsel, thr, icat, paths, lv = walk_dev
            raw = forest_walk(fsel, thr, icat, paths, lv, bins,
                              num_class=K, nan_bin=nan_bin,
                              interpret=interp)
            return jnp.where(mask[None, :], raw, 0.0)
        # ledgered by the CountingJit wrapper (predict_forest_walk)
        return jax.jit(walk_fn)  # graftcheck: disable=jit-raw

    def _make_walk_raw_fn(self):
        import jax
        import jax.numpy as jnp
        from ..ops.pallas_walk import forest_walk_raw

        nan_bin = int(self._nan_bin)
        K = self.num_class
        interp = self._walk_interpret

        def walk_raw_fn(walk_dev, bnd, cats, iscol, X, mask, aff=None):
            fsel, thr, icat, paths, lv = walk_dev
            raw = forest_walk_raw(fsel, thr, icat, paths, lv, bnd, cats,
                                  iscol, X.T, num_class=K,
                                  nan_bin=nan_bin, aff=aff,
                                  interpret=interp)
            raw = jnp.where(mask[None, :], raw, 0.0)
            out = self._transform(raw)
            out = jnp.where(mask[None, :], out, 0.0)
            return raw, out
        # ledgered by the CountingJit wrapper (serve_forest_walk)
        return jax.jit(walk_raw_fn)  # graftcheck: disable=jit-raw

    # ------------------------------------------------------------------
    # fused programs
    def _walk(self, tree_dev, bins, lin_dev=None, xt=None):
        """Per-class Kahan forest sums on ``bins`` [F, B] -> [K, B].

        For a linear forest ``lin_dev`` carries the [K, T, L, Kf]
        coeff/feat stacks and ``xt`` the [F, B] f32 raw covariates (NaN
        pre-imputed to 0.0): the walk gains the per-leaf dot-product
        epilogue (docs/LINEAR_TREES.md) via the separate linear entry
        point, leaving constant forests' programs untouched."""
        import jax
        import jax.numpy as jnp
        from ..ops.predict import (predict_binned_forest,
                                   predict_binned_forest_linear)

        sf, sb, ic, lc, rc, lv = tree_dev
        if lin_dev is not None:
            lcf, lft = lin_dev
            with jax.named_scope("linear_fit"):
                outs = [predict_binned_forest_linear(
                            sf[k], sb[k], ic[k], lc[k], rc[k], lv[k],
                            lcf[k], lft[k], bins, xt, self.num_leaves)
                        for k in range(self.num_class)]
                return jnp.stack(outs, axis=0)
        with jax.named_scope("forest_walk"):
            outs = [predict_binned_forest(sf[k], sb[k], ic[k], lc[k],
                                          rc[k], lv[k], bins,
                                          self.num_leaves)
                    for k in range(self.num_class)]
            return jnp.stack(outs, axis=0)

    def _transform(self, raw):
        """The objective's output transform, fused into the program."""
        import jax
        import jax.numpy as jnp
        with jax.named_scope("transform"):
            if self.transform == "softmax":
                e = jnp.exp(raw - raw.max(axis=0, keepdims=True))
                return e / e.sum(axis=0, keepdims=True)
            if self.transform == "sigmoid":
                return 1.0 / (1.0 + jnp.exp(-self.sigmoid * raw))
            return raw

    def _make_binned_fn(self):
        import jax
        import jax.numpy as jnp

        if self._has_linear:
            # linear forests carry the coeff/feat stacks plus the raw
            # f32 covariates [F, B] (NaN pre-imputed on the host) into
            # the program; constant forests keep the exact pre-linear
            # signature below so their traced programs stay identical
            def binned_lin_fn(tree_dev, bins, mask, lin_dev, xt):
                raw = self._walk(tree_dev, bins, lin_dev, xt)
                raw = jnp.where(mask[None, :], raw, 0.0)
                return raw
            return jax.jit(binned_lin_fn)  # graftcheck: disable=jit-raw

        def binned_fn(tree_dev, bins, mask):
            raw = self._walk(tree_dev, bins)
            raw = jnp.where(mask[None, :], raw, 0.0)
            return raw
        # ledgered by the CountingJit wrapper built right above in
        # from_booster/to_device (program "predict_forest")
        return jax.jit(binned_fn)  # graftcheck: disable=jit-raw

    def _make_raw_fn(self):
        import jax
        import jax.numpy as jnp

        has_linear = self._has_linear

        def raw_fn(tree_dev, bnd, cats, is_cat, X, mask, lin_dev=None):
            # raw floats [B, F] -> cut-table bins [F, B], on device
            with jax.named_scope("bin_lookup"):
                Xt = X.T
                isnan = jnp.isnan(Xt)
                safe = jnp.where(isnan, 0.0, Xt)
                nbin = jax.vmap(
                    lambda c, v: jnp.searchsorted(c, v, side="left"))(
                        bnd, safe).astype(jnp.int32)
                nbin = jnp.where(isnan, self._nan_bin, nbin)
                iv = safe.astype(jnp.int32)
                j = jax.vmap(
                    lambda c, v: jnp.searchsorted(c, v, side="left"))(
                        cats, iv).astype(jnp.int32)
                jc = jnp.minimum(j, cats.shape[1] - 1)
                hit = jnp.take_along_axis(cats, jc, axis=1) == iv
                cbin = jnp.where(hit & ~isnan, jc, -1)
                bins = jnp.where(is_cat[:, None], cbin, nbin)
            if has_linear:
                # the NaN-imputed transpose already built for binning IS
                # the affine covariate matrix [F, B] — no second feed
                raw = self._walk(tree_dev, bins, lin_dev,
                                 safe.astype(jnp.float32))
            else:
                raw = self._walk(tree_dev, bins)
            raw = jnp.where(mask[None, :], raw, 0.0)
            out = self._transform(raw)
            out = jnp.where(mask[None, :], out, 0.0)
            return raw, out
        # ledgered by the CountingJit wrapper built right above
        # (program "serve_forest")
        return jax.jit(raw_fn)  # graftcheck: disable=jit-raw

    # ------------------------------------------------------------------
    # host-side exact binning (f64 compares, identical routing to the
    # host tree walk; feeds the binned program)
    def bin_rows(self, X: np.ndarray) -> np.ndarray:
        """[N, F] raw f64 -> [F, N] int32 cut-table bins (exact)."""
        N = X.shape[0]
        bins = np.zeros((self.num_features, N), np.int32)
        for f, cuts in self._cuts_num.items():
            col = X[:, f]
            isnan = np.isnan(col)
            b = np.searchsorted(cuts, np.where(isnan, 0.0, col),
                                side="left")
            bins[f] = np.where(isnan, self._nan_bin, b)
        for f, cats in self._cuts_cat.items():
            col = X[:, f]
            isnan = np.isnan(col)
            iv = np.where(isnan, 0, col).astype(np.int64)
            j = np.searchsorted(cats, iv, side="left")
            jc = np.minimum(j, len(cats) - 1)
            hit = (cats[jc] == iv) & ~isnan
            bins[f] = np.where(hit, jc, -1)
        return bins

    def host_transform(self, raw: np.ndarray) -> np.ndarray:
        """The same output transform as the fused program, in host f64.
        Delegates to the prediction objective (models/gbdt.py) so the
        host formula has exactly one source."""
        from ..models.gbdt import _objective_for_prediction
        obj = _objective_for_prediction(
            self.transform,
            self.sigmoid if self.transform == "sigmoid" else -1.0,
            self.num_class)
        return np.asarray(obj.convert_output(np.asarray(raw)))

    # ------------------------------------------------------------------
    def _check_width(self, X: np.ndarray) -> np.ndarray:
        if X.ndim != 2:
            X = np.atleast_2d(X)
        if X.shape[1] < self.num_features:
            raise LightGBMError(
                f"input has {X.shape[1]} features; the forest needs "
                f"{self.num_features}")
        return X[:, :self.num_features]

    # ------------------------------------------------------------------
    # strategy dispatch: these two methods are the ONLY call sites of
    # the per-strategy jits — every predict entry point routes through
    # them so fused/gather stay interchangeable everywhere (replicas,
    # warmup, fleet, hedging).  graftcheck rule serve-strategy-parity
    # flags any new direct jit call that bypasses them.
    def _dispatch_binned(self, bucket, bins, mask, xt=None):
        """Host-binned [K, B] raw scores for one padded bucket."""
        if self.walk_strategy == "fused":
            # fused programs take bins in the quantized cut-bin domain:
            # categorical misses (-1) remap to the nan bin, which routes
            # identically (neither ever equals a threshold index)
            bins_q = np.where(bins < 0, self._nan_bin,
                              bins).astype(self._bin_dtype)
            if self._has_linear:
                return self._walk_binned_jit(bucket, self._walk_dev,
                                             self._walk_aff_dev, bins_q,
                                             mask, xt)
            return self._walk_binned_jit(bucket, self._walk_dev, bins_q,
                                         mask)
        if self._has_linear:
            return self._binned_jit(bucket, self._tree_dev, bins, mask,
                                    self._lin_dev, xt)
        return self._binned_jit(bucket, self._tree_dev, bins, mask)

    def _dispatch_raw(self, bucket, Xp, mask):
        """(raw, transformed) for one padded f32 bucket (serving path:
        on-device binning fused into the program)."""
        if self.walk_strategy == "fused":
            if self._has_linear:
                return self._walk_raw_jit(bucket, self._walk_dev,
                                          self._bnd_dev, self._cats_dev,
                                          self._is_cat_col_dev, Xp, mask,
                                          self._walk_aff_dev)
            return self._walk_raw_jit(bucket, self._walk_dev,
                                      self._bnd_dev, self._cats_dev,
                                      self._is_cat_col_dev, Xp, mask)
        if self._has_linear:
            return self._raw_jit(bucket, self._tree_dev, self._bnd_dev,
                                 self._cats_dev, self._is_cat_dev, Xp,
                                 mask, self._lin_dev)
        return self._raw_jit(bucket, self._tree_dev, self._bnd_dev,
                             self._cats_dev, self._is_cat_dev, Xp, mask)

    def raw_scores(self, X) -> np.ndarray:
        """[K, N] f64 raw scores via host-exact binning + the stacked
        walk, bucketed so repeat calls never re-specialize on N."""
        X = self._check_width(np.asarray(X, np.float64))
        N = X.shape[0]
        if N == 0 or self.num_trees == 0:
            return np.zeros((self.num_class, N), np.float64)
        parts = []
        for off, n, bucket in self.ladder.chunks(N):
            Xp, mask = pad_rows(X[off:off + n], bucket)
            bins = self.bin_rows(Xp)
            obs.devprof.transfer("h2d", "serve",
                                 int(np.asarray(bins).nbytes))
            with obs.span("Predict::forest"):
                if self._has_linear:
                    # affine covariates: the same padded rows, NaN->0
                    # f32, [F, B] (docs/LINEAR_TREES.md)
                    xt = np.where(np.isnan(Xp), 0.0,
                                  Xp).T.astype(np.float32)
                    obs.devprof.transfer("h2d", "serve", int(xt.nbytes))
                    raw = self._dispatch_binned(bucket, bins, mask, xt)
                else:
                    raw = self._dispatch_binned(bucket, bins, mask)
            obs.devprof.transfer("d2h", "serve", int(raw.nbytes))
            parts.append(np.asarray(raw, np.float64)[:, :n])
        raw_all = np.concatenate(parts, axis=1)
        col = self._drift
        if col is not None:
            col.offer(X, raw_all)
        return raw_all

    def _device_scores(self, X) -> Tuple[np.ndarray, np.ndarray]:
        """(raw, transformed) [K, N] f32 via the fully fused raw-float
        program (serving hot path; on-device f32 binning)."""
        X = self._check_width(np.asarray(X, np.float32))
        N = X.shape[0]
        if N == 0 or self.num_trees == 0:
            z = np.zeros((self.num_class, N), np.float32)
            return z, self.host_transform(z.astype(np.float64))
        raws, outs = [], []
        for off, n, bucket in self.ladder.chunks(N):
            Xp, mask = pad_rows(X[off:off + n], bucket)
            obs.devprof.transfer("h2d", "serve",
                                 int(Xp.nbytes) + int(mask.nbytes))
            with obs.span("Predict::forest"):
                raw, out = self._dispatch_raw(bucket, Xp, mask)
            obs.devprof.transfer("d2h", "serve",
                                 int(raw.nbytes) + int(out.nbytes))
            raws.append(np.asarray(raw)[:, :n])
            outs.append(np.asarray(out)[:, :n])
        raw_all = np.concatenate(raws, axis=1)
        out_all = np.concatenate(outs, axis=1)
        # drift hook: REAL (unpadded) rows + raw margins, off the device
        # path — drift=off is this one attribute read (ledger-pinned)
        col = self._drift
        if col is not None:
            col.offer(X, raw_all)
        return (raw_all, out_all)

    def predict(self, X, raw_score: bool = False,
                device_binning: bool = False) -> np.ndarray:
        """Predictions shaped like ``Booster.predict``: ``[N]`` for one
        class, ``[N, K]`` for multiclass.  ``device_binning`` selects the
        fully fused raw-float program (f32 binning, in-jit transform —
        the serving path); the default bins on the host in f64, with the
        transform in f64, for exact parity with ``Booster.predict``."""
        if device_binning:
            raw, out = self._device_scores(X)
            res = raw if raw_score else out
        else:
            raw = self.raw_scores(X)
            res = raw if raw_score else self.host_transform(raw)
        res = np.asarray(res)
        return res[0] if res.shape[0] == 1 else res.T

    def batched_fn(self):
        """``rows -> (raw, transformed)`` [K, n] callable for the
        micro-batcher (device-binned serving path)."""
        return self._device_scores

    # ------------------------------------------------------------------
    def to_device(self, device) -> "CompiledForest":
        """A copy of this forest pinned to ``device``: the SoA tree
        stacks and cut tables are ``jax.device_put`` there explicitly,
        and the two fused programs get FRESH jit wrappers so each
        replica compiles (and ``warmup()``s) its own executables for its
        own device.  Because the device arrays are committed, the
        host-numpy request rows follow them — a hot swap that warmed the
        new forest through this path never pays a first-request
        cross-device transfer or compile (serve/fleet.py; the reload
        test asserts zero post-swap compile-ledger events)."""
        import jax

        clone = object.__new__(CompiledForest)
        clone.__dict__.update(self.__dict__)
        clone.device = device
        clone._tree_dev = tuple(jax.device_put(a, device)
                                for a in self._tree_dev)
        clone._bnd_dev = jax.device_put(self._bnd_dev, device)
        clone._cats_dev = jax.device_put(self._cats_dev, device)
        clone._is_cat_dev = jax.device_put(self._is_cat_dev, device)
        if self._lin_dev is not None:
            clone._lin_dev = tuple(jax.device_put(a, device)
                                   for a in self._lin_dev)
        clone._binned_jit = CountingJit(clone._make_binned_fn(),
                                        "predict_forest")
        clone._raw_jit = CountingJit(clone._make_raw_fn(), "serve_forest")
        if self.walk_strategy == "fused":
            clone._walk_dev = tuple(jax.device_put(a, device)
                                    for a in self._walk_dev)
            clone._is_cat_col_dev = jax.device_put(self._is_cat_col_dev,
                                                   device)
            if self._walk_aff_dev is not None:
                clone._walk_aff_dev = jax.device_put(self._walk_aff_dev,
                                                     device)
            clone._walk_binned_jit = CountingJit(
                clone._make_walk_binned_fn(), "predict_forest_walk")
            clone._walk_raw_jit = CountingJit(
                clone._make_walk_raw_fn(), "serve_forest_walk")
            obs.devprof.transfer(
                "h2d", "forest",
                sum(int(a.nbytes) for a in clone._walk_dev)
                + int(clone._is_cat_col_dev.nbytes)
                + (int(clone._walk_aff_dev.nbytes)
                   if clone._walk_aff_dev is not None else 0),
                transfers=len(clone._walk_dev) + 1
                + (1 if clone._walk_aff_dev is not None else 0))
        obs.devprof.transfer(
            "h2d", "forest",
            sum(int(a.nbytes) for a in clone._tree_dev)
            + int(clone._bnd_dev.nbytes) + int(clone._cats_dev.nbytes)
            + int(clone._is_cat_dev.nbytes)
            + sum(int(a.nbytes) for a in (clone._lin_dev or ())),
            transfers=3 + len(clone._tree_dev)
            + len(clone._lin_dev or ()))
        return clone

    def warmup(self, buckets: Optional[Sequence[int]] = None,
               max_bucket: Optional[int] = None) -> "CompiledForest":
        """Pre-compile every bucket BOTH strategy dispatchers can route
        to, so the hot path never hits XLA.  ``max_bucket`` trims the
        ladder (a server whose ``serve_max_batch`` is 4096 need not
        compile the 65536 bucket) — rounded UP to the bucket a
        ``max_bucket``-row request actually dispatches to: a
        ``serve_max_batch`` strictly between two ladder rungs routes its
        largest admitted requests to the rung ABOVE it, which the old
        ``<= max_bucket`` trim silently left cold (first such request
        paid a hot-path compile)."""
        sizes = list(buckets) if buckets else list(self.ladder.sizes)
        if max_bucket:
            cap = self.ladder.bucket_for(int(max_bucket))
            kept = [s for s in sizes if s <= cap]
            sizes = kept or sizes[:1]
        for s in sizes:
            dummy = np.zeros((min(s, 2), self.num_features))
            Xp, mask = pad_rows(np.asarray(dummy, np.float64), s)
            Xp32, mask32 = pad_rows(np.asarray(dummy, np.float32), s)
            if self._has_linear:
                xt = np.where(np.isnan(Xp), 0.0, Xp).T.astype(np.float32)
                self._dispatch_binned(s, self.bin_rows(Xp), mask, xt)
            else:
                self._dispatch_binned(s, self.bin_rows(Xp), mask)
            self._dispatch_raw(s, Xp32, mask32)
        obs.inc("forest_warmups")
        return self

    def info(self) -> Dict[str, object]:
        out = {
            "num_trees": int(self.num_trees),
            "num_class": int(self.num_class),
            "num_features": int(self.num_features),
            "num_leaves_padded": int(self.num_leaves),
            "transform": self.transform,
            "buckets": list(self.ladder.sizes),
            "max_cuts": int(self.max_cuts),
            "linear": bool(self._has_linear),
            "fingerprint": self.data_fingerprint is not None,
            "drift": self._drift is not None,
            "serve_walk": self.walk_strategy,
        }
        if self.walk_strategy == "fused":
            out["walk_vmem_bytes"] = int(self.walk_vmem_bytes())
            out["walk_interpreted"] = bool(self._walk_interpret)
            out["leaf_dtype"] = self.leaf_dtype
            out["bin_dtype"] = np.dtype(self._bin_dtype).name
        if self.device is not None:
            out["device"] = str(self.device)
        return out


def _zero_tree(num_leaves: int):
    """SoA padding block for one absorbing 0-valued 1-leaf tree."""
    L = max(num_leaves, 2)
    M = L - 1
    return (np.zeros((1, M), np.int32), np.zeros((1, M), np.int32),
            np.zeros((1, M), bool), np.full((1, M), ~0, np.int32),
            np.full((1, M), ~0, np.int32), np.zeros((1, L), np.float32))
