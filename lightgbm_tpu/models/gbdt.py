"""GBDT boosting engine: the full training loop state machine.

Reference: src/boosting/gbdt.{h,cpp}.  One boosting iteration
(GBDT::TrainOneIter, gbdt.cpp:295-382) becomes: a jitted objective pass, a
host-side bagging/feature-fraction mask draw, one jitted whole-tree growth
per class (ops/grow.py), and jitted score updates — scores never leave the
device during training; metrics pull them once per eval.
"""

from __future__ import annotations

import contextlib
import functools
import io
import os
import time
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .. import obs
from ..config import Config
from ..io.binning import CATEGORICAL
from ..io.bundling import BundlePlan
from ..io.dataset import BinnedDataset
from ..ops.bundle import BundleDecode
from ..metric import Metric, create_metric
from ..objective import ObjectiveFunction, create_objective
from ..ops import goss as goss_ops, ordered_grow
from ..ops.goss import GossParams
from ..ops.grow import (GrowParams, SerialComm, grow_tree, pack_tree_arrays,
                        unpack_tree_arrays)
from ..ops.predict import (predict_binned_forest,
                           predict_binned_forest_linear,
                           predict_binned_tree)
from ..utils import compile_cache, log, timetag
from ..utils.log import LightGBMError
from .linear import (LinearParams, affine_epilogue, attach_linear,
                     fit_leaf_models, pack_linear, unpack_linear)
from .screening import GainScreener
from .tree import Tree


class _HistView(NamedTuple):
    """One round's histogram-side data view: the (possibly EFB-bundled,
    possibly screening-compacted) column matrix plus its decode tables.
    Passed as a runtime pytree into the shared train_step / grow
    programs, so switching views never rebuilds a closure — the full
    view and the compacted view each trace once and are reused."""
    bins: Any                  # [C, N] column bin codes
    bins_rm: Any               # [N, C] row-major copy or None
    bins_words: Any            # word-packed lanes (ordered grower) or None
    bundle: Any                # ops.bundle.BundleDecode or None


def estimate_train_memory(num_data: int, num_features: int, num_leaves: int,
                          max_bin: int, num_models: int,
                          bin_itemsize: int = 1, *,
                          donate_score: bool = False,
                          leaf_cache: bool = True,
                          linear_k: int = 0) -> Dict[str, int]:
    """Rough per-device HBM footprint (bytes) of training, by component.

    The dense-on-device design (SURVEY §7.2) has no sparse-bin fallback
    (reference sparse_bin.hpp stores sparse data ~20x smaller) and keeps
    the per-leaf histogram cache fully resident instead of LRU-bounding it
    (reference HistogramPool, feature_histogram.hpp:299-455) — so unlike
    the reference, an oversize problem cannot spill; the admission gate
    (``_check_memory_budget`` + ``utils/resource.py``,
    docs/FAULT_TOLERANCE.md §Resource exhaustion) must refuse or degrade
    at construction with this estimate instead of dying in XLA
    allocation.

    Components mirror what training actually allocates: column- and
    row-major bin copies (+ word-packed lanes for the ordered grower,
    padded by ``ordered_grow.lane_pad``), the 9-stream int8 digit payload,
    per-class score buffers, the [L, F, 9, B] int32 histogram cache
    (``leaf_cache=False`` — the ``hist_cache`` degrade step — zeroes
    it), and the score-update double buffer (``donate_score=True`` —
    in-place XLA aliasing — zeroes it).
    ``num_data`` is the PADDED row count when row bucketing is on — the
    pad rows allocate like real ones.  ``working`` doubles the sort
    payload: lax.sort and the window update-slices hold one extra copy
    of their operands live."""
    n, f = num_data, num_features
    pad = ordered_grow.lane_pad(n)
    words = -(-f // 4) if bin_itemsize == 1 else 0
    bins_cm = n * f * bin_itemsize
    bins_rm = n * f * bin_itemsize
    bins_words = (n + pad) * words * 4
    digits = (n + pad) * 16 + n * 9          # dig_w (3 words) + row_ord + [N,9]
    # score, grad, hess, and the per-class prediction delta are all live
    # at once at the peak of a boosting step
    scores = num_models * n * 4 * 4
    # without donation XLA materializes the updated [K, N] score cache
    # NEXT TO the old one at the update peak
    double_buf = 0 if donate_score else num_models * n * 4
    cache = (num_leaves * f * 9 * max_bin * 4) if leaf_cache else 0
    # linear_tree (docs/LINEAR_TREES.md): the resident [F, N] f32 raw
    # copy, the per-row [N, K+1] covariate/phi gather (x2: phi and the
    # per-slot segment-sum operand are live together), and the batched
    # normal equations [L, M, M] (A, its Cholesky factor, and the
    # right-hand sides — ~3 copies at the solve peak)
    linear = 0
    if linear_k > 0:
        m = linear_k + 1
        linear = (n * f * 4 + 2 * n * m * 4
                  + 3 * num_leaves * m * m * 4)
    payload = bins_words + digits
    return {
        "bins_device": bins_cm + bins_rm,
        "packed_payload": payload,
        "scores_and_gradients": scores,
        "score_double_buffer": double_buf,
        "histogram_cache": cache,
        "linear_fit": linear,
        "working": payload,
        "total": (bins_cm + bins_rm + 2 * payload + scores + double_buf
                  + cache + linear),
    }


def estimate_valid_memory(num_data: int, num_features: int,
                          num_models: int,
                          bin_itemsize: int = 1) -> Dict[str, int]:
    """Per-device HBM footprint (bytes) of ATTACHING a validation set.

    A valid set allocates a column-major device bin matrix and a
    per-class f32 score buffer (``_DeviceData`` with
    ``with_row_major=False``); replaying/scoring holds one per-class
    prediction delta live on top.  Counted separately from
    ``estimate_train_memory`` so ``add_valid_dataset`` can fail fast
    instead of dying in a late XLA allocation when the valid set is
    attached after training state already fills the device."""
    n = num_data
    bins = n * num_features * bin_itemsize
    scores = num_models * n * 4
    working = n * 4                 # one class's delta during replay/score
    return {
        "bins_device": bins,
        "scores": scores,
        "working": working,
        "total": bins + scores + working,
    }


def _device_memory_limit() -> Optional[int]:
    """Per-device memory budget in bytes, or None when unknown.

    LGBT_DEVICE_MEMORY_BYTES overrides (test rigs, CPU backends whose
    memory_stats report nothing useful)."""
    env = os.environ.get("LGBT_DEVICE_MEMORY_BYTES")
    if env:
        try:
            return int(env)
        except ValueError:
            log.warning("LGBT_DEVICE_MEMORY_BYTES=%r is not an integer; "
                        "ignoring", env)
    # the CPU backend reports no stats (None): budget unknown.  LOCAL
    # device: under a multi-process runtime the first global device
    # belongs to rank 0 and answers no other rank
    stats = jax.local_devices()[0].memory_stats()
    return stats.get("bytes_limit") if stats else None


class _DeviceData:
    """Device-resident binned dataset + per-dataset score buffer
    (ScoreUpdater, score_updater.hpp:23-99).

    ``padded_rows`` > num_data pads every row-dimension array up to a
    shared shape bucket (utils/compile_cache.py bucket_rows): pad rows
    carry bin 0, zero gradients (via zero ``row_weight``, exactly how
    bagging excludes rows) and a score nobody reads — ``host_score``
    crops them.  Histogram sums are EXACT (the digit path is int32 and
    pad digits are zero), so splits match the unpadded run; only the f32
    leaf-total reductions may re-associate across shapes, the same
    last-bit wiggle any row-count change causes.  In exchange every
    jitted training program is shared across nearby dataset sizes."""

    def __init__(self, dataset: BinnedDataset, num_models: int,
                 with_row_major: bool = False,
                 padded_rows: Optional[int] = None,
                 with_raw: bool = False, mesh=None,
                 padded_cols: Optional[int] = None):
        self.dataset = dataset
        self.num_data = dataset.num_data
        self.padded_rows = max(int(padded_rows or 0), dataset.num_data)
        pad = self.padded_rows - dataset.num_data
        # ``padded_cols``: an EFB layout's rung (ops/ordered_grow.py
        # bundled_shape); a pad column holds bin 0 on every row
        cpad = max(int(padded_cols or 0) - dataset.bins.shape[0], 0)
        bins_np = dataset.bins if pad == 0 and cpad == 0 else \
            np.pad(dataset.bins, ((0, cpad), (0, pad)))
        h2d_xfers, h2d_bytes = 1, int(bins_np.nbytes)
        # ``mesh`` (a row-sharded learner in one process): every device
        # receives its own row block straight from the host, so a chip
        # never holds more than its shard — not even while loading
        self.mesh = mesh
        put_cols = jnp.asarray if mesh is None else \
            functools.partial(_put_row_blocks, mesh)
        # Native uint8/uint16 on device (int32 would 4x the HBM footprint
        # and the histogram kernel's read traffic).
        self.bins = put_cols(bins_np)
        # Row-major copy for the cached serial learner's leaf gathers
        # (ops/leafhist.py needs rows contiguous).
        self.bins_rm = None
        if with_row_major and mesh is None:
            self.bins_rm = jnp.asarray(np.ascontiguousarray(bins_np.T))
        elif with_row_major:
            self.bins_rm = jax.make_array_from_callback(
                bins_np.T.shape, _row_sharding(mesh, 2),
                lambda idx: np.ascontiguousarray(bins_np[:, idx[0]].T))
        if self.bins_rm is not None:
            h2d_xfers += 1
            h2d_bytes += int(bins_np.nbytes)
        # Word-packed payload lanes for the leaf-ordered grower, shared
        # across trees, wherever the bins are of a kind it takes.
        self.bins_words = None
        if self.bins_rm is not None \
                and ordered_grow.accepts(self.bins_rm.dtype):
            self.bins_words = ordered_grow.pack_word_lanes(self.bins, mesh)
        # raw f32 feature values for the linear-tree fit and its replay
        # epilogues (docs/LINEAR_TREES.md): NaN imputed to 0.0 ON UPLOAD
        # so the device fit and every predict path agree exactly; pad
        # rows read as zero and their zero row_weight keeps them out of
        # the normal equations anyway.
        self.raw = None
        if with_raw and dataset.raw is not None:
            raw_np = np.where(np.isnan(dataset.raw), np.float32(0.0),
                              dataset.raw).astype(np.float32)
            if pad:
                raw_np = np.pad(raw_np, ((0, 0), (0, pad)))
            self.raw = put_cols(raw_np)
            h2d_xfers += 1
            h2d_bytes += int(raw_np.nbytes)
        init = np.zeros((num_models, self.padded_rows), np.float32)
        if dataset.metadata.init_score is not None:
            init[:, :self.num_data] += np.asarray(
                dataset.metadata.init_score,
                np.float32).reshape(num_models, self.num_data)
        self.score = put_cols(init)
        obs.devprof.transfer("h2d", "dataset",
                             h2d_bytes + int(init.nbytes),
                             transfers=h2d_xfers + 1)
        obs.setup.placed(h2d_bytes + int(init.nbytes))

    def host_score(self, dtype=np.float64) -> np.ndarray:
        """[num_models, num_data] host copy of the score cache with the
        row-bucket pad cropped — what metrics/snapshots/C-API readers
        must consume instead of the raw (padded) device buffer."""
        return np.asarray(self.score, dtype)[:, :self.num_data]

    def set_score(self, score) -> None:
        """Replace the score cache from a host array of real rows,
        re-padding up to the bucket (snapshot restore)."""
        score = np.asarray(score, np.float32)
        if score.shape[-1] < self.padded_rows:
            score = np.pad(score, ((0, 0),
                                   (0, self.padded_rows - score.shape[-1])))
        self.score = jnp.asarray(score) if self.mesh is None \
            else _put_row_blocks(self.mesh, score)

    def add_tree(self, tree_arrays, is_cat, cls: int, max_steps: int,
                 bundle=None):
        n = tree_arrays.split_feature.shape[0]
        delta, _ = predict_binned_tree(
            tree_arrays.split_feature, tree_arrays.split_bin,
            is_cat[jnp.maximum(tree_arrays.split_feature, 0)],
            tree_arrays.left_child, tree_arrays.right_child,
            tree_arrays.leaf_value, self.bins, max_steps, bundle=bundle)
        self.score = self.score.at[cls].add(delta)


def _row_sharding(mesh, ndim: int, row_axis: int = 0):
    """Rows (dimension ``row_axis`` of ``ndim``) in one block per device
    of the mesh's first axis, everything else whole."""
    from jax.sharding import NamedSharding, PartitionSpec
    spec = [None] * ndim
    spec[row_axis] = mesh.axis_names[0]
    return NamedSharding(mesh, PartitionSpec(*spec))


def _put_row_blocks(mesh, host):
    """A host array whose LAST dimension is rows, placed one row block
    per device: each block goes from the host to its own device."""
    host = np.asarray(host)
    return jax.device_put(host,
                          _row_sharding(mesh, host.ndim, host.ndim - 1))


def shard_padded_rows(num_data: int, shards: int) -> int:
    """Rows of a row-sharded dataset: equal blocks, each a whole number
    of the histogram kernel's 8,192-row blocks once it is that large
    (the root pass then pads nothing); pad rows sit at the end of the
    last block with zero weight."""
    per = -(-max(int(num_data), 1) // shards)
    step = 8192 if per > 8192 else 8
    return shards * (-(-per // step) * step)


@obs.instrumented_jit(program="finite_guard")
def _all_finite(*arrays):
    """One device scalar: every element of every array is finite.  The
    NaN/Inf containment guard (``nan_policy``) reads this per iteration;
    the reduction is jitted and cheap, but *reading* it synchronizes the
    async pipeline — which is why the guard is opt-in."""
    ok = jnp.asarray(True)
    for a in arrays:
        ok = ok & jnp.isfinite(a).all()
    return ok


@obs.instrumented_jit(program="bag_mask",
                      static_argnames=("n", "bag_cnt", "n_real"))
def _device_bag_mask(key, n: int, bag_cnt: int, n_real: int = -1):
    """EXACT-count sample without replacement (reference bag_data_cnt_).

    Ranks rows by raw 32-bit random words with the row index as a total-
    order tie-break: f32 uniforms sit on a ~2^-23 grid, so at N=1M the
    kth order statistic collides with another row in roughly 1 of 8
    draws and a value-only threshold would keep bag_cnt+1 rows.  The
    (word, index) pair is unique, so exactly bag_cnt rows satisfy
    pair <= pair_sorted[bag_cnt - 1].

    ``n_real < n`` marks the tail as row-bucket padding
    (utils/compile_cache.py): pad rows draw the max word, so every real
    (word, index) pair sorts before them and the bag is drawn from real
    rows only."""
    if bag_cnt <= 0:
        # matches the host-draw degenerate case (reference bag_data_cnt=0
        # keeps nothing); the wrapped [-1] index would keep EVERYTHING
        return jnp.zeros((n,), jnp.float32)
    n_real = n if n_real < 0 else n_real
    r = jax.random.bits(key, (n,), jnp.uint32)
    iota = jnp.arange(n, dtype=jnp.int32)
    if n_real < n:
        r = jnp.where(iota < n_real, r, jnp.uint32(0xFFFFFFFF))
    r_sorted, i_sorted = jax.lax.sort((r, iota), num_keys=1,
                                      is_stable=True)
    thr_r = r_sorted[bag_cnt - 1]
    thr_i = i_sorted[bag_cnt - 1]
    keep = (r < thr_r) | ((r == thr_r) & (iota <= thr_i))
    if n_real < n:
        keep &= iota < n_real
    return keep.astype(jnp.float32)


_PACK_TREE = obs.instrumented_jit(pack_tree_arrays, program="pack_tree")


def _donation_enabled() -> bool:
    """Round-to-round buffer donation is gated to accelerator backends.
    On this jax build XLA:CPU's input-output aliasing intermittently
    corrupts donated buffers (freed-buffer reads that surface as
    segfaults in LATER host conversions — reproduced in the round-7
    suite by running training files together), and the double-allocation
    donation avoids only matters for HBM-sized buffers anyway.
    ``LIGHTGBM_TPU_DONATION`` (1/0) overrides for experiments."""
    env = os.environ.get("LIGHTGBM_TPU_DONATION", "").strip().lower()
    if env:
        return env in ("1", "true", "yes", "on")
    return _donation_safe()


def _donation_safe() -> bool:
    """Whether the backend's input-output aliasing is trustworthy at all
    (accelerators yes, XLA:CPU no — see ``_donation_enabled``).  The
    ``score_donation`` degrade step may re-enable donation an env
    override turned off, but never on a backend where aliasing corrupts
    buffers: a memory degrade must not trade OOM for wrong answers."""
    return jax.default_backend() != "cpu"


@obs.instrumented_jit(program="score_update", static_argnames=("cls",),
                      donate_argnums=(0,))
def _score_add_donated(score, delta, cls: int):
    """In-place (donated) per-class score update: XLA writes the new
    score into the old buffer instead of double-allocating the
    [num_class, N] cache every round.  Only used when nan_policy is off
    (containment keeps a pre-iteration reference alive for rollback,
    which donation would invalidate) AND _donation_enabled() says the
    backend supports aliasing safely."""
    return score.at[cls].add(delta)


# ---------------------------------------------------------------------------
# Process-wide training-program registry.
#
# Every GBDT instance used to build its own train_step/train_gradients
# closures, capturing the dataset's bins/labels as compile-time
# constants — so the SECOND same-config booster in a process (rebuilt
# after snapshot-resume, a second engine.train call, the bench's warm
# pass) re-traced and re-compiled everything from scratch.  With the
# objective's functional-gradients interface every per-dataset array is
# now a runtime ARGUMENT, so one traced program per (objective key,
# class count, guard, grow strategy, grow params) serves every booster;
# repeated runs hit the jit's executable cache and record ZERO new
# train_step compiles in the ledger.

_SHARED_JITS: Dict[tuple, Any] = {}

# Entries retain only scalar-bearing objective HOLDERS (program_holder
# strips the per-dataset arrays), so a cached program costs bytes, not a
# dead dataset's HBM.  The cap is a leak backstop for pathological key
# churn (legacy id-keyed objectives in a long sweep); eviction only
# costs a recompile if that config returns.
_SHARED_JITS_MAX = 64


def _shared_jit(key: tuple, make, program: str, **jit_kwargs):
    fn = _SHARED_JITS.get(key)
    if fn is None:
        while len(_SHARED_JITS) >= _SHARED_JITS_MAX:
            _SHARED_JITS.pop(next(iter(_SHARED_JITS)))
        fn = obs.instrumented_jit(make(), program=program, **jit_kwargs)
        _SHARED_JITS[key] = fn
    return fn


def _shared_gradients_fn(objective):
    """Shared jitted gradients program for this objective configuration
    (arrays travel as arguments; scalars key the program)."""
    holder = objective.program_holder()
    return _shared_jit(("train_gradients", objective.program_key()),
                       lambda: holder.gradients_with,
                       program="train_gradients")


def _grower(kind: str, params: GrowParams):
    """The serial grower ``GBDT._choose_grower`` named, as a callable of
    the one signature every grower has here (``GBDT._make_grow_fn`` gives
    the distributed learners the same): ``grow(view, num_bin, is_cat,
    feat_mask, grad, hess, row_weight, lr)`` -> (TreeArrays, leaf_id,
    delta).  The inner grow jits inline under an enclosing trace
    (obs/compile_ledger.py passthrough)."""
    if kind == "ordered":
        # view.bundle is None or the full EFB layout, never a screener's
        # compacted view (the choice guarantees it)
        return lambda view, *a: ordered_grow.grow_tree_ordered(
            view.bins, *a, params, bins_rm=view.bins_rm,
            bins_words=view.bins_words, bundle=view.bundle)
    # ops/grow.py with the resident [L, F, 9, B] histogram cache
    # ("cached"), or in full passes a split without it ("nocache": the
    # hist_cache degrade step; exact parity, both scan the same sums)
    comm = SerialComm(leaf_cache=kind == "cached")
    return lambda view, *a: grow_tree(view.bins, *a, params, comm,
                                      view.bins_rm, bundle=view.bundle)


def _round_step(objective, num_class: int, guard: bool, grow,
                linear: Optional[LinearParams] = None,
                goss: Optional[GossParams] = None):
    """One fused boosting iteration as a PURE function of device arrays:
    gradients -> [row draw] -> per-class grow -> score update -> packed
    host vectors.
    ``grow`` is a grower of ``_grower``'s signature; every per-dataset
    array is an ARGUMENT (closed over, the labels were compiled in as a
    constant: 168 MB at 42M rows, on every device, and the persistent
    compile cache never served the program twice).

    ``linear`` (docs/LINEAR_TREES.md) appends the batched per-leaf
    affine fit after each class's growth: the fitted intercepts replace
    the grown leaf values, the fitted delta replaces the grower's
    constant delta, and the packed transfer grows the (feat, coeff)
    vectors.  ``linear=None`` leaves the trace — and the registry key —
    byte-identical to the pre-linear program.

    ``goss`` (models/goss.py) draws the round's rows from the gradients
    (ops/goss.py ``sample``, phase ``goss/sample``): ``row_weight`` comes
    in as the real-row mask, ``sample`` is the round's int32 state
    (iteration, seed, whether it samples, ``top_cnt``) and the drawn
    ``(row_weight, factor)`` come back as the step's fourth output.  One
    program serves the rounds that sample and those that do not;
    ``goss=None`` leaves the trace as it was.

    Every operation traced here sits under one leaf phase of
    obs/phases.py ROUND_PHASES (the growers scope their own), so a
    profiler window reduces to named phases (obs/devtrace.py)."""
    def step_fn(score, feat_masks, row_weight, lr, view, num_bin, is_cat,
                grad_arrays, raw=None, sample=None):
        with jax.named_scope("gradients"):
            grad, hess = objective.gradients_with(grad_arrays, score)
            ok = (_all_finite(grad, hess) if guard else jnp.asarray(True))
        drawn = None
        if goss is not None:
            drawn = row_weight, factor = goss_ops.sample(
                grad, hess, row_weight, sample, goss)
            with jax.named_scope("goss/sample"):
                grad, hess = grad * factor, hess * factor
        outs = []
        for cls in range(num_class):
            with jax.named_scope("gradients"):
                args = (num_bin, is_cat, feat_masks[cls], grad[cls],
                        hess[cls], row_weight, lr)
            ta, _, delta = grow(view, *args)
            lin = ()
            if linear is not None:
                ta, coeff, feat, delta, fb = fit_leaf_models(
                    ta, view.bins, is_cat, raw, grad[cls], hess[cls],
                    row_weight, lr, linear, bundle=view.bundle)
                lin = ((coeff, feat),)
            with jax.named_scope("score_update"):
                score = score.at[cls].add(delta)
            with jax.named_scope("pack_tree"):
                packed = pack_tree_arrays(ta)
                if linear is not None:
                    packed += pack_linear(coeff, feat, fb)
            outs.append((packed, ta, delta) + lin)
        return score, outs, ok, drawn
    return step_fn


def _shared_train_step(objective, num_class: int, guard: bool, kind: str,
                       params: GrowParams, donate: bool,
                       linear: Optional[LinearParams] = None,
                       goss: Optional[GossParams] = None):
    key = ("train_step", objective.program_key(), num_class, guard, kind,
           params, donate, linear, goss)
    holder = objective.program_holder()

    def make():
        body = _round_step(holder, num_class, guard, _grower(kind, params),
                           linear, goss)

        # the registry's program keeps its flat argument list: the order
        # of a program's parameters is part of its compiled text, and
        # with it of the persistent cache's key
        def step_fn(score, feat_masks, row_weight, lr, bins, num_bin,
                    is_cat, grad_arrays, bins_rm, bins_words, bundle,
                    raw=None, sample=None):
            return body(score, feat_masks, row_weight, lr,
                        _HistView(bins, bins_rm, bins_words, bundle),
                        num_bin, is_cat, grad_arrays, raw, sample)
        return step_fn
    return _shared_jit(
        key, make, program="train_step",
        # round-to-round state donation: the score cache is the only
        # argument that is dead after the call (the caller immediately
        # rebinds it to the output), so XLA may update it in place
        # instead of double-allocating [num_class, N] every iteration
        donate_argnums=(0,) if donate else ())


def _shared_linear_fit(linear: LinearParams):
    """Shared jitted program for the PER-STAGE path's batched leaf fit
    (custom fobj, LGBT_NO_FUSED_STEP — the fused path inlines
    fit_leaf_models into train_step instead).  Keyed on the static
    LinearParams alone: every per-dataset array travels as an argument,
    so rebuilt boosters reuse the compiled program."""
    def make():
        def fit(tree_arrays, bins, is_cat, raw, grad, hess, row_weight,
                lr, bundle):
            return fit_leaf_models(tree_arrays, bins, is_cat, raw, grad,
                                   hess, row_weight, lr, linear,
                                   bundle=bundle)
        return fit
    return _shared_jit(("linear_fit", linear), make, program="linear_fit")


_PACK_LINEAR = obs.instrumented_jit(pack_linear, program="pack_tree")

# the per-stage path's GOSS draw (custom fobj, LGBT_NO_FUSED_STEP, a
# multi-process learner): the fused round's own stage, alone
_GOSS_DRAW = obs.instrumented_jit(goss_ops.sample, program="goss_draw",
                                  static_argnames=("params",))


class GBDT:
    """Gradient Boosting Decision Tree (reference gbdt.h:20-351).

    Training is PIPELINED: ``train_one_iter`` materializes the *previous*
    iteration's trees (one batched device->host transfer) and then
    dispatches this iteration's device work, so the host never blocks on
    the iteration it just dispatched and per-field sync round-trips are
    gone.  ``models`` is a property that flushes the pending iteration, so
    every reader sees the synchronous view.  Subclasses needing tree bodies
    right after training (DART's Normalize) set ``_pipeline = False``.
    """

    submodel_name = "gbdt"
    _pipeline = True
    _pending_iter = None          # [tree_arrays] of the last iteration
    _pending_shrinkage = 1.0
    _no_more_splits = False
    # -- wide-sparse subsystem (docs/SPARSE.md; None/off on loaded
    # prediction-only boosters) ----------------------------------------
    _bundle = None                # ops.bundle.BundleDecode (EFB)
    _bundle_plan = None
    _screener = None              # models/screening.py GainScreener
    _screen_mask_dev = None
    _parallel_grow_active = False
    _grower_kind = "ordered"      # _choose_grower's answer, at _make_grow_fn
    # -- piece-wise linear trees (models/linear.py, docs/LINEAR_TREES.md;
    # None = constant leaves, the default) ------------------------------
    _linear: Optional[LinearParams] = None
    # -- telemetry (lightgbm_tpu/obs/; all optional, None/zero = off) ----
    _telemetry = None             # obs.EventRecorder (set_event_recorder)
    _trace = None                 # obs.TraceCapture window (env/config)
    _comm_traffic = None          # static per-tree collective account
    _comm_traffic_totals = (0, 0)  # (calls, bytes) per tree, precomputed
    _cum_comm_bytes = 0
    _cum_comm_calls = 0
    _bag_cnt = 0                  # rows in the current bagging draw
    # -- GOSS (models/goss.py, ops/goss.py; None = no draw by gradients) -
    _goss: Optional[GossParams] = None
    _goss_factor = None           # the last round's amplification [N]
    _first_round_done = False     # GBDT::first_round, the set-up account
    _pending_iter_idx = -1        # iteration index of _pending_iter
    # -- fault tolerance (docs/FAULT_TOLERANCE.md) ----------------------
    _nan_policy = "none"          # none | fail_fast | skip_tree
    _nan_skips = 0                # poisoned iterations dropped (skip_tree)
    # -- resource degrade ladder (memory_policy=degrade; utils/resource.py,
    # docs/FAULT_TOLERANCE.md §Resource exhaustion) ---------------------
    _degrade_steps: Tuple[str, ...] = ()   # applied steps, in order
    _degrade_force_donate = False  # score_donation step fired
    _degrade_leaf_cache_off = False  # hist_cache step fired
    # -- drift observatory (obs/drift.py, docs/OBSERVABILITY.md §Drift):
    # training-data fingerprint carried in the model artifact.  Distinct
    # from snapshot_state's config "fingerprint" (resume compatibility).
    data_fingerprint = None

    def __init__(self, config: Config, train_set: Optional[BinnedDataset],
                 objective: Optional[ObjectiveFunction] = None):
        self.config = config
        self.iter_ = 0
        self.models: List[Tree] = []  # num_iter * num_class, class-major rows
        self.best_iteration = -1
        self.best_score: Dict[Tuple[int, str], float] = {}
        self.best_msg: Dict[int, str] = {}
        self.num_init_iteration = 0
        self.label_idx = 0
        self.sigmoid = (config.sigmoid if config.objective == "binary" else -1.0)
        if train_set is not None:
            with obs.span("GBDT::setup"):
                self._setup(train_set, objective)

    # ------------------------------------------------------------------
    def _setup(self, train_set: BinnedDataset, objective) -> None:
        cfg = self.config
        self.train_set = train_set
        self.data_fingerprint = getattr(train_set, "data_fingerprint", None)
        self.objective = objective or create_objective(cfg)
        self._mesh = self._learner_mesh(cfg)
        self._init_objective(train_set)
        self.num_class = self.objective.num_tree_per_iteration
        self.num_data = train_set.num_data
        self.num_features = train_set.num_features
        self.max_feature_idx = train_set.num_total_features - 1
        self.feature_names = list(train_set.feature_names)

        self.num_bin = jnp.asarray(train_set.num_bin_per_feature())
        self.is_cat = jnp.asarray(train_set.is_categorical_per_feature())
        self.max_bin = cfg.max_bin
        self.num_columns = train_set.num_columns
        self._setup_bundle(train_set, cfg)
        self.grow_params = self._make_grow_params(cfg)
        self.shrinkage_rate = cfg.learning_rate

        self._place_training_data(cfg, train_set)
        self.valid_data: List[_DeviceData] = []
        self.valid_metrics: List[List[Metric]] = []
        self.train_metrics = self._make_metrics(cfg, train_set)

        self._trace = obs.TraceCapture.from_config(cfg)
        self._nan_policy = str(getattr(cfg, "nan_policy", "none") or "none")
        self._nan_skips = 0
        # distributed desync detection (docs/FAULT_TOLERANCE.md
        # §Distributed): every K rounds, allgather a cheap digest of the
        # replicated state and verify every rank agrees.  Zero overhead
        # single-process: the gate short-circuits on world size before
        # touching anything (no collectives, no compiles).
        self._consistency_every = int(
            getattr(cfg, "distributed_consistency_check", 0) or 0)
        self._desync_policy = str(
            getattr(cfg, "desync_policy", "fail_fast") or "fail_fast")
        self._bag_cnt = self.num_data
        self._bag_key = jax.random.PRNGKey(cfg.bagging_seed)
        self._feature_rng = np.random.RandomState(cfg.feature_fraction_seed)
        self._init_row_state()
        self._grad_arrays = self._make_grad_arrays()
        self._grad_fn = self._make_grad_fn()
        self._setup_screening(cfg)
        self._grow_fn = self._make_grow_fn()
        self._full_view = self._make_full_view()
        # device-constant caches (avoid a host->device transfer per iter)
        self._full_feat_mask = self._feature_row(
            np.ones(self.num_features, bool))
        self._full_feat_masks = jnp.stack([self._full_feat_mask]
                                          * self.num_class)
        self._lr_cache: Tuple[float, jax.Array] = (-1.0, jnp.float32(0))
        self._train_step = None

    def _setup_bundle(self, train_set: BinnedDataset, cfg: Config) -> None:
        """Device decode tables for an EFB-bundled dataset
        (io/bundling.py plan -> ops/bundle.py BundleDecode)."""
        plan = getattr(train_set, "bundle_plan", None)
        self._bundle_plan = plan
        self._bundle = None
        # what the device holds of columns and features: the data's own,
        # or the rung of a bundled layout
        self._device_shape = (self.num_columns, self.num_features)
        if plan is None:
            self._bundle_col_np = np.arange(self.num_features, dtype=np.int64)
            return
        screened = float(getattr(cfg, "feature_screen_ratio", 0.0)
                         or 0.0) > 0.0
        if self._mesh is None and ordered_grow.accepts(train_set.bins.dtype,
                                                       screened):
            # the layout the leaf-ordered grower carries, padded so that
            # nearby plans share its compiled round
            self._device_shape = ordered_grow.bundled_shape(
                self.num_columns, self.num_features)
        dn = plan.decode_arrays(
            [m.num_bin for m in train_set.mappers],
            [m.default_bin for m in train_set.mappers], cfg.max_bin,
            self._device_shape)
        self._bundle = BundleDecode.from_tables(dn)
        self._bundle_col_np = dn["col"][:self.num_features].astype(np.int64)
        fpad = self._device_shape[1] - self.num_features
        self.num_bin = jnp.pad(self.num_bin, (0, fpad), constant_values=1)
        self.is_cat = jnp.pad(self.is_cat, (0, fpad))
        obs.set_gauge("efb_device_columns", self._device_shape[0])
        log.info("EFB active: %d feature(s) in %d column(s) "
                 "(%d bundle(s)); on the device %d column(s) of %d "
                 "feature(s)", self.num_features, self.num_columns,
                 len(plan.bundles), *self._device_shape)

    def _setup_screening(self, cfg: Config) -> None:
        """EMA-FS gain screening state (models/screening.py)."""
        ratio = float(getattr(cfg, "feature_screen_ratio", 0.0) or 0.0)
        self._screener = None
        self._screen_mask_dev = None
        self._screen_mask_np = None
        self._screen_period = -1
        self._active_view = None
        self._identity_decode = None
        if ratio <= 0.0:
            return
        self._screener = GainScreener(
            self.num_features, self.num_columns, self._bundle_col_np,
            ratio=ratio,
            refresh=int(getattr(cfg, "feature_screen_refresh", 10) or 10),
            warmup=int(getattr(cfg, "feature_screen_warmup", 20) or 0),
            decay=float(getattr(cfg, "feature_screen_decay", 0.9) or 0.9))

    def _setup_linear(self, cfg: Config,
                      train_set: BinnedDataset) -> Optional[LinearParams]:
        """Piece-wise linear leaf config (models/linear.py,
        docs/LINEAR_TREES.md), or None when the subsystem is off/inert.
        Unsupportable combinations REFUSE with a named error instead of
        silently training a different model."""
        if not bool(getattr(cfg, "linear_tree", False)):
            return None
        k = int(getattr(cfg, "linear_max_leaf_features", 0) or 0)
        if k <= 0:
            # the documented degenerate case: zero covariate slots means
            # constant leaves — the whole subsystem stays inert, so the
            # run is bit/ledger-identical to linear_tree=false
            log.warn_once(
                "linear_tree_k0",
                "linear_tree=true with linear_max_leaf_features=0: "
                "leaves stay constant (the linear subsystem is inert "
                "and output is identical to linear_tree=false)")
            return None
        parallel = bool(getattr(cfg, "is_parallel", False))
        try:
            parallel = parallel or jax.process_count() > 1
        except Exception:  # pragma: no cover - uninitialized backend
            pass
        if parallel:
            raise LightGBMError(
                "linear_tree is not supported with distributed training "
                "(the per-leaf ridge solve needs the full raw feature "
                "matrix on one device); use tree_learner=serial on a "
                "single process, or set linear_tree=false")
        if train_set.raw is None:
            raise LightGBMError(
                "linear_tree requires the raw feature values, but this "
                "dataset carries none (streamed ingest, or a binary "
                "file saved without linear_tree).  Rebuild the Dataset "
                "from an in-memory matrix with linear_tree=true in its "
                "params, or re-save the binary with it")
        return LinearParams(k, float(cfg.linear_lambda),
                            float(cfg.lambda_l2))

    def _make_full_view(self) -> _HistView:
        td = self.train_data
        return _HistView(bins=td.bins, bins_rm=td.bins_rm,
                         bins_words=td.bins_words, bundle=self._bundle)

    @staticmethod
    def _row_buckets_enabled(cfg: Config) -> bool:
        """Row-bucket padding applies to single-process serial training
        only: the distributed learners shard rows across a device mesh
        (ingest owns their layout), and multihost arrays are promoted
        per process — padding either would change those invariants."""
        if not bool(getattr(cfg, "row_buckets", True)):
            return False
        if getattr(cfg, "is_parallel", False):
            return False
        try:
            if jax.process_count() > 1:
                return False
        except Exception:  # pragma: no cover - uninitialized backend
            pass
        return True

    def _init_row_state(self) -> None:
        """Row-dimension device state at the padded shape: the real-row
        mask and the all-ones (real rows only) weight vector every
        un-bagged iteration reuses."""
        mask = np.zeros(self._padded_rows, bool)
        mask[:self.num_data] = True
        put = jnp.asarray if self._row_mesh is None else \
            functools.partial(_put_row_blocks, self._row_mesh)
        self._real_rows = put(mask)
        self._ones_weight = put(mask.astype(np.float32))
        self._row_weight = self._ones_weight

    @staticmethod
    def _learner_mesh(cfg: Config):
        """The device mesh of a distributed tree learner, or None: the
        serial learner, or a single device to run on.  num_machines
        bounds the mesh (it is the reference's machine count; here a
        device count)."""
        if not getattr(cfg, "is_parallel", False):
            return None
        ndev = len(jax.devices())
        # single-controller-per-host: num_machines counts HOSTS (the
        # reference's machine list, wired up by parallel/multihost.py);
        # under a multi-process runtime the mesh spans every global
        # device.  In one process it bounds the local mesh instead
        # (the virtual-device test rigs).
        k = ndev if jax.process_count() > 1 else min(cfg.num_machines, ndev)
        if k <= 1:
            log.warning("tree_learner=%s requested but only %d device(s) "
                        "available; falling back to serial",
                        cfg.tree_learner, ndev)
            return None
        from jax.sharding import Mesh
        return Mesh(np.array(jax.devices()[:k]), ("data",))

    @property
    def _row_mesh(self):
        """The mesh whose devices each hold one row block of the
        training state: a row-sharded learner (data, voting) in one
        process.  Feature-parallel replicates the rows; under a
        multi-process runtime parallel/multihost.py promotes each
        process's arrays per call."""
        if self._mesh is None or jax.process_count() > 1 \
                or self.config.tree_learner == "feature":
            return None
        return self._mesh

    def _init_objective(self, train_set: BinnedDataset) -> None:
        """``objective.init`` keeps whole-dataset label and weight
        arrays.  For a row-sharded learner they stay on the host's own
        backend (where there is one): ``_make_grad_arrays`` then hands
        each device its block, and no chip holds all labels."""
        if self._mesh is not None:
            self.objective.over_devices()
        with jax.default_device(self._host_device()):
            self.objective.init(train_set.metadata, train_set.num_data)

    def _host_device(self):
        """Where a row-sharded learner keeps whole-dataset arrays until
        they are placed in blocks: the host's own backend, or None (JAX's
        default device) for every other learner and where no CPU backend
        is up."""
        if self._row_mesh is None:
            return None
        try:
            return jax.devices("cpu")[0]
        except RuntimeError:
            return None

    def _place_training_data(self, cfg: Config,
                             train_set: BinnedDataset) -> None:
        """Row padding, the admission gate and the device-resident
        training data.  Serial training pads rows up a shared shape
        bucket (utils/compile_cache.py): nearby dataset sizes share one
        compiled train_step/grow program (legacy custom objectives close
        over unpadded arrays, so they opt out).  A row-sharded learner
        pads to equal row blocks and places one on each device."""
        mesh = self._row_mesh
        if mesh is not None:
            self._padded_rows = shard_padded_rows(self.num_data,
                                                  mesh.devices.size)
        else:
            self._padded_rows = (
                compile_cache.bucket_rows(self.num_data)
                if self._row_buckets_enabled(cfg)
                and not self.objective.uses_legacy_gradients()
                else self.num_data)
        self._linear = self._setup_linear(cfg, train_set)
        self._check_memory_budget(cfg, train_set)
        # leaf-ordered shards read the row-major layout the serial
        # learner does; every other distributed learner reads columns
        with obs.span("Dataset::to_device"):
            self.train_data = _DeviceData(
                train_set, self.num_class,
                with_row_major=(mesh is None
                                or self._choose_grower()[0] == "ordered"),
                padded_rows=self._padded_rows,
                with_raw=self._linear is not None, mesh=mesh,
                padded_cols=self._device_shape[0])

    def _make_grad_arrays(self):
        """The objective's per-dataset arrays at the padded row count;
        for a row-sharded learner the row-aligned ones in row blocks,
        the rest replicated."""
        mesh = self._row_mesh
        if mesh is None:
            return self.objective.gradient_arrays(self._padded_rows)
        from jax.sharding import NamedSharding, PartitionSpec
        with jax.default_device(self._host_device()):
            arrays = self.objective.gradient_arrays(self._padded_rows)
        whole = NamedSharding(mesh, PartitionSpec())

        def place(a):
            if getattr(a, "ndim", 0) and a.shape[-1] == self._padded_rows:
                return _put_row_blocks(mesh, a)
            return jax.device_put(a, whole)
        return jax.tree.map(place, arrays)

    def _make_grad_fn(self):
        """Per-booster binding of the SHARED gradients program: the
        arrays travel per call, so a rebuilt booster (resume, second
        run) reuses the compiled program instead of re-tracing one that
        baked the previous dataset's labels in as constants."""
        jit = _shared_gradients_fn(self.objective)
        arrays = self._grad_arrays
        return lambda score: jit(arrays, score)

    def _choose_grower(self) -> Tuple[str, str]:
        """Which grower this booster's trees grow on, and why: THE choice,
        made from what can be observed (the learner and its mesh, the bin
        dtype, the bundle, the screener, the degrade ladder), never from
        an option.  ``ordered``: ops/ordered_grow.py, whole or one shard
        a device; ``cached`` and ``nocache``: ops/grow.py with and
        without the per-leaf histogram cache (``_grower``; a distributed
        learner's ``nocache`` exchanges through its own comm).  All grow
        the same trees (tests/test_ordered_grow.py)."""
        dtype = self.train_set.bins.dtype
        bundled = self._bundle is not None
        if self._mesh is not None:
            # parallel/grow.py asks the same rule of each view it is
            # handed, so a screener's compacted views take full passes
            from ..parallel.grow import grows_ordered
            if grows_ordered(self.config.tree_learner, dtype, bundled):
                return "ordered", ("leaf-ordered shards, one histogram "
                                   "exchange a split")
            return "nocache", ("full passes a split: leaf-ordered shards "
                               "are for tree_learner=data over uint8 bins "
                               "without EFB columns")
        if self._degrade_leaf_cache_off:
            return "nocache", ("memory_policy=degrade dropped the "
                               "per-leaf histogram cache")
        # an EFB layout is the dataset's own and the lanes carry it (the
        # split member's offset decode, the search in column space); a
        # screener's compacted views change from period to period and
        # stay on the grower that gathers rows
        screened = self._screener is not None
        if ordered_grow.accepts(dtype, screened):
            return "ordered", ("uint8 bins, EFB columns decoded at the split"
                               if bundled else "uint8 bins, no column decode")
        return "cached", ("feature screening's compacted views need the "
                          "column decode of gathered rows" if screened
                          else "max_bin > 256: the leaf-ordered layout "
                               "packs uint8 bins")

    # -- HBM admission control (docs/FAULT_TOLERANCE.md §Resource
    # exhaustion).  The estimate/gate/degrade machinery is host-side
    # arithmetic by construction: ZERO new XLA programs (ledger-pinned
    # by tests/test_resource_chaos.py).

    def _estimate_now(self, cfg: Config, train_set: BinnedDataset,
                      guard: bool,
                      rows: Optional[int] = None) -> Dict[str, int]:
        """The training estimate under the CURRENT construction state —
        re-evaluated after each degrade step so the ladder can stop as
        soon as the footprint fits.  ``rows``: as it WOULD look at
        another row count (the ``row_pad`` step's savings, without
        mutating state yet)."""
        return estimate_train_memory(
            self._rows_per_device() if rows is None else rows,
            self._device_shape[0], cfg.num_leaves,
            cfg.max_bin, self.num_class,
            bin_itemsize=train_set.bins.dtype.itemsize,
            donate_score=not guard and self._donation_on(),
            leaf_cache=not self._degrade_leaf_cache_off,
            linear_k=(self._linear.max_features
                      if self._linear is not None else 0))

    def _rows_per_device(self) -> int:
        """Rows the fullest device holds: all of them, or one block of a
        row-sharded learner's."""
        mesh = self._row_mesh
        return self._padded_rows // (mesh.devices.size if mesh is not None
                                     else 1)

    def _donation_on(self) -> bool:
        """This booster's round-to-round donation decision (before the
        nan-guard veto): the env/default gate, plus the ``score_donation``
        degrade step's override — which only ever fires where
        ``_donation_safe`` says aliasing is trustworthy."""
        if self._degrade_force_donate and _donation_safe():
            return True
        return _donation_enabled()

    def _check_memory_budget(self, cfg: Config,
                             train_set: BinnedDataset) -> None:
        """Pre-flight HBM admission gate: compare the per-component
        estimate against the device budget and apply ``memory_policy``:

        - ``fail_fast`` (default): refuse an over-budget config with a
          named ``MemoryBudgetExceeded`` carrying the component table —
          instead of dying hours later in an opaque XLA allocation;
        - ``degrade``: walk the documented footprint ladder
          (``utils/resource.py DEGRADE_STEPS``) — re-enable score
          donation where safe (drops the score double buffer), drop the
          per-leaf histogram cache (children recompute instead of
          sibling-subtraction; also honors ``histogram_pool_size`` as a
          real bound), cap the row-bucket pad — one ``warn_once`` +
          ``resource_degrade_*`` counter per applied step, refusing only
          if the ladder bottoms out still over budget."""
        from ..utils import resource
        guard = str(getattr(cfg, "nan_policy", "none") or "none") != "none"
        policy = resource.check_memory_policy(
            getattr(cfg, "memory_policy", "fail_fast"))
        est = self._estimate_now(cfg, train_set, guard)
        pool_mb = float(getattr(cfg, "histogram_pool_size", -1.0) or -1.0)
        if pool_mb > 0 and est["histogram_cache"] > pool_mb * (1 << 20):
            if policy == "degrade":
                # the reference's HistogramPool bound, honored the only
                # way fixed-shape jits can: the resident cache goes away
                # entirely and children recompute their histograms
                self._apply_degrade(
                    "hist_cache", est["histogram_cache"],
                    f"histogram_pool_size={pool_mb:g}MB bounds the "
                    f"per-leaf histogram cache "
                    f"({est['histogram_cache'] / (1 << 20):.0f}MB "
                    f"resident): dropping the cache — children "
                    f"recompute instead of sibling-subtraction")
                est = self._estimate_now(cfg, train_set, guard)
            else:
                log.warn_once(
                    "histogram_pool_size",
                    "histogram_pool_size=%.0fMB requested but the TPU "
                    "design keeps the whole per-leaf histogram cache "
                    "resident (%.0fMB for num_leaves=%d x %d columns x 9 "
                    "x %d bins); under memory_policy=fail_fast the "
                    "parameter does NOT bound memory — lower "
                    "num_leaves/max_bin, or set memory_policy=degrade "
                    "to make the bound real", pool_mb,
                    est["histogram_cache"] / (1 << 20), cfg.num_leaves,
                    train_set.num_columns, cfg.max_bin)
        limit = _device_memory_limit()
        obs.set_gauge("hbm_budget_bytes", int(limit) if limit else -1)
        if limit and est["total"] > limit and policy == "degrade":
            est = self._walk_degrade_ladder(cfg, train_set, guard, est,
                                            limit)
        obs.set_gauge("hbm_train_estimate_bytes", int(est["total"]))
        obs.set_gauge("hbm_histogram_cache_bytes",
                      int(est["histogram_cache"]))
        # publish the table for the DeviceOOM diagnosis (the gate's
        # prediction next to what the allocator saw)
        resource.set_budget_table(
            est, f"train rows={self._padded_rows} "
                 f"cols={train_set.num_columns} "
                 f"leaves={cfg.num_leaves} bins={cfg.max_bin}")
        if limit and est["total"] > limit:
            raise resource.refuse(est, limit, "training",
                                  self._degrade_steps)
        # running account for add_valid_dataset's incremental re-check
        self._train_mem_est = int(est["total"])
        self._valid_mem_bytes = 0

    def _apply_degrade(self, step: str, saved_bytes: int,
                       detail: str) -> None:
        from ..utils import resource
        if step == "score_donation":
            self._degrade_force_donate = True
        elif step == "hist_cache":
            self._degrade_leaf_cache_off = True
        elif step == "row_pad":
            self._padded_rows = self.num_data
        self._degrade_steps = self._degrade_steps + (step,)
        resource.note_degrade(step, saved_bytes, detail)

    def _walk_degrade_ladder(self, cfg: Config, train_set: BinnedDataset,
                             guard: bool, est: Dict[str, int],
                             limit: int) -> Dict[str, int]:
        """Apply the footprint ladder in order until the estimate fits
        (or every available step is spent).  Unavailable steps (nan
        guard pins the rollback buffer, CPU aliasing is unsafe, pad
        already zero) are skipped with a debug line — degrading must
        never trade memory for wrong answers."""
        from ..utils import resource
        for step in resource.DEGRADE_STEPS:
            if est["total"] <= limit:
                break
            if step == "score_donation":
                if guard or self._donation_on() or not _donation_safe():
                    log.debug("degrade step score_donation unavailable "
                              "(guard=%s, donation already on=%s, "
                              "backend aliasing safe=%s)", guard,
                              self._donation_on(), _donation_safe())
                    continue
                saved = est["score_double_buffer"]
                detail = ("re-enabling in-place score-buffer donation "
                          "(the [num_class, N] cache updates in place "
                          "instead of double-allocating)")
            elif step == "hist_cache":
                if self._degrade_leaf_cache_off \
                        or est["histogram_cache"] <= 0:
                    continue
                saved = est["histogram_cache"]
                detail = ("dropping the [L, F, 9, B] per-leaf histogram "
                          "cache — children recompute instead of "
                          "sibling-subtraction (slower, never wrong)")
            elif step == "row_pad":
                if self._padded_rows <= self.num_data \
                        or self._row_mesh is not None:
                    continue        # equal row blocks are not a bucket pad
                pad = self._padded_rows - self.num_data
                saved = est["total"] - self._estimate_now(
                    cfg, train_set, guard, rows=self.num_data)["total"]
                detail = (f"capping the row-bucket pad ({pad} pad rows "
                          f"released; this run compiles per-N programs "
                          f"instead of sharing the bucket ladder)")
            else:  # pragma: no cover - DEGRADE_STEPS is closed
                continue
            self._apply_degrade(step, max(int(saved), 0), detail)
            est = self._estimate_now(cfg, train_set, guard)
        return est

    @staticmethod
    def _make_grow_params(cfg: Config) -> GrowParams:
        # bagging / GOSS produce zero-weight rows every round: compact
        # them out of the leaf-ordered layout so tree cost tracks the
        # subsample (gbdt.cpp:271-278's bag-subset dataset switch).
        # GOSS qualifies only when it can actually sample (top+other < 1);
        # its 1/learning_rate warmup rounds compact an all-active mask
        # (one partition of N rows), so that one program serves both.
        goss_samples = (cfg.boosting_type == "goss"
                        and (cfg.top_rate + cfg.other_rate) < 1.0)
        subsampled = (goss_samples
                      or (cfg.bagging_freq > 0 and cfg.bagging_fraction < 1.0))
        return GrowParams(
            num_leaves=cfg.num_leaves, max_bin=cfg.max_bin,
            min_data_in_leaf=cfg.min_data_in_leaf,
            min_sum_hessian_in_leaf=cfg.min_sum_hessian_in_leaf,
            lambda_l1=cfg.lambda_l1, lambda_l2=cfg.lambda_l2,
            min_gain_to_split=cfg.min_gain_to_split,
            max_depth=cfg.max_depth,
            compact_inactive=subsampled)

    @staticmethod
    def _make_metrics(cfg: Config, dataset: BinnedDataset) -> List[Metric]:
        out = []
        for name in cfg.metric:
            m = create_metric(name, cfg)
            if m is not None:
                m.init(dataset.metadata, dataset.num_data)
                out.append(m)
        return out

    def _make_grow_fn(self):
        """Pick the tree learner (TreeLearner::CreateTreeLearner,
        tree_learner.cpp:1-26): serial, or a distributed learner over
        the device mesh ``_learner_mesh`` found."""
        cfg = self.config
        self._comm_traffic = None           # serial: no collectives
        self._comm_traffic_totals = (0, 0)
        self._parallel_grow_active = False
        mesh = self._mesh
        # once a booster, and again where a reset rebuilds the grower
        self._grower_kind, why = self._choose_grower()
        log.info("Growing trees on the %s grower: %s", self._grower_kind,
                 why)
        if mesh is not None:
            from ..parallel import make_parallel_grow
            log.info("Using %s-parallel tree learner over %d devices",
                     cfg.tree_learner, mesh.devices.size)
            fn = make_parallel_grow(mesh, cfg.tree_learner,
                                    self.grow_params, top_k=cfg.top_k)
            # static per-tree collective account (obs layer): computed
            # once from shapes, accumulated per iteration.  Data-parallel
            # exchanges COLUMN-shaped sums (digit sums where it grows
            # leaf-ordered shards, float histograms under EFB or uint16
            # bins); voting/feature ship per-ORIGINAL-feature payloads.
            from ..parallel.comm import traffic_totals
            traffic_f = (self.num_columns if cfg.tree_learner == "data"
                         else self.num_features)
            self._comm_traffic = fn.traffic_per_tree(
                traffic_f, bundled=self._bundle is not None,
                bins_dtype=self.train_data.bins.dtype)
            self._comm_traffic_totals = traffic_totals(self._comm_traffic)
            self._parallel_grow_active = True
            if jax.process_count() > 1:
                # multi-controller runtime: promote per-process inputs
                # to global arrays / gather sharded outputs back
                # (bundling is disabled under multihost loading, so
                # the wrapped signature never carries a bundle)
                from ..parallel.multihost import globalize_grow_fn
                fn = globalize_grow_fn(fn, mesh)
                return (lambda view, nb, ic, fm, g, h, w, lr:
                        fn(view.bins, nb, ic, fm, g, h, w, lr))
            # bins_rm / bins_words: the leaf-ordered shards' resident
            # layout (None for every other learner, which ignores them)
            return (lambda view, nb, ic, fm, g, h, w, lr:
                    fn(view.bins, nb, ic, fm, g, h, w, lr,
                       bundle=view.bundle, bins_rm=view.bins_rm,
                       bins_words=view.bins_words))
        return _grower(self._grower_kind, self.grow_params)

    def reset_config(self, config: Config) -> None:
        """Booster::ResetConfig (c_api.cpp:96-134): re-derive learner
        parameters and metrics against the existing training data (used by
        the reset_parameter callback, e.g. learning-rate schedules)."""
        old_cfg, self.config = getattr(self, "config", None), config
        if not hasattr(self, "train_set"):
            return
        # The pending iteration was packed under the OLD grow_params; it must
        # be unpacked with them before num_leaves can change.
        self._flush_pending()
        self.shrinkage_rate = config.learning_rate
        # feature_screen_* changes (reset_parameter callback) rebuild the
        # screener — ONLY on a real change, so per-round learning-rate
        # schedules don't wipe the gain EWMA every iteration
        def _screen_key(cfg):
            return tuple(float(getattr(cfg, k, 0) or 0) for k in
                         ("feature_screen_ratio", "feature_screen_refresh",
                          "feature_screen_warmup", "feature_screen_decay"))
        if old_cfg is not None and _screen_key(old_cfg) != _screen_key(config):
            self._setup_screening(config)
            self._grow_fn = self._make_grow_fn()
            self._full_view = self._make_full_view()
            self._train_step = None
        new_params = self._make_grow_params(config)
        if old_cfg is not None and (
                old_cfg.tree_learner, old_cfg.num_machines) != (
                config.tree_learner, config.num_machines):
            # another learner or mesh: the training state is placed for
            # the one it was built for (whole on a device, or one row
            # block a device), so it is placed again
            self.grow_params = new_params
            self.reset_training_data(self.train_set)
        elif new_params != self.grow_params:
            # Rebuild only when the jitted growth program actually changes:
            # a fresh closure would force an XLA recompile every iteration
            # under reset_parameter schedules (learning_rate is a runtime
            # argument, not part of the compiled program).
            self.grow_params = new_params
            self._grow_fn = self._make_grow_fn()
            self._full_view = self._make_full_view()
            self._train_step = None
        self.train_metrics = self._make_metrics(config, self.train_set)
        for vi, dd in enumerate(self.valid_data):
            self.valid_metrics[vi] = self._make_metrics(config, dd.dataset)

    def reset_training_data(self, train_set: BinnedDataset) -> None:
        """GBDT::ResetTrainingData (gbdt.cpp:101-167 via c_api.cpp:70-97):
        swap the training dataset (mapper-aligned), re-init objective and
        training metrics against it, and replay the existing models into a
        fresh score buffer."""
        self._flush_pending()
        old = getattr(self, "train_set", None)
        if old is not None and not _mappers_aligned(old, train_set):
            # Dataset::CheckAlign (gbdt.cpp ResetTrainingData): bin-space
            # tree state is only meaningful against identical mappers
            log.fatal("Cannot reset training data, since new training data "
                      "has different bin mappers")
        cfg = self.config
        self.train_set = train_set
        # the fingerprint follows the data: a delta-trained model ships
        # the FRESH data's fingerprint (train_delta compares it against
        # the base model's before the swap)
        new_fp = getattr(train_set, "data_fingerprint", None)
        if new_fp is not None:
            self.data_fingerprint = new_fp
        self.num_data = train_set.num_data
        self._mesh = self._learner_mesh(cfg)
        self._init_objective(train_set)
        self.num_bin = jnp.asarray(train_set.num_bin_per_feature())
        self.is_cat = jnp.asarray(train_set.is_categorical_per_feature())
        self.num_columns = train_set.num_columns
        self._setup_bundle(train_set, cfg)
        # re-run the HBM admission gate against the NEW dataset: the
        # recomputed pad would otherwise silently undo a row_pad degrade
        # step, and a larger reset dataset must be refused/degraded here
        # — not hours later in an opaque XLA RESOURCE_EXHAUSTED.  The
        # valid-set accounting survives the gate's reset (valid sets
        # are not touched by a training-data swap).
        valid_bytes = getattr(self, "_valid_mem_bytes", 0)
        self._place_training_data(cfg, train_set)
        self._valid_mem_bytes = valid_bytes
        self.train_metrics = self._make_metrics(cfg, train_set)
        self._init_row_state()
        self._full_feat_mask = self._feature_row(
            np.ones(self.num_features, bool))
        self._full_feat_masks = jnp.stack([self._full_feat_mask]
                                          * self.num_class)
        # rebind the SHARED gradients program to this dataset's arrays
        # (no retrace unless the shapes changed — the labels are runtime
        # arguments now, not compile-time constants)
        self._grad_arrays = self._make_grad_arrays()
        self._grad_fn = self._make_grad_fn()
        self._setup_screening(cfg)
        self._grow_fn = self._make_grow_fn()
        self._full_view = self._make_full_view()
        self._train_step = None
        for i, tree in enumerate(self._models):
            self._add_host_tree_to(self.train_data, tree, i % self.num_class)

    def add_valid_dataset(self, valid_set: BinnedDataset) -> None:
        """GBDT::AddValidDataset (gbdt.cpp:169-199)."""
        if not _mappers_aligned(self.train_set, valid_set):
            # Dataset::CheckAlign: bin-space replay/scoring is only
            # meaningful when the valid set shares the training mappers
            # (create it with reference=train / LGBM_DatasetCreateFromX
            # with the train handle as reference)
            log.fatal("Cannot add validation data, since it has different "
                      "bin mappers with training data")
        # Re-run the fail-fast memory budget with this valid set counted:
        # the late-attach path is exactly where the original construction
        # check cannot see the allocation coming and training would die
        # in an XLA OOM after hours of work.
        est = estimate_valid_memory(
            valid_set.num_data, valid_set.num_columns, self.num_class,
            bin_itemsize=valid_set.bins.dtype.itemsize)
        valid_bytes = getattr(self, "_valid_mem_bytes", 0) + int(est["total"])
        total = getattr(self, "_train_mem_est", 0) + valid_bytes
        obs.set_gauge("hbm_total_estimate_bytes", int(total))
        limit = _device_memory_limit()
        if limit and total > limit:
            log.fatal(
                "attaching this validation set (%d rows: bins=%.0fMB, "
                "scores=%.0fMB) brings the estimated device footprint to "
                "%.0fMB, over the budget %.0fMB (training state %.0fMB + "
                "valid sets %.0fMB).  Evaluate on fewer/smaller valid "
                "sets, or shrink the training state (num_leaves/max_bin).",
                valid_set.num_data, est["bins_device"] / (1 << 20),
                est["scores"] / (1 << 20), total / (1 << 20),
                limit / (1 << 20),
                getattr(self, "_train_mem_est", 0) / (1 << 20),
                valid_bytes / (1 << 20))
        self._valid_mem_bytes = valid_bytes
        if self._linear is not None and valid_set.raw is None:
            log.fatal("linear_tree validation scoring needs the valid "
                      "set's raw feature values (the per-leaf affine "
                      "epilogue reads them); create the valid set with "
                      "reference=train from an in-memory matrix")
        with obs.span("Dataset::to_device"):
            dd = _DeviceData(
                valid_set, self.num_class,
                padded_rows=(compile_cache.bucket_rows(valid_set.num_data)
                             if self._row_buckets_enabled(self.config)
                             else valid_set.num_data),
                with_raw=self._linear is not None)
        # replay existing trees (continued training)
        for i, tree in enumerate(self.models):
            cls = i % self.num_class
            self._add_host_tree_to(dd, tree, cls)
        self.valid_data.append(dd)
        metrics = []
        for name in self.config.metric:
            m = create_metric(name, self.config)
            if m is not None:
                m.init(valid_set.metadata, valid_set.num_data)
                metrics.append(m)
        self.valid_metrics.append(metrics)

    # ------------------------------------------------------------------
    def _bagging_mask(self, iter_: int) -> jax.Array:
        """Bagging (gbdt.cpp:201-280): pick bagging_fraction*N rows without
        replacement every bagging_freq iterations.

        The draw runs ON DEVICE (uniforms + order-statistic threshold):
        a host-side np.random.choice without replacement at 1M rows costs
        tens of ms plus a 4 MB upload EVERY round at bagging_freq=1 —
        more than the tree it was supposed to shrink."""
        cfg = self.config
        if cfg.bagging_freq <= 0 or cfg.bagging_fraction >= 1.0:
            self._bag_cnt = self.num_data
            return self._ones_weight
        if iter_ % cfg.bagging_freq == 0:
            bag_cnt = int(cfg.bagging_fraction * self.num_data)
            self._bag_key, sub = jax.random.split(self._bag_key)
            self._row_weight = _device_bag_mask(sub, self._padded_rows,
                                                bag_cnt, self.num_data)
            self._bag_cnt = bag_cnt
            obs.inc("bagging_draws")
        return self._row_weight

    def _feature_mask(self) -> jax.Array:
        """feature_fraction sampling per tree (serial_tree_learner.cpp:226+)
        intersected with this round's gain-screening mask (EMA-FS,
        models/screening.py) when one is active."""
        frac = self.config.feature_fraction
        screen = self._screen_mask_dev
        if frac >= 1.0:
            return (self._full_feat_mask if screen is None
                    else self._full_feat_mask & screen)
        used = max(1, int(self.num_features * frac))
        idx = self._feature_rng.choice(self.num_features, used, replace=False)
        mask = np.zeros(self.num_features, bool)
        mask[idx] = True
        out = self._feature_row(mask)
        return out if screen is None else out & screen

    def _feature_row(self, mask: np.ndarray) -> jax.Array:
        """A per-feature mask as the device holds it: a bundled layout's
        pad features (``_setup_bundle``) are never searched."""
        return jnp.asarray(np.pad(
            mask, (0, self._device_shape[1] - self.num_features)))

    def _feature_masks_all(self) -> jax.Array:
        """[num_class, F] per-class feature masks for the fused step (same
        RNG draw order as per-class _feature_mask calls)."""
        frac = self.config.feature_fraction
        if frac >= 1.0:
            screen = self._screen_mask_dev
            return (self._full_feat_masks if screen is None
                    else self._full_feat_masks & screen[None, :])
        return jnp.stack([self._feature_mask()
                          for _ in range(self.num_class)])

    # -- gain-informed screening views (docs/SPARSE.md) ----------------
    def _select_view(self) -> "_HistView":
        """Pick this round's histogram view and screening mask.

        Warmup and refresh rounds run the FULL view with every feature
        unmasked; screened rounds run the compacted active-column view
        (when available) under the EWMA-derived mask.  Both views and
        the masks are runtime arguments to the shared programs, so
        toggling costs zero recompiles after each view's first trace
        (ledger-pinned in tests/test_screening.py)."""
        scr = self._screener
        if scr is None:
            return self._full_view
        it = self.iter_ - self.num_init_iteration
        mode = scr.round_mode(it)
        if mode != "screened":
            self._screen_mask_dev = None
            self._screen_mask_np = None
            obs.set_gauge("screen_active_features", self.num_features)
            if mode == "refresh":
                obs.inc("screen_refresh_total")
                scr.refresh_total += 1
            return self._full_view
        period = scr.period(it)
        if period != self._screen_period:
            self._screen_period = period
            cols = scr.active_columns()
            self._screen_mask_np = scr.screen_mask(cols)
            self._screen_mask_dev = jnp.asarray(self._screen_mask_np)
            self._active_view = self._build_active_view(cols)
        obs.set_gauge("screen_active_features",
                      int(self._screen_mask_np.sum()))
        return (self._active_view if self._active_view is not None
                else self._full_view)

    def _screen_decode_base(self) -> BundleDecode:
        """Decode tables the compacted view derives from: the EFB tables
        when the dataset is bundled, else identity tables (a trivial
        all-singleton plan)."""
        if self._bundle is not None:
            return self._bundle
        if self._identity_decode is None:
            plan = BundlePlan([[f] for f in range(self.num_features)],
                              [[0]] * self.num_features, self.num_features)
            dn = plan.decode_arrays(
                [m.num_bin for m in self.train_set.mappers],
                [m.default_bin for m in self.train_set.mappers],
                self.config.max_bin)
            self._identity_decode = BundleDecode.from_tables(dn)
        return self._identity_decode

    def _build_active_view(self, cols: np.ndarray) -> Optional["_HistView"]:
        """Gather the active columns into a fixed-budget [C_pad, N]
        block (one device gather per refresh period).  C_pad is the
        compile-cache bucket of the CONSTANT keep_cols budget, so every
        screened round of the run shares one compiled program.  Returns
        None (mask-only screening) under the distributed learners or
        when compaction would not shrink the pass."""
        if self._parallel_grow_active:
            return None
        try:
            if jax.process_count() > 1:
                return None
        except Exception:  # pragma: no cover - uninitialized backend
            pass
        c_pad = compile_cache.bucket_rows(len(cols))
        if c_pad >= self.num_columns:
            return None
        idx = np.full(c_pad, 1 << 30, np.int64)
        idx[:len(cols)] = cols
        idx_dev = jnp.asarray(idx)
        td = self.train_data
        bins_act = jnp.take(td.bins, idx_dev, axis=0,
                            mode="fill", fill_value=0)
        bins_rm_act = (jnp.take(td.bins_rm, idx_dev, axis=1,
                                mode="fill", fill_value=0)
                       if td.bins_rm is not None else None)
        base = self._screen_decode_base()
        pos = np.zeros(self.num_features, np.int32)
        pos_of = {int(c): i for i, c in enumerate(cols)}
        for f in range(self.num_features):
            # dropped features point at column 0; they are masked out of
            # the scan, so the junk expansion is never consulted
            pos[f] = pos_of.get(int(self._bundle_col_np[f]), 0)
        # (the column-space search's tables describe the full layout)
        bundle_act = base._replace(col=jnp.asarray(pos), col_feat=None,
                                   slot_feat=None, multi=None)
        obs.inc("screen_compactions_total")
        return _HistView(bins=bins_act, bins_rm=bins_rm_act,
                         bins_words=None, bundle=bundle_act)

    # ------------------------------------------------------------------
    def _gradients(self) -> Tuple[jax.Array, jax.Array]:
        return self._grad_fn(self.train_data.score)

    def _goss_round(self) -> Optional[jax.Array]:
        """This round's state of the GOSS draw (ops/goss.py ``sample``):
        int32 iteration, ``bagging_seed``'s bits, whether the round
        samples (not in the first ``1 / learning_rate`` rounds,
        goss.hpp:129, nor where ``top_rate`` keeps no row or the two
        rates keep them all) and ``top_cnt``; None where the booster does
        not sample by gradients."""
        g = self._goss
        if g is None:
            return None
        n = self.num_data
        top_cnt, other_cnt = int(g.top_rate * n), int(g.other_rate * n)
        warmup = int(1.0 / max(self.config.learning_rate, 1e-12))
        sampled = (self.iter_ >= warmup and top_cnt > 0
                   and top_cnt + other_cnt < n)
        # telemetry: the draw is this round's "bag" (a*N + b*N rows)
        self._bag_cnt = top_cnt + other_cnt if sampled else n
        if sampled:
            obs.inc("bagging_draws")
            obs.inc("goss_sampled_rounds")
        seed = int(self.config.bagging_seed) & 0xFFFFFFFF
        return jnp.asarray(
            [self.iter_, seed - (1 << 32) if seed >= 1 << 31 else seed,
             int(sampled), top_cnt], jnp.int32)

    def _make_train_step(self):
        """One fused jit for a full boosting iteration on the standard
        (non-fobj) path: gradients -> per-class grow -> score update ->
        packed host transfer vectors.  A single device dispatch per
        iteration instead of ~5: every dispatch pays host submit
        latency, which at >10 iters/sec is a first-order cost.

        Serial growth binds the process-wide SHARED train_step program
        (every per-dataset array is an argument), so a rebuilt booster —
        snapshot resume, a second run in the same process — reuses the
        compiled program: zero new train_step compiles in the ledger.
        The score argument is DONATED when nan_policy is off and the
        backend is an accelerator (_donation_enabled), so XLA updates
        the [num_class, N] cache in place instead of double-allocating
        it every round."""
        # NaN/Inf containment: the grad/hess finiteness reduction runs
        # INSIDE the fused jit (the gradients never visit the host), so
        # the guarded path pays one extra scalar in the transfer — the
        # ungated path compiles the check away entirely.
        guard = self._nan_policy != "none"
        if self._parallel_grow_active:
            return self._make_train_step_local(guard)
        jit = _shared_train_step(self.objective, self.num_class, guard,
                                 self._grower_kind, self.grow_params,
                                 donate=not guard and self._donation_on(),
                                 linear=self._linear, goss=self._goss)
        num_bin, is_cat = self.num_bin, self.is_cat
        grad_arrays = self._grad_arrays
        raw = self.train_data.raw if self._linear is not None else None

        def args(score, feat_masks, row_weight, lr, view, sample=None):
            return (score, feat_masks, row_weight, lr, view.bins,
                    num_bin, is_cat, grad_arrays, view.bins_rm,
                    view.bins_words, view.bundle, raw, sample)

        def step(*a, sample=None):
            return jit(*args(*a, sample))
        # same passthrough InstrumentedJit gives the distributed step:
        # callers inspect the lowered program (chip_smoke.py looks for
        # the Pallas custom call in the compiled text)
        step.lower = lambda *a, sample=None: jit.lower(*args(*a, sample))
        return step

    def _make_train_step_local(self, guard: bool):
        """Per-booster fused step for the distributed learners: their
        grow fn closes over a device mesh (shard_map), which the shared
        registry cannot key portably.  The body is the shared step's
        (``_round_step``), constant leaves only: ``_setup_linear``
        refuses ``linear_tree`` under distributed training."""
        step_fn = obs.instrumented_jit(
            _round_step(self.objective.program_holder(), self.num_class,
                        guard, self._grow_fn, goss=self._goss),
            program="train_step")
        data = (self.num_bin, self.is_cat, self._grad_arrays)

        def step(*a, sample=None):
            return step_fn(*a, *data, None, sample)
        step.lower = lambda *a, sample=None: step_fn.lower(*a, *data, None,
                                                           sample)
        return step

    # -- pipelined host materialization --------------------------------
    @property
    def models(self) -> List[Tree]:
        """Host trees, class-major rows.  Flushes the pending iteration so
        external readers (save/predict/DART/R bindings) always see the
        synchronous view."""
        self._flush_pending()
        return self._models

    @models.setter
    def models(self, value: List[Tree]) -> None:
        self._flush_pending()
        self._models = value

    def _flush_pending(self) -> None:
        """Materialize the pending iteration's trees.  The 13 TreeArrays
        fields travel as TWO packed vectors per class (every device->host
        round-trip stalls the pipelined host path).  Detects
        reference-style saturation (GBDT::TrainOneIter, gbdt.cpp:362-378):
        an iteration where no class could split is popped and marks
        training stopped."""
        pend = self._pending_iter
        if not pend:
            return
        self._pending_iter = None
        pend_idx, self._pending_iter_idx = self._pending_iter_idx, -1
        with obs.span("GBDT::host_tree"):
            host = jax.device_get([packed for packed, _, _ in pend])
        obs.devprof.transfer(
            "d2h", "host_tree",
            sum(int(a.nbytes) for vecs in host for a in vecs))
        L = self.grow_params.num_leaves
        lin = self._linear
        trees = []
        for vecs in host:
            tree = Tree.from_arrays(
                unpack_tree_arrays(vecs[0], vecs[1], L),
                self.train_set.mappers,
                self.train_set.used_feature_map,
                self._pending_shrinkage)
            if len(vecs) > 2 and lin is not None:
                # linear transport rides the SAME device_get: two more
                # packed vectors per class (models/linear.py)
                coeff, feat, fb = unpack_linear(vecs[2], vecs[3], L,
                                                lin.max_features)
                attach_linear(tree, coeff, feat,
                              self.train_set.used_feature_map)
                if fb:
                    obs.inc("linear_fallback_total", fb)
            trees.append(tree)
        if self._screener is not None:
            # realized split gains feed the EMA-FS feature EWMA
            # (models/screening.py); 1-leaf saturated trees contribute
            # nothing, so observing before the saturation check is safe
            self._screener.observe_trees(trees)
        rec = self._telemetry
        shapes = ([{"num_leaves": int(t.num_leaves),
                    "max_depth": int(t.max_depth())} for t in trees]
                  if rec is not None and pend_idx >= 0 else None)
        if all(t.num_leaves <= 1 for t in trees):
            log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements.")
            self._no_more_splits = True
            obs.inc("saturated_iterations")
            self.iter_ -= 1
            if shapes is not None:
                rec.note(pend_idx, saturated=True, trees=shapes)
        else:
            self._models.extend(trees)
            obs.inc("trees_grown", len(trees))
            if shapes is not None:
                rec.note(pend_idx, trees=shapes)

    # -- telemetry (lightgbm_tpu/obs/) ---------------------------------
    def set_event_recorder(self, recorder) -> None:
        """Attach an ``obs.EventRecorder``: one JSONL record per boosting
        iteration (phase wall times, bag count, grown-tree shape,
        cumulative collective bytes; eval values arrive via
        ``callback.log_telemetry``).  ``None`` detaches."""
        self._telemetry = recorder

    def _note_iter_event(self, it: int, t0: float, tt0, *,
                         discarded: bool = False) -> None:
        """Per-iteration telemetry epilogue: close the trace window and
        note this iteration's host-side fields.  ``tt0`` is the timetag
        accumulator baseline captured at iteration start (None when the
        serializing TIMETAG mode is off — then only the honest async wall
        time is recorded)."""
        report = None
        if self._trace is not None:
            self._trace.iter_end(it, sync=self.train_data.score)
            report = self._trace.take_report()
        rec = self._telemetry
        if rec is None:
            return
        if report is not None:
            # the window closed on this round: its reduction to named
            # device phases (obs/devtrace.py, device_phases.json)
            rec.note(it, device_phases=report)
        phases = {}
        if tt0 is not None:
            now = timetag.get_timings()
            phases = {k: round(v - tt0.get(k, 0.0), 6)
                      for k, v in now.items() if v > tt0.get(k, 0.0)}
        rec.note(it, wall_s=round(time.perf_counter() - t0, 6),
                 phases=phases, bag_cnt=int(self._bag_cnt),
                 comm_bytes_cum=int(self._cum_comm_bytes),
                 comm_calls_cum=int(self._cum_comm_calls))
        if discarded:
            # dispatched but undone (the previous iteration saturated);
            # the reference would never have trained it
            rec.note(it, discarded=True, trees=[])

    def close_trace(self) -> None:
        """Stop a trace window the training loop ended inside (otherwise
        it would keep recording unrelated work until process exit)."""
        if self._trace is not None:
            self._trace.close()

    def train_one_iter(self, grad=None, hess=None) -> bool:
        """One boosting round (gbdt.cpp:295-382).  Returns True when
        training should stop (no more splits possible on every class).
        The whole round is timed by an ``obs.span``: one observe into the
        ``phase_seconds_gbdt_iteration`` wall-time histogram per call —
        host bookkeeping only, the async device pipeline is never synced
        by it (docs/OBSERVABILITY.md)."""
        # desync check runs at round ENTRY, before this round's gradients
        # consume the (possibly diverged) state: for the built-in
        # objectives (gradients computed inside the impl) a resync here
        # restores the clean trajectory BEFORE a poisoned rank's
        # gradients can leak into the round's cross-process histogram
        # sums.  Custom-fobj gradients arrive precomputed from upstream
        # — one post-resync round still trains on them (consistent
        # pod-wide, flagged below; the next round is clean).
        resynced = self._maybe_check_consistency()
        if resynced and grad is not None:
            log.warning(
                "desync resync at iteration %d arrived after this "
                "round's custom-objective gradients were computed from "
                "the pre-resync scores; the pod stays consistent but "
                "this one round ingests the stale gradients", self.iter_)
        # round_scope splits the span's wall time into host vs device
        # shares from the device-seconds estimate accumulated inside it
        # (no-op unless devprof is on — the span itself never syncs)
        # a booster's first round holds the step's compile or cache load;
        # when it returns the job's set-up is over (obs/setup.py).  The
        # round's own span stays the outer one: a round is a trace root
        first, self._first_round_done = not self._first_round_done, True
        try:
            with obs.devprof.round_scope(), obs.span("GBDT::iteration"), \
                    (obs.span("GBDT::first_round") if first
                     else contextlib.nullcontext()):
                return self._train_one_iter_impl(grad, hess)
        finally:
            if first:
                obs.setup.close(self._telemetry)

    # -- distributed desync detection ----------------------------------
    def _maybe_check_consistency(self) -> bool:
        """Every ``distributed_consistency_check`` rounds under a
        multi-process runtime, verify the replication invariant the
        module header of parallel/multihost.py only states in prose:
        every rank holds identical trees, score caches and RNG streams.
        Single-process (or K=0): returns before touching jax — no new
        collectives, no new compiles.  Returns True when a resync
        restored state on any rank."""
        K = self._consistency_every
        if K <= 0:
            return False
        from ..parallel.multihost import process_rank_world
        rank, world = process_rank_world()
        if world <= 1:
            return False
        it = self.iter_ - self.num_init_iteration
        if it <= 0 or it % K != 0:
            return False
        # same guard as Comm::grow: a rank dying during THIS allgather
        # must become a bounded named abort, not a silent hang
        from ..parallel.watchdog import active_watchdog
        wd = active_watchdog()
        with obs.span("Dist::consistency"):
            if wd is not None:
                with wd.guard("Dist::consistency"):
                    return self._check_distributed_consistency(rank, world)
            return self._check_distributed_consistency(rank, world)

    def _consistency_digests(self) -> Dict[str, int]:
        """Cheap per-field uint64 digests of the replicated training
        state (flushes the pipelined iteration first so every rank
        digests the synchronous view).  Field granularity is what makes
        the divergence diagnostic name WHAT desynced, not just that
        something did."""
        import hashlib
        import pickle

        self._flush_pending()

        def d(blob: bytes) -> int:
            return int.from_bytes(hashlib.sha256(blob).digest()[:8],
                                  "little")

        return {
            "iter": d(np.int64([self.iter_, len(self._models)]).tobytes()),
            "trees": d(pickle.dumps(self._models,
                                    protocol=pickle.HIGHEST_PROTOCOL)),
            "score": d(self.train_data.host_score(np.float32).tobytes()),
            "rng": d(np.asarray(self._bag_key).tobytes()
                     + pickle.dumps(self._feature_rng.get_state())
                     + np.asarray(self._row_weight).tobytes()),
        }

    def _check_distributed_consistency(self, rank: int,
                                       world: int) -> bool:
        """Allgather the per-field digests, compare, and apply
        ``desync_policy`` on divergence: ``fail_fast`` dies with a
        diagnostic naming the diverged rank(s) and field(s) (the same
        allgather runs on every rank, so the whole pod stops together);
        ``resync`` broadcasts rank 0's full snapshot state and restores
        it on the divergent ranks, then training continues (returns
        True)."""
        from ..parallel.comm import allgather_host_array, \
            broadcast_host_bytes
        fields = self._consistency_digests()
        names = list(fields)
        mine = np.array([fields[n] for n in names], np.uint64)
        gathered = np.asarray(allgather_host_array(mine))  # [world, F]
        if bool((gathered == gathered[0]).all()):
            return False
        obs.inc("desync_detected_total")
        diverged: Dict[str, List[int]] = {}
        for fi, name in enumerate(names):
            col = gathered[:, fi]
            vals, counts = np.unique(col, return_counts=True)
            top = int(counts.max())
            majority = {int(v) for v, c in zip(vals, counts)
                        if int(c) == top}
            # majority wins; ties (e.g. any 2-process pod) defer to
            # rank 0, consistent with resync trusting rank 0's state
            ref = (int(col[0]) if int(col[0]) in majority
                   else next(iter(sorted(majority))))
            bad = [r for r in range(world) if int(col[r]) != ref]
            if bad:
                diverged[name] = bad
        detail = "; ".join(
            f"field {name!r} diverged on rank(s) {bad}"
            for name, bad in diverged.items())
        if self._desync_policy == "fail_fast":
            log.fatal(
                "distributed state desync detected at iteration %d "
                "(%d-process run): %s.  Every rank must hold identical "
                "replicated training state; set desync_policy=resync to "
                "broadcast rank 0's state instead of stopping, and see "
                "docs/FAULT_TOLERANCE.md §Distributed.",
                self.iter_, world, detail)
        if any(0 in bad for bad in diverged.values()):
            # resync trusts rank 0; the majority just voted rank 0 THE
            # diverged one (only possible at world >= 3 — 2-rank ties
            # defer to rank 0).  Broadcasting its state would propagate
            # the corruption pod-wide while logging "healed": refuse.
            log.fatal(
                "distributed state desync detected at iteration %d: %s — "
                "rank 0 is the resync source of truth but is itself the "
                "diverged rank; refusing to propagate its state "
                "(desync_policy=resync falls back to failing fast here).",
                self.iter_, detail)
        import pickle
        log.warning("distributed state desync detected at iteration %d: "
                    "%s — resyncing every rank from rank 0's state",
                    self.iter_, detail)
        payload = (pickle.dumps(self.snapshot_state(),
                                protocol=pickle.HIGHEST_PROTOCOL)
                   if rank == 0 else None)
        blob = broadcast_host_bytes(payload, is_source=(rank == 0))
        if rank != 0:
            self.restore_state(pickle.loads(blob))
        obs.inc("desync_resyncs_total")
        return True

    def _train_one_iter_impl(self, grad=None, hess=None) -> bool:
        """Body of one boosting round.

        With ``_pipeline`` the saturation signal arrives one call later than
        the reference's (the saturated iteration is detected when the NEXT
        call flushes it, AFTER that call has dispatched its own device work
        — the dispatch must come first so the host transfer overlaps device
        growth).  The resulting model and scores are identical: a saturated
        iteration's trees are 1-leaf with value 0 (_GrowState.cur_value is
        only written on splits), so their score deltas are exactly zero; the
        trees are popped like GBDT::TrainOneIter's pop (gbdt.cpp:362-378),
        and the extra dispatched iteration is discarded with its (possibly
        nonzero, under bagging) deltas subtracted back out.  The only
        observable deviation from the reference is one extra eval/callback
        round for the popped iteration, with metrics unchanged from the
        round before.  The flag is cleared on detection so an explicit retry
        re-attempts growth, as the reference would."""
        if self._no_more_splits:
            # saturation detected by an out-of-band flush (models getter,
            # reset_config, rollback): deliver the stop signal without
            # dispatching — and clear it so a later retry trains afresh
            self._no_more_splits = False
            return True
        # -- telemetry (obs layer): iteration index, wall clock, optional
        # timetag baseline for per-phase deltas, trace window entry.  All
        # gated so the disabled path costs two attribute reads.
        it = self.iter_
        rec = self._telemetry
        t_iter0 = time.perf_counter() if rec is not None else 0.0
        tt0 = (timetag.get_timings()
               if rec is not None and timetag.ENABLED else None)
        if self._trace is not None:
            self._trace.iter_begin(it, sync=self.train_data.score)
        # The fused step computes gradients INSIDE the jit and never calls
        # the _gradients hook, so it only applies when this instance uses
        # the base implementations of the per-round hooks (a custom booster
        # that overrides one needs the per-stage path; _bagging_mask is
        # checked too, conservatively, so any hook override routes through
        # the path that visibly runs every hook).  GOSS's draw is a stage
        # of both paths.  LGBT_NO_FUSED_STEP=1/true also forces per-stage
        # (same results; smaller XLA programs for compile-constrained
        # setups).
        fused = (grad is None and hess is None
                 and type(self)._gradients is GBDT._gradients
                 and type(self)._bagging_mask is GBDT._bagging_mask
                 and jax.process_count() == 1  # multihost grow fn is a
                 # host-side bridge (globalize_grow_fn), not jit-traceable
                 and os.environ.get("LGBT_NO_FUSED_STEP", "").lower()
                 not in ("1", "true", "yes"))
        if self._lr_cache[0] != self.shrinkage_rate:
            self._lr_cache = (self.shrinkage_rate,
                              jnp.float32(self.shrinkage_rate))
        lr_dev = self._lr_cache[1]
        # NaN/Inf containment (nan_policy != "none"): keep handles to the
        # pre-iteration score arrays — device arrays are immutable, so a
        # poisoned iteration rolls back by reassignment, no arithmetic
        # undo (which NaN would defeat: x + NaN - NaN != x).
        guard = self._nan_policy != "none"
        # one donation decision per round: rollback references and the
        # backend gate both veto in-place score updates (the
        # score_donation degrade step may re-enable an env opt-out)
        donate = not guard and self._donation_on()
        poisoned = None               # which check tripped, for diagnostics
        if guard:
            score0 = self.train_data.score
            vscores0 = [dd.score for dd in self.valid_data]
        # gain screening (models/screening.py): pick this round's
        # histogram view + feature mask BEFORE any mask draw reads it
        view = self._select_view()
        cur = []
        if fused:
            # standard objective: ONE device dispatch for the whole round
            with obs.span("GBDT::bagging"):
                row_weight = self._bagging_mask(self.iter_)
                sample = self._goss_round()
            if self._train_step is None:
                self._train_step = self._make_train_step()
            feat_masks = self._feature_masks_all()
            with obs.span("GBDT::tree") as tt:
                self.train_data.score, outs, gh_ok, drawn = self._train_step(
                    self.train_data.score, feat_masks, row_weight, lr_dev,
                    view, sample=sample)
                tt.sync(self.train_data.score)
            if drawn is not None:
                self._row_weight, self._goss_factor = drawn
            if guard:
                ok_gh, ok_sc = jax.device_get(
                    (gh_ok, _all_finite(self.train_data.score)))
                if not bool(ok_gh):
                    poisoned = "gradients/hessians"
                elif not bool(ok_sc):
                    poisoned = "scores"
            if poisoned is None:
                for cls, out in enumerate(outs):
                    # linear steps append (coeff, feat) as a 4th element
                    # (docs/LINEAR_TREES.md) — the valid replay epilogue
                    # needs them
                    packed, tree_arrays, delta = out[0], out[1], out[2]
                    lin = out[3] if len(out) > 3 else None
                    vdeltas = []
                    with obs.span("GBDT::valid_score") as tt:
                        for dd in self.valid_data:
                            vd = self._device_tree_delta(dd, tree_arrays,
                                                         lin)
                            dd.score = self._score_add(dd.score, vd,
                                                       cls, donate)
                            vdeltas.append(vd)
                        tt.sync(vdeltas)
                    cur.append((packed, delta, vdeltas))
        else:
            # per-stage path: custom fobj, overridden _gradients hooks, a
            # multi-process learner or LGBT_NO_FUSED_STEP.  Gradients
            # BEFORE the row draw: GOSS draws this round's sample from them
            # (gbdt.cpp Bagging-before-Boosting ordering).
            with obs.span("GBDT::boosting") as tt:
                if grad is None or hess is None:
                    grad, hess = self._gradients()
                else:
                    grad = jnp.asarray(grad, jnp.float32).reshape(
                        self.num_class, -1)
                    hess = jnp.asarray(hess, jnp.float32).reshape(
                        self.num_class, -1)
                    if grad.shape[1] < self._padded_rows:
                        # host fobj gradients cover the REAL rows; pad up
                        # to the shared row bucket (the pad's zero
                        # row_weight keeps it out of every tree)
                        w = ((0, 0), (0, self._padded_rows - grad.shape[1]))
                        grad, hess = jnp.pad(grad, w), jnp.pad(hess, w)
                tt.sync((grad, hess))
            if guard and not bool(_all_finite(grad, hess)):
                # caught BEFORE growing: the poisoned round skips the
                # whole tree pass, not just its bookkeeping
                poisoned = "gradients/hessians"
            with obs.span("GBDT::bagging"):
                row_weight = self._bagging_mask(self.iter_)
                sample = self._goss_round()
                if sample is not None:
                    # the GOSS draw is objective-agnostic (the reference's
                    # Bagging step): host-provided gradients sample too
                    row_weight, self._goss_factor = _GOSS_DRAW(
                        grad, hess, row_weight, sample, params=self._goss)
                    self._row_weight = row_weight
                    grad = grad * self._goss_factor
                    hess = hess * self._goss_factor
            classes = range(self.num_class) if poisoned is None else ()
            for cls in classes:
                feat_mask = self._feature_mask()
                with obs.span("GBDT::tree") as tt:
                    tree_arrays, leaf_id, delta = self._grow_fn(
                        view, self.num_bin, self.is_cat,
                        feat_mask, grad[cls], hess[cls], row_weight, lr_dev)
                    tt.sync(delta)
                lin = None
                if self._linear is not None:
                    # batched per-leaf affine fit (models/linear.py):
                    # intercepts replace the grown leaf values and the
                    # fitted delta replaces the grower's constant delta
                    with obs.span("Bin::linear_fit") as tt:
                        (tree_arrays, l_coeff, l_feat, delta,
                         l_fb) = _shared_linear_fit(self._linear)(
                            tree_arrays, view.bins, self.is_cat,
                            self.train_data.raw, grad[cls], hess[cls],
                            row_weight, lr_dev, view.bundle)
                        lin = (l_coeff, l_feat)
                        tt.sync(delta)
                with obs.span("GBDT::train_score") as tt:
                    self.train_data.score = self._score_add(
                        self.train_data.score, delta, cls, donate)
                    tt.sync(self.train_data.score)
                vdeltas = []
                with obs.span("GBDT::valid_score") as tt:
                    for dd in self.valid_data:
                        vd = self._device_tree_delta(dd, tree_arrays, lin)
                        dd.score = self._score_add(dd.score, vd, cls,
                                                   donate)
                        vdeltas.append(vd)
                    tt.sync(vdeltas)
                packed = _PACK_TREE(tree_arrays)
                if lin is not None:
                    packed = tuple(packed) + tuple(
                        _PACK_LINEAR(l_coeff, l_feat, l_fb))
                cur.append((packed, delta, vdeltas))
            if guard and poisoned is None \
                    and not bool(_all_finite(self.train_data.score)):
                # finite gradients can still yield a non-finite tree
                # (degenerate hessian sums); catch it after the update
                poisoned = "scores"
        if poisoned is not None:
            return self._contain_poisoned_iter(it, poisoned, score0,
                                               vscores0)
        self.iter_ += 1
        obs.inc("iterations")
        if self._comm_traffic_totals[1]:
            # static per-tree collective account × trees dispatched now
            calls, nbytes = self._comm_traffic_totals
            self._cum_comm_calls += calls * self.num_class
            self._cum_comm_bytes += nbytes * self.num_class
            obs.inc("comm_collective_calls", calls * self.num_class)
            obs.inc("comm_collective_bytes", nbytes * self.num_class)
            # distribution series (comm_bytes / comm_bytes_<kind>): one
            # sample per tree dispatched this round (parallel/comm.py)
            from ..parallel.comm import observe_traffic
            observe_traffic(self._comm_traffic, trees=self.num_class)
        shrink = self.shrinkage_rate
        if not self._pipeline:
            self._pending_iter = cur
            self._pending_iter_idx = it
            self._pending_shrinkage = shrink
            self._flush_pending()
            self._note_iter_event(it, t_iter0, tt0)
            if self._no_more_splits:
                self._no_more_splits = False
                return True
            return False
        # Materialize the PREVIOUS iteration while the device runs this one.
        # If it saturated, the reference would never have trained this
        # iteration: undo its score deltas and discard it.
        self._flush_pending()
        if self._no_more_splits:
            self._no_more_splits = False
            for cls, (_, delta, vds) in enumerate(cur):
                self.train_data.score = \
                    self.train_data.score.at[cls].add(-delta)
                for dd, vd in zip(self.valid_data, vds):
                    dd.score = dd.score.at[cls].add(-vd)
            self.iter_ -= 1
            self._note_iter_event(it, t_iter0, tt0, discarded=True)
            return True
        self._pending_iter = cur
        self._pending_iter_idx = it
        self._pending_shrinkage = shrink
        self._note_iter_event(it, t_iter0, tt0)
        return False

    @staticmethod
    def _score_add(score, delta, cls: int, donate: bool):
        """Per-class score update; donated (in-place for XLA) unless a
        NaN-containment rollback reference must stay alive."""
        if donate:
            return _score_add_donated(score, delta, cls)
        return score.at[cls].add(delta)

    def _contain_poisoned_iter(self, it: int, what: str, score0,
                               vscores0) -> bool:
        """NaN/Inf containment (``nan_policy``): a check tripped for
        iteration ``it``.  Roll the score caches back to their
        pre-iteration arrays, record the event, then either die with a
        real diagnostic (``fail_fast``) or drop the round and continue
        (``skip_tree``).  The dropped round's dispatched device work is
        simply discarded — nothing was committed to ``models``.  Always
        returns False (training continues) on the skip path; the next
        call re-attempts the same iteration index."""
        self.train_data.score = score0
        for dd, s0 in zip(self.valid_data, vscores0):
            dd.score = s0
        obs.inc("nan_iterations_dropped")
        rec = self._telemetry
        if rec is not None:
            rec.note(it, nan_poisoned=what, nan_policy=self._nan_policy)
        if self._trace is not None:
            self._trace.iter_end(it, sync=self.train_data.score)
        obj = getattr(getattr(self, "objective", None), "name", "?")
        if self._nan_policy == "fail_fast":
            log.fatal(
                "non-finite %s at boosting iteration %d (objective=%s).  "
                "The model up to iteration %d is intact; inspect the "
                "objective/labels (or a custom fobj), or set "
                "nan_policy=skip_tree to drop poisoned iterations and "
                "continue.", what, it, obj, it)
        self._nan_skips += 1
        log.warning("nan_policy=skip_tree: dropping boosting iteration %d "
                    "(non-finite %s, objective=%s); %d iteration(s) "
                    "dropped so far", it, what, obj, self._nan_skips)
        return False

    def rollback_one_iter(self) -> None:
        """GBDT::RollbackOneIter (gbdt.cpp:384-402)."""
        # Flush BEFORE the iter_ guard: a pending saturated iteration is
        # popped by the flush (decrementing iter_), and rolling back must
        # target the last REAL iteration.
        self._flush_pending()
        if self.iter_ <= 0:
            return
        for cls in reversed(range(self.num_class)):
            tree = self.models.pop()
            if tree.num_leaves > 1:
                neg = _negate_tree(tree)
                self._add_host_tree_to(self.train_data, neg, cls)
                for dd in self.valid_data:
                    self._add_host_tree_to(dd, neg, cls)
        self.iter_ -= 1

    # ------------------------------------------------------------------
    # Crash-safe snapshot/resume state hooks (lightgbm_tpu/snapshot.py).
    # Everything ``init_model`` continued training DISCARDS lives here:
    # score caches, RNG streams, bag state, best-iteration bookkeeping.
    # Subclasses with extra mutable state (DART drop weights) extend both
    # hooks; GOSS's draw is a function of the iteration and needs none.

    def snapshot_state(self) -> Dict:
        """Full resumable training state, host-side.  Flushes the
        pipelined iteration first so the captured view is synchronous.
        Restoring this onto a same-config booster over the same data is
        bit-exact: scores are saved as arrays (not re-derived by tree
        replay, which would re-order float additions) and every RNG
        stream resumes mid-sequence."""
        if not hasattr(self, "train_set"):
            log.fatal("snapshot_state requires a training booster "
                      "(loaded prediction-only models have no "
                      "resumable state)")
        self._flush_pending()
        return {
            "submodel": self.submodel_name,
            "fingerprint": {
                "objective": getattr(self.objective, "name", "?"),
                "num_class": int(self.num_class),
                "num_data": int(self.num_data),
                "num_features": int(self.num_features),
                "num_leaves": int(self.grow_params.num_leaves),
            },
            "models": list(self._models),
            "iter_": int(self.iter_),
            "num_init_iteration": int(self.num_init_iteration),
            "best_iteration": int(self.best_iteration),
            "best_score": dict(self.best_score),
            "best_msg": dict(self.best_msg),
            "shrinkage_rate": float(self.shrinkage_rate),
            "no_more_splits": bool(self._no_more_splits),
            # saved at the REAL row count (row-bucket pad cropped): the
            # pad region is derived state nobody reads, and cropping
            # keeps snapshots portable across row_buckets settings
            "train_score": self.train_data.host_score(np.float32),
            "valid_scores": [dd.host_score(np.float32)
                             for dd in self.valid_data],
            "bag_key": np.asarray(self._bag_key),
            "row_weight": np.asarray(self._row_weight)[:self.num_data],
            "bag_cnt": int(self._bag_cnt),
            "feature_rng": self._feature_rng.get_state(),
            "cum_comm": (int(self._cum_comm_calls),
                         int(self._cum_comm_bytes)),
            "nan_skips": int(self._nan_skips),
            # EMA-FS screener EWMA (models/screening.py): without it a
            # resumed run would re-warm the gain estimates from zero
            "screen_state": (self._screener.state()
                             if self._screener is not None else None),
        }

    def restore_state(self, state: Dict) -> None:
        """Inverse of ``snapshot_state``, applied to a freshly built
        booster (same params, same data).  Valid sets attached before
        the restore get their saved score caches back by position; any
        extra valid set (attached on resume but absent from the
        snapshot) is brought up to date by replaying the restored
        trees."""
        if state.get("submodel") != self.submodel_name:
            log.fatal("snapshot was taken by a %r booster; this run is "
                      "configured as %r", state.get("submodel"),
                      self.submodel_name)
        fp = state.get("fingerprint", {})
        mine = {
            "objective": getattr(self.objective, "name", "?"),
            "num_class": int(self.num_class),
            "num_data": int(self.num_data),
            "num_features": int(self.num_features),
            "num_leaves": int(self.grow_params.num_leaves),
        }
        if fp and fp != mine:
            diff = {k: (fp.get(k), mine[k]) for k in mine
                    if fp.get(k) != mine[k]}
            log.fatal("snapshot/config mismatch, refusing to resume "
                      "(snapshot vs current): %s", diff)
        self._flush_pending()
        self._models = list(state["models"])
        self.iter_ = int(state["iter_"])
        self.num_init_iteration = int(state["num_init_iteration"])
        self.best_iteration = int(state["best_iteration"])
        self.best_score = dict(state["best_score"])
        self.best_msg = dict(state["best_msg"])
        self.shrinkage_rate = float(state["shrinkage_rate"])
        self._no_more_splits = bool(state["no_more_splits"])
        self.train_data.set_score(state["train_score"])
        saved_valid = state.get("valid_scores", [])
        for vi, dd in enumerate(self.valid_data):
            saved = saved_valid[vi] if vi < len(saved_valid) else None
            if saved is not None and np.shape(saved)[0] == self.num_class \
                    and np.shape(saved)[-1] in (dd.num_data,
                                                dd.padded_rows):
                dd.set_score(np.asarray(saved)[:, :dd.num_data])
            else:
                for i, tree in enumerate(self._models):
                    self._add_host_tree_to(dd, tree, i % self.num_class)
        self._bag_key = jnp.asarray(state["bag_key"], jnp.uint32)
        rw = np.zeros(self._padded_rows, np.float32)
        saved_rw = np.asarray(state["row_weight"], np.float32)
        rw[:min(len(saved_rw), self.num_data)] = saved_rw[:self.num_data]
        self._row_weight = jnp.asarray(rw)
        self._bag_cnt = int(state["bag_cnt"])
        self._feature_rng.set_state(state["feature_rng"])
        self._cum_comm_calls, self._cum_comm_bytes = \
            (int(v) for v in state["cum_comm"])
        self._nan_skips = int(state.get("nan_skips", 0))
        if self._screener is not None:
            self._screener.restore(state.get("screen_state"))
            # force the active view/mask to rebuild from restored EWMA
            self._screen_period = -1
            self._screen_mask_dev = None
            self._active_view = None

    # ------------------------------------------------------------------
    def _device_tree_delta(self, dd: _DeviceData, tree_arrays,
                           lin=None) -> jax.Array:
        delta, leaf = predict_binned_tree(
            tree_arrays.split_feature, tree_arrays.split_bin,
            self.is_cat[jnp.maximum(tree_arrays.split_feature, 0)],
            tree_arrays.left_child, tree_arrays.right_child,
            tree_arrays.leaf_value, dd.bins,
            self.grow_params.num_leaves, bundle=self._bundle)
        if lin is not None:
            # per-leaf affine epilogue (models/linear.py); ``lin`` is the
            # device (coeff [L, K], feat [L, K] inner-index) pair
            delta = delta + affine_epilogue(leaf, lin[0], lin[1], dd.raw)
        return delta

    def _add_host_tree_to(self, dd: _DeviceData, tree: Tree, cls: int):
        if tree.num_leaves <= 1:
            dd.score = dd.score.at[cls].add(float(tree.leaf_value[0])
                                            if tree.num_leaves else 0.0)
            return
        # loaded (from_string) trees carry raw thresholds only; rebuild the
        # bin-space split representation against THIS dataset's mappers
        if not tree.ensure_inner(self.train_set.real_to_inner,
                                 self.train_set.mappers):
            log.fatal("Cannot replay a loaded tree on this dataset: it "
                      "splits on a feature the dataset binned as trivial")
        delta, leaf = predict_binned_tree(
            jnp.asarray(tree.split_feature_inner),
            jnp.asarray(tree.threshold_in_bin),
            jnp.asarray(tree.decision_type == 1),
            jnp.asarray(tree.left_child), jnp.asarray(tree.right_child),
            jnp.asarray(tree.leaf_value, jnp.float32), dd.bins,
            int(tree.num_leaves), bundle=self._bundle)
        if tree.has_linear():
            if dd.raw is None:
                log.fatal("Cannot replay a linear tree on this dataset: "
                          "no raw feature values are resident (build the "
                          "booster with linear_tree=true so the device "
                          "raw copy is uploaded)")
            inner = self._linear_inner_feat(tree)
            delta = delta + affine_epilogue(
                leaf, jnp.asarray(tree.leaf_coeff, jnp.float32),
                jnp.asarray(inner), dd.raw)
        dd.score = dd.score.at[cls].add(delta)

    def _linear_inner_feat(self, tree: Tree) -> np.ndarray:
        """A linear tree's leaf_feat (REAL feature indices, like
        split_feature) mapped into the training dataset's inner used-
        feature space — what the device raw matrix is indexed by.
        Refuses when an affine model reads a feature this dataset
        binned as trivial (there is no raw column to read)."""
        r2i = np.asarray(self.train_set.real_to_inner, np.int64)
        lf = np.asarray(tree.leaf_feat, np.int64)
        inner = np.where(lf >= 0, r2i[np.maximum(lf, 0)], -1)
        bad = (lf >= 0) & (inner < 0) \
            & (np.asarray(tree.leaf_coeff) != 0.0)
        if np.any(bad):
            log.fatal("Cannot replay a linear tree on this dataset: a "
                      "leaf's affine model reads feature(s) %s, which "
                      "the dataset binned as trivial",
                      sorted(set(lf[bad].tolist())))
        return inner.astype(np.int32)

    # ------------------------------------------------------------------
    def eval_and_check_early_stopping(self) -> bool:
        """Metric evaluation + early-stop bookkeeping (gbdt.cpp:404-509).
        Returns True to stop training."""
        cfg = self.config
        out_lines = []
        if cfg.is_training_metric and self.train_metrics:
            with obs.span("GBDT::metric"):
                score = self.train_data.host_score()
                for m in self.train_metrics:
                    for name, v in zip(m.names, m.eval(score)):
                        out_lines.append(
                            f"Iteration:{self.iter_}, training {name} : {v:g}")
        stop = False
        for vi, (dd, metrics) in enumerate(zip(self.valid_data,
                                               self.valid_metrics)):
            score = dd.host_score()
            for mi, m in enumerate(metrics):
                values = m.eval(score)
                for name, v in zip(m.names, values):
                    out_lines.append(
                        f"Iteration:{self.iter_}, valid_{vi + 1} {name} : {v:g}")
                key = (vi, m.names[0])
                cur = m.factor_to_bigger_better * values[0]
                if key not in self.best_score or cur > self.best_score[key]:
                    self.best_score[key] = cur
                    if mi == 0:
                        self.best_iteration = self.iter_
                        self.best_msg[vi] = "\n".join(out_lines)
                elif cfg.early_stopping_round > 0 and mi == 0:
                    if self.iter_ - self.best_iteration >= cfg.early_stopping_round:
                        log.info("Early stopping at iteration %d, best iteration %d",
                                 self.iter_, self.best_iteration)
                        stop = True
        if out_lines and (self.iter_ % max(cfg.output_freq, 1) == 0):
            for line in out_lines:
                log.info("%s", line)
        return stop

    def eval_metrics(self) -> Dict[str, Dict[str, float]]:
        """All current metric values, for callbacks/evals_result."""
        with obs.span("GBDT::metric"):
            return self._eval_metrics_impl()

    def _eval_metrics_impl(self) -> Dict[str, Dict[str, float]]:
        out: Dict[str, Dict[str, float]] = {}
        if self.train_metrics:
            score = self.train_data.host_score()
            out["training"] = {}
            for m in self.train_metrics:
                for name, v in zip(m.names, m.eval(score)):
                    out["training"][name] = v
        for vi, (dd, metrics) in enumerate(zip(self.valid_data,
                                               self.valid_metrics)):
            key = f"valid_{vi + 1}"
            score = dd.host_score()
            out[key] = {}
            for m in metrics:
                for name, v in zip(m.names, m.eval(score)):
                    out[key][name] = v
        return out

    # ------------------------------------------------------------------
    def train(self, num_iterations: Optional[int] = None) -> None:
        """Application::Train equivalent loop (application.cpp:224-240)."""
        n = num_iterations or self.config.num_iterations
        try:
            for it in range(n):
                stop = self.train_one_iter()
                if not stop and (self.valid_data
                                 or self.config.is_training_metric):
                    stop = self.eval_and_check_early_stopping() or stop
                if stop:
                    break
        finally:
            self.close_trace()

    # ------------------------------------------------------------------
    # Prediction (host entry: raw feature values)

    _DEVICE_PREDICT_MIN_ROWS = 4096

    def predict_raw(self, X: np.ndarray, num_iteration: int = -1) -> np.ndarray:
        """[K, n] raw scores (GBDT::PredictRaw, gbdt.cpp:791-798).

        Large batches take the device path (the parallel-Predictor
        equivalent, predictor.hpp:81-129): rows are binned with the
        training mappers on the host (f64-exact, so integer bin compares
        ROUTE rows identically to the reference's double threshold
        compares; the forest sum itself is Kahan-compensated f32, ~1e-7
        relative of the f64 host sum).  Small batches and mapper-less
        loaded models use the vectorized host walk."""
        X = np.asarray(X, np.float64)
        n_models = len(self.models)
        if num_iteration > 0:
            n_models = min(n_models, num_iteration * self.num_class)
        if (X.shape[0] >= self._DEVICE_PREDICT_MIN_ROWS and n_models > 0
                and getattr(self, "train_set", None) is not None
                and self.train_set.mappers
                and all(t.ensure_inner(self.train_set.real_to_inner,
                                       self.train_set.mappers)
                        for t in self.models[:n_models])
                and self._linear_device_ok(n_models)):
            return self._predict_raw_device(X, n_models)
        out = np.zeros((self.num_class, X.shape[0]), np.float64)
        for i in range(n_models):
            out[i % self.num_class] += self.models[i].predict(X)
        return out

    def _linear_device_ok(self, n_models: int) -> bool:
        """Device batch predict serves linear trees only when every
        affine feature maps into this dataset's inner (used-feature)
        space — the device raw matrix has no column for a trivially
        binned feature.  Unmappable models take the host walk, which
        reads REAL indices directly."""
        r2i = np.asarray(self.train_set.real_to_inner, np.int64)
        for t in self.models[:n_models]:
            if not t.has_linear():
                continue
            lf = np.asarray(t.leaf_feat, np.int64)
            used = (lf >= 0) & (np.asarray(t.leaf_coeff) != 0.0)
            if np.any(used & (r2i[np.maximum(lf, 0)] < 0)):
                return False
        return True

    def _predict_raw_device(self, X: np.ndarray, n_models: int) -> np.ndarray:
        ts = self.train_set
        n = X.shape[0]
        # host walk sends NaN right (numerical: NaN <= th is False;
        # categorical: int64(NaN) equals no category).  Route identically:
        # numerical NaN -> +inf before binning (last bin > any threshold),
        # categorical NaN -> bin -1, which equals no split's threshold bin
        # (a real category's bin would be routed left at a split on it).
        bins_np = np.zeros((len(ts.used_feature_map), n), dtype=np.int32)
        for inner, f in enumerate(ts.used_feature_map):
            col = X[:, f]
            isnan = np.isnan(col)
            if ts.mappers[inner].bin_type == CATEGORICAL:
                b = ts.mappers[inner].value_to_bin(
                    np.where(isnan, 0.0, col))
                bins_np[inner] = np.where(isnan, -1, b)
            else:
                bins_np[inner] = ts.mappers[inner].value_to_bin(
                    np.where(isnan, np.inf, col))
        # Shape-bucketed dispatch (serve/batcher.py): the forest jit
        # specializes on N, so pad rows up the bucket ladder instead of
        # compiling a fresh program for every batch size the caller
        # happens to send (chunked file predict alone produces two).
        # The padded bin matrix transfers to device ONCE per chunk and
        # is shared by every class's tree stack.
        from ..serve.batcher import BucketLadder
        ladder = BucketLadder(
            list(getattr(self.config, "predict_buckets", []) or []) or None)
        counting = _counting_forest_jit()
        # linear forests also ship the raw f32 covariates per chunk
        # (NaN imputed to 0.0, exactly the training upload's policy)
        linear = any(t.has_linear() for t in self.models[:n_models])
        raw_np = None
        if linear:
            Xr = X[:, list(ts.used_feature_map)].T.astype(np.float32)
            raw_np = np.where(np.isnan(Xr), np.float32(0.0), Xr)
        dev_chunks = []
        for off, m, bucket in ladder.chunks(n):
            bpad = np.zeros((bins_np.shape[0], bucket), np.int32)
            bpad[:, :m] = bins_np[:, off:off + m]
            rdev = None
            nbytes = int(bpad.nbytes)
            if linear:
                rpad = np.zeros((raw_np.shape[0], bucket), np.float32)
                rpad[:, :m] = raw_np[:, off:off + m]
                rdev = jnp.asarray(rpad)
                nbytes += int(rpad.nbytes)
            dev_chunks.append((off, m, bucket, jnp.asarray(bpad), rdev))
            obs.devprof.transfer("h2d", "predict", nbytes)
        # continued training may hold trees larger than grow_params allows
        L = max(max(t.num_leaves for t in self.models[:n_models]), 2)
        out = np.zeros((self.num_class, n), np.float64)
        for cls in range(self.num_class):
            trees = self.models[cls:n_models:self.num_class]
            if not trees:
                continue
            T = len(trees)
            sf = np.zeros((T, max(L - 1, 1)), np.int32)
            sb = np.zeros((T, max(L - 1, 1)), np.int32)
            ic = np.zeros((T, max(L - 1, 1)), bool)
            lc = np.zeros((T, max(L - 1, 1)), np.int32)
            rc = np.zeros((T, max(L - 1, 1)), np.int32)
            lv = np.zeros((T, L), np.float32)
            kf = (max([t.leaf_feat.shape[1] for t in trees
                       if t.has_linear()] or [1]) if linear else 0)
            lcf = np.zeros((T, L, max(kf, 1)), np.float32)
            lft = np.full((T, L, max(kf, 1)), -1, np.int32)
            for t, tree in enumerate(trees):
                k = tree.num_leaves - 1
                if k <= 0:
                    lv[t, 0] = tree.leaf_value[0] if tree.num_leaves else 0.0
                    # no nodes: make the walk stay at node 0 -> leaf 0
                    lc[t, 0] = ~0
                    rc[t, 0] = ~0
                    continue
                sf[t, :k] = tree.split_feature_inner
                sb[t, :k] = tree.threshold_in_bin
                ic[t, :k] = tree.decision_type == 1
                lc[t, :k] = tree.left_child
                rc[t, :k] = tree.right_child
                lv[t, :tree.num_leaves] = tree.leaf_value
                if linear and tree.has_linear():
                    nl, tk = tree.leaf_coeff.shape
                    lcf[t, :nl, :tk] = tree.leaf_coeff
                    lft[t, :nl, :tk] = self._linear_inner_feat(tree)
            args = (jnp.asarray(sf), jnp.asarray(sb), jnp.asarray(ic),
                    jnp.asarray(lc), jnp.asarray(rc), jnp.asarray(lv))
            if linear:
                lin_args = (jnp.asarray(lcf), jnp.asarray(lft))
                counting_lin = _counting_forest_linear_jit()
                for off, m, bucket, bdev, rdev in dev_chunks:
                    val = counting_lin(bucket, *args, *lin_args, bdev,
                                       rdev, max_steps=L)
                    out[cls, off:off + m] = np.asarray(val, np.float64)[:m]
            else:
                for off, m, bucket, bdev, _ in dev_chunks:
                    val = counting(bucket, *args, bdev, max_steps=L)
                    out[cls, off:off + m] = np.asarray(val, np.float64)[:m]
        return out

    def predict(self, X: np.ndarray, num_iteration: int = -1) -> np.ndarray:
        """With sigmoid/softmax transform (gbdt.cpp:799-815)."""
        raw = self.predict_raw(X, num_iteration)
        return np.asarray(self.objective.convert_output(raw)) \
            if hasattr(self, "objective") and self.objective is not None else raw

    def predict_leaf_index(self, X: np.ndarray,
                           num_iteration: int = -1) -> np.ndarray:
        X = np.asarray(X, np.float64)
        n_models = len(self.models)
        if num_iteration > 0:
            n_models = min(n_models, num_iteration * self.num_class)
        return np.stack([self.models[i].predict_leaf_index(X)
                         for i in range(n_models)], axis=1)

    # ------------------------------------------------------------------
    # Model serialization (gbdt.cpp:625-760)
    def save_model_to_string(self, num_iteration: int = -1) -> str:
        buf = io.StringIO()
        buf.write(self.submodel_name + "\n")
        buf.write(f"num_class={self.num_class}\n")
        buf.write(f"label_index={self.label_idx}\n")
        buf.write(f"max_feature_idx={self.max_feature_idx}\n")
        if getattr(self, "objective", None) is not None:
            buf.write(f"objective={self.objective.name}\n")
        buf.write(f"sigmoid={self.sigmoid:g}\n")
        buf.write("feature_names=" + " ".join(self.feature_names) + "\n")
        buf.write("feature_infos=" + " ".join(
            self.train_set.feature_infos() if hasattr(self, "train_set")
            else getattr(self, "feature_infos_", [])) + "\n")
        buf.write("\n")
        n_models = len(self.models)
        if num_iteration > 0:
            n_models = min(n_models, num_iteration * self.num_class)
        for i in range(n_models):
            buf.write(f"Tree={i}\n")
            buf.write(self.models[i].to_string())
            buf.write("\n")
        buf.write("\nfeature importances:\n")
        for name, cnt in self.feature_importance():
            buf.write(f"{name}={cnt}\n")
        # optional drift fingerprint section (obs/drift.py) AFTER the
        # footer: old readers ignore the tail, absent section = no
        # fingerprint — the PR 18 linear-section back-compat pattern
        fp = getattr(self, "data_fingerprint", None)
        if fp is not None:
            if fp.score_hist is None and getattr(self, "train_data",
                                                 None) is not None:
                # raw-margin training-score histogram, filled lazily at
                # first save (serve compares raw scores — no transform
                # disagreement between objectives)
                fp.set_score_hist(self.train_data.host_score(np.float64))
            buf.write("\n" + fp.to_text())
        return buf.getvalue()

    def save_model_to_file(self, path: str, num_iteration: int = -1) -> None:
        # atomic artifact write (utils/diskguard.py): a full disk fails
        # the save with a named, classified error, and the tmp+replace
        # protocol keeps the PREVIOUS good file — never a half-written
        # model mistaken for a good one, never a truncated-in-place
        # last-good destroyed by the failure
        from ..utils.diskguard import write_artifact_atomic
        text = self.save_model_to_string(num_iteration)
        write_artifact_atomic(path, text.encode(), "model_file")

    def feature_importance(self):
        """Split-count importance (gbdt.cpp:765-789)."""
        counts = np.zeros(self.max_feature_idx + 1, np.int64)
        for tree in self.models:
            for f in tree.split_feature[:tree.num_leaves - 1]:
                counts[f] += 1
        pairs = [(self.feature_names[f], int(counts[f]))
                 for f in range(len(counts)) if counts[f] > 0]
        pairs.sort(key=lambda kv: -kv[1])
        return pairs

    def load_model_from_string(self, text: str) -> None:
        """gbdt.cpp:679-760.

        Truncation/corruption containment (docs/FAULT_TOLERANCE.md
        §Data boundary): every header field, tree section, and the
        footer is validated, and any damage raises ``LightGBMError``
        naming the section, the tree index, and the file line — a
        half-written model file is a clean client error through the
        serve ``/reload`` 400 path and the CLI ``input_model``, never
        an index crash mid-predict."""
        import re

        lines = text.splitlines()
        kv: Dict[str, str] = {}
        for ln in lines:
            if ln.startswith("Tree="):
                break
            if "=" in ln:
                k, v = ln.split("=", 1)
                kv[k.strip()] = v.strip()
        if "num_class" not in kv:
            log.fatal("Model file doesn't specify the number of classes")

        def _header_int(key, default):
            raw = kv.get(key, default)
            try:
                return int(raw)
            except ValueError:
                log.fatal("Model file header: %s=%r is not an integer "
                          "— corrupt model file?", key, raw)

        def _header_float(key, default):
            raw = kv.get(key, default)
            try:
                return float(raw)
            except ValueError:
                log.fatal("Model file header: %s=%r is not a number "
                          "— corrupt model file?", key, raw)

        self.num_class = _header_int("num_class", "1")
        if self.num_class < 1:
            log.fatal("Model file header: num_class=%d must be >= 1",
                      self.num_class)
        self.label_idx = _header_int("label_index", 0)
        self.max_feature_idx = _header_int("max_feature_idx", 0)
        self.sigmoid = _header_float("sigmoid", -1.0)
        self.feature_names = kv.get("feature_names", "").split()
        self.feature_infos_ = kv.get("feature_infos", "").split()
        self.objective_name = kv.get("objective", "")
        # parse tree blocks; the footer ("feature importances:",
        # written by every save — reference gbdt.cpp too) doubles as
        # the truncation sentinel: a file chopped anywhere before it
        # is detectably incomplete even when the chop lands exactly on
        # a tree boundary
        footer_pos = text.find("\nfeature importances")
        if footer_pos < 0:
            log.fatal("Model file ends without the 'feature importances' "
                      "footer — truncated mid-write? (re-save the model "
                      "or restore from a good copy)")
        tree_marks = list(re.finditer(r"(?m)^Tree=(.*)$", text))
        tree_marks = [m for m in tree_marks if m.start() < footer_pos]
        self.models = []
        for i, m in enumerate(tree_marks):
            idx_s = m.group(1).strip()
            line_no = text.count("\n", 0, m.start()) + 1
            if idx_s != str(i):
                log.fatal("Model file: expected Tree=%d, found Tree=%s "
                          "(line %d) — trees missing or reordered; "
                          "corrupt model file?", i, idx_s, line_no)
            start = m.end()
            end = tree_marks[i + 1].start() if i + 1 < len(tree_marks) \
                else footer_pos
            try:
                self.models.append(Tree.from_string(text[start:end]))
            except LightGBMError as exc:
                log.fatal("Model file: Tree=%s (line %d): %s",
                          idx_s, line_no, exc)
        if self.models and len(self.models) % self.num_class != 0:
            log.fatal("Model file: %d tree(s) is not a multiple of "
                      "num_class=%d — trees missing; truncated model "
                      "file?", len(self.models), self.num_class)
        self.num_init_iteration = len(self.models) // max(self.num_class, 1)
        self.iter_ = self.num_init_iteration
        if not hasattr(self, "objective") or self.objective is None:
            self.objective = _objective_for_prediction(
                self.objective_name, self.sigmoid, self.num_class)
        # optional drift fingerprint after the footer (obs/drift.py):
        # absent -> None, truncated/garbled -> named LightGBMError with
        # the model-file framing the rest of this loader uses
        from ..obs.drift import DataFingerprint
        try:
            self.data_fingerprint = DataFingerprint.parse(
                text[footer_pos:])
        except LightGBMError as exc:
            log.fatal("%s", exc)

    def num_trees(self) -> int:
        return len(self.models)

    # -- merge (Boosting::MergeFrom) -----------------------------------
    def _merge_identity(self):
        """(num_class, feature width, objective name) for compatibility
        checks.  Objective name is '' when unknown (bare loaded model),
        in which case the objective gate abstains."""
        name = getattr(getattr(self, "objective", None), "name", "") \
            or getattr(self, "objective_name", "")
        if name == "none":
            name = ""
        return self.num_class, self.max_feature_idx, name

    def merge_from(self, other: "GBDT",
                   shrinkage_decay: float = 1.0) -> None:
        """Append ``other``'s trees to this model with their leaf outputs
        scaled by ``shrinkage_decay`` — Boosting::MergeFrom with decay.
        Refuses (named LightGBMError) rather than silently corrupting
        predictions when the two boosters are structurally incompatible."""
        d = float(shrinkage_decay)
        if not (0.0 < d <= 1.0) or d != d:
            raise LightGBMError(
                f"Cannot merge: shrinkage_decay must be in (0, 1], "
                f"got {shrinkage_decay!r}")
        nc_a, fw_a, obj_a = self._merge_identity()
        nc_b, fw_b, obj_b = other._merge_identity()
        if nc_a != nc_b:
            raise LightGBMError(
                f"Cannot merge: num_class mismatch "
                f"(base={nc_a}, other={nc_b})")
        if fw_a != fw_b:
            raise LightGBMError(
                f"Cannot merge: feature width mismatch "
                f"(base max_feature_idx={fw_a}, other={fw_b})")
        if obj_a and obj_b and obj_a != obj_b:
            raise LightGBMError(
                f"Cannot merge: objective mismatch "
                f"(base={obj_a!r}, other={obj_b!r})")
        merged = list(self.models)
        merged.extend(t.scaled_copy(d) for t in other.models)
        self.models = merged
        self.iter_ = len(self.models) // max(self.num_class, 1)


_COUNTING_FOREST_JIT = None


def _counting_forest_jit():
    """Process-wide compile-counting wrapper around the shared
    ``predict_binned_forest`` jit (one instance, one ledger identity)."""
    global _COUNTING_FOREST_JIT
    if _COUNTING_FOREST_JIT is None:
        from ..serve.batcher import CountingJit
        _COUNTING_FOREST_JIT = CountingJit(predict_binned_forest,
                                           "predict_forest")
    return _COUNTING_FOREST_JIT


_COUNTING_FOREST_LINEAR_JIT = None


def _counting_forest_linear_jit():
    """Linear-forest twin of ``_counting_forest_jit``: one process-wide
    compile-counting wrapper around ``predict_binned_forest_linear``.
    A separate entry point so constant-leaf predict keeps its exact
    pre-linear program (docs/LINEAR_TREES.md)."""
    global _COUNTING_FOREST_LINEAR_JIT
    if _COUNTING_FOREST_LINEAR_JIT is None:
        from ..serve.batcher import CountingJit
        _COUNTING_FOREST_LINEAR_JIT = CountingJit(
            predict_binned_forest_linear, "predict_forest")
    return _COUNTING_FOREST_LINEAR_JIT


def _mappers_aligned(a: BinnedDataset, b: BinnedDataset) -> bool:
    """True when two datasets share identical bin mappers (feature map,
    bin counts, and boundaries) — Dataset::CheckAlign equivalent.  With
    EFB the bundle plans must match too: replay/scoring runs on the
    bundled column matrix, so both sides need one column layout."""
    if a.used_feature_map != b.used_feature_map:
        return False
    pa, pb = getattr(a, "bundle_plan", None), getattr(b, "bundle_plan", None)
    if (pa is None) != (pb is None):
        return False
    if pa is not None and pa is not pb and pa.signature() != pb.signature():
        return False
    for ma, mb in zip(a.mappers, b.mappers):
        if ma is mb:
            continue
        if ma.num_bin != mb.num_bin or ma.bin_type != mb.bin_type:
            return False
        if not np.array_equal(ma.bin_upper_bound, mb.bin_upper_bound):
            return False
        if list(ma.bin_2_categorical) != list(mb.bin_2_categorical):
            return False
    return True


def _negate_tree(tree: Tree) -> Tree:
    """Copy with every leaf OUTPUT negated (DART drop / rollback replay).
    Routed through the single leaf-mutation point so affine leaves
    negate their slopes too (docs/LINEAR_TREES.md)."""
    return tree.scaled_copy(-1.0)


class _PredictionObjective(ObjectiveFunction):
    """Stand-in objective for loaded models (transform only)."""

    def __init__(self, name, sigmoid, num_class):
        self.name = name or "none"
        self.sigmoid = sigmoid
        self.num_class = num_class
        self.num_tree_per_iteration = num_class

    def convert_output(self, score):
        if self.num_class > 1:
            e = np.exp(score - score.max(axis=0, keepdims=True))
            return e / e.sum(axis=0, keepdims=True)
        if self.sigmoid > 0:
            return 1.0 / (1.0 + np.exp(-self.sigmoid * score))
        return score


def _objective_for_prediction(name, sigmoid, num_class):
    return _PredictionObjective(name, sigmoid, num_class)
