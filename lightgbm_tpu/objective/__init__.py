"""Objective functions: gradients/hessians as vmapped XLA ops.

Each objective mirrors the exact math of the reference implementation
(src/objective/*.hpp, factory objective_function.cpp:9-29) but computes the
whole gradient vector in one fused jitted op instead of an OpenMP loop.

Score layout: [num_tree_per_iteration, N] (class-major like the reference's
score[k * num_data + i], multiclass_objective.hpp:32-36) — [1, N] for
single-model objectives.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .. import obs
from ..utils import device, log
from ..io.dataset import Metadata
from ..ops import rank_lambda


def _pad_rows(arr, num_rows: Optional[int]):
    """Pad a row-aligned [N] / [..., N] array with zeros up to num_rows
    (the shared row-bucket shape, utils/compile_cache.py bucket_rows).
    Zero labels/weights on pad rows are harmless: tree growth multiplies
    every padded row's gradients by its zero ``row_weight``."""
    if arr is None or num_rows is None:
        return arr
    n = arr.shape[-1]
    if num_rows <= n:
        return arr
    return jnp.pad(arr, [(0, 0)] * (arr.ndim - 1) + [(0, num_rows - n)])


class ObjectiveFunction:
    """Base: subclasses define the gradient math over score[K,N].

    Two call forms:

    - ``gradients(score)`` — the historical entry point, closing over
      this instance's dataset arrays (label, weights, ...).
    - ``gradients_with(arrays, score)`` — the FUNCTIONAL form: every
      per-dataset array travels as an argument (the pytree built by
      ``gradient_arrays()``), and the method reads only scalar
      parameters off ``self``.  This is what lets ``models/gbdt.py``
      share ONE jitted gradient/train-step program across boosters: two
      same-config runs hash to the same ``program_key()``, reuse the
      same traced program, and feed it their own arrays — zero
      recompiles on the second run instead of a fresh XLA program per
      booster (the labels used to be baked in as compile-time
      constants).

    Subclasses implement ``gradients_with`` and extend
    ``gradient_arrays``/``program_key`` when they carry extra state.
    """

    name = "none"
    num_tree_per_iteration = 1
    # sigmoid parameter recorded in the model file; <=0 means no transform
    sigmoid = -1.0

    def init(self, metadata: Metadata, num_data: int) -> None:
        self.num_data = num_data
        self.label = jnp.asarray(metadata.label, jnp.float32)
        self.weights = (None if metadata.weights is None
                        else jnp.asarray(metadata.weights, jnp.float32))

    def over_devices(self) -> None:
        """Told before ``init`` that the round is ONE program over several
        devices (a parallel learner's mesh): an objective with a form the
        partitioner cannot split (a Pallas kernel) falls back to the one
        it can."""

    # -- functional gradient interface ---------------------------------
    def gradient_arrays(self, num_rows: Optional[int] = None) -> dict:
        """Pytree of the per-dataset arrays ``gradients_with`` consumes,
        row-aligned arrays zero-padded to ``num_rows`` (the shared row
        bucket) when given."""
        if self.uses_legacy_gradients():
            # legacy subclasses carry their state in closures; nothing
            # to thread through the argument pytree
            return {}
        return {"label": _pad_rows(self.label, num_rows),
                "weights": _pad_rows(self.weights, num_rows)}

    def uses_legacy_gradients(self) -> bool:
        """True for subclasses written against the pre-round-7 contract:
        they override ``gradients`` but not ``gradients_with``, so their
        gradient math closes over instance state and cannot join the
        shared-program registry (or the row-bucket padding, which would
        feed them padded scores their captured arrays don't match)."""
        cls = type(self)
        return (cls.gradients is not ObjectiveFunction.gradients
                and cls.gradients_with is ObjectiveFunction.gradients_with)

    def program_key(self) -> tuple:
        """Hashable fingerprint of everything ``gradients_with`` bakes
        into its traced program BESIDES the argument arrays (scalar
        hyper-parameters, data-derived scalars).  Two objectives with
        equal keys may share one jitted program."""
        if self.uses_legacy_gradients():
            # instance-specific closure state: never share across
            # instances (matches the pre-round-7 one-jit-per-booster
            # behavior for custom objective subclasses)
            return (type(self).__name__, id(self))
        return (type(self).__name__,)

    # instance attrs that hold per-dataset (O(num_data)) arrays; dropped
    # by program_holder so the process-wide jit registry retains only
    # scalars, not a dead dataset's device memory
    _ARRAY_ATTRS = ("label", "weights", "label_int", "label_pos_weights",
                    "query_classes", "query_slabs", "discounts",
                    "label_gain_j")

    def program_holder(self) -> "ObjectiveFunction":
        """The object the shared-program registry may retain for process
        lifetime: a shallow copy with every per-dataset array attribute
        removed (``gradients_with`` must read arrays from its argument
        pytree only — a stripped holder turns a violation into a loud
        AttributeError instead of silently pinning HBM).  Legacy
        subclasses (``uses_legacy_gradients``) are returned as-is; their
        id-based program_key already scopes them to this instance."""
        if self.uses_legacy_gradients():
            return self
        import copy
        holder = copy.copy(self)
        for attr in self._ARRAY_ATTRS:
            if attr in holder.__dict__:
                del holder.__dict__[attr]
        return holder

    def gradients_with(self, arrays: dict, score: jax.Array
                       ) -> Tuple[jax.Array, jax.Array]:
        if self.uses_legacy_gradients():
            # pre-round-7 custom subclass: route through its gradients()
            # (closure state and all; arrays argument unused)
            return self.gradients(score)
        raise NotImplementedError

    def gradients(self, score: jax.Array) -> Tuple[jax.Array, jax.Array]:
        return self.gradients_with(self.gradient_arrays(), score)

    @staticmethod
    def _apply_weight(arrays, grad, hess):
        w = arrays.get("weights")
        if w is None:
            return grad, hess
        return grad * w, hess * w

    def convert_output(self, score: np.ndarray) -> np.ndarray:
        """Raw score -> prediction transform (GBDT::Predict, gbdt.cpp:799-815)."""
        return score

    def boost_from_average(self) -> float:
        return 0.0


class RegressionL2Loss(ObjectiveFunction):
    """g = score - label, h = 1 (regression_objective.hpp:25-53)."""
    name = "regression"

    def gradients_with(self, arrays, score):
        g = score[0] - arrays["label"]
        h = jnp.ones_like(g)
        g, h = self._apply_weight(arrays, g, h)
        return g[None], h[None]


def _gaussian_hessian(score, label, grad, eta, weight):
    """Common::ApproximateHessianWithGaussian (common.h:416-425)."""
    diff = score - label
    x = jnp.abs(diff)
    a = 2.0 * jnp.abs(grad) * weight
    c = jnp.maximum((jnp.abs(score) + jnp.abs(label)) * eta, 1.0e-10)
    return weight * jnp.exp(-x * x / (2.0 * c * c)) * a / (c * jnp.sqrt(2 * jnp.pi))


class RegressionL1Loss(ObjectiveFunction):
    """g = ±weight, h = Gaussian approx (regression_objective.hpp:58-113)."""
    name = "regression_l1"

    def __init__(self, config):
        self.eta = float(config.gaussian_eta)

    def program_key(self):
        return (type(self).__name__, self.eta)

    def gradients_with(self, arrays, score):
        s = score[0]
        label, weights = arrays["label"], arrays["weights"]
        w = weights if weights is not None else jnp.ones_like(s)
        diff = s - label
        g = jnp.where(diff >= 0.0, w, -w)
        h = _gaussian_hessian(s, label, g, self.eta, w)
        return g[None], h[None]


class RegressionHuberLoss(ObjectiveFunction):
    """L2 within delta, clipped gradient + Gaussian hessian outside
    (regression_objective.hpp:115-180)."""
    name = "huber"

    def __init__(self, config):
        self.delta = float(config.huber_delta)
        self.eta = float(config.gaussian_eta)

    def program_key(self):
        return (type(self).__name__, self.delta, self.eta)

    def gradients_with(self, arrays, score):
        s = score[0]
        label, weights = arrays["label"], arrays["weights"]
        w = weights if weights is not None else jnp.ones_like(s)
        diff = s - label
        inside = jnp.abs(diff) <= self.delta
        g_in = diff * w
        g_out = jnp.where(diff >= 0.0, self.delta * w, -self.delta * w)
        g = jnp.where(inside, g_in, g_out)
        h_out = _gaussian_hessian(s, label, g_out, self.eta, w)
        h = jnp.where(inside, w, h_out)
        return g[None], h[None]


class RegressionFairLoss(ObjectiveFunction):
    """g = c*x/(|x|+c), h = c^2/(|x|+c)^2 (regression_objective.hpp:182-235)."""
    name = "fair"

    def __init__(self, config):
        self.c = float(config.fair_c)

    def program_key(self):
        return (type(self).__name__, self.c)

    def gradients_with(self, arrays, score):
        x = score[0] - arrays["label"]
        c = self.c
        g = c * x / (jnp.abs(x) + c)
        h = c * c / ((jnp.abs(x) + c) ** 2)
        g, h = self._apply_weight(arrays, g, h)
        return g[None], h[None]


class RegressionPoissonLoss(ObjectiveFunction):
    """g = score - label, h = score + max_delta_step at this pin
    (regression_objective.hpp:237-289)."""
    name = "poisson"

    def __init__(self, config):
        self.max_delta_step = float(config.poisson_max_delta_step)

    def program_key(self):
        return (type(self).__name__, self.max_delta_step)

    def gradients_with(self, arrays, score):
        s = score[0]
        g = s - arrays["label"]
        h = s + self.max_delta_step
        g, h = self._apply_weight(arrays, g, h)
        return g[None], h[None]


class BinaryLogloss(ObjectiveFunction):
    """label -> ±1; response = -l*sigma/(1+exp(l*sigma*s)); class-imbalance
    reweighting via is_unbalance / scale_pos_weight
    (binary_objective.hpp:13-120)."""
    name = "binary"

    def __init__(self, config):
        self.sigmoid = float(config.sigmoid)
        self.is_unbalance = bool(config.is_unbalance)
        self.scale_pos_weight = float(config.scale_pos_weight)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        label = np.asarray(metadata.label)
        cnt_pos = int((label > 0).sum())
        cnt_neg = int(num_data - cnt_pos)
        log.info("Number of positive: %d, number of negative: %d",
                 cnt_pos, cnt_neg)
        if cnt_pos == 0 or cnt_neg == 0:
            log.fatal("Training data only contains one class")
        w_neg, w_pos = 1.0, 1.0
        if self.is_unbalance:
            if cnt_pos > cnt_neg:
                w_neg = cnt_pos / cnt_neg
            else:
                w_pos = cnt_neg / cnt_pos
        w_pos *= self.scale_pos_weight
        self.label_weight_pos = w_pos
        self.label_weight_neg = w_neg

    def program_key(self):
        # label_weight_pos/neg are data-derived SCALARS (class counts):
        # they are baked into the traced program, so they must key it
        return (type(self).__name__, self.sigmoid,
                float(self.label_weight_pos), float(self.label_weight_neg))

    def gradients_with(self, arrays, score):
        s = score[0]
        is_pos = arrays["label"] > 0
        lbl = jnp.where(is_pos, 1.0, -1.0)
        lw = jnp.where(is_pos, self.label_weight_pos, self.label_weight_neg)
        sig = self.sigmoid
        response = -lbl * sig / (1.0 + jnp.exp(lbl * sig * s))
        abs_resp = jnp.abs(response)
        g = response * lw
        h = abs_resp * (sig - abs_resp) * lw
        g, h = self._apply_weight(arrays, g, h)
        return g[None], h[None]

    def convert_output(self, score):
        return 1.0 / (1.0 + np.exp(-self.sigmoid * score))


class MulticlassLogloss(ObjectiveFunction):
    """Softmax over class-major scores; g = p - 1{y=k}, h = 2p(1-p); optional
    per-class unbalance weights (multiclass_objective.hpp:13-120)."""
    name = "multiclass"

    def __init__(self, config):
        self.num_class = int(config.num_class)
        self.num_tree_per_iteration = self.num_class
        self.is_unbalance = bool(config.is_unbalance)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        label_int = np.asarray(metadata.label).astype(np.int32)
        if label_int.min() < 0 or label_int.max() >= self.num_class:
            log.fatal("Label must be in [0, %d)", self.num_class)
        self.label_int = jnp.asarray(label_int)
        pos_w = np.ones(self.num_class, np.float32)
        if self.is_unbalance:
            cnts = np.bincount(label_int, minlength=self.num_class)
            pos_w = ((num_data - cnts) / np.maximum(cnts, 1)).astype(np.float32)
        self.label_pos_weights = jnp.asarray(pos_w)

    def gradient_arrays(self, num_rows=None):
        arrays = super().gradient_arrays(num_rows)
        arrays["label_int"] = _pad_rows(self.label_int, num_rows)
        arrays["label_pos_weights"] = self.label_pos_weights
        return arrays

    def program_key(self):
        return (type(self).__name__, self.num_class)

    def gradients_with(self, arrays, score):
        # score: [K, N]
        p = jax.nn.softmax(score, axis=0)
        onehot = (jnp.arange(self.num_class, dtype=jnp.int32)[:, None]
                  == arrays["label_int"][None, :])
        pw = arrays["label_pos_weights"][:, None]
        g = jnp.where(onehot, (p - 1.0) * pw, p)
        h = jnp.where(onehot, 2.0 * p * (1.0 - p) * pw, 2.0 * p * (1.0 - p))
        weights = arrays["weights"]
        if weights is not None:
            g = g * weights[None, :]
            h = h * weights[None, :]
        return g, h

    def convert_output(self, score):
        e = np.exp(score - score.max(axis=0, keepdims=True))
        return e / e.sum(axis=0, keepdims=True)


def default_label_gain(size: int = 31):
    """2^i - 1 (config.cpp label_gain default)."""
    return [float((1 << i) - 1) for i in range(size)]


class LambdarankNDCG(ObjectiveFunction):
    """Per-query pairwise LambdaRank with NDCG weighting
    (rank_objective.hpp:19-228).

    Two forms of the same arithmetic (all label-differing pairs,
    ``max_position`` only in the inverse max DCG, float32; the
    reference's 1M-entry sigmoid lookup table, rank_objective.hpp:177-190,
    is replaced by the exact sigmoid 2/(1+exp(2*sigma*d)) it
    approximates), chosen by the backend and never by an option:

    - on a TPU the slab frame of ops/rank_lambda.py: a query's scores
      are read as whole rows of 128 documents, a query's pair work is
      the ``rank_lambda`` kernel's, and nothing is gathered, sorted or
      scattered a document at a time;
    - elsewhere, and under a parallel learner's mesh, the ``jax.numpy``
      form (the tests' oracle): queries bucketed by power-of-two size
      class (a query of 100 docs pads to 128, not to the global max),
      the pairwise lambda matrix [P, P] computed per query with masking,
      queries processed in blocks via lax.map, one scatter-add per class
      into the row-order gradient.
    """
    name = "lambdarank"

    def __init__(self, config):
        self.sigmoid = float(config.sigmoid)
        if self.sigmoid <= 0:
            log.fatal("Sigmoid param %f should be greater than zero", self.sigmoid)
        gains = list(config.label_gain) or default_label_gain()
        self.label_gain = np.asarray(gains, np.float64)
        self.optimize_pos_at = int(config.max_position)
        # the pair work's form follows the backend, as the grower's two
        # kernels do (ops/partition.py, ops/leafhist.py): never an option
        self.use_kernel = device.on_tpu()

    def over_devices(self):
        # a Mosaic kernel cannot be partitioned automatically; the
        # ``jax.numpy`` form's gathers of a row-sharded score can
        self.use_kernel = False

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            log.fatal("Lambdarank tasks require query information")
        with obs.span("Rank::bucket"):
            self._bucket(metadata)

    def _bucket(self, metadata):
        """The host's bucketing, by array operations a size class: the
        inverse maximum DCG of every query, then the tables of the form
        this backend runs (the slab frame of ops/rank_lambda.py on a TPU,
        the padded classes of the ``jax.numpy`` form elsewhere).  The
        counters say what was bucketed."""
        qb = np.asarray(metadata.query_boundaries, np.int64)
        self.num_queries = len(qb) - 1
        sizes = np.diff(qb)
        label = np.asarray(metadata.label).astype(np.int64)
        # the jax.numpy form's classes: the power of two at or over a
        # query's size, 16 at least; discounts cover the LARGEST class
        pad_of = np.maximum(
            16, 2 ** np.ceil(np.log2(np.maximum(sizes, 1))).astype(np.int64))
        discounts = 1.0 / np.log2(np.arange(int(pad_of.max())) + 2.0)
        self.discounts = jnp.asarray(discounts, jnp.float32)
        self.label_gain_j = jnp.asarray(self.label_gain, jnp.float32)
        inv_max_dcg = np.zeros(self.num_queries, np.float64)
        padded = {}
        for P in np.unique(pad_of):
            q = np.flatnonzero(pad_of == P)
            doc_idx = qb[q, None] + np.arange(P)
            doc_valid = np.arange(P) < sizes[q, None]
            doc_idx = np.where(doc_valid, doc_idx, 0)
            lab = np.where(doc_valid, label[doc_idx], -1)
            # inverse max DCG per query (rank_objective.hpp:54-64): the
            # labels in falling order, the first max_position of them
            k = min(self.optimize_pos_at, int(P))
            top = -np.sort(-lab, axis=1)[:, :k]
            dcg = (np.where(top >= 0, self.label_gain[np.maximum(top, 0)],
                            0.0) * discounts[:k]).sum(axis=1)
            inv_max_dcg[q] = np.where(dcg > 0, 1.0 / np.where(dcg > 0, dcg,
                                                               1.0), 0.0)
            padded[int(P)] = (q, doc_idx, doc_valid, lab)
        n_labels = len(self.label_gain)
        per_label = np.bincount(
            np.repeat(np.arange(self.num_queries), sizes) * n_labels + label,
            minlength=self.num_queries * n_labels).reshape(-1, n_labels)
        pairs_real = int(((sizes ** 2 - (per_label ** 2).sum(axis=1))
                          // 2).sum())
        self.query_classes, self.query_slabs = [], None
        if self.use_kernel:
            self.query_slabs, slots = rank_lambda.slab_tables(
                qb, label, self.label_gain, inv_max_dcg)
            n_classes = len(self.query_slabs)
        else:
            for P, (q, doc_idx, doc_valid, lab) in sorted(padded.items()):
                self.query_classes.append({
                    "P": P,
                    "doc_idx": jnp.asarray(doc_idx.astype(np.int32)),
                    "doc_valid": jnp.asarray(doc_valid),
                    "label": jnp.asarray(np.maximum(lab, 0)
                                         .astype(np.int32)),
                    "inv_max_dcg": jnp.asarray(inv_max_dcg[q], jnp.float32),
                })
            slots = int(sum(len(v[0]) * P * P for P, v in padded.items()))
            n_classes = len(self.query_classes)
        obs.set_gauge("rank_queries", self.num_queries)
        obs.set_gauge("rank_size_classes", n_classes)
        obs.set_gauge("rank_pairs_real", pairs_real)
        obs.set_gauge("rank_pair_slots", slots)

    def gradient_arrays(self, num_rows=None):
        arrays = super().gradient_arrays(num_rows)
        if self.query_slabs is not None:
            arrays["slabs"] = self.query_slabs
            return arrays
        arrays["discounts"] = self.discounts
        arrays["label_gain_j"] = self.label_gain_j
        # per-size-class query tables WITHOUT the static pad size P —
        # gradients_with recovers it from doc_idx.shape (static under
        # trace), so the whole bundle travels as a plain arg pytree
        arrays["classes"] = tuple(
            {k: v for k, v in cls.items() if k != "P"}
            for cls in self.query_classes)
        return arrays

    def program_key(self):
        return (type(self).__name__, self.sigmoid, self.optimize_pos_at,
                self.use_kernel)

    def gradients_with(self, arrays, score):
        s = jnp.asarray(score)[0]
        if "slabs" in arrays:
            # the TPU's form: one slice a query, the pair work in the
            # rank_lambda kernel (ops/rank_lambda.py)
            g, h = rank_lambda.slab_gradients(
                arrays["slabs"], s, sigma=self.sigmoid,
                interpret=not device.on_tpu())
        else:
            g = jnp.zeros_like(s)
            h = jnp.zeros_like(s)
            for cls in arrays["classes"]:
                g, h = self._class_gradients(arrays, s, cls, g, h)
        weights = arrays["weights"]
        if weights is not None:
            g = g * weights
            h = h * weights
        return g[None], h[None]

    def _class_gradients(self, arrays, s, cls, g, h):
        M = cls["doc_idx"].shape[1]

        def one_query(args):
            doc_idx, valid, labels, inv_max_dcg = args
            sc = jnp.where(valid, s[doc_idx], -jnp.inf)
            order = jnp.argsort(-sc)  # descending; invalid sink to the end
            sc_sorted = sc[order]
            lbl_sorted = labels[order]
            valid_sorted = valid[order]
            gain_sorted = arrays["label_gain_j"][lbl_sorted]
            disc = arrays["discounts"][:M]
            n_valid = valid.sum()
            best = sc_sorted[0]
            worst = sc_sorted[jnp.maximum(n_valid - 1, 0)]
            # pairwise [i=high, j=low] in sorted positions
            delta = sc_sorted[:, None] - sc_sorted[None, :]
            dcg_gap = gain_sorted[:, None] - gain_sorted[None, :]
            paired_disc = jnp.abs(disc[:, None] - disc[None, :])
            delta_ndcg = dcg_gap * paired_disc * inv_max_dcg
            norm = jnp.where(best != worst, 0.01 + jnp.abs(delta), 1.0)
            delta_ndcg = delta_ndcg / norm
            p = 2.0 / (1.0 + jnp.exp(2.0 * delta * self.sigmoid))
            lam = -p * delta_ndcg
            hes = p * (2.0 - p) * 2.0 * delta_ndcg
            pair_ok = ((lbl_sorted[:, None] > lbl_sorted[None, :])
                       & valid_sorted[:, None] & valid_sorted[None, :])
            lam = jnp.where(pair_ok, lam, 0.0)
            hes = jnp.where(pair_ok, hes, 0.0)
            g_sorted = lam.sum(axis=1) - lam.sum(axis=0)
            h_sorted = hes.sum(axis=1) + hes.sum(axis=0)
            # unsort back to query-document order
            g_q = jnp.zeros(M, jnp.float32).at[order].set(g_sorted)
            h_q = jnp.zeros(M, jnp.float32).at[order].set(h_sorted)
            return g_q, h_q

        g_pad, h_pad = jax.lax.map(
            one_query,
            (cls["doc_idx"], cls["doc_valid"], cls["label"],
             cls["inv_max_dcg"]),
            batch_size=max(1, 65536 // max(M, 1)))
        flat_idx = cls["doc_idx"].reshape(-1)
        flat_valid = cls["doc_valid"].reshape(-1)
        g = g.at[flat_idx].add(jnp.where(flat_valid, g_pad.reshape(-1), 0.0))
        h = h.at[flat_idx].add(jnp.where(flat_valid, h_pad.reshape(-1), 0.0))
        return g, h


_OBJECTIVES = {
    "regression": RegressionL2Loss,
    "regression_l1": RegressionL1Loss,
    "huber": RegressionHuberLoss,
    "fair": RegressionFairLoss,
    "poisson": RegressionPoissonLoss,
    "binary": BinaryLogloss,
    "multiclass": MulticlassLogloss,
    "lambdarank": LambdarankNDCG,
}


class NoneObjective(ObjectiveFunction):
    """Placeholder for python-side custom objectives (fobj): gradients come
    from the user callback via Booster.update(fobj=...); this only carries
    num_tree_per_iteration and an identity output transform (the reference
    trains with a NULL objective through LGBM_BoosterUpdateOneIterCustom,
    c_api.h:372-388)."""

    name = "none"

    def __init__(self, config=None):
        self.num_class = getattr(config, "num_class", 1) if config else 1
        self.num_tree_per_iteration = max(self.num_class, 1)

    def init(self, metadata, num_data):
        pass

    def gradient_arrays(self, num_rows=None):
        return {}

    def gradients_with(self, arrays, score):
        raise RuntimeError(
            "objective=none requires a custom fobj passed to train()/update()")

    def convert_output(self, score):
        return score


_OBJECTIVES["none"] = NoneObjective


def create_objective(config) -> ObjectiveFunction:
    """Factory (objective_function.cpp:9-29)."""
    name = config.objective
    if name not in _OBJECTIVES:
        log.fatal("Unknown objective type name: %s", name)
    cls = _OBJECTIVES[name]
    try:
        return cls(config)
    except TypeError:
        return cls()
