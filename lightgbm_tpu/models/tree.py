"""Host-side decision tree model: flat arrays + reference-compatible text
serialization.

Mirrors the reference Tree (include/LightGBM/tree.h:17-194, src/io/tree.cpp):
flat left/right child arrays with leaves encoded as ``~leaf_index``,
numerical decision ``value <= threshold`` (decision_type 0) and categorical
``int(value) == int(threshold)`` (decision_type 1), and the exact
``Tree=...`` text block format (tree.cpp:295-338) so models interchange with
the reference CLI.

Prediction on raw values is implemented by binning the input with the
training BinMappers and walking with integer bin comparisons — exactly
equivalent to the reference's double comparison because
``value <= bin_upper_bound[t]  <=>  value_to_bin(value) <= t``.
"""

from __future__ import annotations

import copy
from typing import Dict, List, Optional, Sequence

import numpy as np


def _fmt(x: float) -> str:
    """C++ ostream with setprecision(digits10+2) ~ %.17g, but trimmed."""
    return f"{x:.17g}"


def _fmt_arr(arr) -> str:
    return " ".join(_fmt(float(v)) for v in arr)


def _fmt_int_arr(arr) -> str:
    return " ".join(str(int(v)) for v in arr)


class Tree:
    """A trained decision tree (host representation)."""

    # piece-wise linear leaves (models/linear.py, docs/LINEAR_TREES.md):
    # when set, leaf l predicts
    #   leaf_value[l] + sum_k leaf_coeff[l, k] * x[leaf_feat[l, k]]
    # (leaf_feat holds REAL feature indices, -1 = unused pad slot; NaN
    # inputs read as 0.0).  Class-level None so old pickles/snapshots
    # deserialize as constant-leaf trees.
    leaf_coeff: Optional[np.ndarray] = None   # [num_leaves, K] float64
    leaf_feat: Optional[np.ndarray] = None    # [num_leaves, K] int32

    def __init__(self, num_leaves: int):
        self.num_leaves = num_leaves
        n = max(num_leaves - 1, 0)
        self.split_feature_inner = np.zeros(n, dtype=np.int32)
        self.split_feature = np.zeros(n, dtype=np.int32)  # real feature idx
        self.split_gain = np.zeros(n, dtype=np.float64)
        self.threshold_in_bin = np.zeros(n, dtype=np.int32)
        self.threshold = np.zeros(n, dtype=np.float64)    # real-value threshold
        self.decision_type = np.zeros(n, dtype=np.int8)
        self.left_child = np.zeros(n, dtype=np.int32)
        self.right_child = np.zeros(n, dtype=np.int32)
        self.leaf_parent = np.zeros(num_leaves, dtype=np.int32)
        self.leaf_value = np.zeros(num_leaves, dtype=np.float64)
        self.leaf_count = np.zeros(num_leaves, dtype=np.int32)
        self.internal_value = np.zeros(n, dtype=np.float64)
        self.internal_count = np.zeros(n, dtype=np.int32)
        self.shrinkage = 1.0

    # ------------------------------------------------------------------
    @classmethod
    def from_arrays(cls, tree_arrays, mappers, used_feature_map,
                    learning_rate: float) -> "Tree":
        """Build from device TreeArrays (ops/grow.py).  Leaf values arrive
        already shrunk; ``shrinkage`` records the rate like Tree::Shrinkage.

        Accepts device or host arrays; device pytrees are fetched with ONE
        transfer instead of 13 per-field round-trips)."""
        import jax
        tree_arrays = jax.device_get(tree_arrays)
        num_leaves = int(tree_arrays.num_leaves)
        t = cls(num_leaves)
        n = num_leaves - 1
        sf = np.asarray(tree_arrays.split_feature)[:n]
        sb = np.asarray(tree_arrays.split_bin)[:n]
        t.split_feature_inner = sf.astype(np.int32)
        t.split_feature = np.asarray(
            [used_feature_map[f] for f in sf], dtype=np.int32)
        t.split_gain = np.asarray(tree_arrays.split_gain, dtype=np.float64)[:n]
        t.threshold_in_bin = sb.astype(np.int32)
        t.threshold = np.asarray(
            [mappers[f].bin_to_value(b) for f, b in zip(sf, sb)],
            dtype=np.float64)
        t.decision_type = np.asarray(
            [1 if mappers[f].bin_type == 1 else 0 for f in sf], dtype=np.int8)
        t.left_child = np.asarray(tree_arrays.left_child, dtype=np.int32)[:n]
        t.right_child = np.asarray(tree_arrays.right_child, dtype=np.int32)[:n]
        t.leaf_parent = np.asarray(tree_arrays.leaf_parent,
                                   dtype=np.int32)[:num_leaves]
        t.leaf_value = np.asarray(tree_arrays.leaf_value,
                                  dtype=np.float64)[:num_leaves]
        t.leaf_count = np.asarray(tree_arrays.leaf_count,
                                  dtype=np.int32)[:num_leaves]
        t.internal_value = np.asarray(tree_arrays.internal_value,
                                      dtype=np.float64)[:n]
        t.internal_count = np.asarray(tree_arrays.internal_count,
                                      dtype=np.int32)[:n]
        t.shrinkage = learning_rate
        t.inner_valid = True
        return t

    def ensure_inner(self, real_to_inner, mappers) -> bool:
        """Make split_feature_inner / threshold_in_bin valid against the
        given dataset (BinMapper::ValueToBin of the raw threshold — the
        reference's threshold_in_bin_ reconstruction for loaded models).
        Returns False when a split feature is not usable in this dataset
        (trivial/ignored there), in which case callers must stay on the
        raw-value host path."""
        cached = getattr(self, "_inner_mappers_ref", None)
        if getattr(self, "inner_valid", False) and \
                (cached is None or cached is mappers):
            # from_arrays trees are native to the training mappers; all
            # datasets reaching here are alignment-checked against them
            # (GBDT._mappers_aligned), so a None ref means "native".  The
            # strong reference (not id()) is immune to GC address reuse.
            return True
        n = self.num_leaves - 1
        if n <= 0:
            self.inner_valid = True
            return True
        inner = np.asarray([int(real_to_inner[f])
                            for f in self.split_feature], np.int32)
        if (inner < 0).any():
            return False
        tbin = np.zeros(n, np.int32)
        for i in range(n):
            tbin[i] = int(mappers[inner[i]].value_to_bin(
                np.asarray([self.threshold[i]]))[0])
        self.split_feature_inner = inner
        self.threshold_in_bin = tbin
        self.inner_valid = True
        self._inner_mappers_ref = mappers
        return True

    # ------------------------------------------------------------------
    def has_linear(self) -> bool:
        """True when this tree carries a non-trivial affine part.  A
        linear fit where every leaf fell back (all-zero coefficients) is
        semantically a constant tree — and must SERIALIZE as one, so a
        fully degenerate linear run stays byte-identical to
        ``linear_tree=false`` (docs/LINEAR_TREES.md)."""
        return (self.leaf_coeff is not None and self.leaf_coeff.size > 0
                and bool(np.any(self.leaf_coeff != 0.0)))

    def _affine_part(self, X: np.ndarray, leaf_idx: np.ndarray) -> np.ndarray:
        """Per-row affine contribution for rows resolved to
        ``leaf_idx``.  NaN covariates read as 0.0 — the same imputation
        the device fit/predict paths apply (models/linear.py)."""
        lf = self.leaf_feat[leaf_idx]                       # [n, K]
        vals = X[np.arange(X.shape[0])[:, None], np.maximum(lf, 0)]
        vals = np.where((lf >= 0) & ~np.isnan(vals), vals, 0.0)
        return (self.leaf_coeff[leaf_idx] * vals).sum(axis=1)

    def predict(self, X: np.ndarray) -> np.ndarray:
        """Raw-value prediction, vectorized node walk (tree.h:197-227)."""
        n = X.shape[0]
        if self.num_leaves <= 1:
            return np.full(n, self.leaf_value[0] if self.num_leaves else 0.0)
        node = np.zeros(n, dtype=np.int32)
        active = np.ones(n, dtype=bool)
        out = np.zeros(n, dtype=np.float64)
        linear = self.leaf_coeff is not None and self.leaf_coeff.size > 0
        leaf_idx = np.zeros(n, dtype=np.int64) if linear else None
        for _ in range(self.num_leaves):  # max depth bound
            if not active.any():
                break
            idx = node[active]
            fv = X[active, self.split_feature[idx]]
            th = self.threshold[idx]
            is_cat = self.decision_type[idx] == 1
            go_left = np.where(is_cat, fv.astype(np.int64) == th.astype(np.int64),
                               fv <= th)
            nxt = np.where(go_left, self.left_child[idx], self.right_child[idx])
            node_active = node.copy()
            node_active[active] = nxt
            node = node_active
            arrived = active & (node < 0)
            out[arrived] = self.leaf_value[~node[arrived]]
            if linear:
                leaf_idx[arrived] = ~node[arrived]
            active = active & (node >= 0)
        if linear:
            out = out + self._affine_part(X, leaf_idx)
        return out

    def predict_leaf_index(self, X: np.ndarray) -> np.ndarray:
        n = X.shape[0]
        if self.num_leaves <= 1:
            return np.zeros(n, dtype=np.int32)
        node = np.zeros(n, dtype=np.int32)
        for _ in range(self.num_leaves):
            if (node < 0).all():
                break
            live = node >= 0
            idx = node[live]
            fv = X[live, self.split_feature[idx]]
            th = self.threshold[idx]
            is_cat = self.decision_type[idx] == 1
            go_left = np.where(is_cat, fv.astype(np.int64) == th.astype(np.int64),
                               fv <= th)
            node[live] = np.where(go_left, self.left_child[idx],
                                  self.right_child[idx])
        return (~node).astype(np.int32)

    def max_depth(self) -> int:
        if self.num_leaves <= 1:
            return 0
        depth = np.zeros(self.num_leaves - 1, dtype=np.int32)
        best = 1
        for node in range(self.num_leaves - 1):
            for child in (self.left_child[node], self.right_child[node]):
                if child >= 0:
                    depth[child] = depth[node] + 1
                    best = max(best, depth[child] + 1)
                else:
                    best = max(best, depth[node] + 1)
        return best

    def scale_leaf_outputs(self, factor: float) -> "Tree":
        """Scale EVERY leaf output by ``factor``, in place — the single
        mutation point for leaf values (Tree::Shrinkage).  Scales the
        constant values, the affine coefficients (an affine leaf's
        output is ``const + coeff . x``, so both terms scale together —
        a half-scaled linear leaf would silently corrupt DART
        normalization and merge decay), ``internal_value`` and the
        recorded ``shrinkage`` so the text serialization stays
        self-consistent.  Returns self."""
        f = float(factor)
        if f == 1.0:
            return self
        self.leaf_value = np.asarray(self.leaf_value, np.float64) * f
        if self.leaf_coeff is not None:
            self.leaf_coeff = np.asarray(self.leaf_coeff, np.float64) * f
        self.internal_value = np.asarray(self.internal_value,
                                         np.float64) * f
        self.shrinkage = float(self.shrinkage) * f
        return self

    def scaled_copy(self, factor: float) -> "Tree":
        """Deep copy with every leaf output scaled by ``factor`` —
        Tree::Shrinkage applied at merge time (GBDT.merge_from's
        ``shrinkage_decay``); the original tree is never touched (the
        donor model keeps predicting exactly what it did)."""
        return copy.deepcopy(self).scale_leaf_outputs(factor)

    # ------------------------------------------------------------------
    def to_string(self) -> str:
        """Tree::ToString (tree.cpp:295-324) byte-compatible layout."""
        n = self.num_leaves - 1
        lines = [
            f"num_leaves={self.num_leaves}",
            f"split_feature={_fmt_int_arr(self.split_feature[:n])}",
            f"split_gain={_fmt_arr(self.split_gain[:n])}",
            f"threshold={_fmt_arr(self.threshold[:n])}",
            f"decision_type={_fmt_int_arr(self.decision_type[:n])}",
            f"left_child={_fmt_int_arr(self.left_child[:n])}",
            f"right_child={_fmt_int_arr(self.right_child[:n])}",
            f"leaf_parent={_fmt_int_arr(self.leaf_parent[:self.num_leaves])}",
            f"leaf_value={_fmt_arr(self.leaf_value[:self.num_leaves])}",
            f"leaf_count={_fmt_int_arr(self.leaf_count[:self.num_leaves])}",
            f"internal_value={_fmt_arr(self.internal_value[:n])}",
            f"internal_count={_fmt_int_arr(self.internal_count[:n])}",
            f"shrinkage={_fmt(self.shrinkage)}",
        ]
        if self.has_linear():
            # affine-leaf sections (docs/LINEAR_TREES.md).  Written ONLY
            # when some coefficient is non-zero: absent sections parse
            # as constant leaves, so old readers/files interop and a
            # degenerate (all-fallback) linear run serializes
            # byte-identically to linear_tree=false
            nl, k = self.leaf_coeff.shape
            lines += [
                f"num_linear_features={k}",
                f"leaf_feat={_fmt_int_arr(self.leaf_feat.ravel())}",
                f"leaf_coeff={_fmt_arr(self.leaf_coeff.ravel())}",
            ]
        lines.append("")
        return "\n".join(lines) + "\n"

    @classmethod
    def from_string(cls, text: str) -> "Tree":
        """Tree(str) parser (tree.cpp:368-430).

        Corruption is contained, never propagated: a missing section, a
        short array (the signature of a file truncated mid-row), an
        unparseable number, or structurally impossible child/feature
        indices all raise :class:`LightGBMError` naming the offending
        section — a half-written model file must be a clean, named
        client error (serve ``/reload`` -> 400, CLI ``input_model`` ->
        fatal), not an index crash at predict time."""
        from ..utils.log import LightGBMError
        kv: Dict[str, str] = {}
        for line in text.splitlines():
            if "=" in line:
                k, v = line.split("=", 1)
                k, v = k.strip(), v.strip()
                if k and v:
                    kv[k] = v
        required = ("num_leaves", "split_feature", "split_gain", "threshold",
                    "left_child", "right_child", "leaf_parent", "leaf_value",
                    "internal_value", "internal_count", "leaf_count",
                    "shrinkage", "decision_type")
        missing = [k for k in required if k not in kv]
        if missing and kv.get("num_leaves") != "1":
            raise LightGBMError(
                f"Tree model string format error: missing section(s) "
                f"{missing} — truncated or corrupt model file?")
        try:
            num_leaves = int(kv["num_leaves"])
        except ValueError:
            raise LightGBMError(
                f"Tree model string format error: num_leaves="
                f"{kv['num_leaves']!r} is not an integer")
        if num_leaves < 1:
            raise LightGBMError(
                f"Tree model string format error: num_leaves="
                f"{num_leaves} must be >= 1")
        if num_leaves > (1 << 20):
            raise LightGBMError(
                f"Tree model string format error: num_leaves="
                f"{num_leaves} is absurd (corrupt header digit?) — "
                f"refusing the allocation")
        t = cls(num_leaves)

        def _values(key, count, conv, dtype):
            if count <= 0 or key not in kv:
                return np.zeros(max(count, 0), dtype=dtype)
            toks = kv[key].split()
            if len(toks) < count:
                raise LightGBMError(
                    f"Tree model string format error: section {key} has "
                    f"{len(toks)} value(s), expected {count} — file "
                    f"truncated mid-row?")
            try:
                vals = [conv(x) for x in toks[:count]]
                return np.asarray(vals, dtype=dtype)
            except (ValueError, OverflowError) as exc:
                # OverflowError: int(float("1e999")) or an int past the
                # int32 range — a corrupt digit making a section
                # unrepresentable
                raise LightGBMError(
                    f"Tree model string format error: section {key}: "
                    f"{exc}")

        def ints(key, count):
            return _values(key, count, lambda x: int(float(x)), np.int32)

        def floats(key, count):
            return _values(key, count, float, np.float64)

        n = num_leaves - 1
        t.split_feature = ints("split_feature", n)
        t.split_feature_inner = t.split_feature.copy()
        t.split_gain = floats("split_gain", n)
        t.threshold = floats("threshold", n)
        t.decision_type = ints("decision_type", n).astype(np.int8)
        t.left_child = ints("left_child", n)
        t.right_child = ints("right_child", n)
        t.leaf_parent = ints("leaf_parent", num_leaves)
        t.leaf_value = floats("leaf_value", num_leaves)
        t.leaf_count = ints("leaf_count", num_leaves)
        t.internal_value = floats("internal_value", n)
        t.internal_count = ints("internal_count", n)
        try:
            t.shrinkage = float(kv["shrinkage"])
        except ValueError:
            raise LightGBMError(
                f"Tree model string format error: shrinkage="
                f"{kv['shrinkage']!r} is not a number")
        # structural sanity: child links must stay inside the node/leaf
        # ranges (an internal node i in [0, n), a leaf ~l with l in
        # [0, num_leaves)) and split features must be non-negative —
        # out-of-range values walk predict() straight into garbage
        for key, arr in (("left_child", t.left_child),
                         ("right_child", t.right_child)):
            if arr.size and (
                    (arr >= n).any() or (arr < -num_leaves).any()):
                raise LightGBMError(
                    f"Tree model string format error: section {key} "
                    f"holds an out-of-range node index (num_leaves="
                    f"{num_leaves}) — corrupt model file?")
        if t.split_feature.size and (t.split_feature < 0).any():
            raise LightGBMError(
                "Tree model string format error: negative "
                "split_feature index — corrupt model file?")
        # optional affine-leaf sections (absent => constant leaves;
        # old model files never carry them)
        if "num_linear_features" in kv or "leaf_coeff" in kv \
                or "leaf_feat" in kv:
            try:
                k = int(kv.get("num_linear_features", ""))
            except ValueError:
                raise LightGBMError(
                    "Tree model string format error: num_linear_features="
                    f"{kv.get('num_linear_features')!r} is not an integer "
                    "(linear sections present but header missing/corrupt?)")
            if k < 0 or k > (1 << 16):
                raise LightGBMError(
                    "Tree model string format error: "
                    f"num_linear_features={k} is out of range")
            if k > 0:
                for key in ("leaf_feat", "leaf_coeff"):
                    if key not in kv:
                        raise LightGBMError(
                            "Tree model string format error: "
                            f"num_linear_features={k} but section {key} "
                            "is missing — file truncated mid-tree?")
                feat = _values("leaf_feat", num_leaves * k,
                               lambda x: int(float(x)), np.int32)
                coeff = _values("leaf_coeff", num_leaves * k, float,
                                np.float64)
                if (feat < -1).any():
                    raise LightGBMError(
                        "Tree model string format error: section "
                        "leaf_feat holds an index below -1 — corrupt "
                        "model file?")
                t.leaf_feat = feat.reshape(num_leaves, k)
                t.leaf_coeff = coeff.reshape(num_leaves, k)
        return t

    def to_json(self) -> dict:
        """Tree::ToJSON structure (tree.cpp:326-366)."""
        def node_json(index: int):
            if index >= 0:
                return {
                    "split_index": int(index),
                    "split_feature": int(self.split_feature[index]),
                    "split_gain": float(self.split_gain[index]),
                    "threshold": float(self.threshold[index]),
                    "decision_type": "no_greater" if self.decision_type[index] == 0 else "is",
                    "internal_value": float(self.internal_value[index]),
                    "internal_count": int(self.internal_count[index]),
                    "left_child": node_json(int(self.left_child[index])),
                    "right_child": node_json(int(self.right_child[index])),
                }
            leaf = ~index
            out = {
                "leaf_index": int(leaf),
                "leaf_parent": int(self.leaf_parent[leaf]),
                "leaf_value": float(self.leaf_value[leaf]),
                "leaf_count": int(self.leaf_count[leaf]),
            }
            if self.has_linear():
                keep = self.leaf_feat[leaf] >= 0
                out["leaf_features"] = [
                    int(f) for f in self.leaf_feat[leaf][keep]]
                out["leaf_coeff"] = [
                    float(c) for c in self.leaf_coeff[leaf][keep]]
            return out
        return {"num_leaves": int(self.num_leaves),
                "shrinkage": float(self.shrinkage),
                "tree_structure": node_json(0) if self.num_leaves > 1 else {
                    "leaf_value": float(self.leaf_value[0]) if self.num_leaves else 0.0}}
