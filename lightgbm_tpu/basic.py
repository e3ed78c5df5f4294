"""User-facing Dataset / Booster API.

Mirrors the reference python package's surface (python-package/lightgbm/
basic.py): lazy Dataset construction with pandas/categorical handling
(basic.py:224-267, 531-1150), reference-aligned validation sets
(basic.py:792-819), and a Booster with train/eval/predict/save/load plus
model-string pickling (basic.py:1155-1262).  The ctypes/C-API layer is
replaced by direct calls into the JAX engine (models/gbdt.py).
"""

from __future__ import annotations

import copy
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from .config import Config
from .io.dataset import BinnedDataset, Metadata
from .io.parser import parse_file
from .models import create_boosting
from .utils import log
from .utils.log import LightGBMError


def _is_sparse(data) -> bool:
    """A scipy.sparse matrix, told without importing scipy."""
    return hasattr(data, "tocsc")


def _to_dense(data):
    """Accept numpy / pandas / scipy-sparse / list-of-lists.  (A sparse
    matrix handed to ``Dataset`` never comes here: it is binned from its
    stored entries, io/sparse.py; ``Booster.predict`` still widens the
    rows it is handed.)"""
    if hasattr(data, "toarray"):          # scipy CSR/CSC without importing it
        data = data.toarray()
    if hasattr(data, "values") and hasattr(data, "dtypes"):  # pandas
        data = data.values
    arr = np.asarray(data, dtype=np.float64)
    if arr.ndim == 1:
        arr = arr.reshape(1, -1)
    return arr


def _data_from_pandas(data, feature_name, categorical_feature):
    """Pandas handling (reference _data_from_pandas, basic.py:224-267):
    auto feature names from columns, categorical dtype -> codes."""
    if not (hasattr(data, "dtypes") and hasattr(data, "columns")):
        return data, feature_name, categorical_feature
    df = data.copy()
    if feature_name == "auto":
        feature_name = [str(c) for c in df.columns]
    cat_cols = [c for c in df.columns
                if str(df[c].dtype) == "category"]
    if categorical_feature == "auto":
        categorical_feature = [str(c) for c in cat_cols]
    for c in cat_cols:
        df[c] = df[c].cat.codes.astype(np.float64)
    return df.astype(np.float64).values, feature_name, categorical_feature


class Dataset:
    """Dataset in LightGBM-TPU (reference Dataset, basic.py:531).

    Construction is lazy: binning happens on first use (construct()), so
    parameters/fields set before training are honoured like the reference.
    """

    def __init__(self, data, label=None, max_bin=255, reference=None,
                 weight=None, group=None, silent=False,
                 feature_name="auto", categorical_feature="auto",
                 params=None, free_raw_data=True):
        self.data = data
        self.label = label
        self.max_bin = max_bin
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = None
        self.silent = silent
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = dict(params) if params else {}
        self.free_raw_data = free_raw_data
        self.used_indices: Optional[np.ndarray] = None
        self._binned: Optional[BinnedDataset] = None
        self._predictor = None

    # -- lazy construction ----------------------------------------------
    def construct(self) -> "Dataset":
        if self._binned is not None:
            return self
        from . import obs
        with obs.span("Dataset::construct"):
            return self._construct()

    def _construct(self) -> "Dataset":
        if self.reference is not None:
            ref = self.reference.construct()._binned
        else:
            ref = None

        data = self.data
        streamed = None
        file_roles = None
        file_label_idx = 0
        file_guard = None
        if isinstance(data, str):
            cfg_probe = Config({**self.params, "task": "train"})
            # In-data column roles (dataset_loader.cpp SetHeader, :22-157):
            # label against the full header, everything else against the
            # label-removed names.
            from .io.column_roles import resolve_label_idx, resolve_roles
            full_names = None
            if cfg_probe.has_header:
                from .io.streaming import read_full_header_names
                full_names, _ = read_full_header_names(data)
            file_label_idx = resolve_label_idx(
                str(cfg_probe.label_column or ""), full_names)
            feat_names_for_roles = None
            if full_names is not None:
                feat_names_for_roles = (
                    full_names[:file_label_idx]
                    + full_names[file_label_idx + 1:])
            elif self.feature_name != "auto" and self.feature_name:
                feat_names_for_roles = list(self.feature_name)
            if (cfg_probe.weight_column or cfg_probe.group_column
                    or cfg_probe.ignore_column
                    or cfg_probe.categorical_column):
                file_roles = resolve_roles(
                    str(cfg_probe.weight_column or ""),
                    str(cfg_probe.group_column or ""),
                    str(cfg_probe.ignore_column or ""),
                    str(cfg_probe.categorical_column or ""),
                    feature_names=feat_names_for_roles)
            if cfg_probe.use_two_round_loading:
                # streaming loader: never materializes the float matrix
                # (dataset_loader.cpp:191-206 use_two_round semantics).
                # Categorical features must resolve to indices BEFORE the
                # load; name-based entries need header names.
                cat = self.categorical_feature
                cat_idx_stream: List[int] = []
                if cat not in ("auto", None):
                    names = (None if self.feature_name == "auto"
                             else list(self.feature_name))
                    if names is None and cfg_probe.has_header:
                        from .io.streaming import read_header_names
                        names = read_header_names(data, file_label_idx)
                    for c in cat:
                        if isinstance(c, str):
                            if names is None or c not in names:
                                raise LightGBMError(
                                    f"Unknown categorical feature name "
                                    f"{c!r} (two-round loading resolves "
                                    f"names from the file header)")
                            cat_idx_stream.append(names.index(c))
                        else:
                            cat_idx_stream.append(int(c))
                from .io.guard import IngestGuard
                from .io.streaming import load_file_two_round
                if file_roles is not None:
                    cat_idx_stream = sorted(set(cat_idx_stream)
                                            | file_roles.categorical)
                streamed = load_file_two_round(
                    data, has_header=cfg_probe.has_header,
                    label_idx=file_label_idx,
                    guard=IngestGuard(
                        data,
                        policy=str(cfg_probe.bad_data_policy),
                        max_bad_rows=int(cfg_probe.max_bad_rows),
                        max_bad_row_fraction=float(
                            cfg_probe.max_bad_row_fraction)),
                    max_bin=int(self.params.get("max_bin", self.max_bin)),
                    min_data_in_bin=cfg_probe.min_data_in_bin,
                    min_data_in_leaf=cfg_probe.min_data_in_leaf,
                    bin_construct_sample_cnt=cfg_probe.bin_construct_sample_cnt,
                    categorical_features=cat_idx_stream,
                    ignore_features=(file_roles.ignore
                                     if file_roles is not None else ()),
                    weight_idx=(file_roles.weight_idx
                                if file_roles is not None else -1),
                    group_idx=(file_roles.group_idx
                               if file_roles is not None else -1),
                    data_random_seed=cfg_probe.data_random_seed,
                    reference=ref,
                    enable_bundle=bool(cfg_probe.enable_bundle),
                    max_conflict_rate=float(cfg_probe.max_conflict_rate),
                    is_enable_sparse=bool(cfg_probe.is_enable_sparse))
                data = None
            else:
                from .io.guard import IngestGuard
                file_guard = IngestGuard(
                    data,
                    policy=str(cfg_probe.bad_data_policy),
                    max_bad_rows=int(cfg_probe.max_bad_rows),
                    max_bad_row_fraction=float(
                        cfg_probe.max_bad_row_fraction))
                label, X, header = parse_file(
                    data,
                    has_header=cfg_probe.has_header,
                    label_idx=file_label_idx,
                    guard=file_guard)
                if self.label is None:
                    self.label = label
                if header and self.feature_name == "auto":
                    self.feature_name = header
                data = X
        else:
            data, self.feature_name, self.categorical_feature = \
                _data_from_pandas(data, self.feature_name,
                                  self.categorical_feature)
            from . import obs
            if not _is_sparse(data):
                with obs.span("Bin::apply"):
                    # the float64 widening of the whole matrix is part of
                    # applying the bins (io/dataset.py reads the wide copy)
                    data = _to_dense(data)

        feature_name = (None if self.feature_name == "auto"
                        else list(self.feature_name))
        cat = self.categorical_feature
        cat_idx: List[int] = []
        if streamed is None and cat not in ("auto", None):
            # (the streamed branch resolved its categorical indices from
            # the file header before loading)
            for c in cat:
                if isinstance(c, str):
                    if feature_name is None or c not in feature_name:
                        raise LightGBMError(
                            f"Unknown categorical feature name {c!r}")
                    cat_idx.append(feature_name.index(c))
                else:
                    cat_idx.append(int(c))

        if streamed is not None:
            if feature_name is not None and \
                    len(feature_name) == streamed.num_total_features:
                streamed.feature_names = list(feature_name)
            self._binned = streamed
        elif self.used_indices is not None:
            # Subset of a constructed reference (reference subset(),
            # basic.py:820-837)
            base = self.reference.construct()._binned
            self._binned = base.subset(self.used_indices)
        elif ref is not None:
            self._binned = ref.create_valid(data, self.label)
        else:
            cfg = Config({**self.params, "max_bin": self.max_bin,
                          "task": "train"})
            if file_roles is not None:
                cat_idx = sorted(set(cat_idx) | file_roles.categorical)
            build = (BinnedDataset.from_sparse if _is_sparse(data)
                     else BinnedDataset.from_matrix)
            self._binned = build(
                data, self.label,
                max_bin=int(self.params.get("max_bin", self.max_bin)),
                min_data_in_leaf=cfg.min_data_in_leaf,
                min_data_in_bin=cfg.min_data_in_bin,
                bin_construct_sample_cnt=cfg.bin_construct_sample_cnt,
                categorical_features=cat_idx,
                ignore_features=(file_roles.ignore
                                 if file_roles is not None else ()),
                feature_names=feature_name,
                data_random_seed=cfg.data_random_seed,
                enable_bundle=bool(cfg.enable_bundle),
                max_conflict_rate=float(cfg.max_conflict_rate),
                is_enable_sparse=bool(cfg.is_enable_sparse),
                keep_raw=bool(cfg.linear_tree))
        md = self._binned.metadata
        if self.label is not None and self.used_indices is None:
            md.set_label(np.asarray(self.label))
        if self.weight is not None:
            md.set_weights(np.asarray(self.weight))
        if self.group is not None:
            md.set_query(np.asarray(self.group))
        if self.init_score is not None:
            md.set_init_score(np.asarray(self.init_score))
        if isinstance(self.data, str) and streamed is None:
            # the streaming loader already side-loaded .weight/.query/.init;
            # quarantined rows make positional side files un-alignable —
            # named refusal, not silent misalignment
            if file_guard is not None:
                from .io.guard import check_side_files_alignment
                check_side_files_alignment(self.data,
                                           file_guard.bad_total)
            md.load_side_files(self.data)
            if file_roles is not None and data is not None:
                # in-data weight/group columns override side files
                # (Metadata::Init re-allocates when the idx is set,
                # dataset_loader.cpp:101-131)
                from .io.column_roles import qid_to_query_sizes
                from .utils import log as _log
                for what, idx in (("weight_column", file_roles.weight_idx),
                                  ("group_column", file_roles.group_idx)):
                    if idx >= data.shape[1]:
                        _log.fatal("%s index %d out of range (file has %d "
                                   "feature columns)", what, idx,
                                   data.shape[1])
                if file_roles.weight_idx >= 0 and self.weight is None:
                    md.set_weights(np.asarray(
                        data[:, file_roles.weight_idx], np.float64))
                if file_roles.group_idx >= 0 and self.group is None:
                    md.set_query(qid_to_query_sizes(
                        data[:, file_roles.group_idx]))
        if self._predictor is not None:
            # continued training: init scores = prior model's raw predictions
            # (reference _set_predictor flow, dataset_loader.cpp:10)
            if streamed is not None:
                # chunked predict: never materialize the full float matrix
                from .io.guard import IngestGuard
                from .io.streaming import (_numbered_data_lines,
                                           _parse_chunk, _probe_format)
                path = self.data
                has_h = bool(self.params.get("has_header", False))
                fmt = _probe_format(path, has_h)
                nf = streamed.num_total_features if fmt == "libsvm" else None
                lbl_idx = int(self.params.get("label_column", 0) or 0)
                # shadow guard: the two-round load above already
                # classified (and counted) this file's bad rows — this
                # re-read must make the SAME skip decisions so the init
                # scores align with the binned rows, without
                # double-counting bad_rows_* or rewriting the sink
                shadow = IngestGuard(
                    path,
                    policy=str(self.params.get("bad_data_policy",
                                               "fail_fast")),
                    record=False)
                chunks = []
                buf: List[str] = []
                nums: List[int] = []
                for lineno, line in _numbered_data_lines(path, has_h):
                    buf.append(line)
                    nums.append(lineno)
                    if len(buf) >= 262144:
                        _, Xc = _parse_chunk(buf, fmt, lbl_idx, nf,
                                             guard=shadow,
                                             line_numbers=nums)
                        chunks.append(np.asarray(
                            self._predictor.predict(Xc, raw_score=True)))
                        buf = []
                        nums = []
                if buf:
                    _, Xc = _parse_chunk(buf, fmt, lbl_idx, nf,
                                         guard=shadow, line_numbers=nums)
                    chunks.append(np.asarray(
                        self._predictor.predict(Xc, raw_score=True)))
                raw = np.concatenate(chunks, axis=0)
            else:
                raw = np.asarray(self._predictor.predict(
                    self.data if data is None else data, raw_score=True))
            # class-major flatten for multiclass (score[k*num_data + i])
            md.set_init_score(raw.reshape(-1, order="F"))
        if self.free_raw_data:
            self.data = None
        return self

    # -- setters (reference set_field wrappers) -------------------------
    def set_label(self, label):
        self.label = label
        if self._binned is not None and label is not None:
            self._binned.metadata.set_label(np.asarray(label))
        return self

    def set_weight(self, weight):
        self.weight = weight
        if self._binned is not None and weight is not None:
            self._binned.metadata.set_weights(np.asarray(weight))
        return self

    def set_group(self, group):
        self.group = group
        if self._binned is not None and group is not None:
            self._binned.metadata.set_query(np.asarray(group))
        return self

    def set_init_score(self, init_score):
        self.init_score = init_score
        if self._binned is not None and init_score is not None:
            self._binned.metadata.set_init_score(np.asarray(init_score))
        return self

    def set_reference(self, reference):
        if self._binned is not None:
            raise LightGBMError("Cannot set reference after construction")
        self.reference = reference
        return self

    def set_feature_name(self, feature_name):
        self.feature_name = feature_name
        if self._binned is not None and feature_name not in (None, "auto"):
            self._binned.feature_names = list(feature_name)
        return self

    def set_categorical_feature(self, categorical_feature):
        if self._binned is not None and \
                categorical_feature != self.categorical_feature:
            raise LightGBMError(
                "Cannot set categorical feature after construction")
        self.categorical_feature = categorical_feature
        return self

    def _update_params(self, params):
        self.params.update(params)
        return self

    def _set_predictor(self, predictor):
        if self._binned is not None and predictor is not None \
                and predictor is not self._predictor:
            # continued training on an already-constructed Dataset: the
            # reference re-constructs from raw data to bake the new init
            # scores in (basic.py _set_predictor + free_raw_data
            # semantics); without raw data it must refuse
            if self.data is None or self.free_raw_data:
                raise LightGBMError(
                    "Cannot set predictor after construction (set "
                    "free_raw_data=False to allow continued training on "
                    "a constructed Dataset)")
            self._binned = None
        self._predictor = predictor
        return self

    # -- getters ---------------------------------------------------------
    def get_label(self):
        if self._binned is not None:
            return self._binned.metadata.label
        return self.label

    def get_weight(self):
        if self._binned is not None:
            return self._binned.metadata.weights
        return self.weight

    def get_group(self):
        if self._binned is not None and \
                self._binned.metadata.query_boundaries is not None:
            qb = self._binned.metadata.query_boundaries
            return np.diff(qb)
        return self.group

    def get_init_score(self):
        if self._binned is not None:
            return self._binned.metadata.init_score
        return self.init_score

    def num_data(self) -> int:
        return self.construct()._binned.num_data

    def num_feature(self) -> int:
        return self.construct()._binned.num_total_features

    def subset(self, used_indices, params=None) -> "Dataset":
        """Row subset sharing this dataset's bin mappers."""
        sub = Dataset(None, reference=self,
                      feature_name=self.feature_name,
                      categorical_feature=self.categorical_feature,
                      params=params or self.params)
        sub.used_indices = np.asarray(used_indices)
        return sub

    def create_valid(self, data, label=None, weight=None, group=None,
                     silent=False, params=None) -> "Dataset":
        """Validation Dataset aligned with this one (reference
        create_valid, basic.py:792-819)."""
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, silent=silent, params=params)

    def save_binary(self, filename) -> "Dataset":
        self.construct()._binned.save_binary(filename)
        return self


class Booster:
    """Booster in LightGBM-TPU (reference Booster, basic.py:1155)."""

    # compiled-forest inference artifacts (lightgbm_tpu/serve/):
    # _compiled is the explicit ``compile()`` snapshot, _auto_forest the
    # lazily built large-array fast path.  Class-level defaults so
    # pickled/old instances behave.
    _compiled = None
    _auto_forest = None

    def __init__(self, params=None, train_set=None, model_file=None,
                 silent=False):
        params = dict(params or {})
        self.best_iteration = -1
        self.__train_data_name = "training"
        self.__attr: Dict[str, str] = {}
        self._train_set: Optional[Dataset] = None
        self._valid_sets: List[Dataset] = []
        self._name_valid_sets: List[str] = []

        if train_set is not None:
            if not isinstance(train_set, Dataset):
                raise TypeError("Training data should be Dataset instance, "
                                f"met {type(train_set).__name__}")
            if params.get("linear_tree") and train_set._binned is None:
                # raw-feature retention is decided at bin time, so the
                # Dataset must see the flag BEFORE construct() (engine
                # .train pushes the full params dict the same way)
                train_set._update_params(
                    {"linear_tree": params["linear_tree"]})
            from . import obs
            with obs.span("Booster::init"):
                train_set.construct()
                self.config = Config({**train_set.params, **params})
                self._booster = create_boosting(self.config,
                                                train_set._binned)
            self._train_set = train_set
        elif model_file is not None:
            with open(model_file) as fh:
                model_str = fh.read()
            self.config = Config({**params, "task": "predict"})
            self._booster = create_boosting(self.config, None,
                                            model_str=model_str)
            self.best_iteration = -1
        else:
            raise TypeError("At least one of train_set or model_file "
                            "should be set")

    # -- training --------------------------------------------------------
    def set_train_data_name(self, name):
        self.__train_data_name = name
        return self

    def add_valid(self, data: Dataset, name: str) -> "Booster":
        if not isinstance(data, Dataset):
            raise TypeError("Validation data should be Dataset instance, "
                            f"met {type(data).__name__}")
        data.construct()
        self._booster.add_valid_dataset(data._binned)
        self._valid_sets.append(data)
        self._name_valid_sets.append(name)
        return self

    def reset_parameter(self, params) -> "Booster":
        """reset_parameter (basic.py:1291): rebuild config keeping state."""
        self.config = Config({**self.config.raw_params(), **params})
        self._booster.reset_config(self.config)
        return self

    def update(self, train_set=None, fobj=None) -> bool:
        """One boosting iteration; returns True if no further splits
        (reference update, basic.py:1310-1350)."""
        if train_set is not None and train_set is not self._train_set:
            raise LightGBMError("Replacing train_set is not supported; "
                                "create a new Booster")
        if fobj is None:
            return self._booster.train_one_iter()
        grad, hess = fobj(self.__inner_predict(0), self._train_set)
        return self.__boost(grad, hess)

    def __boost(self, grad, hess) -> bool:
        grad = np.asarray(grad, np.float32)
        hess = np.asarray(hess, np.float32)
        n = self._booster.num_data * self._booster.num_class
        if grad.size != n or hess.size != n:
            raise ValueError(
                f"Lengths of gradient({grad.size}) and hessian({hess.size}) "
                f"don't match training data ({n})")
        return self._booster.train_one_iter(grad, hess)

    def rollback_one_iter(self) -> "Booster":
        self._booster.rollback_one_iter()
        return self

    def current_iteration(self) -> int:
        return self._booster.iter_

    # -- evaluation ------------------------------------------------------
    def __inner_predict(self, data_idx: int) -> np.ndarray:
        """Raw scores of train (0) or valid_i (i+1), flattened class-major
        like the reference (basic.py:1689)."""
        b = self._booster
        dd = b.train_data if data_idx == 0 else b.valid_data[data_idx - 1]
        # host_score crops the row-bucket pad (models/gbdt.py)
        return dd.host_score().reshape(-1)

    def __eval_at(self, data_idx: int, name: str, feval=None):
        from . import obs
        b = self._booster
        out = []
        metrics = (b.train_metrics if data_idx == 0
                   else b.valid_metrics[data_idx - 1])
        dd = b.train_data if data_idx == 0 else b.valid_data[data_idx - 1]
        with obs.span("GBDT::metric"):
            score = dd.host_score()
            for m in metrics:
                for mname, v in zip(m.names, m.eval(score)):
                    out.append((name, mname, v,
                                m.factor_to_bigger_better > 0))
        if feval is not None:
            ds = (self._train_set if data_idx == 0
                  else self._valid_sets[data_idx - 1])
            ret = feval(self.__inner_predict(data_idx), ds)
            if isinstance(ret, list):
                for fname, val, bigger in ret:
                    out.append((name, fname, val, bigger))
            elif ret is not None:
                fname, val, bigger = ret
                out.append((name, fname, val, bigger))
        return out

    def eval(self, data, name, feval=None):
        for i, vs in enumerate(self._valid_sets):
            if vs is data:
                return self.__eval_at(i + 1, name, feval)
        if data is self._train_set:
            return self.eval_train(feval)
        raise LightGBMError("Data should be either train or a valid set")

    def eval_train(self, feval=None):
        return self.__eval_at(0, self.__train_data_name, feval)

    def eval_valid(self, feval=None):
        out = []
        for i, name in enumerate(self._name_valid_sets):
            out.extend(self.__eval_at(i + 1, name, feval))
        return out

    # -- model I/O -------------------------------------------------------
    def save_model(self, filename, num_iteration=-1) -> "Booster":
        self._booster.save_model_to_file(filename, num_iteration)
        return self

    def model_to_string(self, num_iteration=-1) -> str:
        return self._booster.save_model_to_string(num_iteration)

    def dump_model(self, num_iteration=-1) -> dict:
        """JSON-style dict dump (reference dump_model, basic.py:1522)."""
        b = self._booster
        n_models = len(b.models)
        if num_iteration > 0:
            n_models = min(n_models, num_iteration * b.num_class)
        return {
            "name": "tree",
            "num_class": b.num_class,
            "label_index": b.label_idx,
            "max_feature_idx": b.max_feature_idx,
            "feature_names": list(b.feature_names),
            "tree_info": [b.models[i].to_json() for i in range(n_models)],
        }

    def merge(self, other: "Booster",
              shrinkage_decay: Optional[float] = None) -> "Booster":
        """Append ``other``'s trees to this booster (Boosting::MergeFrom)
        with their leaf outputs scaled by ``shrinkage_decay`` — raw
        scores are additive, so the merged model predicts exactly
        ``base + decay * delta``.  Defaults to the ``shrinkage_decay``
        param (1.0 = plain merge).  Refuses incompatible merges
        (num_class / feature width / objective) with a named
        LightGBMError; ``other`` is never modified.  Returns self."""
        if not isinstance(other, Booster):
            raise TypeError(
                f"Booster.merge expects a Booster, got {type(other).__name__}")
        if shrinkage_decay is None:
            shrinkage_decay = float(
                getattr(self.config, "shrinkage_decay", 1.0))
        self._booster.merge_from(other._booster,
                                 shrinkage_decay=float(shrinkage_decay))
        # drop stale compiled-forest snapshots — the model just grew
        self._compiled = None
        self._auto_forest = None
        return self

    # -- prediction ------------------------------------------------------
    _PREDICT_CHUNK_ROWS = 1 << 16

    def compile(self, num_iteration=-1, buckets=None, warmup=False):
        """Freeze the current model into a ``serve.CompiledForest`` and
        make it this booster's predict fast path for ALL array sizes
        (without an explicit compile, only large arrays of trained
        boosters route through the artifact; loaded model files keep the
        f64 host walk).  Returns the forest, which is also the artifact
        ``python -m lightgbm_tpu serve`` and the micro-batching server
        consume — see docs/SERVING.md.

        ``buckets`` overrides the batch bucket ladder (defaulting to the
        ``predict_buckets`` param, then powers of two); ``warmup=True``
        pre-compiles every bucket so no later predict hits XLA."""
        from .serve.forest import CompiledForest
        cf = CompiledForest.from_booster(self, num_iteration=num_iteration,
                                         buckets=buckets
                                         or self._config_buckets())
        if warmup:
            cf.warmup()
        self._compiled = (self._model_key(), int(num_iteration), cf)
        return cf

    def _model_key(self):
        """Staleness key for cached CompiledForests: the model count AND
        the last tree's identity, so rollback_one_iter + retraining to
        the same count still invalidates the artifact.  Holding the Tree
        object keeps the identity stable while the cache lives."""
        models = self._booster.models
        return (len(models), models[-1] if models else None)

    def _compiled_for(self, num_iteration, n_rows):
        """The CompiledForest to serve this predict, or None for the
        legacy paths.  An explicit ``compile()`` snapshot wins while it
        matches the current model; otherwise trained boosters lazily
        freeze one for large arrays (the old per-shape device path's
        threshold), so chunked file predict and varying batch sizes
        share one bucketed compile cache."""
        b = self._booster
        n_models = len(b.models)
        if num_iteration > 0:
            n_models = min(n_models, int(num_iteration) * b.num_class)
        if self._compiled is not None:
            mkey, ni, cf = self._compiled
            if mkey == self._model_key() and ni == int(num_iteration):
                return cf
        if (n_rows >= b._DEVICE_PREDICT_MIN_ROWS and n_models > 0
                and getattr(b, "train_set", None) is not None):
            key = (self._model_key(), int(num_iteration))
            if self._auto_forest is not None \
                    and self._auto_forest[0] == key:
                return self._auto_forest[1]
            from .serve.forest import CompiledForest
            cf = CompiledForest.from_booster(
                self, num_iteration=num_iteration,
                buckets=self._config_buckets())
            self._auto_forest = (key, cf)
            return cf
        return None

    def _config_buckets(self):
        """The ``predict_buckets`` param as a ladder override (None =
        the default power-of-two ladder)."""
        buckets = list(getattr(self.config, "predict_buckets", []) or [])
        return buckets or None

    def predict(self, data, num_iteration=-1, raw_score=False,
                pred_leaf=False, data_has_header=False, is_reshape=True):
        """Batch prediction (reference predict, basic.py:1560).

        File inputs stream through parse -> predict in chunks of
        _PREDICT_CHUNK_ROWS rows, so peak memory is O(chunk + result) —
        the reference Predictor's pipelined chunk loop
        (src/application/predictor.hpp:81-129)."""
        b = self._booster
        if isinstance(data, str):
            parts = list(self.predict_chunks(
                data, num_iteration=num_iteration, raw_score=raw_score,
                pred_leaf=pred_leaf, data_has_header=data_has_header))
            if not parts:
                # empty file: predict an empty matrix so the result keeps
                # the normal shape contract ((0, trees) for pred_leaf,
                # (num_class, 0) otherwise)
                parts.append(self._predict_array(
                    np.zeros((0, b.max_feature_idx + 1)),
                    num_iteration, raw_score, pred_leaf))
            out = np.concatenate(parts, axis=-1 if not pred_leaf else 0)
        else:
            data, _, _ = _data_from_pandas(data, "auto", "auto")
            X = _to_dense(data)
            out = self._predict_array(X, num_iteration, raw_score, pred_leaf)
        if pred_leaf:
            return out
        if out.shape[0] == 1:
            return out[0]
        if is_reshape:
            return out.T                      # [n, num_class]
        return out.reshape(-1)

    def predict_chunks(self, data_path, num_iteration=-1, raw_score=False,
                       pred_leaf=False, data_has_header=False):
        """Stream a data file's predictions chunk by chunk: yields one
        prediction array per parsed chunk of ``_PREDICT_CHUNK_ROWS``
        rows ([num_class, n] — or [n, num_trees] for ``pred_leaf``), so
        callers can write results with O(chunk) peak memory.  The single
        source of the file-predict loop: ``predict`` concatenates these,
        the CLI's ``task=predict`` streams them to ``output_result``."""
        b = self._booster
        from .io.parser import parse_file_chunks
        for _, X in parse_file_chunks(
                data_path, has_header=data_has_header,
                label_idx=b.label_idx,
                num_features=b.max_feature_idx + 1,
                chunk_rows=self._PREDICT_CHUNK_ROWS):
            if X.size == 0:
                continue
            yield self._predict_array(X, num_iteration, raw_score,
                                      pred_leaf)

    def _predict_array(self, X, num_iteration, raw_score, pred_leaf):
        b = self._booster
        if pred_leaf:
            return b.predict_leaf_index(X, num_iteration)
        cf = self._compiled_for(num_iteration, X.shape[0])
        if cf is not None:
            # compiled-forest fast path: host-exact cut-table binning +
            # the stacked SoA walk, bucketed so mixed batch sizes reuse
            # compiles (serve/forest.py)
            raw = cf.raw_scores(X)
            if raw_score:
                return raw
            obj = getattr(b, "objective", None)
            return raw if obj is None else np.asarray(
                obj.convert_output(raw))
        out = (b.predict_raw(X, num_iteration) if raw_score
               else b.predict(X, num_iteration))
        return np.asarray(out)

    # -- telemetry (lightgbm_tpu/obs/) -----------------------------------
    def set_event_recorder(self, recorder) -> "Booster":
        """Attach an ``obs.EventRecorder`` for the per-iteration JSONL
        event stream (engine.train's ``events_file`` does this for you).
        The caller owns the recorder: flush the pipeline (e.g. read
        ``num_trees()``) before ``recorder.close()`` so the final
        iteration's tree shape is captured."""
        self._booster.set_event_recorder(recorder)
        return self

    def telemetry(self) -> Dict[str, Any]:
        """Snapshot of the process-wide counters/gauges (obs registry,
        plus timetag phase totals when enabled) and this booster's
        cumulative collective-traffic account — the static per-tree
        byte/call math from parallel/comm.py accumulated over training."""
        from . import obs
        snap = obs.snapshot()
        b = self._booster
        snap["comm"] = {
            "bytes_cum": int(getattr(b, "_cum_comm_bytes", 0)),
            "calls_cum": int(getattr(b, "_cum_comm_calls", 0)),
            "per_tree": getattr(b, "_comm_traffic", None),
        }
        return snap

    # -- fault tolerance (lightgbm_tpu/snapshot.py) ----------------------
    def save_snapshot(self, directory: str, evals_result=None,
                      keep: int = 0, rounds_done=None) -> Optional[str]:
        """Write a crash-safe, checksummed training snapshot into
        ``directory`` (atomic tmp + ``os.replace``) and return its path.
        ``engine.train`` does this automatically under
        ``snapshot_freq``/``snapshot_dir``; this is the manual hook for
        custom ``update()`` loops.  Under multihost only rank 0 writes
        (the state is replicated) — other ranks return None.  See
        docs/FAULT_TOLERANCE.md.

        ``rounds_done`` defaults to the booster's successful iteration
        count.  An ``engine.train`` resume treats it as the number of
        boosting-loop rounds already consumed — the two agree unless
        rounds were dropped (``nan_policy=skip_tree``, saturation); when
        snapshotting from a callback in such a run, pass the engine's
        ``env.iteration + 1`` explicitly so resume does not re-attempt
        the dropped slots."""
        from .snapshot import save_snapshot
        gb = self._booster
        gb._flush_pending()
        if rounds_done is None:
            rounds_done = gb.iter_ - gb.num_init_iteration
        return save_snapshot(directory, self, int(rounds_done),
                             evals_result=evals_result, keep=keep)

    def restore_snapshot(self, directory_or_state) -> int:
        """Restore this (freshly built, same params/data) booster from a
        snapshot directory's newest valid file, or from an already-read
        state dict.  Returns the number of completed boosting rounds.
        Raises ``LightGBMError`` when a directory holds no valid
        snapshot or the snapshot's config fingerprint mismatches."""
        from .snapshot import load_latest_snapshot, restore_booster_state
        state = directory_or_state
        if isinstance(state, str):
            found = load_latest_snapshot(state)
            if found is None:
                raise LightGBMError(
                    f"no valid snapshot found in {directory_or_state!r}")
            _, state = found
        return restore_booster_state(self, state)

    # -- introspection ---------------------------------------------------
    def feature_name(self) -> List[str]:
        return list(self._booster.feature_names)

    def feature_importance(self, importance_type="split") -> np.ndarray:
        b = self._booster
        counts = np.zeros(b.max_feature_idx + 1, np.float64)
        for tree in b.models:
            nl = tree.num_leaves - 1
            for i in range(nl):
                f = tree.split_feature[i]
                if importance_type == "split":
                    counts[f] += 1
                elif importance_type == "gain":
                    counts[f] += tree.split_gain[i]
        if importance_type == "split":
            return counts.astype(np.int64)
        return counts

    def num_trees(self) -> int:
        return self._booster.num_trees()

    def attr(self, key):
        return self.__attr.get(key)

    def set_attr(self, **kwargs) -> "Booster":
        for k, v in kwargs.items():
            if v is None:
                self.__attr.pop(k, None)
            else:
                self.__attr[k] = str(v)
        return self

    # -- pickling via model string (basic.py:1243-1262) ------------------
    def __getstate__(self):
        state = self.__dict__.copy()
        state.pop("_booster", None)
        state.pop("_train_set", None)
        state.pop("_valid_sets", None)
        # compiled forests hold device buffers and jit caches; rebuild
        # on demand after unpickling instead of serializing them
        state.pop("_compiled", None)
        state.pop("_auto_forest", None)
        state["_model_str"] = self.model_to_string()
        return state

    def __setstate__(self, state):
        model_str = state.pop("_model_str")
        self.__dict__.update(state)
        self._train_set = None
        self._valid_sets = []
        self.config = Config({"task": "predict"})
        self._booster = create_boosting(self.config, None,
                                        model_str=model_str)

    def __copy__(self):
        return self.__deepcopy__(None)

    def __deepcopy__(self, _):
        new = Booster.__new__(Booster)
        new.__setstate__(self.__getstate__())
        return new

    def _to_predictor(self) -> "Booster":
        return self
