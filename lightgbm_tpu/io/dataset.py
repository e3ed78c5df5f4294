"""Binned dataset + metadata: the training-side data representation.

Reference: include/LightGBM/dataset.h + src/io/dataset.cpp, dataset_loader.cpp.
TPU-first design decisions (SURVEY.md §7 step 2):
  * storage is dense, feature-major ``bins[F_used, N]`` uint8/uint16 — no
    sparse/4-bit variants (TPU wants dense contiguous lanes; sparse features
    simply bin densely),
  * histograms are built from the full bin codes, so there is no default-bin
    FixHistogram reconstruction step (dataset.cpp:451-471 becomes a no-op),
  * one feature per group (the reference's Construct also always uses NoGroup
    at this pin, dataset.cpp:36-61).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..utils import log
from ..utils.threads import map_features
from .binning import BinMapper, CATEGORICAL, NUMERICAL
from .bundling import BundlePlan, plan_bundles
from .sparse import SparseColumns

_BINARY_TOKEN = b"__lightgbm_tpu_dataset_v1__"


class Metadata:
    """Labels, weights, query boundaries, init scores (dataset.h:35-247)."""

    def __init__(self, num_data: int = 0):
        self.num_data = num_data
        self.label: Optional[np.ndarray] = None
        self.weights: Optional[np.ndarray] = None
        self.query_boundaries: Optional[np.ndarray] = None
        self.query_weights: Optional[np.ndarray] = None
        self.init_score: Optional[np.ndarray] = None

    def set_label(self, label) -> None:
        self.label = np.asarray(label, dtype=np.float32).ravel()

    def set_weights(self, weights) -> None:
        if weights is None:
            self.weights = None
            return
        self.weights = np.asarray(weights, dtype=np.float32).ravel()
        self._update_query_weights()

    def set_init_score(self, init_score) -> None:
        if init_score is None:
            self.init_score = None
            return
        self.init_score = np.asarray(init_score, dtype=np.float64).ravel()

    def set_query(self, group) -> None:
        """``group`` is per-query sizes (python API) -> cumulative boundaries."""
        if group is None:
            self.query_boundaries = None
            return
        group = np.asarray(group, dtype=np.int64).ravel()
        self.query_boundaries = np.concatenate([[0], np.cumsum(group)]).astype(np.int64)
        self._update_query_weights()

    def set_query_id(self, qid) -> None:
        """Per-row query ids (file .query format variant)."""
        qid = np.asarray(qid).ravel()
        change = np.nonzero(np.diff(qid))[0] + 1
        bounds = np.concatenate([[0], change, [len(qid)]])
        self.query_boundaries = bounds.astype(np.int64)
        self._update_query_weights()

    def _update_query_weights(self) -> None:
        # Sum of row weights per query (metadata.cpp query weight init).
        if self.query_boundaries is None or self.weights is None:
            self.query_weights = None
            return
        num_queries = len(self.query_boundaries) - 1
        qw = np.zeros(num_queries, dtype=np.float32)
        for i in range(num_queries):
            a, b = self.query_boundaries[i], self.query_boundaries[i + 1]
            qw[i] = self.weights[a:b].sum() / max(1, b - a)
        self.query_weights = qw

    def load_side_files(self, data_path: str) -> None:
        """Companion ``.weight`` / ``.query`` / ``.init`` files
        (metadata.cpp file side-loading)."""
        wpath = data_path + ".weight"
        if os.path.exists(wpath):
            self.set_weights(np.loadtxt(wpath, dtype=np.float64).ravel())
            log.info("Loading weights from %s", wpath)
        qpath = data_path + ".query"
        if os.path.exists(qpath):
            sizes = np.loadtxt(qpath, dtype=np.int64).ravel()
            self.query_boundaries = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
            self._update_query_weights()
            log.info("Loading query boundaries from %s", qpath)
        ipath = data_path + ".init"
        if os.path.exists(ipath):
            self.set_init_score(np.loadtxt(ipath, dtype=np.float64).ravel())

    def num_queries(self) -> int:
        return 0 if self.query_boundaries is None else len(self.query_boundaries) - 1


def build_mappers_from_sample(sample, num_data: int, *,
                              max_bin: int, min_data_in_bin: int,
                              min_data_in_leaf: int,
                              categorical_features=frozenset(),
                              ignore_features=frozenset(),
                              predefined_mappers=None,
                              feature_indices=None):
    """Per-REAL-feature BinMapper list (None for ignored features) from a
    row sample — the FindBin stage of dataset_loader.cpp:656-722, shared
    by in-memory, two-round/streaming, and distributed loading so all
    three produce identical mappers from identical samples.  ``sample``
    is the ``[S, F]`` rows or their ``SparseColumns`` (io/sparse.py):
    ``find_bin`` takes a column's non-zero values and the sample's row
    count either way, so a column's stored entries are all it reads.

    The trivial-feature filter count is scaled to the sample
    (dataset_loader.cpp:490,704): 0.95 * min_data_in_leaf / num_data *
    sample_cnt.  ``feature_indices`` restricts the work to a subset of
    features (the feature-sharded distributed FindBin); unlisted features
    get None."""
    total_sample_cnt = sample.shape[0]
    sparse = isinstance(sample, SparseColumns)
    filter_cnt = int(0.95 * min_data_in_leaf / max(1, num_data)
                     * total_sample_cnt)
    todo = range(sample.shape[1]) if feature_indices is None \
        else feature_indices
    out: List[Optional[BinMapper]] = [None] * sample.shape[1]
    for f in todo:
        if f in ignore_features:
            continue
        if predefined_mappers is not None and \
                predefined_mappers[f] is not None:
            out[f] = predefined_mappers[f]
            continue
        col = sample.values(f) if sparse else sample[:, f]
        nonzero = col[col != 0.0]
        out[f] = BinMapper().find_bin(
            nonzero, total_sample_cnt, max_bin, min_data_in_bin,
            filter_cnt,
            CATEGORICAL if f in categorical_features else NUMERICAL)
    return out


def _bins_dtype(mappers, plan) -> type:
    """uint8 unless some COLUMN needs more than 256 bin codes (a bundle's
    total bin budget is capped at max_bin, so bundling never forces a
    wider dtype than the widest single feature would)."""
    per_col = [m.num_bin for m in mappers] or [1]
    if plan is not None:
        per_col = [1 + sum(mappers[f].num_bin - 1 for f in members)
                   if len(members) > 1 else mappers[members[0]].num_bin
                   for members in plan.column_members]
    return np.uint8 if max(per_col or [1]) <= 256 else np.uint16


def _raw_rows(data, used) -> np.ndarray:
    """[len(used), N] f32 raw values of the used features: feature-major
    like ``bins`` so the linear-fit gather reads contiguous lanes; f32
    (the fit solves in f32 anyway).  Dense by design of the linear fit,
    whatever the input was."""
    if not isinstance(data, SparseColumns):
        return np.ascontiguousarray(data[:, used].T, dtype=np.float32)
    raw = np.zeros((len(used), data.num_rows), np.float32)
    for inner, f in enumerate(used):
        raw[inner, data.rows(f)] = data.values(f)
    return raw


class BinnedDataset:
    """Column-binned training matrix.

    Attributes:
      bins: [num_columns, num_data] uint8/uint16 column-major bin codes —
        one column per used feature, or per EFB bundle when
        ``bundle_plan`` is set (io/bundling.py: mutually-exclusive sparse
        features share a column with offset-encoded bin sub-ranges).
      mappers: per *used* feature BinMapper (always original space).
      used_feature_map: used feature -> real (original) feature index.
      real_to_inner: real feature index -> used index or -1 (trivial/ignored).
      num_total_features: F of the raw matrix.
      feature_names: real-feature names.
      bundle_plan: Optional[BundlePlan] — None = plain per-feature layout.
      metadata: Metadata.
    """

    def __init__(self) -> None:
        self.bins: np.ndarray = np.zeros((0, 0), dtype=np.uint8)
        self.mappers: List[BinMapper] = []
        self.used_feature_map: List[int] = []
        self.real_to_inner: np.ndarray = np.zeros(0, dtype=np.int64)
        self.num_total_features = 0
        self.feature_names: List[str] = []
        self.bundle_plan: Optional[BundlePlan] = None
        self.metadata = Metadata()
        self.max_bin = 255
        self.label_idx = 0
        # [num_used_features, N] f32 raw values (NaN preserved) in USED
        # feature order — retained only when keep_raw was requested at
        # bin time (linear_tree needs the raw values for the per-leaf
        # affine fits; docs/LINEAR_TREES.md).  Streamed two-round loads
        # never materialize the full matrix, so they leave this None and
        # linear training refuses with a named error.
        self.raw: Optional[np.ndarray] = None
        # drift fingerprint (obs/drift.py) — built by from_matrix only;
        # streamed/subset/binary-cache paths leave it None and the drift
        # observatory quietly abstains
        self.data_fingerprint = None

    # -- construction ---------------------------------------------------
    @classmethod
    def from_matrix(cls, data: np.ndarray, label=None, **kwargs
                    ) -> "BinnedDataset":
        """Bin a raw [N, F] float matrix (dataset_loader.cpp:656-820 flow:
        sample rows -> per-feature FindBin -> extract features).  Keyword
        arguments: ``_build``'s."""
        from .. import obs
        with obs.span("Bin::apply"):
            # the float64 widening is part of applying the bins: every
            # column read below reads the widened copy (a no-op when
            # basic.py's _to_dense already widened it, under this name)
            data = np.ascontiguousarray(np.asarray(data, dtype=np.float64))
        if data.ndim != 2:
            raise ValueError("data must be 2-D [num_data, num_features]")
        return cls._build(data, label, **kwargs)

    @classmethod
    def from_sparse(cls, data, label=None, **kwargs) -> "BinnedDataset":
        """Bin a ``scipy.sparse`` matrix (anything with ``tocsc``; a CSR is
        converted once) from its stored entries: the same mappers, bundle
        plan and ``bins`` as ``from_matrix`` gives the dense matrix of
        the same values, in O(stored entries) of work and memory beside
        the ``[C, N]`` codes themselves; no dense ``[N, F]`` or ``[S, F]``
        array is ever made (io/sparse.py, docs/SPARSE.md)."""
        from .. import obs
        with obs.span("Bin::apply"):
            data = SparseColumns.from_scipy(data)
        obs.set_gauge("sparse_stored_entries", data.nnz)
        obs.set_gauge("sparse_stored_per_row",
                      data.nnz / max(1, data.num_rows))
        return cls._build(data, label, **kwargs)

    @classmethod
    def _build(cls, data, label=None, *,
               max_bin: int = 255, min_data_in_bin: int = 5,
               min_data_in_leaf: int = 100,
               bin_construct_sample_cnt: int = 200000,
               categorical_features: Sequence[int] = (),
               ignore_features: Sequence[int] = (),
               feature_names: Optional[Sequence[str]] = None,
               data_random_seed: int = 1,
               label_idx: int = 0,
               predefined_mappers: Optional[List[Optional[BinMapper]]] = None,
               enable_bundle: bool = False,
               max_conflict_rate: float = 0.0,
               is_enable_sparse: bool = True,
               keep_raw: bool = False,
               ) -> "BinnedDataset":
        """The one construction both inputs share.  ``data`` is the
        float64 ``[N, F]`` matrix or its ``SparseColumns``; the row draw,
        FindBin, the bundle planner and the encoding are the same
        functions on either, each reading a column's stored entries
        where the matrix is sparse."""
        from .. import obs
        sparse = isinstance(data, SparseColumns)
        num_data, num_features = data.shape
        self = cls()
        self.num_total_features = num_features
        self.max_bin = max_bin
        self.label_idx = label_idx
        cat = set(int(c) for c in categorical_features)
        ignored = set(int(c) for c in ignore_features)
        if feature_names is None:
            self.feature_names = [f"Column_{i}" for i in range(num_features)]
        else:
            self.feature_names = list(feature_names)

        # Row sampling for bin construction (config bin_construct_sample_cnt,
        # dataset_loader.cpp sample_cnt default 200k).
        with obs.span("Bin::sample"):
            rng = np.random.RandomState(data_random_seed)
            if num_data > bin_construct_sample_cnt:
                sample_idx = np.sort(rng.choice(
                    num_data, bin_construct_sample_cnt, replace=False))
                sample = data.take_rows(sample_idx) if sparse \
                    else data[sample_idx]
            else:
                sample = data

        with obs.span("Bin::find_bin"):
            per_real = build_mappers_from_sample(
                sample, num_data, max_bin=max_bin,
                min_data_in_bin=min_data_in_bin,
                min_data_in_leaf=min_data_in_leaf,
                categorical_features=cat, ignore_features=ignored,
                predefined_mappers=predefined_mappers)
        self.real_to_inner = np.full(num_features, -1, dtype=np.int64)
        mappers: List[BinMapper] = []
        used: List[int] = []
        for f, mapper in enumerate(per_real):
            if mapper is None or mapper.is_trivial:
                continue
            self.real_to_inner[f] = len(used)
            used.append(f)
            mappers.append(mapper)
        self.used_feature_map = used
        self.mappers = mappers
        if not used:
            log.warning("All features are trivial; dataset has no usable feature")

        # EFB (io/bundling.py): pack mutually-exclusive sparse features
        # into shared columns before any device array is built.  The plan
        # is drawn over the SAME sample FindBin saw, so in-memory and
        # two-round loading agree on bundles for identical samples.
        self.bundle_plan = plan_bundles(
            sample, mappers, used,
            max_conflict_rate=max_conflict_rate, max_total_bin=max_bin,
            enable_bundle=enable_bundle, is_enable_sparse=is_enable_sparse)

        dtype = _bins_dtype(mappers, self.bundle_plan)
        with obs.span("Bin::apply"):
            if sparse or self.bundle_plan is not None:
                self.bins = self._encode(data, dtype, exact_to=int(
                    float(max_conflict_rate) * num_data))
            else:
                self.bins = np.zeros((len(used), num_data), dtype=dtype)

                def fill(inner):
                    self.bins[inner] = mappers[inner].value_to_bin(
                        data[:, used[inner]]).astype(dtype)
                map_features(fill, range(len(used)), num_data)

        if keep_raw and used:
            self.raw = _raw_rows(data, used)

        self.metadata = Metadata(num_data)
        if label is not None:
            self.metadata.set_label(label)
        else:
            self.metadata.set_label(np.zeros(num_data, dtype=np.float32))

        # drift fingerprint (obs/drift.py, docs/OBSERVABILITY.md §Drift):
        # bin occupancy straight from the FindBin sample the mappers just
        # retained, missing rates exact over the full matrix.  Cheap host
        # bookkeeping at bin time; serialized with the model artifact.
        # (Not from a sparse matrix: it rebins every dense column; the
        # observatory abstains, as it does for a streamed load.)
        if used and not sparse:
            from ..obs.drift import DataFingerprint
            with obs.span("Bin::fingerprint"):
                self.data_fingerprint = DataFingerprint.from_training(
                    mappers, used, self.feature_names, data,
                    np.asarray(label, np.float64) if label is not None
                    else None)
        return self

    def _encode(self, data, dtype, exact_to: Optional[int] = None
                ) -> np.ndarray:
        """``bins`` of ``data`` (the float64 matrix or its
        ``SparseColumns``) under this dataset's mappers and bundle plan.
        From a sparse matrix each used feature's stored values are binned
        and every other row is left at the bin of 0.0
        (``BinMapper.default_bin``).  ``exact_to``: the training set's
        own encoding, which holds the plan to that many conflicts a
        column on ALL rows and may move members (``BundlePlan.
        encode_exact``: ``bundle_plan`` is then the plan as encoded)."""
        mappers, used = self.mappers, self.used_feature_map
        plan = self.bundle_plan
        if plan is None:
            # every used feature a column of its own
            plan = BundlePlan([[i] for i in range(len(used))],
                              [[0]] * len(used), len(used))
        if isinstance(data, SparseColumns):
            def stored_bins(inner):
                f = used[inner]
                return data.rows(f), np.asarray(
                    mappers[inner].value_to_bin(data.values(f)))
        else:
            stored_bins = plan.stored_of(
                lambda inner: mappers[inner].value_to_bin(
                    data[:, used[inner]]), data.shape[0])
        zero_bins = [m.default_bin for m in mappers]
        if exact_to is None or self.bundle_plan is None:
            return plan.encode_columns_sparse(stored_bins, zero_bins,
                                              data.shape[0], dtype)
        bins, self.bundle_plan = plan.encode_exact(
            stored_bins, zero_bins, data.shape[0], dtype,
            [m.num_bin for m in mappers], self.max_bin, exact_to)
        self.bundle_plan.publish()
        return bins

    def create_valid(self, data: np.ndarray, label=None) -> "BinnedDataset":
        """Bin a validation matrix with *this* dataset's mappers
        (CreateValid/CopyFeatureMapperFrom, dataset.cpp:124-208); a
        ``scipy.sparse`` one from its stored entries."""
        sparse = hasattr(data, "tocsc")
        data = SparseColumns.from_scipy(data) if sparse \
            else np.asarray(data, dtype=np.float64)
        valid = BinnedDataset()
        valid.num_total_features = self.num_total_features
        valid.max_bin = self.max_bin
        valid.feature_names = list(self.feature_names)
        valid.used_feature_map = list(self.used_feature_map)
        valid.real_to_inner = self.real_to_inner.copy()
        valid.mappers = self.mappers
        valid.bundle_plan = self.bundle_plan
        num_data = data.shape[0]
        if sparse or self.bundle_plan is not None:
            # validation rows ride the TRAINING bundles: replay/scoring
            # happens on the bundled device matrix, so both sides must
            # share one column layout (Dataset::CheckAlign)
            valid.bins = valid._encode(data, self.bins.dtype)
        else:
            valid.bins = np.zeros((len(self.used_feature_map), num_data),
                                  dtype=self.bins.dtype)
            for inner in range(len(self.used_feature_map)):
                valid.bins[inner] = self.mappers[inner].value_to_bin(
                    data[:, self.used_feature_map[inner]]).astype(
                        self.bins.dtype)
        if self.raw is not None and self.used_feature_map:
            # valid raw rides along whenever the training set kept raw:
            # linear-tree valid scoring replays affine leaves on it
            valid.raw = _raw_rows(data, self.used_feature_map)
        valid.metadata = Metadata(num_data)
        if label is not None:
            valid.metadata.set_label(label)
        else:
            valid.metadata.set_label(np.zeros(num_data, dtype=np.float32))
        return valid

    def subset(self, indices: np.ndarray) -> "BinnedDataset":
        """Row subset sharing mappers (CopySubset, dataset.cpp:210-230)."""
        indices = np.asarray(indices, dtype=np.int64)
        sub = BinnedDataset()
        sub.num_total_features = self.num_total_features
        sub.max_bin = self.max_bin
        sub.feature_names = list(self.feature_names)
        sub.used_feature_map = list(self.used_feature_map)
        sub.real_to_inner = self.real_to_inner.copy()
        sub.mappers = self.mappers
        sub.bundle_plan = self.bundle_plan
        sub.bins = np.ascontiguousarray(self.bins[:, indices])
        if self.raw is not None:
            sub.raw = np.ascontiguousarray(self.raw[:, indices])
        sub.metadata = Metadata(len(indices))
        md, smd = self.metadata, sub.metadata
        if md.label is not None:
            smd.set_label(md.label[indices])
        if md.weights is not None:
            smd.set_weights(md.weights[indices])
        if md.init_score is not None and md.num_data:
            # init_score may be class-major [num_class * num_data].
            per_class = md.init_score.reshape(-1, md.num_data)
            smd.set_init_score(per_class[:, indices].ravel())
        if md.query_boundaries is not None:
            # Reconstruct per-query boundaries for the subset; rows of one
            # query must stay contiguous (metadata.cpp CheckOrPartition
            # Log::Fatal on misalignment).
            qid = np.searchsorted(md.query_boundaries, indices, side="right") - 1
            if np.any(np.diff(qid) < 0):
                log.fatal("Data partition in subset is not aligned with query boundaries")
            change = np.nonzero(np.diff(qid))[0] + 1
            bounds = np.concatenate([[0], change, [len(indices)]])
            smd.query_boundaries = bounds.astype(np.int64)
            smd._update_query_weights()
        return sub

    # -- accessors -------------------------------------------------------
    @property
    def num_data(self) -> int:
        return self.bins.shape[1]

    @property
    def num_features(self) -> int:
        """Number of *used* (non-trivial) ORIGINAL features — the split
        finder's feature space.  Equal to ``num_columns`` unless EFB
        bundled features into shared columns."""
        return len(self.used_feature_map)

    @property
    def num_columns(self) -> int:
        """Physical bin-matrix columns (== num_features when unbundled)."""
        return self.bins.shape[0]

    def num_bin_per_feature(self) -> np.ndarray:
        return np.asarray([m.num_bin for m in self.mappers], dtype=np.int32)

    def is_categorical_per_feature(self) -> np.ndarray:
        return np.asarray([m.bin_type == CATEGORICAL for m in self.mappers],
                          dtype=bool)

    def feature_infos(self) -> List[str]:
        """Per real feature info strings for the model file."""
        infos = []
        for f in range(self.num_total_features):
            inner = self.real_to_inner[f]
            infos.append("none" if inner < 0 else self.mappers[inner].feature_info())
        return infos

    # -- binary cache ----------------------------------------------------
    def save_binary(self, path: str) -> None:
        """Binary dataset cache (dataset.cpp:306-389 equivalent).

        Format: token header + npz archive of raw arrays, with non-array
        metadata as a JSON blob.  Deliberately pickle-free so loading an
        untrusted cache cannot execute code."""
        meta_json = json.dumps({
            "mappers": [m.to_state() for m in self.mappers],
            "used_feature_map": self.used_feature_map,
            "num_total_features": self.num_total_features,
            "feature_names": self.feature_names,
            "max_bin": self.max_bin,
            "bundle_plan": (self.bundle_plan.to_state()
                            if self.bundle_plan is not None else None),
        })
        arrays: Dict[str, Any] = {
            "bins": self.bins,
            "real_to_inner": self.real_to_inner,
            "meta_json": np.frombuffer(meta_json.encode(), dtype=np.uint8),
        }
        if self.raw is not None:
            # keep the cache linear_tree-capable; old caches load with
            # raw=None and linear training refuses with a named error
            arrays["raw"] = self.raw
        for key in ("label", "weights", "query_boundaries", "init_score"):
            value = getattr(self.metadata, key)
            if value is not None:
                arrays[key] = value
        # atomic artifact write (utils/diskguard.py): the archive
        # streams into <path>.tmp and os.replace-s on success, so a
        # disk filling mid-save keeps the previous good cache file —
        # without staging the (possibly multi-GB) archive in host RAM
        from ..utils.diskguard import artifact_write
        with artifact_write(path, "binary_dataset", mode="wb",
                            atomic=True) as fh:
            fh.write(_BINARY_TOKEN)
            np.savez_compressed(fh, **arrays)
        log.info("Saved binary dataset to %s", path)

    @classmethod
    def is_binary_file(cls, path: str) -> bool:
        try:
            with open(path, "rb") as fh:
                return fh.read(len(_BINARY_TOKEN)) == _BINARY_TOKEN
        except OSError:
            return False

    @classmethod
    def load_binary(cls, path: str) -> "BinnedDataset":
        with open(path, "rb") as fh:
            token = fh.read(len(_BINARY_TOKEN))
            if token != _BINARY_TOKEN:
                raise ValueError(f"{path} is not a lightgbm_tpu binary dataset")
            with np.load(fh, allow_pickle=False) as npz:
                arrays = {k: npz[k] for k in npz.files}
        meta = json.loads(bytes(arrays["meta_json"]).decode())
        self = cls()
        self.bins = arrays["bins"]
        self.mappers = [BinMapper.from_state(s) for s in meta["mappers"]]
        self.used_feature_map = list(meta["used_feature_map"])
        self.real_to_inner = np.asarray(arrays["real_to_inner"])
        self.num_total_features = int(meta["num_total_features"])
        self.feature_names = list(meta["feature_names"])
        self.max_bin = int(meta["max_bin"])
        self.bundle_plan = BundlePlan.from_state(meta.get("bundle_plan"))
        self.raw = arrays.get("raw")
        self.metadata = Metadata(self.bins.shape[1])
        if "label" in arrays:
            self.metadata.label = arrays["label"]
        self.metadata.weights = arrays.get("weights")
        self.metadata.query_boundaries = arrays.get("query_boundaries")
        self.metadata.init_score = arrays.get("init_score")
        self.metadata._update_query_weights()
        return self
