"""Exclusive feature bundling (EFB): the host-side planner.

Wide-sparse workloads (CTR-style one-hot blocks) pay the full ``[F, B]``
histogram cost for every feature even though most features are zero on
most rows.  EFB (the reference's ``enable_bundle``/``max_conflict_rate``,
src/io/dataset.cpp bundling pass) packs *mutually exclusive* sparse
features — features that are rarely non-default on the same row — into
shared columns whose bin space is partitioned into per-member sub-ranges
(offset encoding, reference FeatureGroup style).  The device bin matrix
shrinks from ``[F, N]`` to ``[C, N]``; histograms are built per column
and expanded back to original-feature space before split finding
(``ops/bundle.py``), so trees, the model text format, prediction and the
whole serve path stay in original feature space by construction.

Planner (:func:`plan_bundles`): greedy graph coloring over the mapper
sample — candidates are non-trivial NUMERICAL features whose default bin
is 0 (value 0 binned into bin 0 — the sparse-feature shape) with
``sparse_rate`` >= :data:`MIN_BUNDLE_SPARSE_RATE`, ranked sparsest
first.  A feature joins a bundle when (a) the bundle's cumulative
conflict count (rows where both the bundle and the feature are
non-default) stays within ``max_conflict_rate * sample_rows`` and (b)
the bundle's total bin budget stays within ``max_bin`` (so the bundled
columns ride the existing ``[C, max_bin]`` histogram shapes and uint8
storage unchanged).  Conflicting rows keep the LAST member's value in
column order — the bounded approximation EFB trades for the histogram
savings; ``max_conflict_rate=0`` admits only perfectly exclusive
features, which is what makes the zero-conflict bit-parity pin
(tests/test_bundling.py) meaningful.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from ..utils import log
from ..utils.threads import map_features
from .sparse import SparseColumns

# Candidates must be at least this sparse (BinMapper.sparse_rate = share
# of rows in the default bin).  Denser features gain little from
# bundling and burn conflict budget.
MIN_BUNDLE_SPARSE_RATE = 0.8


class BundlePlan:
    """The bundling decision: which used features share which column.

    ``column_members[c]`` lists the inner (used) feature indices stored
    in column ``c``; ``column_offsets[c]`` gives each member's offset —
    the column slot of that member's local bin 1 — with offset 0 marking
    an identity-encoded singleton (its column IS its own bin codes).
    """

    def __init__(self, column_members: List[List[int]],
                 column_offsets: List[List[int]], num_features: int,
                 sample_conflicts: int = 0):
        self.column_members = [list(m) for m in column_members]
        self.column_offsets = [list(o) for o in column_offsets]
        self.num_features = int(num_features)
        self.sample_conflicts = int(sample_conflicts)

    # -- shape accessors -------------------------------------------------
    @property
    def num_columns(self) -> int:
        return len(self.column_members)

    @property
    def bundles(self) -> List[List[int]]:
        """Multi-member columns only."""
        return [m for m in self.column_members if len(m) > 1]

    @property
    def features_bundled(self) -> int:
        return sum(len(m) for m in self.bundles)

    def publish(self) -> None:
        """The plan's shape as gauges (again after ``encode_exact`` moved
        members)."""
        from .. import obs
        obs.set_gauge("efb_bundles", len(self.bundles))
        obs.set_gauge("efb_features_bundled", self.features_bundled)
        obs.set_gauge("efb_columns", self.num_columns)

    def signature(self) -> tuple:
        """Cheap equality key for Dataset::CheckAlign-style alignment."""
        return (tuple(tuple(m) for m in self.column_members),
                tuple(tuple(o) for o in self.column_offsets))

    # -- encoding --------------------------------------------------------
    def encode_columns(self, feature_bins: Callable[[int], np.ndarray],
                       n: int, dtype) -> np.ndarray:
        """[C, n] column bin codes from per-feature bin codes.

        ``feature_bins(inner)`` returns that used feature's original bin
        codes for the n rows.  Bundle members write their non-default
        bins at ``offset + bin - 1``; on a conflicting row the LAST
        member in column order wins (deterministic)."""
        return self._encode(self.stored_of(feature_bins, n),
                            [0] * self.num_features, n, dtype)[0]

    def encode_columns_sparse(self, stored_bins: Callable, zero_bins, n: int,
                              dtype) -> np.ndarray:
        """``encode_columns`` from stored entries alone: O(stored) writes
        into the ``[C, n]`` codes, never a dense column a feature.

        ``stored_bins(inner)`` returns ``(rows, bins)`` of that used
        feature's stored entries; ``zero_bins[inner]`` is the bin of 0.0,
        which every other row of a singleton column holds (a bundle
        member's is 0: ``_is_candidate``).  A member writes only its
        stored rows; the LAST member in column order wins a conflicting
        row, as above."""
        return self._encode(stored_bins, zero_bins, n, dtype)[0]

    def encode_exact(self, stored_bins: Callable, zero_bins, n: int, dtype,
                     num_bins: Sequence[int], max_total_bin: int,
                     max_conflicts: int):
        """``(bins, plan)``: the training set's encoding, with the plan
        held to its conflict budget on ALL rows.

        The planner sees the FindBin sample; two members that never met
        there can meet on rows outside it (two rare levels of different
        categoricals of a 12M-row one-hot table do, on a few hundred
        rows), and the later member's write would silently erase the
        earlier one's value: the column-space histograms and the rows'
        routing would then differ from the features' own, budget or
        not.  So a member whose rows would take its column's conflicts
        past ``max_conflicts`` (``max_conflict_rate`` of the rows: 0
        admits none) is taken out of the column as it is met, and the
        members taken out are packed greedily, by the same rule on all
        rows, into columns appended behind the plan's (a lone one stays
        an identity column).  With ``max_conflict_rate=0`` the encoding
        is therefore exact on every row, whatever the sample missed."""
        bins, gone = self._encode(stored_bins, zero_bins, n, dtype,
                                  max_conflicts)
        members = [[f for f in m if f not in gone]
                   for m in self.column_members]
        offsets = [[o for f, o in zip(m, offs) if f not in gone]
                   for m, offs in zip(self.column_members,
                                      self.column_offsets)]
        pending = sorted(gone)
        extra = []
        while pending:
            col = np.zeros(n, dtype)
            took, offs, conflicts, width = [], [], 0, 1
            for f in pending:
                nb = int(num_bins[f])
                if width + nb - 1 > max_total_bin:
                    continue
                rows, vb = stored_bins(f)
                nz = vb > 0
                hit = int(np.count_nonzero(col[rows[nz]]))
                if conflicts + hit > max_conflicts:
                    continue
                col[rows[nz]] = width + vb[nz] - 1
                took.append(f)
                offs.append(width)
                conflicts += hit
                width += nb - 1
            if len(took) == 1:              # alone: its own bin codes
                rows, vb = stored_bins(took[0])
                col.fill(zero_bins[took[0]])
                col[rows] = vb
                offs = [0]
            pending = [f for f in pending if f not in took]
            members.append(took)
            offsets.append(offs)
            extra.append(col)
        if extra:
            log.info("EFB: %d member(s) met on rows outside the sample and "
                     "moved into %d further column(s)", len(gone), len(extra))
            bins = np.concatenate([bins, np.stack(extra)])
        return bins, BundlePlan(members, offsets, self.num_features,
                                self.sample_conflicts)

    def stored_of(self, feature_bins: Callable[[int], np.ndarray], n: int):
        """``stored_bins`` for the encoders from whole columns of bin
        codes: a bundle member's non-default rows, a singleton's every
        row."""
        alone = {m[0] for m, o in zip(self.column_members,
                                      self.column_offsets)
                 if len(m) == 1 and o[0] == 0}

        def stored(inner):
            vb = np.asarray(feature_bins(inner), np.int64)
            rows = np.arange(n) if inner in alone else np.flatnonzero(vb)
            return rows, vb[rows]
        return stored

    def _encode(self, stored_bins, zero_bins, n, dtype, max_conflicts=None):
        """The one encoder.  Returns the codes and, where a conflict
        budget is given, the set of members it took out of their columns
        (their slots stay unused)."""
        out = np.zeros((self.num_columns, n), dtype)

        def fill(c):
            members, offsets = self.column_members[c], self.column_offsets[c]
            if len(members) == 1 and offsets[0] == 0:
                out[c].fill(zero_bins[members[0]])
                rows, vb = stored_bins(members[0])
                out[c][rows] = vb
                return []
            gone, conflicts = [], 0
            for f, off in zip(members, offsets):
                rows, vb = stored_bins(f)
                nz = vb > 0
                rows = rows[nz]
                if max_conflicts is not None:
                    hit = int(np.count_nonzero(out[c][rows]))
                    if conflicts + hit > max_conflicts:
                        gone.append(f)
                        continue
                    conflicts += hit
                out[c][rows] = off + vb[nz] - 1
            return gone
        gone = map_features(fill, range(self.num_columns), n)
        return out, {f for g in gone for f in g}

    # -- device decode tables (ops/bundle.py BundleDecode) ---------------
    def decode_arrays(self, num_bins: Sequence[int],
                      default_bins: Sequence[int], max_bin: int,
                      shape: Optional[Tuple[int, int]] = None) -> dict:
        """Numpy decode tables for :class:`ops.bundle.BundleDecode`.

        ``shape``: the ``(columns, features)`` the tables are made for,
        at least the plan's own (ops/ordered_grow.py ``bundled_shape``:
        the rung of the device layout, so that tables of nearby plans
        share one compiled program).  What lies beyond the plan stands
        for nothing: a column that holds no feature, a feature of no
        slot that no search is ever told of (``multi`` is filled up to a
        power of two with -1).

        ``num_bins``/``default_bins`` are per used original feature; the
        slot map routes each feature's default bin (and any bin past its
        range) to the zero slot ``max_bin`` so the expansion's integer
        default-bin reconstruction never double-counts.

        The last three tables are the column-space split search's
        (ops/bundle.py ``find_best_split_columns``), which reads every
        feature where it lies instead of expanding ``[F, B]``:
        ``col_feat [C]`` the feature an identity column holds (-1: a
        bundle's column), ``slot_feat [C, B]`` the two-bin member whose
        one non-default bin is that slot of a bundle's column (-1: none),
        ``multi [M]`` the bundled members of more than two bins, which
        alone are still expanded."""
        C, F = shape or (self.num_columns, self.num_features)
        B = int(max_bin)
        col = np.zeros(F, np.int32)
        off = np.zeros(F, np.int32)
        width = np.zeros(F, np.int32)
        slot_map = np.full((F, B), B, np.int32)
        default = np.zeros(F, np.int32)
        col_feat = np.full(C, -1, np.int32)
        slot_feat = np.full((C, B), -1, np.int32)
        multi = []
        for c, (members, offsets) in enumerate(
                zip(self.column_members, self.column_offsets)):
            for f, o in zip(members, offsets):
                nb = int(num_bins[f])
                col[f] = c
                off[f] = o
                width[f] = max(nb - 1, 0)
                default[f] = int(default_bins[f])
                if o == 0:
                    col_feat[c] = f
                elif nb == 2 and o < B:
                    slot_feat[c, o] = f
                else:
                    multi.append(f)
                if o == 0:
                    b = np.arange(min(nb, B))
                    slot_map[f, b] = b
                else:
                    b = np.arange(1, min(nb, B + 1))
                    slot_map[f, b] = o + b - 1
                if 0 <= default[f] < B:
                    slot_map[f, default[f]] = B
        if shape and multi:
            multi += [-1] * ((1 << (len(multi) - 1).bit_length())
                             - len(multi))
        return {"col": col, "off": off, "width": width,
                "slot_map": slot_map, "default_bin": default,
                "col_feat": col_feat, "slot_feat": slot_feat,
                "multi": np.asarray(multi, np.int32)}

    # -- serialization (binary dataset cache) ----------------------------
    def to_state(self) -> dict:
        return {"column_members": self.column_members,
                "column_offsets": self.column_offsets,
                "num_features": self.num_features,
                "sample_conflicts": self.sample_conflicts}

    @classmethod
    def from_state(cls, state: Optional[dict]) -> Optional["BundlePlan"]:
        if not state:
            return None
        return cls([list(map(int, m)) for m in state["column_members"]],
                   [list(map(int, o)) for o in state["column_offsets"]],
                   int(state["num_features"]),
                   int(state.get("sample_conflicts", 0)))


def _is_candidate(mapper) -> bool:
    from .binning import NUMERICAL
    return (not mapper.is_trivial
            and mapper.bin_type == NUMERICAL
            and mapper.default_bin == 0
            and mapper.num_bin > 1
            and mapper.sparse_rate >= MIN_BUNDLE_SPARSE_RATE)


def plan_bundles(sample, mappers, used_feature_map,
                 *, max_conflict_rate: float, max_total_bin: int,
                 enable_bundle: bool = True,
                 is_enable_sparse: bool = True) -> Optional[BundlePlan]:
    """Greedy conflict-bounded bundling over the mapper sample.

    Args:
      sample: [S, F_real] raw sampled rows (the same sample FindBin
        saw), or their ``SparseColumns``: a feature's non-default rows
        are then read from its stored entries, O(stored) in all.
      mappers: per-USED-feature BinMapper list.
      used_feature_map: used index -> real column in ``sample``.
      max_conflict_rate: allowed conflicting-row share per bundle.
      max_total_bin: bin budget per bundled column (cfg.max_bin, so the
        existing [C, max_bin] histogram shapes hold).
    Returns a BundlePlan when at least one multi-member bundle formed,
    else None (the dataset stays in plain per-feature layout).
    """
    if not enable_bundle or not is_enable_sparse or len(mappers) == 0:
        return None
    try:
        from ..parallel.multihost import process_rank_world
        if process_rank_world()[1] > 1:
            # each rank loads its own shard: independently-drawn plans
            # would desync the replicated feature space pod-wide
            from .. import obs
            obs.set_gauge("efb_disabled_multihost", 1)
            log.warn_once("efb_multihost",
                          "enable_bundle: feature bundling is disabled "
                          "under multihost loading (per-rank samples "
                          "would draw diverging bundle plans)")
            return None
    except Exception:  # pragma: no cover - uninitialized backend
        pass
    from .. import obs
    with obs.span("Bin::bundle"):
        plan = _plan_bundles_impl(sample, mappers, used_feature_map,
                                  max_conflict_rate, max_total_bin)
    if plan is not None:
        plan.publish()
        # the one-line dataset sparsity summary (reference-style)
        n_sparse = sum(1 for m in mappers if _is_candidate(m))
        log.info("EFB: %d sparse feature(s), %d bundled into %d bundle(s) "
                 "(%d -> %d columns, %d conflicting sample rows)",
                 n_sparse, plan.features_bundled, len(plan.bundles),
                 plan.num_features, plan.num_columns,
                 plan.sample_conflicts)
    return plan


def _popcount(words: np.ndarray) -> int:
    return int(np.bitwise_count(words).sum())


def _nondefault_bits(sample, col: int, mapper) -> np.ndarray:
    """The sample rows on which the feature is not in its default bin
    (bin 0: ``_is_candidate``), as a bit set in uint64 words."""
    S = sample.shape[0]
    if isinstance(sample, SparseColumns):
        rows = sample.rows(col)
        nd = np.zeros(S, bool)
        nd[rows[np.asarray(mapper.value_to_bin(sample.values(col))) != 0]] \
            = True
    else:
        nd = np.asarray(mapper.value_to_bin(sample[:, col])) != 0
    packed = np.packbits(nd)
    return np.pad(packed, (0, (-len(packed)) % 8)).view(np.uint64)


def _plan_bundles_impl(sample, mappers, used_feature_map,
                       max_conflict_rate, max_total_bin):
    F = len(mappers)
    S = sample.shape[0]
    cand = [f for f in range(F) if _is_candidate(mappers[f])]
    if len(cand) < 2:
        return None
    # sparsest first: the emptiest features pack tightest and burn the
    # least conflict budget (the ISSUE's sparse_rate ranking)
    cand.sort(key=lambda f: (-mappers[f].sparse_rate, f))
    budget = int(float(max_conflict_rate) * S)

    # bit sets over the sample rows (25 KB a feature at 200,000 rows): a
    # conflict count is one AND and one population count
    bundles: List[List[int]] = []       # member lists
    occupied: List[np.ndarray] = []     # per-bundle any-member-nonzero
    conflicts: List[int] = []           # per-bundle cumulative conflicts
    bins_used: List[int] = []           # per-bundle 1 + sum(nb - 1)
    for f in cand:
        nd = _nondefault_bits(sample, used_feature_map[f], mappers[f])
        nb = int(mappers[f].num_bin)
        placed = False
        for bi in range(len(bundles)):
            if bins_used[bi] + (nb - 1) > max_total_bin:
                continue
            c = _popcount(occupied[bi] & nd)
            if conflicts[bi] + c > budget:
                continue
            bundles[bi].append(f)
            occupied[bi] |= nd
            conflicts[bi] += c
            bins_used[bi] += nb - 1
            placed = True
            break
        if not placed:
            bundles.append([f])
            occupied.append(nd)
            conflicts.append(0)
            bins_used.append(1 + (nb - 1))
    keep = {}
    total_conflicts = 0
    for bi, members in enumerate(bundles):
        if len(members) > 1:
            for f in members:
                keep[f] = bi
            total_conflicts += conflicts[bi]
    if not keep:
        return None

    # column order: walk used features ascending; a bundle's column sits
    # at its first member's position, members sorted ascending (the
    # deterministic conflict-overwrite order)
    emitted = set()
    column_members: List[List[int]] = []
    column_offsets: List[List[int]] = []
    for f in range(F):
        if f in emitted:
            continue
        bi = keep.get(f)
        if bi is None:
            column_members.append([f])
            column_offsets.append([0])
            emitted.add(f)
            continue
        members = sorted(bundles[bi])
        offs = []
        o = 1
        for m in members:
            offs.append(o)
            o += int(mappers[m].num_bin) - 1
        column_members.append(members)
        column_offsets.append(offs)
        emitted.update(members)
    return BundlePlan(column_members, column_offsets, F,
                      sample_conflicts=total_conflicts)
