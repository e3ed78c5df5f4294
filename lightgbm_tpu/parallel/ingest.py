"""Sharded ingestion + distributed FindBin.

Reference flow (dataset_loader.cpp): rank-partitioned row loading
(:549-655) and distributed bin construction (:723-816) — every machine
reads only its rows, the bin mappers are found with the FEATURES sharded
across machines, and two collectives make every machine agree on the full
mapper set before local rows are binned.

TPU-native formulation (single-controller JAX; the same code runs
per-process under multi-host jax.distributed — brought up from reference
machine_list_file confs by parallel/multihost.py):

1. *Deterministic global sample*: sample row indices are drawn from the
   GLOBAL row count with the same seed/order as the single-host path
   (BinnedDataset.from_matrix), so the distributed mappers are IDENTICAL
   to single-host mappers — stronger than the reference, whose per-rank
   sampling drifts from its single-machine result.
2. *Sample exchange as one psum*: each shard contributes a [S, F] buffer
   holding only its owned sampled rows (zeros elsewhere); a psum over the
   mesh axis reconstitutes the full sample on every shard.  Disjoint
   ownership makes sum == gather, and psum rides ICI optimally.
3. *Feature-sharded FindBin*: shard r runs the (host-side, data-dependent)
   greedy binning of io/binning.py for features f with f % k == r.
4. *Mapper agreement as one psum*: mappers are encoded into fixed-width
   f64 rows (encode_mapper), each shard fills its feature slice, and a
   second psum distributes the full table; decode_mapper rebuilds
   BinMapper objects everywhere.
5. Each shard bins its local rows with the agreed mappers.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..io.binning import NUMERICAL, BinMapper
from ..io.dataset import BinnedDataset, Metadata
from ..obs.compile_ledger import instrumented_jit
from ..utils import log


# ---------------------------------------------------------------------------
# rank-partitioned loading (dataset_loader.cpp:549-655)
# ---------------------------------------------------------------------------

def row_partition(num_data: int, num_shards: int) -> List[Tuple[int, int]]:
    """Contiguous [start, stop) row ranges, balanced like
    Partition(num_data, num_machines)."""
    base = num_data // num_shards
    rem = num_data % num_shards
    out = []
    start = 0
    for r in range(num_shards):
        cnt = base + (1 if r < rem else 0)
        out.append((start, start + cnt))
        start += cnt
    return out


def load_file_sharded(path: str, num_shards: int, has_header: bool = False,
                      label_idx: int = 0):
    """Parse a data file and return per-shard (rows, labels) partitions.
    A real multi-host deployment parses only the local range per process;
    single-controller splits after one parse."""
    from ..io.parser import parse_file
    label, X, header = parse_file(path, has_header=has_header,
                                  label_idx=label_idx)
    parts = row_partition(X.shape[0], num_shards)
    shards = [(X[a:b], None if label is None else label[a:b])
              for a, b in parts]
    return shards, header


# ---------------------------------------------------------------------------
# mapper <-> fixed-width f64 row
# ---------------------------------------------------------------------------

def mapper_width(max_bin: int) -> int:
    return 7 + max_bin + 1


def encode_mapper(m: Optional[BinMapper], max_bin: int) -> np.ndarray:
    """Fixed-width f64 encoding (payload = upper bounds or categories)."""
    w = mapper_width(max_bin)
    row = np.zeros(w, np.float64)
    if m is None:
        row[0] = -1.0
        return row
    row[0] = m.num_bin
    row[1] = m.bin_type
    row[2] = 1.0 if m.is_trivial else 0.0
    row[3] = m.sparse_rate
    row[4] = m.min_val
    row[5] = m.max_val
    row[6] = m.default_bin
    if m.bin_type == NUMERICAL:
        ub = np.asarray(m.bin_upper_bound, np.float64)
        row[7:7 + len(ub)] = ub
    else:
        cats = np.asarray(m.bin_2_categorical, np.float64)
        row[7:7 + len(cats)] = cats
    return row


def decode_mapper(row: np.ndarray) -> Optional[BinMapper]:
    if row[0] < 0:
        return None
    m = BinMapper()
    m.num_bin = int(row[0])
    m.bin_type = int(row[1])
    m.is_trivial = bool(row[2] > 0.5)
    m.sparse_rate = float(row[3])
    m.min_val = float(row[4])
    m.max_val = float(row[5])
    m.default_bin = int(row[6])
    if m.bin_type == NUMERICAL:
        m.bin_upper_bound = np.asarray(row[7:7 + m.num_bin], np.float64)
        m.bin_2_categorical = []
        m.categorical_2_bin = {}
    else:
        m.bin_upper_bound = np.zeros(0, np.float64)
        m.bin_2_categorical = [int(c) for c in row[7:7 + m.num_bin]]
        m.categorical_2_bin = {c: i for i, c in
                               enumerate(m.bin_2_categorical)}
    return m


# ---------------------------------------------------------------------------
# the distributed FindBin
# ---------------------------------------------------------------------------

def global_sample_indices(num_data: int, sample_cnt: int,
                          seed: int) -> np.ndarray:
    """EXACTLY the single-host sampling of BinnedDataset.from_matrix."""
    if num_data <= sample_cnt:
        return np.arange(num_data, dtype=np.int64)
    rng = np.random.RandomState(seed)
    return np.sort(rng.choice(num_data, sample_cnt, replace=False))


def _f64_to_f32x3(x: np.ndarray) -> np.ndarray:
    """[3, ...] f32 components whose sum reconstructs x exactly (24+24+24
    mantissa bits > f64's 53).  Devices run f32; host reassembles f64.

    Exact for |x| <= f32 max; finite values beyond that saturate through
    cascading clamps (sum ~ +-1.02e39, then +-inf) instead of the
    hi=inf/lo=NaN corruption a plain cast residual would produce."""
    f32max = np.float64(np.finfo(np.float32).max)
    finite = np.isfinite(x)
    hi = np.where(finite, np.clip(x, -f32max, f32max), x).astype(np.float32)
    r1 = np.where(finite, x - np.where(finite, hi, 0).astype(np.float64), 0.0)
    mid = np.clip(r1, -f32max, f32max).astype(np.float32)
    r2 = r1 - mid.astype(np.float64)
    lo = np.clip(r2, -f32max, f32max).astype(np.float32)
    return np.stack([hi, mid, lo])


def _f32x3_to_f64(c: np.ndarray) -> np.ndarray:
    return (c[0].astype(np.float64) + c[1].astype(np.float64)
            + c[2].astype(np.float64))


def make_psum(mesh: Mesh, axis: str):
    """One-collective exchange: disjoint f64 contributions -> full array
    everywhere (psum over the mesh axis).

    With disjoint ownership the per-position sum is value + zeros, so the
    3-component f32 transport is exact: no f64 precision is lost even
    though the devices compute in f32 (x64 stays off)."""

    @instrumented_jit(program="dist_psum_exchange")
    def _psum(x_stacked):
        # x_stacked: [k, 3, ...] one contribution per shard
        def body(x):
            return jax.lax.psum(x[0], axis)

        return jax.shard_map(body, mesh=mesh, in_specs=P(axis),
                             out_specs=P(), check_vma=False)(x_stacked)

    def exchange(contrib_f64: np.ndarray) -> np.ndarray:
        comp = np.stack([_f64_to_f32x3(c) for c in contrib_f64])  # [k,3,...]
        return _f32x3_to_f64(np.asarray(_psum(jnp.asarray(comp))))

    return exchange


def distributed_find_bin(mesh: Mesh, axis: str,
                         shards: Sequence[np.ndarray],
                         *, max_bin: int = 255, min_data_in_bin: int = 5,
                         min_data_in_leaf: int = 100,
                         bin_construct_sample_cnt: int = 200000,
                         categorical_features: Sequence[int] = (),
                         data_random_seed: int = 1) -> List[Optional[BinMapper]]:
    """Agree on per-feature BinMappers across row shards.

    Every shard ends up with the full mapper list, bit-identical to the
    single-host BinnedDataset.from_matrix result on the concatenated
    rows.  Two psum collectives over ``mesh[axis]`` carry the sample and
    the encoded mappers (dataset_loader.cpp:723-816's Allreduce/Allgather
    pair)."""
    k = len(shards)
    F = shards[0].shape[1]
    counts = [s.shape[0] for s in shards]
    offsets = np.concatenate([[0], np.cumsum(counts)])
    num_data = int(offsets[-1])
    cat = set(int(c) for c in categorical_features)

    sample_idx = global_sample_indices(num_data, bin_construct_sample_cnt,
                                       data_random_seed)
    S = len(sample_idx)

    # 1. each shard fills its owned sampled rows; psum reconstitutes
    contrib = np.zeros((k, S, F), np.float64)
    for r in range(k):
        lo, hi = offsets[r], offsets[r + 1]
        owned = (sample_idx >= lo) & (sample_idx < hi)
        local_rows = sample_idx[owned] - lo
        contrib[r, np.nonzero(owned)[0]] = shards[r][local_rows]
    exchange = make_psum(mesh, axis)
    sample_global = exchange(contrib)

    # 2. feature-sharded FindBin + 3. encoded-mapper psum
    from ..io.dataset import build_mappers_from_sample
    w = mapper_width(max_bin)
    enc = np.zeros((k, F, w), np.float64)
    for r in range(k):
        per_real = build_mappers_from_sample(
            sample_global, num_data, max_bin=max_bin,
            min_data_in_bin=min_data_in_bin,
            min_data_in_leaf=min_data_in_leaf,
            categorical_features=cat,
            feature_indices=range(r, F, k))
        for f in range(r, F, k):
            enc[r, f] = encode_mapper(per_real[f], max_bin)
    enc_global = exchange(enc)
    return [decode_mapper(enc_global[f]) for f in range(F)]


def binned_dataset_from_shards(mesh: Mesh, axis: str,
                               shards: Sequence[Tuple[np.ndarray, Optional[np.ndarray]]],
                               *, max_bin: int = 255,
                               min_data_in_bin: int = 5,
                               min_data_in_leaf: int = 100,
                               bin_construct_sample_cnt: int = 200000,
                               categorical_features: Sequence[int] = (),
                               data_random_seed: int = 1) -> BinnedDataset:
    """Full sharded-ingestion flow: agree on mappers, bin each shard's rows
    locally, assemble a BinnedDataset whose ``bins`` can be device-sharded
    over ``mesh[axis]`` (device_put_sharded per row range).

    The result is identical to BinnedDataset.from_matrix on the
    concatenated rows — asserted by tests/test_ingest.py."""
    rows = [s[0] for s in shards]
    labels = [s[1] for s in shards]
    mappers_per_real = distributed_find_bin(
        mesh, axis, rows, max_bin=max_bin, min_data_in_bin=min_data_in_bin,
        min_data_in_leaf=min_data_in_leaf,
        bin_construct_sample_cnt=bin_construct_sample_cnt,
        categorical_features=categorical_features,
        data_random_seed=data_random_seed)

    ds = BinnedDataset()
    F = rows[0].shape[1]
    num_data = sum(r.shape[0] for r in rows)
    ds.num_total_features = F
    ds.max_bin = max_bin
    ds.feature_names = [f"Column_{i}" for i in range(F)]
    ds.real_to_inner = np.full(F, -1, dtype=np.int64)
    used, mappers = [], []
    for f, m in enumerate(mappers_per_real):
        if m is None or m.is_trivial:
            continue
        ds.real_to_inner[f] = len(used)
        used.append(f)
        mappers.append(m)
    ds.used_feature_map = used
    ds.mappers = mappers
    if not used:
        log.warning("All features are trivial; dataset has no usable feature")
    dtype = np.uint8 if max([m.num_bin for m in mappers] or [1]) <= 256 \
        else np.uint16
    # each shard bins ITS rows; single-controller assembles the columns
    ds.bins = np.zeros((len(used), num_data), dtype=dtype)
    off = 0
    for r in rows:
        n = r.shape[0]
        for inner, f in enumerate(used):
            ds.bins[inner, off:off + n] = \
                mappers[inner].value_to_bin(r[:, f]).astype(dtype)
        off += n
    ds.metadata = Metadata(num_data)
    lab = (np.concatenate([np.asarray(x, np.float32) for x in labels])
           if all(x is not None for x in labels)
           else np.zeros(num_data, np.float32))
    ds.metadata.set_label(lab)
    return ds


def shard_bins_to_devices(mesh: Mesh, axis: str, ds: BinnedDataset):
    """Place ds.bins row-sharded over mesh[axis]: [F, N] with N split on
    the axis — the layout the data-parallel tree learner consumes."""
    sharding = NamedSharding(mesh, P(None, axis))
    n = ds.bins.shape[1]
    k = int(np.prod([mesh.shape[a] for a in (axis,)]))
    pad = (-n) % k
    bins = np.pad(ds.bins, ((0, 0), (0, pad))) if pad else ds.bins
    return jax.device_put(jnp.asarray(bins), sharding)
