"""Mesh-level entry points for distributed tree growth.

Builds a jitted ``grow`` function that runs one of the serial growers
under ``jax.shard_map`` over a ``jax.sharding.Mesh`` with the
communication strategy of the requested tree_learner type ("data" |
"feature" | "voting" — the reference's TreeLearner factory,
src/treelearner/tree_learner.cpp).  Data-parallel over uint8, unbundled
bins (what the serial learner grows leaf-ordered) runs
ops/ordered_grow.py on each shard's own row block with one histogram
exchange a split (comm.py ``HistExchange``); everything else runs
ops/grow.py ``_grow_tree_impl``, which passes over all local rows at
every split.  The returned TreeArrays are replicated (every shard
deterministically grows the identical tree); leaf_id and the score delta
stay row-sharded in data/voting modes.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..obs.compile_ledger import instrumented_jit
from ..ops import ordered_grow
from ..ops.grow import GrowParams, _grow_tree_impl
from .comm import (DataParallelComm, FeatureParallelComm, HistExchange,
                   VotingParallelComm)


def make_comm(mode: str, axis_name: str, num_shards: int,
              num_features: int, top_k: int = 20):
    if mode == "data":
        return DataParallelComm(axis_name, num_shards)
    if mode == "feature":
        f_block = -(-num_features // num_shards)
        return FeatureParallelComm(axis_name, num_shards, f_block)
    if mode == "voting":
        return VotingParallelComm(axis_name, num_shards, top_k)
    raise ValueError(f"unknown parallel tree learner mode: {mode!r}")


def grows_ordered(mode: str, bins_dtype, bundled: bool) -> bool:
    """Whether a learner grows leaf-ordered shards: the data-parallel
    one, over data the leaf-ordered grower accepts."""
    return mode == "data" and ordered_grow.accepts(bins_dtype, bundled)


def make_parallel_grow(mesh: Mesh, mode: str, params: GrowParams,
                       axis_name: Optional[str] = None, top_k: int = 20):
    """Build a jitted distributed grow(bins, num_bin, is_cat, feat_mask,
    grad, hess, row_weight, learning_rate) for the given mesh.

    Accepts unpadded inputs: rows are padded to a multiple of the mesh axis
    with zero row_weight (dead rows), features to a multiple with a False
    feat_mask (dead features); outputs are cropped back.

    ``bins_rm`` ([N, F], rows sharded) and ``bins_words``
    (``ordered_grow.pack_word_lanes`` over the mesh) are the
    leaf-ordered shards' resident layout, shared across trees;
    left out, or where rows had to be padded, each shard derives them
    from its block of ``bins`` once a tree.
    """
    axis_name = axis_name or mesh.axis_names[0]
    k = mesh.shape[axis_name]
    row_sharded = mode in ("data", "voting")

    if row_sharded:
        in_specs = (P(None, axis_name), P(), P(), P(),
                    P(axis_name), P(axis_name), P(axis_name), P())
        out_specs = (P(), P(axis_name), P(axis_name))
    else:
        in_specs = (P(None, None), P(), P(), P(), P(), P(), P(), P())
        out_specs = (P(), P(), P())

    # one program per (mesh, mode, params) factory call — ledgered as
    # dist_grow_tree so a distributed run's compiles are attributable
    # like the serial growers' (the factory result is cached per
    # booster; a second same-config factory still recompiles, which the
    # ledger now makes visible instead of silent)
    @instrumented_jit(program="dist_grow_tree")
    def grow(bins, num_bin, is_cat, feat_mask, grad, hess, row_weight,
             learning_rate, bundle=None, bins_rm=None, bins_words=None):
        F, N = bins.shape
        pad_n = ((-N) % k) if row_sharded else 0
        pad_f = ((-F) % k) if mode == "feature" else 0
        # what the boundary itself costs (the pads here, the crops below)
        # is layout; the growers scope everything inside, and an
        # operation they leave unscoped stays unscoped
        with jax.named_scope("layout"):
            if pad_n or pad_f:
                bins = jnp.pad(bins, ((0, pad_f), (0, pad_n)))
                grad = jnp.pad(grad, (0, pad_n))
                hess = jnp.pad(hess, (0, pad_n))
                row_weight = jnp.pad(row_weight, (0, pad_n))  # 0 = dead row
            if pad_f and bundle is None:
                # EFB keeps feature metadata in ORIGINAL space; only the
                # column matrix pads (a zero pad column owns no feature)
                num_bin = jnp.pad(num_bin, (0, pad_f))
                is_cat = jnp.pad(is_cat, (0, pad_f))
                feat_mask = jnp.pad(feat_mask, (0, pad_f))  # False = dead

        args = (bins, num_bin, is_cat, feat_mask, grad, hess, row_weight,
                learning_rate)
        specs = in_specs
        if grows_ordered(mode, bins.dtype, bundle is not None):
            exchange = HistExchange(axis_name, k)
            resident = ()
            if not pad_n and bins_rm is not None and bins_words is not None:
                resident = (bins_rm, tuple(bins_words))
                specs += (P(axis_name, None),
                          (P(axis_name),) * len(bins_words))

            def local_fn(b, nb, ic, fm, g, h, w, lr, *res):
                rm, words = res if res else (None, None)
                return ordered_grow.grow_tree_ordered(
                    b, nb, ic, fm, g, h, w, lr, params, bins_rm=rm,
                    bins_words=words, exchange=exchange)
            args += resident
        else:
            comm = make_comm(mode, axis_name, k, F + pad_f, top_k)

            def local_fn(b, nb, ic, fm, g, h, w, lr, *bnd):
                return _grow_tree_impl(b, nb, ic, fm, g, h, w, lr, params,
                                       comm, bundle=bnd[0] if bnd else None)
            if bundle is not None:
                specs += (P(),)
                args += (bundle,)
        # no varying-manual-axes check: the growers' replicated outputs
        # are deterministic by construction (every shard grows the
        # identical tree)
        sharded = jax.shard_map(local_fn, mesh=mesh, in_specs=specs,
                                out_specs=out_specs, check_vma=False)
        tree, leaf_id, delta = sharded(*args)
        if pad_n:
            with jax.named_scope("layout"):
                leaf_id = leaf_id[:N]
                delta = delta[:N]
        return tree, leaf_id, delta

    def traffic_per_tree(num_features: int, bundled: bool = False,
                         bins_dtype=jnp.uint8):
        """Static per-tree collective traffic of this learner at the given
        (unpadded) feature count — the comm strategy's own account with
        the same feature padding and the same choice of grower the jitted
        path makes (obs layer)."""
        if grows_ordered(mode, bins_dtype, bundled):
            comm = HistExchange(axis_name, k)
            pad_f = 0
        else:
            pad_f = ((-num_features) % k) if mode == "feature" else 0
            comm = make_comm(mode, axis_name, k, num_features + pad_f, top_k)
        return comm.traffic_per_tree(num_features + pad_f, params.max_bin,
                                     params.num_leaves)

    grow.traffic_per_tree = traffic_per_tree
    return grow
