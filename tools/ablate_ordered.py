"""In-loop ablation harness for the ordered grower (run on the chip).

Usage: python tools/ablate_ordered.py [variant ...]

Variants stub one stage of ops/ordered_grow.py at a time and re-time the
WHOLE tree in a data-dependent loop (g depends on the previous delta), so
no dispatch repeats an earlier one.  Costs are read as differences
between variants, not absolutes (and stubbing a stage changes the split
sequence: PERF.md, "Carried over").
"""

from __future__ import annotations

import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, ".")

from lightgbm_tpu.ops.grow import GrowParams  # noqa: E402

N = int(1e6)
F = 28
B = 255
L = 63
ITERS = 8

VARIANT = set(sys.argv[1:]) or {"base"}


def patched_grow():
    """Import ordered_grow with stage stubs applied per VARIANT."""
    import lightgbm_tpu.ops.ordered_grow as og
    import importlib
    importlib.reload(og)

    if "nokeygather" in VARIANT or "nogather" in VARIANT:
        # replace the [P, F] row gather feeding the key with a contiguous
        # slice of the same shape (wrong values, same downstream costs)
        real_take = jnp.take

        def fake_take(arr, idx, axis=None, **kw):
            if axis == 0 and idx.ndim == 1 and arr.ndim == 2:
                return jax.lax.dynamic_slice(
                    arr, (idx[0] % 128, 0), (idx.shape[0], arr.shape[1]))
            return real_take(arr, idx, axis=axis, **kw)
        og.jnp = type(sys)("jnp_patch")
        og.jnp.__dict__.update(jnp.__dict__)
        og.jnp.take = fake_take
    if "nosort" in VARIANT:
        real_sort = jax.lax.sort

        def fake_sort(operands, num_keys=1, is_stable=False):
            return operands
        og.jax = type(sys)("jax_patch")
        og.jax.__dict__.update(jax.__dict__)
        og.jax.lax = type(sys)("lax_patch")
        og.jax.lax.__dict__.update(jax.lax.__dict__)
        og.jax.lax.sort = fake_sort
    return og


def main():
    og = patched_grow()
    rng = np.random.RandomState(0)
    bins_rm = jnp.asarray(rng.randint(0, B, size=(N, F)), jnp.uint8)
    bins = bins_rm.T
    num_bin = jnp.full((F,), B, jnp.int32)
    is_cat = jnp.zeros((F,), bool)
    feat_mask = jnp.ones((F,), bool)
    w = jnp.ones((N,), jnp.float32)
    y = jnp.asarray(rng.randint(0, 2, size=N), jnp.float32)
    params = GrowParams(num_leaves=L, max_bin=B, min_data_in_leaf=50,
                        min_sum_hessian_in_leaf=1e-3)

    score = jnp.zeros(N, jnp.float32)

    @jax.jit
    def grads(score):
        p = jax.nn.sigmoid(score)
        return p - y, p * (1 - p)

    def one(score):
        g, h = grads(score)
        tree, leaf_id, delta = og.grow_tree_ordered(
            bins, num_bin, is_cat, feat_mask, g, h, w,
            jnp.float32(0.1), params, bins_rm=bins_rm)
        return score + delta

    t0 = time.time()
    score = one(score)
    jax.block_until_ready(score)
    print(f"variant={sorted(VARIANT)} compile+first={time.time() - t0:.1f}s")

    t0 = time.time()
    for _ in range(ITERS):
        score = one(score)
    jax.block_until_ready(score)
    dt = (time.time() - t0) / ITERS
    print(f"variant={sorted(VARIANT)} per_tree_ms={dt * 1e3:.1f}")


if __name__ == "__main__":
    main()
