"""Parity of the Pallas TPU histogram kernel against the scatter reference,
run in the Pallas interpreter so the TPU production path is checked on CPU
(including the row-padding and max_bin->lane-multiple cropping paths)."""

import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops.histogram import (build_children_histograms,
                                        build_root_histogram)
from lightgbm_tpu.ops.pallas_histogram import (children_histograms_pallas,
                                               root_histogram_pallas)


def _data(seed, n, f, B, dtype=jnp.int32):
    rng = np.random.RandomState(seed)
    bins = jnp.asarray(rng.randint(0, B, size=(f, n)), dtype)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.abs(g) + 0.1
    w = jnp.asarray((rng.rand(n) > 0.3), jnp.float32)  # bagging-style mask
    leaf = jnp.asarray(rng.randint(0, 5, size=n), jnp.int32)
    return bins, g, h, w, leaf


@pytest.mark.parametrize("n,B,n_blk,dtype,leaves", [
    (1024, 16, 256, jnp.int32, (1, 3)),   # exact block multiple
    (1000, 16, 256, jnp.int32, (1, 3)),   # row padding path
    (700, 255, 256, jnp.int32, (1, 3)),   # max_bin not a lane multiple: crop
    (700, 21, 256, jnp.uint8, (1, 3)),    # the bins as the device holds them
    # the root as the left child of a split nothing went right in
    # (right_leaf -2 matches no row): what root_histogram_pallas calls
    (512, 8, 256, jnp.uint8, (0, -2)),
])
def test_children_parity_interpret(n, B, n_blk, dtype, leaves):
    bins, g, h, w, leaf = _data(0, n, 5, B, dtype)
    want = np.asarray(build_children_histograms(bins, g, h, w, leaf,
                                                *leaves, B))
    got = np.asarray(children_histograms_pallas(bins, g, h, w, leaf, *leaves,
                                                B, n_blk=n_blk,
                                                interpret=True))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    if leaves[1] < 0:
        assert not got[1].any() and got[0].any()


def test_root_parity_interpret():
    bins, g, h, w, _ = _data(1, 900, 4, 32)
    want = np.asarray(build_root_histogram(bins, g, h, w, 32))
    got = np.asarray(root_histogram_pallas(bins, g, h, w, 32, n_blk=256,
                                           interpret=True))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
