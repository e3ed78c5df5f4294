"""The counting partition kernel (ops/partition.py) against the stable
sort it replaced, in interpret mode on the CPU.  What the chip's compiler
accepts is in tests/test_tpu_compile.py; what the chip computes, in
tools/probe_partition.py."""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from lightgbm_tpu.ops import partition  # noqa: E402


def _oracle(lanes, do_split, inseg, go_r):
    """The grower's partition as it was before the kernel: one stable
    sort on the three-valued key (0 left, 1 right, 2 frozen: the suffix
    beyond the segment, and everything when the split is rejected)."""
    key = jnp.where(do_split & inseg, go_r.astype(jnp.uint8), jnp.uint8(2))
    return jax.lax.sort((key,) + tuple(lanes), num_keys=1,
                        is_stable=True)[1:]


def _window(rows, lanes, seed, small_bytes=False):
    rng = np.random.RandomState(seed)
    if small_bytes:
        # every byte of every word in 128..255: negative words, and the
        # int8 contraction hands each byte back sign-extended
        raw = rng.randint(128, 256, size=(lanes, rows, 4)).astype(np.uint8)
        words = raw.view(np.int32)[:, :, 0]
    else:
        words = rng.randint(-2**31, 2**31 - 1, size=(lanes, rows),
                            dtype=np.int64).astype(np.int32)
    return tuple(jnp.asarray(w) for w in words), rng


# rows, lanes, segment, what goes right, split accepted, sub-blocks a step
CASES = {
    "rejected_split_is_identity": (8192, 11, 5000, "random", False, 16),
    "all_rows_left": (8192, 11, 8192, "none", True, 16),
    "all_rows_right": (8192, 11, 8192, "all", True, 16),
    "suffix_stays_where_it_was": (8192, 11, 3001, "random", True, 16),
    "segment_is_the_window": (8192, 11, 8192, "random", True, 16),
    "n_left_a_multiple_of_the_tile": (8192, 11, 8192, "after_4096", True, 16),
    "n_left_one_past_a_tile": (8192, 11, 8192, "after_4097", True, 16),
    "one_grid_step_a_pass": (8192, 11, 6000, "random", True, 64),
    "several_grid_steps": (32768, 11, 20011, "random", True, 16),
    "bytes_of_128_and_over": (8192, 11, 7000, "random", True, 16),
    "five_lanes": (8192, 5, 7000, "random", True, 16),
    "thirty_eight_lanes": (8192, 38, 7000, "random", True, 16),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_is_the_stable_sort(case):
    rows, n_lanes, seg, right, accepted, nsub = CASES[case]
    lanes, rng = _window(rows, n_lanes, seed=len(case),
                         small_bytes=case == "bytes_of_128_and_over")
    iota = np.arange(rows)
    if right == "random":
        go_r = rng.rand(rows) < 0.4
    elif right.startswith("after_"):
        go_r = iota >= int(right[6:])
    else:
        go_r = np.full(rows, right == "all")
    go_r, inseg = jnp.asarray(go_r), jnp.asarray(iota < seg)
    do_split = jnp.asarray(accepted)
    want = _oracle(lanes, do_split, inseg, go_r)
    got = jax.jit(functools.partial(
        partition.segment_partition, sub_blocks_per_step=nsub,
        interpret=True))(lanes, do_split & inseg & ~go_r)
    assert len(got) == n_lanes
    for lane, (g, w) in enumerate(zip(got, want)):
        assert g.dtype == jnp.int32 and g.shape == (rows,)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"lane {lane}")
    # off the TPU the dispatcher is the sort itself
    for g, w in zip(partition.stable_partition(
            lanes, do_split & inseg & ~go_r), want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_grower_with_the_kernel_returns_the_sort_paths_tree(monkeypatch):
    """``grow_tree_ordered`` with the kernel in the sort's place (forced
    on, interpreted) returns the ``TreeArrays``, ``leaf_id`` and
    ``output_delta`` of the sort path, equal exactly."""
    from lightgbm_tpu.ops import ordered_grow
    from lightgbm_tpu.ops.grow import GrowParams
    rng = np.random.RandomState(3)
    n, f = 5000, 6
    bins = jnp.asarray(rng.randint(0, 64, size=(f, n)).astype(np.uint8))
    y = (np.asarray(bins[0]) > 30) ^ (np.asarray(bins[3]) > 11)
    grad = jnp.asarray((0.5 - y + 0.1 * rng.normal(size=n))
                       .astype(np.float32))
    hess = jnp.full(n, 0.25, jnp.float32)
    args = (bins, jnp.full(f, 64, jnp.int32), jnp.zeros(f, bool),
            jnp.ones(f, bool), grad, hess, jnp.ones(n, jnp.float32),
            jnp.float32(0.1))
    params = GrowParams(num_leaves=9, max_bin=64, min_data_in_leaf=20)
    grow = ordered_grow.grow_tree_ordered
    want = grow(*args, params=params)
    calls = []

    def kernel(lanes, is_left):
        calls.append(lanes[0].shape[0])
        return partition.segment_partition(lanes, is_left, interpret=True)
    monkeypatch.setattr(partition, "stable_partition", kernel)
    jax.clear_caches()                   # or the sort path's program answers
    got = grow(*args, params=params)
    jax.clear_caches()
    assert calls == [8192], calls        # one size class at 5,000 rows
    assert int(want[0].num_leaves) == 9
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("compact_inactive", [False, True])
def test_grower_with_the_lanes_histogram_returns_the_scatter_paths_tree(
        monkeypatch, compact_inactive):
    """``grow_tree_ordered`` with the histogram kernel that reads the word
    lanes (forced on, interpreted: the root, the compacted root under
    bagging and every child window) returns the ``TreeArrays``,
    ``leaf_id`` and ``output_delta`` of the scatter path, equal exactly."""
    from lightgbm_tpu.ops import leafhist, ordered_grow
    from lightgbm_tpu.ops.grow import GrowParams
    from lightgbm_tpu.utils import device
    rng = np.random.RandomState(5)
    n, f = 5000, 6
    bins = jnp.asarray(rng.randint(0, 64, size=(f, n)).astype(np.uint8))
    y = (np.asarray(bins[1]) > 20) ^ (np.asarray(bins[4]) > 40)
    grad = jnp.asarray((0.5 - y + 0.1 * rng.normal(size=n))
                       .astype(np.float32))
    hess = jnp.full(n, 0.25, jnp.float32)
    weight = jnp.asarray((rng.rand(n) < 0.7).astype(np.float32)) \
        if compact_inactive else jnp.ones(n, jnp.float32)
    args = (bins, jnp.full(f, 64, jnp.int32), jnp.zeros(f, bool),
            jnp.ones(f, bool), grad, hess, weight, jnp.float32(0.1))
    params = GrowParams(num_leaves=9, max_bin=64, min_data_in_leaf=20,
                        compact_inactive=compact_inactive)
    grow = ordered_grow.grow_tree_ordered
    want = grow(*args, params=params)
    calls = []

    def kernel(bin_lanes, dig_lanes, *a, **kw):
        calls.append(bin_lanes[0].shape[0])
        return lanes_kernel(bin_lanes, dig_lanes, *a, interpret=True, **kw)
    lanes_kernel = leafhist.digit_histogram_lanes
    monkeypatch.setattr(leafhist, "digit_histogram_lanes", kernel)
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    # the partition stays the sort: this test is the histogram's
    monkeypatch.setattr(partition, "stable_partition",
                        partition.sort_partition)
    jax.clear_caches()                # or the scatter path's program answers
    got = grow(*args, params=params)
    jax.clear_caches()
    # the root (one window of whole kernel steps, or its size class under
    # bagging) and the one size class's child window (P/2 is P/8 here)
    assert calls == [8192, 4096], calls
    assert int(want[0].num_leaves) == 9
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
