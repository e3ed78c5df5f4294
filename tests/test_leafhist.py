"""Leaf-proportional integer histogram engine (ops/leafhist.py):
quantization round-trip, scatter/pallas parity, compaction, and the
exact-subtraction property that replaces the reference's f64 accumulators
(bin.h:25-27)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops import leafhist as lh


def _data(n=5000, f=6, b=64, seed=0):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    g = rng.normal(size=n).astype(np.float32) * 3
    h = rng.uniform(0.05, 0.3, size=n).astype(np.float32)
    w = (rng.uniform(size=n) < 0.8).astype(np.float32)
    return bins, g, h, w


def _ref_hist(bins, vals, b):
    """f64 numpy reference histogram [F, B, 3]."""
    f = bins.shape[1]
    out = np.zeros((f, b, 3), np.float64)
    for fi in range(f):
        for v in range(3):
            out[fi, :, v] = np.bincount(
                bins[:, fi].astype(np.int64),
                weights=vals[v].astype(np.float64), minlength=b)[:b]
    return out


def test_quantize_roundtrip():
    _, g, h, w = _data()
    scales = lh.compute_scales(jnp.asarray(g), jnp.asarray(h), jnp.asarray(w))
    digits = np.asarray(lh.quantize_digits(
        jnp.asarray(g), jnp.asarray(h), jnp.asarray(w), scales))
    assert digits.shape == (g.size, 9) and digits.dtype == np.int8
    sc = np.asarray(scales)
    for v, x in enumerate([g, h, w]):
        rec = (digits[:, 3 * v].astype(np.int64) * 65536
               + digits[:, 3 * v + 1].astype(np.int64) * 256
               + digits[:, 3 * v + 2]).astype(np.float64)
        rec = rec * sc[v] / (1 << lh.QBITS)
        np.testing.assert_allclose(rec, x, atol=sc[v] * 2.0**-lh.QBITS)


def test_digit_histogram_matches_f64_reference():
    b = 64
    bins, g, h, w = _data(b=b)
    scales = lh.compute_scales(jnp.asarray(g), jnp.asarray(h), jnp.asarray(w))
    digits = lh.quantize_digits(jnp.asarray(g), jnp.asarray(h),
                                jnp.asarray(w), scales)
    sums = lh.digit_histogram(jnp.asarray(bins), digits, b)
    hist = np.asarray(lh.combine_digit_sums(sums, scales))   # [F, B, 3]
    hist = hist.transpose(0, 2, 1)                           # [F, 3, B]
    ref = _ref_hist(bins, [g, h, w], b).transpose(0, 2, 1)
    np.testing.assert_allclose(hist, ref, atol=2e-4 * np.abs(ref).max())


def test_pallas_interpret_matches_scatter():
    b = 128
    bins, g, h, w = _data(n=4096, b=b)
    scales = lh.compute_scales(jnp.asarray(g), jnp.asarray(h), jnp.asarray(w))
    digits = lh.quantize_digits(jnp.asarray(g), jnp.asarray(h),
                                jnp.asarray(w), scales)
    via_scatter = np.asarray(
        lh.digit_histogram_scatter(jnp.asarray(bins), digits, b))
    via_pallas = np.asarray(lh.digit_histogram_pallas(
        jnp.asarray(bins), digits, b, n_blk=1024, interpret=True))
    # both are exact integer sums -> bit-identical
    np.testing.assert_array_equal(via_scatter, via_pallas)


def test_compact_rows():
    rng = np.random.RandomState(3)
    mask = jnp.asarray(rng.uniform(size=1000) < 0.3)
    idx, valid = lh.compact_rows(mask, 512)
    want = np.nonzero(np.asarray(mask))[0]
    got = np.asarray(idx)[np.asarray(valid)]
    np.testing.assert_array_equal(np.sort(got), want)


def test_leaf_histogram_sizes_and_subtraction_exactness():
    """Parent digit sums == left + right digit sums EXACTLY (int32), the
    property the reference needs f64 for."""
    b = 32
    n = 20000
    bins, g, h, w = _data(n=n, b=b, seed=7)
    leaf = (np.random.RandomState(1).uniform(size=n) < 0.23)
    scales = lh.compute_scales(jnp.asarray(g), jnp.asarray(h), jnp.asarray(w))
    digits = lh.quantize_digits(jnp.asarray(g), jnp.asarray(h),
                                jnp.asarray(w), scales)
    classes = lh.size_classes(n, min_size=1024)
    parent = lh.digit_histogram(jnp.asarray(bins), digits, b)
    small = lh.leaf_histogram(jnp.asarray(bins), digits, jnp.asarray(leaf),
                              jnp.asarray(leaf.sum(), jnp.int32), b, classes)
    large = lh.leaf_histogram(jnp.asarray(bins), digits, jnp.asarray(~leaf),
                              jnp.asarray((~leaf).sum(), jnp.int32), b,
                              classes)
    np.testing.assert_array_equal(np.asarray(parent),
                                  np.asarray(small) + np.asarray(large))
    # derived sibling == directly built sibling, exactly
    np.testing.assert_array_equal(np.asarray(parent) - np.asarray(small),
                                  np.asarray(large))


def test_size_classes():
    assert lh.size_classes(1_000_000) == (8192, 16384, 32768, 65536,
                                          131072, 262144, 524288)
    assert lh.size_classes(10000, min_size=1024) == (1024, 2048, 4096, 8192)
    assert lh.size_classes(100, min_size=8192) == (64,)


# --- the kernel over the leaf-ordered layout's word lanes ------------------
# rows of the lanes, window, features, max_bin, off, scnt, rows a grid step,
# what fills the rows
LANES_CASES = {
    "segment_at_the_windows_start":
        (8192, 4096, 6, 255, 0, 3001, 4096, "random"),
    "window_ends_with_the_arrays":
        (8192, 4096, 6, 255, 6000, 2192, 4096, "random"),
    "segment_inside_the_window":
        (8192, 4096, 6, 255, 5000, 700, 4096, "random"),
    "scnt_zero": (8192, 4096, 6, 255, 1234, 0, 4096, "random"),
    "scnt_is_the_window": (8192, 4096, 6, 255, 4096, 4096, 4096, "random"),
    "bins_of_0_and_255": (4096, 4096, 6, 256, 0, 4096, 4096, "bin_ends"),
    "digits_of_minus_128_and_127":
        (4096, 4096, 6, 255, 3, 4000, 4096, "digit_ends"),
    "max_bin_63": (4096, 4096, 6, 63, 100, 3000, 4096, "random"),
    "twenty_eight_features": (4096, 4096, 28, 255, 100, 3000, 4096, "random"),
    "hundred_thirty_six_features":
        (1024, 1024, 136, 255, 10, 1000, 1024, "random"),
    "several_grid_steps": (16384, 8192, 6, 255, 9000, 5000, 2048, "random"),
    "one_small_grid_step": (2048, 1024, 6, 255, 1000, 600, 1024, "random"),
}


@pytest.mark.parametrize("case", sorted(LANES_CASES))
def test_lanes_kernel_is_the_scatter_of_the_unpacked_rows(case):
    """``digit_histogram_lanes`` (interpreted) over a window of the word
    lanes, cut as ``ordered_grow.hist_window`` cuts it, equals
    ``digit_histogram_scatter`` of the unpacked rows with the rows outside
    the segment masked, exactly."""
    from lightgbm_tpu.ops import ordered_grow as og
    total, psz, f, max_bin, off, scnt, step, fill = LANES_CASES[case]
    rng = np.random.RandomState(len(case))
    bins = rng.randint(0, max_bin, size=(total, f)).astype(np.uint8)
    digits = rng.randint(-128, 128, size=(total, 9)).astype(np.int8)
    if fill == "bin_ends":
        bins = np.where(rng.rand(total, f) < 0.5, 0, 255).astype(np.uint8)
    if fill == "digit_ends":
        # bytes of 128 and over come back sign-extended
        digits = np.where(rng.rand(total, 9) < 0.5, -128, 127) \
            .astype(np.int8)
    bw = og.pack_u8_words(jnp.asarray(bins))
    dw = og.pack_u8_words(jax.lax.bitcast_convert_type(
        jnp.asarray(digits), jnp.uint8))
    assert len(bw) == -(-f // 4) and len(dw) == 3
    start = min(off, total - psz)
    first = off - start
    got = lh.digit_histogram_lanes(
        tuple(x[start:start + psz] for x in bw),
        tuple(x[start:start + psz] for x in dw),
        jnp.int32(first), jnp.int32(scnt), f, max_bin, step_rows=step,
        interpret=True)
    inside = (np.arange(total) >= off) & (np.arange(total) < off + scnt)
    want = lh.digit_histogram_scatter(
        jnp.asarray(bins), jnp.asarray(np.where(inside[:, None], digits, 0)),
        max_bin)
    assert got.shape == (f, 9, max_bin) and got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if scnt:
        assert np.asarray(got).any()
