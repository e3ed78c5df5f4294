"""The leaf-ordered grower's window histogram on the live TPU: the kernel
that reads the word lanes as they lie (ops/leafhist.py
``digit_histogram_lanes``) against the row-major kernel behind the XLA
feed it had until PR 33 (slice ten lanes, stack them on a new minor axis,
bitcast to bytes, mask the digit rows, write ``[Psz, 28]`` uint8 and
``[Psz, 9]`` int8 out for ``digit_histogram_pallas``), side by side inside
a data-dependent ``fori_loop``: each step's window starts where the
previous step's sums say, so no dispatch repeats another and every timing
ends behind ``block_until_ready`` (standalone dispatches lie).  Reports ms
a call and ns a row slot with the window's slices inside both numbers, and
first holds both to numpy's sums on the chip itself.

    chiprun -- python tools/probe_hist.py [rows ...]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from lightgbm_tpu.ops import leafhist  # noqa: E402
from lightgbm_tpu.ops import ordered_grow  # noqa: E402

F, B = 28, 255        # the cells' width: 7 bin lanes and 3 digit lanes


def window(lanes, start, rows):
    return tuple(jax.lax.dynamic_slice(x, (start,), (rows,)) for x in lanes)


def row_major_feed(bw, dw, start, first, scnt, rows):
    """``hist_window`` as it fed the row-major kernel until PR 33."""
    ch_bins = ordered_grow._unpack_words(window(bw, start, rows), F)
    ch_dig = jax.lax.bitcast_convert_type(
        ordered_grow._unpack_words(window(dw, start, rows), 9), jnp.int8)
    row = jnp.arange(rows, dtype=jnp.int32)[:, None]
    ch_dig = jnp.where((row >= first) & (row < first + scnt), ch_dig, 0)
    return leafhist.digit_histogram_pallas(ch_bins, ch_dig, B)


def word_lanes(bw, dw, start, first, scnt, rows):
    return leafhist.digit_histogram_lanes(
        window(bw, start, rows), window(dw, start, rows), first, scnt, F, B)


def numpy_sums(bins, digits, lo, hi):
    out = np.zeros((F, 9, B), np.int64)
    for f in range(F):
        for k in range(9):
            out[f, k] = np.bincount(bins[lo:hi, f], digits[lo:hi, k],
                                    minlength=B)[:B]
    return out


def timed(name, rows, hist, bw, dw, reps):
    total = bw[0].shape[0]
    scnt = jnp.int32(rows * 3 // 4)

    @jax.jit
    def loop(bw, dw, off):
        def body(i, carry):
            off, acc = carry
            start = jnp.minimum(off, total - rows)
            sums = hist(bw, dw, start, off - start, scnt, rows)
            # the next window starts where these sums say
            off = jnp.abs(sums[0, 0, 0] + sums[3, 8, 7] + i) % (total - scnt)
            return off, acc + sums
        return jax.lax.fori_loop(0, reps, body,
                                 (off, jnp.zeros((F, 9, B), jnp.int32)))
    t0 = time.time()
    off, _ = jax.block_until_ready(loop(bw, dw, jnp.int32(5)))  # compile
    cold = time.time() - t0
    t0 = time.time()
    jax.block_until_ready(loop(bw, dw, off))
    dt = (time.time() - t0) / reps
    print(f"rows {rows:9d}  {name:34s} {dt * 1e3:9.3f} ms  "
          f"{dt / rows * 1e9:7.2f} ns/row slot   (first call {cold:.1f} s)",
          flush=True)


def main(kernels=None):
    """``kernels``: name -> function of ``word_lanes``'s signature, for a
    variant under trial beside the committed two."""
    kernels = kernels or {"row-major kernel behind its feed": row_major_feed,
                          "word lanes as they lie": word_lanes}
    sizes = [int(a) for a in sys.argv[1:]] or [8192, 1 << 20, 1 << 22, 1 << 23]
    print(jax.devices()[0].device_kind, flush=True)
    rng = np.random.RandomState(0)
    for rows in sizes:
        total = 2 * rows
        bins = rng.randint(0, B, (total, F)).astype(np.uint8)
        bins[:4] = np.array([0, B - 1, 0, B - 1], np.uint8)[:, None]
        digits = rng.randint(-128, 128, (total, 9)).astype(np.int8)
        digits[:4] = np.array([-128, 127, 127, -128], np.int8)[:, None]
        bw = ordered_grow.pack_u8_words(jnp.asarray(bins))
        dw = ordered_grow.pack_u8_words(
            jax.lax.bitcast_convert_type(jnp.asarray(digits), jnp.uint8))
        # the segment in the middle of a window at the arrays' end, and
        # one that is its whole window at their start; numpy up to 4M rows
        for off, scnt in ((total - rows // 2 - 77, rows // 2), (0, rows)):
            start = min(off, total - rows)
            want = numpy_sums(bins, digits, off, off + scnt) \
                if rows <= 1 << 22 else None
            for name, hist in kernels.items():
                got = np.asarray(jax.jit(hist, static_argnums=5)(
                    bw, dw, jnp.int32(start), jnp.int32(off - start),
                    jnp.int32(scnt), rows))
                if want is None:
                    want, said = got, "taken as the reference past 4M rows"
                else:
                    said = "equal" if np.array_equal(got, want) \
                        else "DIFFERS"
                print(f"rows {rows:9d}  off {off:9d} scnt {scnt:9d}  "
                      f"{name}: {said}", flush=True)
        reps = 30 if rows <= 1 << 20 else 8
        for name, hist in kernels.items():
            timed(name, rows, hist, bw, dw, reps)


if __name__ == "__main__":
    main()
