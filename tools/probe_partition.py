"""The window partition on the live TPU: the counting kernel
(ops/partition.py ``segment_partition``) and, with ``--sort``, the stable
12-operand sort it replaced, side by side inside a data-dependent
``fori_loop`` (each step partitions the previous step's output on a bit
of its own data, so no dispatch repeats another and every timing ends
behind ``block_until_ready``; standalone dispatches lie).  Reports ms a
call and ns a row slot, with the kernel's XLA passes (the mask, the
slices of its output into lanes) inside the number, and first holds the
kernel to numpy's stable partition on the chip itself.

    chiprun -- python tools/probe_partition.py [--sort] [rows ...]

One instance of the sort compiles in 125 to 150 s on the chip at a
million rows and more (PERF.md, PR 31), the kernel in 1 to 3 s.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from lightgbm_tpu.ops import partition  # noqa: E402

LANES = 11        # 7 bin words + 3 digit words + row order at 28 features
VARIANTS = ((128, 64), (256, 32))       # sub-block, sub-blocks a grid step


def timed(name, rows, step, lanes, reps):
    @jax.jit
    def loop(lanes):
        def body(i, ls):
            # a bit of the data itself, another one every step
            is_left = ((ls[0] >> (i % 8)) & 1) == 1
            return step(ls, is_left)
        return jax.lax.fori_loop(0, reps, body, lanes)
    t0 = time.time()
    out = jax.block_until_ready(loop(lanes))              # compile + warm
    cold = time.time() - t0
    t0 = time.time()
    out = jax.block_until_ready(loop(out))
    dt = (time.time() - t0) / reps
    print(f"rows {rows:9d}  {name:28s} {dt * 1e3:9.3f} ms  "
          f"{dt / rows * 1e9:7.2f} ns/row slot   (first call {cold:.1f} s)",
          flush=True)
    return dt


def kernel(b, nsub):
    return lambda ls, m: partition.segment_partition(
        ls, m, sub_block=b, sub_blocks_per_step=nsub)


def main():
    args = [a for a in sys.argv[1:] if a != "--sort"]
    sizes = [int(a) for a in args] or [8192, 1 << 20, 1 << 22, 1 << 24]
    print(jax.devices()[0].device_kind, flush=True)
    rng = np.random.RandomState(0)
    for rows in sizes:
        host = rng.randint(-2**31, 2**31 - 1, (LANES, rows),
                           np.int64).astype(np.int32)
        left = rng.rand(rows) < 0.37
        lanes = tuple(jnp.asarray(h) for h in host)
        variants = [(b, n) for b, n in VARIANTS if rows // b >= n]
        for b, nsub in variants:
            got = jax.jit(kernel(b, nsub))(lanes, jnp.asarray(left))
            # every lane up to 4M rows, the first and the last beyond
            held = range(LANES) if rows <= 1 << 22 else (0, LANES - 1)
            same = all(np.array_equal(
                np.asarray(got[i]),
                np.concatenate([host[i][left], host[i][~left]]))
                for i in held)
            print(f"rows {rows:9d}  kernel b={b} T={b * nsub}: "
                  + ("equal to the stable partition" if same else "DIFFERS"),
                  flush=True)
        reps = 30 if rows <= 1 << 20 else 8
        if "--sort" in sys.argv:
            timed("sort, 12 operands", rows, partition.sort_partition, lanes,
                  reps)
        for b, nsub in variants:
            timed(f"kernel b={b} T={b * nsub}", rows, kernel(b, nsub), lanes,
                  reps)


if __name__ == "__main__":
    main()
