"""In-loop microbench of partition primitives on the live TPU.

Times each primitive inside a data-dependent fori_loop (output feeds the
next iteration's input), so no call repeats an earlier one and every
timing ends behind ``block_until_ready``.  Reports ns/row.
"""

import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

P = int(sys.argv[1]) if len(sys.argv) > 1 else 1 << 20
REPS = 30


def timeit(name, fn, *args):
    jfn = jax.jit(fn)
    out = jax.block_until_ready(jfn(*args))      # compile + warm
    # chain: the timed call's input is the warm call's OUTPUT, so the
    # timed dispatch is not a repeat of the warm one
    args2 = (out,) + args[1:]
    t0 = time.time()
    out = jax.block_until_ready(jfn(*args2))
    dt = (time.time() - t0) / REPS
    print(f"{name:28s} {dt * 1e3:8.3f} ms  {dt / P * 1e9:7.2f} ns/row")
    return out


rng = np.random.RandomState(0)
idx0 = jnp.asarray(rng.permutation(P).astype(np.int32))
key0 = jnp.asarray(rng.randint(0, 2, P).astype(np.uint8))
words = [jnp.asarray(rng.randint(-2**31, 2**31 - 1, P, np.int64)
                     .astype(np.int32)) for _ in range(11)]
mat_u8 = jnp.asarray(rng.randint(0, 255, (P, 28)).astype(np.uint8))
mat_w = jnp.stack(words, axis=1)  # [P, 11] i32


def loop(body):
    def fn(x, *rest):
        def step(_, c):
            return body(c, *rest)
        return jax.lax.fori_loop(0, REPS, step, x)
    return fn


# 2-op stable sort (u8 key + i32 payload)
timeit("sort2 (u8,i32)", loop(
    lambda i, k: jax.lax.sort((k, i), num_keys=1, is_stable=True)[1]),
    idx0, key0)

# 12-op stable sort (the round-2 partition)
def sort12(ws_key):
    k = ws_key[:P].astype(jnp.uint8)
    ops = (k,) + tuple(words)
    out = jax.lax.sort(ops, num_keys=1, is_stable=True)
    return out[1] + out[2]
timeit("sort12 (u8,11xi32)", loop(lambda i: sort12(i)), idx0)

# 1-D i32 gather
timeit("take1d i32", loop(lambda i: jnp.take(words[0], i) ^ i), idx0)

# 1-D i32 gather via [P,1] 2-D form
timeit("take2d [P,1] i32", loop(
    lambda i: jnp.take(words[0][:, None], i, axis=0)[:, 0] ^ i), idx0)

# 2-D row gather [P, 28] u8
timeit("take2d [P,28] u8", loop(
    lambda i: (jnp.take(mat_u8, i, axis=0)[:, 0].astype(jnp.int32) ^ i)),
    idx0)

# 2-D row gather [P, 11] i32
timeit("take2d [P,11] i32", loop(
    lambda i: jnp.take(mat_w, i, axis=0)[:, 0] ^ i), idx0)

# 11 x 1-D i32 gathers (permutation apply, word-major)
def apply_perm(i):
    acc = i
    for w in words:
        acc = acc ^ jnp.take(w, i)
    return acc
timeit("11x take1d i32", loop(apply_perm), idx0)

# scatter 1-D i32 (unique indices)
timeit("scatter1d i32", loop(
    lambda i: jnp.zeros(P, jnp.int32).at[i].set(i, unique_indices=True)),
    idx0)

# cumsum i32 (prefix pass reference)
timeit("cumsum i32", loop(lambda i: jnp.cumsum(i) ^ i), idx0)

# contiguous copy reference
timeit("copy i32", loop(lambda i: i + 1), idx0)
