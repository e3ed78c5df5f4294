"""De-risk the round-4 Mosaic partition kernel: does pltpu.roll compile
(static + dynamic shifts), and what does a bitonic-style chain of
28 x (roll + compare + 12 selects) cost per row?  In-loop chained timing
(each call's input is the previous call's output).  See PERF.md,
"Carried over", the counting-partition design."""

import sys
import time
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NB = 2048
WORDS = 12
STAGES = 28


def kernel(x_ref, out_ref):
    # x: [WORDS, NB] i32; emulate a stable-0/1-bitonic stage chain:
    # per stage: key roll + compare + per-word roll/select
    words = [x_ref[w, :] for w in range(WORDS)]
    key = words[0]
    for s in range(STAGES):
        shift = 1 << (s % 7)
        k_sh = pltpu.roll(key, shift, 0)
        take = k_sh < key
        new_words = []
        for w in range(WORDS):
            w_sh = pltpu.roll(words[w], shift, 0)
            new_words.append(jnp.where(take, w_sh, words[w]))
        words = new_words
        key = words[0]
    for w in range(WORDS):
        out_ref[w, :] = words[w]


def main():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randint(-2**31, 2**31 - 1, (WORDS, NB), np.int64)
                    .astype(np.int32))

    call = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec((WORDS, NB), lambda: (0, 0))],
        out_specs=pl.BlockSpec((WORDS, NB), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((WORDS, NB), jnp.int32),
    )

    @jax.jit
    def loop(x):
        def body(_, acc):
            return call(acc) ^ 1
        return jax.lax.fori_loop(0, 50, body, x)

    try:
        t0 = time.time()
        out = jax.block_until_ready(loop(x))
        print(f"compile+run {time.time() - t0:.1f}s")
        t0 = time.time()
        out = jax.block_until_ready(loop(out))
        dt = (time.time() - t0) / 50
        print(f"roll-chain kernel: {dt * 1e6:8.1f} us/block  "
              f"{dt / NB * 1e9:6.2f} ns/row "
              f"({STAGES} stages x {WORDS} words)")
    except Exception as e:
        print("FAIL:", str(e).split(chr(10))[0][:200])


if __name__ == "__main__":
    main()
