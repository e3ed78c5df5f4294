"""Standalone TPU compile+timing probe for dynamic-grid hist kernel
variants.  Chained in-loop timing (each call consumes the last one's
output)."""

import sys
import time
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, ".")
from lightgbm_tpu.ops.ordered_grow import pack_u8_words  # noqa: E402

N = 1 << 20
F, B = 28, 256
W = 7


def make_variant(name, nb):
    if name == "laneconcat":
        def kernel(s_ref, *refs, nb=nb):
            bins_refs = refs[:W]
            dig_refs = refs[W:W + 3]
            out_ref, acc_ref = refs[W + 3], refs[W + 4]
            i = pl.program_id(0)

            @pl.when(i == 0)
            def _():
                acc_ref[:] = jnp.zeros_like(acc_ref)
            shift, scnt = s_ref[1], s_ref[2]
            row = jax.lax.broadcasted_iota(jnp.int32, (nb, 1), 0) + i * nb
            live = (row >= shift) & (row < shift + scnt)
            cols = []
            for j in range(9):
                b = (dig_refs[j // 4][:] >> (8 * (j % 4))) & 0xFF
                cols.append((b - ((b & 0x80) << 1))[:, None])
            dig = jnp.where(live, jnp.concatenate(cols, axis=1),
                            0).astype(jnp.int8)
            iota = jax.lax.broadcasted_iota(jnp.int32, (nb, B), 1)
            for f in range(F):
                b_f = ((bins_refs[f // 4][:] >> (8 * (f % 4))) & 0xFF)[:, None]
                onehot = (b_f == iota).astype(jnp.int8)
                part = jax.lax.dot_general(
                    dig, onehot, dimension_numbers=(((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)
                acc_ref[f] += part

            @pl.when(i == pl.num_programs(0) - 1)
            def _():
                out_ref[:] = acc_ref[:]
        return kernel
    if name == "subconcat_T":
        def kernel(s_ref, *refs, nb=nb):
            bins_refs = refs[:W]
            dig_refs = refs[W:W + 3]
            out_ref, acc_ref = refs[W + 3], refs[W + 4]
            i = pl.program_id(0)

            @pl.when(i == 0)
            def _():
                acc_ref[:] = jnp.zeros_like(acc_ref)
            shift, scnt = s_ref[1], s_ref[2]
            row = jax.lax.broadcasted_iota(jnp.int32, (1, nb), 1) + i * nb
            live = (row >= shift) & (row < shift + scnt)
            rows9 = []
            for j in range(9):
                b = (dig_refs[j // 4][:] >> (8 * (j % 4))) & 0xFF
                rows9.append((b - ((b & 0x80) << 1))[None, :])
            dig_t = jnp.where(live, jnp.concatenate(rows9, axis=0),
                              0).astype(jnp.int8)          # [9, nb]
            dig = dig_t.T                                   # [nb, 9]
            iota = jax.lax.broadcasted_iota(jnp.int32, (nb, B), 1)
            for f in range(F):
                b_f = ((bins_refs[f // 4][:] >> (8 * (f % 4))) & 0xFF)[:, None]
                onehot = (b_f == iota).astype(jnp.int8)
                part = jax.lax.dot_general(
                    dig, onehot, dimension_numbers=(((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)
                acc_ref[f] += part

            @pl.when(i == pl.num_programs(0) - 1)
            def _():
                out_ref[:] = acc_ref[:]
        return kernel
    if name == "digmat":
        # digits as a separate [S, 9] i8 2-D input (no in-kernel unpack)
        def kernel(s_ref, *refs, nb=nb):
            bins_refs = refs[:W]
            dig_ref = refs[W]
            out_ref, acc_ref = refs[W + 1], refs[W + 2]
            i = pl.program_id(0)

            @pl.when(i == 0)
            def _():
                acc_ref[:] = jnp.zeros_like(acc_ref)
            shift, scnt = s_ref[1], s_ref[2]
            row = jax.lax.broadcasted_iota(jnp.int32, (nb, 1), 0) + i * nb
            live = (row >= shift) & (row < shift + scnt)
            dig = jnp.where(live, dig_ref[:, :], 0)
            iota = jax.lax.broadcasted_iota(jnp.int32, (nb, B), 1)
            for f in range(F):
                b_f = ((bins_refs[f // 4][:] >> (8 * (f % 4))) & 0xFF)[:, None]
                onehot = (b_f == iota).astype(jnp.int8)
                part = jax.lax.dot_general(
                    dig, onehot, dimension_numbers=(((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)
                acc_ref[f] += part

            @pl.when(i == pl.num_programs(0) - 1)
            def _():
                out_ref[:] = acc_ref[:]
        return kernel
    raise ValueError(name)


def run(name, nb, with_dig_input):
    rng = np.random.RandomState(0)
    bins_rm = jnp.asarray(rng.randint(0, B - 1, size=(N, F)), jnp.uint8)
    digits = jnp.asarray(rng.randint(-128, 127, size=(N, 9)), jnp.int8)
    bw = jax.jit(pack_u8_words)(bins_rm)
    dw = jax.jit(pack_u8_words)(
        jax.lax.bitcast_convert_type(digits, jnp.uint8))
    kernel = make_variant(name, nb)

    n_in = W + (1 if with_dig_input else 3)
    in_specs = [pl.BlockSpec((nb,), lambda i, s: (s[0] + i,))
                for _ in range(W)]
    if with_dig_input:
        in_specs += [pl.BlockSpec((nb, 9), lambda i, s: (s[0] + i, 0))]
    else:
        in_specs += [pl.BlockSpec((nb,), lambda i, s: (s[0] + i,))
                     for _ in range(3)]

    @jax.jit
    def call(off, scnt, *ops):
        off0 = off // nb
        shift = off - off0 * nb
        nblocks = jnp.maximum((shift + scnt + nb - 1) // nb, 1)
        scalars = jnp.stack([off0, shift, scnt]).astype(jnp.int32)
        gs = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(nblocks,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((F, 9, B), lambda i, s: (0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((F, 9, B), jnp.int32)])
        return pl.pallas_call(
            kernel, grid_spec=gs,
            out_shape=jax.ShapeDtypeStruct((F, 9, B), jnp.int32))(
                scalars, *ops)

    ops = bw + ((digits,) if with_dig_input else dw)

    @jax.jit
    def loop(off):
        def body(k, carry):
            off, acc = carry
            o = call(off, jnp.int32(N // 2), *ops)
            return (o[0, 0, 0] % 128, acc + o[0, 0, 1])
        return jax.lax.fori_loop(0, 10, body, (off, jnp.int32(0)))

    try:
        t0 = time.time()
        r = jax.block_until_ready(loop(jnp.int32(5)))
        ct = time.time() - t0
        t0 = time.time()
        r = jax.block_until_ready(loop(r[0]))
        dt = (time.time() - t0) / 10
        rows = N // 2
        print(f"{name:14s} nb={nb:5d}: compile {ct:5.1f}s  "
              f"{dt * 1e3:7.2f} ms/call  {dt / rows * 1e9:6.2f} ns/row")
    except Exception as e:
        msg = str(e).split("\n")[0][:140]
        print(f"{name:14s} nb={nb:5d}: FAIL {msg}")


if __name__ == "__main__":
    for name, nb, wd in [("laneconcat", 2048, False),
                         ("laneconcat", 4096, False),
                         ("subconcat_T", 8192, False),
                         ("digmat", 8192, True),
                         ("digmat", 4096, True)]:
        run(name, nb, wd)
