"""Isolate the 375us: start from the known-fast signature, add one diff at
a time."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from lightgbm_tpu import obs
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lightgbm_tpu.runtime import enable_compile_cache
enable_compile_cache()

REPS = 254
N = 1 << 20
W = 128
work = jnp.zeros((2, N, W), jnp.uint8)
table = jnp.zeros((1, 255), jnp.float32)


def bench(name, with_table, four_scalars, write_out2, use_dma):
    def kern(*refs):
        if with_table:
            sref, w_in, tref, w_ref, o_ref, sem = refs
        else:
            sref, w_in, w_ref, o_ref, sem = refs
        if write_out2:
            o_ref[...] = jnp.zeros((256, W), jnp.uint8)
        if use_dma:
            cp = pltpu.make_async_copy(w_in.at[0, pl.ds(0, 256), :],
                                       o_ref.at[...], sem)
            cp.start()
            cp.wait()

    in_specs = [pl.BlockSpec(memory_space=pltpu.HBM)]
    if with_table:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.VMEM))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec(memory_space=pltpu.HBM),
                   pl.BlockSpec(memory_space=pltpu.VMEM)],
        scratch_shapes=[pltpu.SemaphoreType.DMA],
    )

    @jax.jit
    def chain(work, cnt):
        def body(i, carry):
            work, acc = carry
            if four_scalars:
                scalars = jnp.stack([jax.lax.rem(i, 2), jnp.int32(1024),
                                     cnt, jax.lax.rem(i, 28)])
            else:
                scalars = jnp.stack([i.astype(jnp.int32)])
            args = (scalars, work, table) if with_table else (scalars, work)
            w2, o = pl.pallas_call(
                kern, name="spec_bisect2", grid_spec=grid_spec,
                out_shape=[jax.ShapeDtypeStruct(work.shape, work.dtype),
                           jax.ShapeDtypeStruct((256, W), jnp.uint8)],
                input_output_aliases={1: 0},
            )(*args)
            return w2, acc + jnp.sum(o.astype(jnp.int32))
        return jax.lax.fori_loop(0, REPS, body, (work, jnp.int32(0)))

    obs.sync(chain(work, jnp.int32(256)))
    best = 1e9
    for _ in range(2):
        with obs.wall("spec_bisect2/stage", record=False) as w:
            obs.sync(chain(work, jnp.int32(256)))
        best = min(best, w.seconds)
    print("%-48s %7.1f us/call" % (name, best / REPS * 1e6))


bench("fast baseline (dma copy, 1 scalar)", False, False, False, True)
bench("+ 4 scalars", False, True, False, True)
bench("+ table input", True, True, False, True)
bench("no dma, no write", False, False, False, False)
bench("no dma, write out2", False, False, True, False)
