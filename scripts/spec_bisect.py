"""Which pallas_call spec feature costs ~350us/call?"""
import os
import sys

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


def bench(name, scratch, smem_out, semN, vlimit, dimsem, vmem_in):
    def kern(sref, w_in, tref, w_ref, lt_ref, *scr):
        if smem_out:
            lt_ref[0] = sref[2]
        else:
            lt_ref[...] = jnp.full((8, 128), sref[2], jnp.int32)

    out_specs = [pl.BlockSpec(memory_space=pltpu.HBM),
                 pl.BlockSpec(memory_space=pltpu.SMEM if smem_out
                              else pltpu.VMEM)]
    scratch_shapes = []
    if scratch:
        scratch_shapes = [
            pltpu.VMEM((256, 256), jnp.bfloat16),
            pltpu.VMEM((2, 1024, W), jnp.uint8),
            pltpu.VMEM((2, 32, W), jnp.uint8),
            pltpu.VMEM((3 * 1024, W), jnp.float32),
            pltpu.VMEM((3 * 1024, W), jnp.float32),
            pltpu.VMEM((2, 1024, W), jnp.uint8),
            pltpu.VMEM((2, 1024, W), jnp.uint8),
        ]
    if semN:
        scratch_shapes.append(pltpu.SemaphoreType.DMA((semN,)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.HBM),
                  pl.BlockSpec(memory_space=pltpu.VMEM if vmem_in
                               else pltpu.HBM)],
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    cp = {}
    if dimsem:
        cp["dimension_semantics"] = ("arbitrary",)
    if vlimit:
        cp["vmem_limit_bytes"] = 100 * 1024 * 1024

    @jax.jit
    def chain(work, cnt):
        def body(i, carry):
            work, tot = carry
            scalars = jnp.stack([jax.lax.rem(i, 2), jnp.int32(1024),
                                 cnt, jax.lax.rem(i, 28)])
            w2, lt = pl.pallas_call(
                kern, name="spec_bisect", grid_spec=grid_spec,
                out_shape=[jax.ShapeDtypeStruct(work.shape, work.dtype),
                           jax.ShapeDtypeStruct((1,) if smem_out else (8, 128),
                                              jnp.int32)],
                input_output_aliases={1: 0},
                compiler_params=pltpu.CompilerParams(**cp) if cp else None,
            )(scalars, work, table)
            return w2, tot + lt.reshape(-1)[0]
        return jax.lax.fori_loop(0, REPS, body, (work, jnp.int32(0)))

    obs.sync(chain(work, jnp.int32(256)))
    best = 1e9
    for _ in range(2):
        with obs.wall("spec_bisect/stage", record=False) as w:
            obs.sync(chain(work, jnp.int32(256)))
        best = min(best, w.seconds)
    print("%-44s %7.1f us/call" % (name, best / REPS * 1e6))


bench("bare (no scratch, vmem out, no sem)", False, False, 0, False, False, True)
bench("+ smem out", False, True, 0, False, False, True)
bench("+ dma sem(8)", False, True, 8, False, False, True)
bench("+ dimension_semantics", False, True, 8, False, True, True)
bench("+ vmem_limit", False, True, 8, False, True, True)
bench("+ big scratch", True, True, 8, True, True, True)
bench("scratch only", True, False, 0, False, False, True)
bench("sem only", False, False, 1, False, False, True)
