"""Measure bare pallas_call launch overhead: trivial kernel chained 254x."""
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


def kern(x_ref, o_ref):
    o_ref[...] = x_ref[...] + 1.0


@jax.jit
def chain(x):
    def body(i, x):
        return pl.pallas_call(
            kern,
            name="launch_probe",
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        )(x)
    return jax.lax.fori_loop(0, REPS, body, x)


x = jnp.zeros((256, 128), jnp.float32)
obs.sync(chain(x))
best = 1e9
for _ in range(3):
    with obs.wall("pallas_launch/trivial", record=False) as w:
        obs.sync(chain(x))
    best = min(best, w.seconds)
print("trivial pallas: %.1f us/call" % (best / REPS * 1e6))


# same but as a plain XLA op for comparison
@jax.jit
def chain_xla(x):
    def body(i, x):
        return x + 1.0
    return jax.lax.fori_loop(0, REPS, body, x)


obs.sync(chain_xla(x))
best = 1e9
for _ in range(3):
    with obs.wall("pallas_launch/xla", record=False) as w:
        obs.sync(chain_xla(x))
    best = min(best, w.seconds)
print("plain XLA add: %.1f us/call" % (best / REPS * 1e6))

# trivial kernel with HBM work buffer + aliasing + scalar prefetch,
# mimicking the partition call signature
N = 1 << 21
work = jnp.zeros((2, N, 128), jnp.uint8)


def kern2(sref, w_in, w_ref, o_ref, sem):
    i = sref[0]
    cp = pltpu.make_async_copy(w_in.at[0, pl.ds(0, 256), :],
                               o_ref.at[...], sem)
    cp.start()
    cp.wait()


@jax.jit
def chain2(work):
    def body(i, carry):
        work, acc = carry
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=[pl.BlockSpec(memory_space=pltpu.HBM),
                       pl.BlockSpec(memory_space=pltpu.VMEM)],
            scratch_shapes=[pltpu.SemaphoreType.DMA],
        )
        w2, o = pl.pallas_call(
            kern2,
            name="launch_probe_grid",
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(work.shape, work.dtype),
                       jax.ShapeDtypeStruct((256, 128), jnp.uint8)],
            input_output_aliases={1: 0},
        )(jnp.stack([i.astype(jnp.int32)]), work)
        return w2, acc + jnp.sum(o.astype(jnp.int32))
    return jax.lax.fori_loop(0, REPS, body, (work, jnp.int32(0)))


obs.sync(chain2(work))
best = 1e9
for _ in range(3):
    with obs.wall("pallas_launch/hbm_alias", record=False) as w:
        obs.sync(chain2(work))
    best = min(best, w.seconds)
print("HBM+alias pallas: %.1f us/call" % (best / REPS * 1e6))
