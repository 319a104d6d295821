"""Bisect the partition kernel's ~400us fixed cost: strip pieces, measure.

The hardware harness behind the ``tpu_part_chunk`` auto knob (rows per
partition compaction launch): the 1024-pallas / 2048-xla defaults are
the chunk points this bisect measured on v5e.
"""
import os
import sys
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from lightgbm_tpu import obs
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lightgbm_tpu.runtime import enable_compile_cache
enable_compile_cache()

N = 1 << 20
CH = 1024
SB = 256
W = 128
REPS = 254
ALIGN = 32

work = jnp.zeros((2, N, W), jnp.uint8)
table = jnp.zeros((1, 255), jnp.float32)


def make(variant):
    def kern(sref, w_in, tref, w_ref, lt_ref, tril, cin, pre, lstage, rstage,
             lfb, rfb, sem):
        f32 = jnp.float32
        src_plane = sref[0]
        start = sref[1]
        cnt = sref[2]
        feat = sref[3]
        dst_plane = 1 - src_plane
        lbase0 = (start // ALIGN) * ALIGN
        head = start - lbase0
        tot = head + cnt
        nchunks = (tot + CH - 1) // CH

        if variant >= 1:  # tril init
            row_i = jax.lax.broadcasted_iota(jnp.int32, (SB, SB), 0)
            col_i = jax.lax.broadcasted_iota(jnp.int32, (SB, SB), 1)
            tril[:] = jnp.clip(row_i - col_i, 0, 1).astype(f32) \
                .astype(jnp.bfloat16)

        if variant >= 2:  # prefill DMAs
            p0 = pltpu.make_async_copy(
                w_in.at[dst_plane, pl.ds(lbase0, ALIGN), :], pre.at[0],
                sem.at[2])
            p0.start()
            p0.wait()
            lstage[0:ALIGN, :] = pre[0].astype(jnp.int32).astype(f32)

        if variant >= 3:  # chunk loop: DMA in + trivial consume + DMA out
            def body(i, acc):
                slot = jax.lax.rem(i, 2)
                cp = pltpu.make_async_copy(
                    w_in.at[src_plane,
                            pl.ds(((start + i * CH) // ALIGN) * ALIGN, CH), :],
                    cin.at[slot], sem.at[slot])
                cp.start()
                cp.wait()
                if variant >= 4:  # u8 -> f32 convert
                    cf = cin[slot].astype(jnp.int32).astype(f32)
                    lstage[0:CH, :] = cf
                if variant >= 5:  # route: col extract + one-hot table
                    cf = lstage[0:CH, :]
                    lane_w = jax.lax.broadcasted_iota(jnp.int32, (CH, W), 1)
                    col = jnp.sum(jnp.where(lane_w == feat, cf, 0.0), axis=1,
                                  keepdims=True)
                    bin_l = jax.lax.broadcasted_iota(jnp.int32, (CH, 255), 1)
                    oh = (1 - jnp.clip(jnp.abs(bin_l - col.astype(jnp.int32)),
                                       0, 1)).astype(f32)
                    go = jnp.sum(oh * tref[:], axis=1, keepdims=True) > 0.5
                    acc = acc + jnp.sum(go.astype(jnp.int32))
                if variant >= 6:  # 4x perm matmuls + stage blends
                    cf = lstage[0:CH, :]
                    iota_sb8 = jax.lax.broadcasted_iota(
                        jnp.int32, (SB + 8, 1), 0)
                    for s in range(CH // SB):
                        sub = cf[s * SB:(s + 1) * SB]
                        flags = jnp.concatenate(
                            [jnp.ones((SB, 1), jnp.bfloat16),
                             jnp.zeros((SB, 1), jnp.bfloat16)], axis=1)
                        ranks = jax.lax.dot(tril[:], flags,
                                            preferred_element_type=f32)
                        dest = ranks[:, 0:1].astype(jnp.int32)
                        j_i = jax.lax.broadcasted_iota(
                            jnp.int32, (SB + 8, SB), 0)
                        perm = (1 - jnp.clip(
                            jnp.abs(j_i - dest.reshape(1, SB)), 0, 1)) \
                            .astype(f32).astype(jnp.bfloat16)
                        out = jax.lax.dot(perm, sub.astype(jnp.bfloat16),
                                          preferred_element_type=f32)
                        rstage[pl.ds(s * (SB + 8), SB + 8)] = out
                # write out one tile
                ob = rstage[0:CH, :].astype(jnp.int32).astype(jnp.uint8)
                lfb[0] = ob
                wr = pltpu.make_async_copy(
                    lfb.at[0],
                    w_ref.at[dst_plane,
                             pl.ds(((start + i * CH) // ALIGN) * ALIGN,
                                   CH), :],
                    sem.at[4])
                wr.start()
                wr.wait()
                return acc

            acc = jax.lax.fori_loop(0, nchunks, body, jnp.int32(0))
            lt_ref[0] = acc
        else:
            lt_ref[0] = cnt

    return kern


def bench(variant):
    kern = make(variant)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.HBM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=[pl.BlockSpec(memory_space=pltpu.HBM),
                   pl.BlockSpec(memory_space=pltpu.SMEM)],
        scratch_shapes=[
            pltpu.VMEM((SB, SB), jnp.bfloat16),
            pltpu.VMEM((2, CH, W), jnp.uint8),
            pltpu.VMEM((2, ALIGN, W), jnp.uint8),
            pltpu.VMEM((3 * CH, W), jnp.float32),
            pltpu.VMEM((3 * CH, W), jnp.float32),
            pltpu.VMEM((2, CH, W), jnp.uint8),
            pltpu.VMEM((2, CH, W), jnp.uint8),
            pltpu.SemaphoreType.DMA((8,)),
        ],
    )

    @jax.jit
    def chain(work, cnt):
        def body(i, carry):
            work, tot = carry
            scalars = jnp.stack([jax.lax.rem(i, 2), jnp.int32(CH),
                                 cnt, jax.lax.rem(i, 28)])
            w2, lt = pl.pallas_call(
                kern, name="part_bisect", grid_spec=grid_spec,
                out_shape=[jax.ShapeDtypeStruct(work.shape, work.dtype),
                           jax.ShapeDtypeStruct((1,), jnp.int32)],
                input_output_aliases={1: 0},
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("arbitrary",),
                    vmem_limit_bytes=100 * 1024 * 1024),
            )(scalars, work, table)
            return w2, tot + lt[0]
        return jax.lax.fori_loop(0, REPS, body, (work, jnp.int32(0)))

    for cnt in (256, 16384):
        obs.sync(chain(work, jnp.int32(cnt)))
        best = 1e9
        for _ in range(2):
            with obs.wall("part_bisect/variant", record=False) as w:
                obs.sync(chain(work, jnp.int32(cnt)))
            best = min(best, w.seconds)
        print("variant=%d cnt=%6d: %7.1f us/call" %
              (variant, cnt, best / REPS * 1e6))


for v in (0, 1, 2, 3, 4, 5, 6):
    bench(v)
