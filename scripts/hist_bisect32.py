"""Device-profiled bisect of the pallas hist kernel's per-chunk cost."""
import collections
import glob
import gzip
import json
import os
import shutil
import sys
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lightgbm_tpu.runtime import enable_compile_cache
enable_compile_cache()

from lightgbm_tpu.ops.partition import pack_rows, work_spec

N = 2_000_000
F = 28
B = 255
CH = 4096
LO_W = 4
SH = (B + LO_W - 1) // LO_W
NCH = 5
REPS = int(os.environ.get("HREPS", 10))

rng = np.random.RandomState(0)
bins = rng.randint(0, B, size=(N, F)).astype(np.uint8)
ghc = rng.randn(N, 3).astype(np.float32)
guard, width = work_spec(F, False, "pallas", 1024, 4096)
pad = ((guard, guard), (0, 0))
w0 = pack_rows(jnp.pad(jnp.asarray(bins), pad), jnp.pad(jnp.asarray(ghc), pad))
w0 = jnp.pad(w0, ((0, 0), (0, width - w0.shape[1])))
work = jnp.stack([w0, jnp.zeros_like(w0)])


def make_kernel(variant):
    f32 = jnp.float32
    i32 = jnp.int32

    def kern(sref, work_in, acc_ref, cin, sem):
        plane = sref[0]
        start = sref[1]
        cnt = sref[2]
        astart = (start // 32) * 32
        head = start - astart
        tot = head + cnt
        nchunks = jnp.maximum((tot + CH - 1) // CH, 1)
        acc_ref[...] = jnp.zeros((F * SH, LO_W * NCH), f32)

        def start_in(i, slot):
            at = ((astart + i * CH) // 32) * 32
            pltpu.make_async_copy(
                work_in.at[plane, pl.ds(at, CH), :],
                cin.at[slot], sem.at[slot]).start()

        start_in(0, 0)
        sub_i = jax.lax.broadcasted_iota(i32, (CH, 1), 0)
        iota_sh = jax.lax.broadcasted_iota(i32, (CH, SH), 1)
        jl = jax.lax.broadcasted_iota(i32, (CH, LO_W * NCH), 1) // NCH

        def word(gb, o):
            return jax.lax.bitcast_convert_type(
                gb[:, o:o + 1] + gb[:, o + 1:o + 2] * 256
                + gb[:, o + 2:o + 3] * 65536
                + gb[:, o + 3:o + 4] * 16777216, f32)

        def body(i, carry):
            slot = jax.lax.rem(i, 2)
            at = ((astart + i * CH) // 32) * 32
            pltpu.make_async_copy(
                work_in.at[plane, pl.ds(at, CH), :],
                cin.at[slot], sem.at[slot]).wait()

            @pl.when(i + 1 < nchunks)
            def _():
                start_in(i + 1, 1 - slot)

            cw = cin[slot].astype(i32)
            bi = cw[:, :F]
            hi = bi // LO_W
            lo = bi - hi * LO_W
            gb = cw[:, F:F + 12]
            pos = sub_i + i * CH
            valid = ((pos >= head) & (pos < tot)).astype(f32)
            g = word(gb, 0) * valid
            h = word(gb, 4) * valid
            c = word(gb, 8) * valid
            g_hi = g.astype(jnp.bfloat16)
            g_lo = (g - g_hi.astype(f32)).astype(jnp.bfloat16)
            h_hi = h.astype(jnp.bfloat16)
            h_lo = (h - h_hi.astype(f32)).astype(jnp.bfloat16)
            chs = jnp.concatenate(
                [g_hi, g_lo, h_hi, h_lo, c.astype(jnp.bfloat16)], axis=1)
            tiled = jnp.concatenate([chs] * LO_W, axis=1)

            if variant == "preamble":
                acc_ref[0:8, 0:1] += jnp.sum(tiled[:, 0:1], axis=0,
                                             keepdims=True) \
                    + jnp.sum(hi[:, 0:1] + lo[:, 0:1], axis=0, keepdims=True) \
                    .astype(f32)
                return carry
            for f in range(F):
                hioh = (hi[:, f:f + 1] == iota_sh).astype(jnp.bfloat16)
                logf = jnp.where(lo[:, f:f + 1] == jl, tiled, jnp.bfloat16(0))
                if variant == "onehots":
                    acc_ref[0:8, 0:1] += (
                        jnp.sum(hioh[:, 0:1].astype(f32), axis=0,
                                keepdims=True)
                        + jnp.sum(logf[:, 0:1].astype(f32), axis=0,
                                  keepdims=True))
                    continue
                ps = jax.lax.dot_general(
                    hioh, logf, (((0,), (0,)), ((), ())),
                    preferred_element_type=f32)
                if variant == "dots":
                    acc_ref[0:8, 0:1] += ps[0:8, 0:1]
                else:
                    acc_ref[f * SH:(f + 1) * SH, :] += ps
            return carry

        jax.lax.fori_loop(0, nchunks, body, 0)

    return kern


import lightgbm_tpu.ops.histogram as H


def profile(variant):
    if variant == "real":
        kern = partial(H._hist_pallas_kernel, ch=CH, width=width, num_feat=F,
                       sh=SH, lo_w=LO_W, nch=NCH)
    else:
        kern = make_kernel(variant)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        scratch_shapes=[pltpu.VMEM((2, CH, width), jnp.uint8),
                        pltpu.SemaphoreType.DMA((2,))],
    )

    @jax.jit
    def chain(work):
        def body(i, acc):
            a, = pl.pallas_call(
                kern, name="hist_bisect32", grid_spec=grid_spec,
                out_shape=[jax.ShapeDtypeStruct((F * SH, LO_W * NCH),
                                                jnp.float32)],
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("arbitrary",),
                    vmem_limit_bytes=100 * 1024 * 1024),
            )(jnp.stack([jnp.int32(0), jnp.int32(guard), jnp.int32(N)]), work)
            return acc + a[0, 0] + i.astype(jnp.float32)
        return jax.lax.fori_loop(0, REPS, body, jnp.float32(0))

    jax.block_until_ready(chain(work))
    tdir = "/tmp/jaxtrace_hb"
    shutil.rmtree(tdir, ignore_errors=True)
    with jax.profiler.trace(tdir):
        jax.block_until_ready(chain(work))
    path = sorted(glob.glob(tdir + "/plugins/profile/*/*.trace.json.gz"))[-1]
    data = json.load(gzip.open(path, "rt"))
    events = data["traceEvents"]
    pids = {e["pid"]: e["args"].get("name", "") for e in events
            if e.get("ph") == "M" and e.get("name") == "process_name"}
    tot = collections.Counter()
    cnt = collections.Counter()
    for e in events:
        if e.get("ph") != "X":
            continue
        if "TPU" not in pids.get(e["pid"], ""):
            continue
        tot[e["name"]] += e.get("dur", 0)
        cnt[e["name"]] += 1
    best = max(((d, n) for n, d in tot.items() if "call" in n),
               default=(0, "?"))
    per_chunk = best[0] / REPS / ((N + CH - 1) // CH)
    print("%-10s kernel: %8.1f us/call  %6.2f us/chunk  %5.2f ns/row"
          % (variant, best[0] / REPS, per_chunk, best[0] / REPS / N * 1e3))


for v in (sys.argv[1:] or ["full", "preamble", "onehots", "dots"]):
    profile(v)
