"""Bisect the REAL partition kernel's per-call fixed cost (post table fix).

Variants strip stages (results wrong for stripped ones — timing only).
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

import lightgbm_tpu.ops.partition as P

ALIGN = P.ALIGN
N = 1 << 20
CH = 1024
SB = 256
REPS = 254
W = 128

work = jnp.zeros((2, N + 4 * CH, W), jnp.uint8)


def make_kernel(ch, sb, width, *, do_prefill, do_chunks, do_sub, do_flush,
                do_drain, do_rmw):
    f32 = jnp.float32
    lcap = 2 * ch
    nsub = ch // sb

    def kern(sref, work_in, work_ref, lt_ref, tril, cin, pre, lstage, rstage,
             lfb, rfb, sem):
        src_plane = sref[0]
        start = sref[1]
        cnt = sref[2]
        feat = sref[3]
        dst_plane = 1 - src_plane

        def a32(x):
            return (x // ALIGN) * ALIGN

        lbase0 = (start // ALIGN) * ALIGN
        head_l = start - lbase0
        end = start + cnt
        rtop = ((end - 1) // ALIGN) * ALIGN
        rbase0 = rtop + ALIGN
        tail_r = rbase0 - end
        astart = lbase0
        head = head_l
        tot = head + cnt
        nchunks = (tot + ch - 1) // ch

        row_i = jax.lax.broadcasted_iota(jnp.int32, (sb, sb), 0)
        col_i = jax.lax.broadcasted_iota(jnp.int32, (sb, sb), 1)
        tril[:] = jnp.clip(row_i - col_i, 0, 1).astype(f32) \
            .astype(jnp.bfloat16)
        iota_sb = jax.lax.broadcasted_iota(jnp.int32, (sb, 1), 0)
        lane_w = jax.lax.broadcasted_iota(jnp.int32, (ch, width), 1)
        sub_i = jax.lax.broadcasted_iota(jnp.int32, (ch, 1), 0)

        if do_prefill:
            pl_in = pltpu.make_async_copy(
                work_in.at[dst_plane, pl.ds(lbase0, ALIGN), :], pre.at[0],
                sem.at[2])
            pl_in.start()
            pr_in = pltpu.make_async_copy(
                work_in.at[dst_plane, pl.ds(rtop, ALIGN), :], pre.at[1],
                sem.at[3])
            pr_in.start()

        def start_in(i, slot):
            pltpu.make_async_copy(
                work_in.at[src_plane, pl.ds(a32(astart + i * ch), ch), :],
                cin.at[slot], sem.at[slot]).start()

        start_in(0, 0)
        if do_prefill:
            pl_in.wait()
            lstage[0:ALIGN, :] = pre[0].astype(jnp.int32).astype(f32)
            pr_in.wait()
            rstage[ch - ALIGN:ch, :] = pre[1].astype(jnp.int32).astype(f32)

        def flush(stage, fb, flushed, left, sem_base):
            half = jax.lax.rem(flushed // ch, 2)
            slot = half
            nflush = flushed // ch

            @pl.when(nflush >= 2)
            def _():
                pltpu.make_async_copy(
                    fb.at[slot], work_ref.at[dst_plane, pl.ds(0, ch), :],
                    sem.at[sem_base + slot]).wait()
            hs = (half * ch // 8) * 8
            fb[slot] = stage[pl.ds(hs, ch)].astype(jnp.int32) \
                .astype(jnp.uint8)
            if left:
                at = a32(lbase0 + flushed)
            else:
                at = a32(rbase0 - flushed - ch)
            pltpu.make_async_copy(
                fb.at[slot], work_ref.at[dst_plane, pl.ds(at, ch), :],
                sem.at[sem_base + slot]).start()

        iota_sb8 = jax.lax.broadcasted_iota(jnp.int32, (sb + 8, 1), 0)

        def append(stage, out8, n_, ws, dlt, fill_sel_left):
            ws8 = (ws // 8) * 8
            win = stage[pl.ds(ws8, sb + 8)]
            if fill_sel_left:
                m = (iota_sb8 >= dlt) & (iota_sb8 < dlt + n_)
            else:
                m = (iota_sb8 >= dlt + sb - n_) & (iota_sb8 < dlt + sb)
            stage[pl.ds(ws8, sb + 8)] = jnp.where(m, out8, win)

            @pl.when(ws + sb > lcap)
            def _():
                ov = ws + sb - lcap
                stage[0:sb, :] = jnp.where(iota_sb < ov,
                                           stage[lcap:lcap + sb, :],
                                           stage[0:sb, :])

        def body(i, carry):
            p_l, p_r, fl_l, fl_r = carry
            slot = jax.lax.rem(i, 2)
            pltpu.make_async_copy(
                work_in.at[src_plane, pl.ds(a32(astart + i * ch), ch), :],
                cin.at[slot], sem.at[slot]).wait()

            @pl.when(i + 1 < nchunks)
            def _():
                start_in(i + 1, 1 - slot)

            cf = cin[slot].astype(jnp.int32).astype(f32)
            col = jnp.sum(jnp.where(lane_w == feat, cf, 0.0), axis=1,
                          keepdims=True)
            coli = col.astype(jnp.int32)
            word = jax.lax.shift_right_logical(coli, 5)
            wvals = jnp.zeros((ch, 1), jnp.int32)
            for w in range(P.TABLE_WORDS):
                wvals = jnp.where(word == w, sref[4 + w], wvals)
            bit = jnp.bitwise_and(coli, 31)
            go = jnp.bitwise_and(
                jax.lax.shift_right_logical(wvals, bit), 1) > 0
            pos = sub_i + i * ch
            valid = (pos >= head) & (pos < tot)

            if do_sub:
                for s in range(nsub):
                    sub = cf[s * sb:(s + 1) * sb]
                    gl = go[s * sb:(s + 1) * sb] & valid[s * sb:(s + 1) * sb]
                    gr = (~go[s * sb:(s + 1) * sb]) \
                        & valid[s * sb:(s + 1) * sb]
                    flags = jnp.concatenate(
                        [gl.astype(jnp.bfloat16), gr.astype(jnp.bfloat16)],
                        axis=1)
                    ranks = jax.lax.dot(tril[:], flags,
                                        preferred_element_type=f32)
                    nl = jnp.sum(gl.astype(jnp.int32))
                    nr = jnp.sum(gr.astype(jnp.int32))
                    lrank = ranks[:, 0:1].astype(jnp.int32)
                    rrank = ranks[:, 1:2].astype(jnp.int32)
                    ws_l = jax.lax.rem(p_l, lcap)
                    dlt_l = ws_l - (ws_l // 8) * 8
                    ws_r = jax.lax.rem(
                        ch - jax.lax.rem(p_r, lcap) - sb + 2 * lcap, lcap)
                    dlt_r = ws_r - (ws_r // 8) * 8
                    dest_l = jnp.where(gl, lrank + dlt_l, -1)
                    dest_r = jnp.where(gr, sb - 1 - rrank + dlt_r, -1)
                    j_i = jax.lax.broadcasted_iota(jnp.int32, (sb + 8, sb), 0)
                    perm_l = (1 - jnp.clip(jnp.abs(j_i - dest_l.reshape(1, sb)),
                                           0, 1)).astype(f32) \
                        .astype(jnp.bfloat16)
                    perm_r = (1 - jnp.clip(jnp.abs(j_i - dest_r.reshape(1, sb)),
                                           0, 1)).astype(f32) \
                        .astype(jnp.bfloat16)
                    sub_bf = sub.astype(jnp.bfloat16)
                    out_l = jax.lax.dot(perm_l, sub_bf,
                                        preferred_element_type=f32)
                    out_r = jax.lax.dot(perm_r, sub_bf,
                                        preferred_element_type=f32)
                    append(lstage, out_l, nl, ws_l, dlt_l, True)
                    p_l = p_l + nl
                    if do_flush:
                        @pl.when(p_l - fl_l >= ch)
                        def _():
                            flush(lstage, lfb, fl_l, True, 4)
                        fl_l = jnp.where(p_l - fl_l >= ch, fl_l + ch, fl_l)
                    append(rstage, out_r, nr, ws_r, dlt_r, False)
                    p_r = p_r + nr
                    if do_flush:
                        @pl.when(p_r - fl_r >= ch)
                        def _():
                            flush(rstage, rfb, fl_r, False, 6)
                        fl_r = jnp.where(p_r - fl_r >= ch, fl_r + ch, fl_r)
            return p_l, p_r, fl_l, fl_r

        if do_chunks:
            p_l, p_r, fl_l, fl_r = jax.lax.fori_loop(
                0, nchunks, body, (head_l, tail_r, jnp.int32(0), jnp.int32(0)))
        else:
            p_l, p_r, fl_l, fl_r = (head_l + cnt, tail_r, jnp.int32(0),
                                    jnp.int32(0))

        if do_drain:
            fill_l = p_l - fl_l
            fill_r = p_r - fl_r
            d = fill_l + fill_r
            dstart = lbase0 + fl_l
            for base, fl in ((4, fl_l), (6, fl_r)):
                nf = fl // ch
                for back in (1, 2):
                    @pl.when(nf >= back)
                    def _(base=base, nf=nf, back=back):
                        pltpu.make_async_copy(
                            lfb.at[jax.lax.rem(nf - back, 2)],
                            work_ref.at[dst_plane, pl.ds(0, ch), :],
                            sem.at[base + jax.lax.rem(nf - back, 2)]).wait()

            def read_circ(stage, qstart):
                qs = jax.lax.rem(jax.lax.rem(qstart, lcap) + lcap, lcap)
                qs8 = (qs // 8) * 8
                dlt = qs - qs8
                a = pltpu.roll(stage[pl.ds(qs8, ch + 8)], -dlt, 0)[:ch]
                b = stage[pl.ds(0, ch)]
                lim = lcap - qs
                rolled = pltpu.roll(b, lim, 0)
                return jnp.where(sub_i[:ch] < lim, a, rolled)

            qr0 = jax.lax.rem(ch - jax.lax.rem(p_r, lcap) + 2 * lcap, lcap)

            def drain_tile(o):
                lrows = read_circ(lstage, fl_l + o)
                rrows = read_circ(rstage, qr0 + (o - fill_l))
                off = sub_i[:ch] + o
                return jnp.where(off < fill_l, lrows, rrows)

            nfull = d // ch
            MAXT = 4

            def dbody(t, _):
                @pl.when(t < nfull)
                def _():
                    slot = jax.lax.rem(t, 2)

                    @pl.when(t >= 2)
                    def _():
                        pltpu.make_async_copy(
                            lfb.at[slot],
                            work_ref.at[dst_plane, pl.ds(0, ch), :],
                            sem.at[4 + slot]).wait()
                    lfb[slot] = drain_tile(t * ch).astype(jnp.int32) \
                        .astype(jnp.uint8)
                    pltpu.make_async_copy(
                        lfb.at[slot],
                        work_ref.at[dst_plane,
                                    pl.ds(a32(dstart + t * ch), ch), :],
                        sem.at[4 + slot]).start()
                return 0

            jax.lax.fori_loop(0, MAXT, dbody, 0)
            for back in range(1, 3):
                @pl.when(nfull >= back)
                def _(back=back):
                    pltpu.make_async_copy(
                        lfb.at[jax.lax.rem(nfull - back, 2)],
                        work_ref.at[dst_plane, pl.ds(0, ch), :],
                        sem.at[4 + jax.lax.rem(nfull - back, 2)]).wait()

            rem_ = d - nfull * ch
            if do_rmw:
                @pl.when(rem_ > 0)
                def _():
                    at = a32(dstart + d - ch)
                    rd = pltpu.make_async_copy(
                        work_in.at[dst_plane, pl.ds(at, ch), :], lfb.at[0],
                        sem.at[4])
                    rd.start()
                    rd.wait()
                    tile = drain_tile(d - ch)
                    old = lfb[0].astype(jnp.int32).astype(f32)
                    off = sub_i[:ch] + (d - ch)
                    keep_new = (off >= jnp.int32(nfull) * ch) & (off >= 0)
                    merged = jnp.where(keep_new, tile, old)
                    lfb[0] = merged.astype(jnp.int32).astype(jnp.uint8)
                    wr = pltpu.make_async_copy(
                        lfb.at[0], work_ref.at[dst_plane, pl.ds(at, ch), :],
                        sem.at[4])
                    wr.start()
                    wr.wait()
        else:
            # still must consume the in-flight input DMA semaphores? they
            # were waited in body; nothing outstanding unless flushes ran
            pass
        lt_ref[0] = p_l - head_l

    return kern


def bench(name, **flags):
    kern = make_kernel(CH, SB, W, **flags)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.HBM)],
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
            scalars = jnp.concatenate([
                jnp.stack([jax.lax.rem(i, 2), jnp.int32(2 * CH), cnt,
                           jax.lax.rem(i, 28)]),
                jnp.zeros((P.TABLE_WORDS,), jnp.int32)])
            w2, lt = pl.pallas_call(
                kern, name="part_bisect2", grid_spec=grid_spec,
                out_shape=[jax.ShapeDtypeStruct(work.shape, work.dtype),
                           jax.ShapeDtypeStruct((1,), jnp.int32)],
                input_output_aliases={1: 0},
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("arbitrary",),
                    vmem_limit_bytes=100 * 1024 * 1024),
            )(scalars, work)
            return w2, tot + lt[0]
        return jax.lax.fori_loop(0, REPS, body, (work, jnp.int32(0)))

    for cnt in (256,):
        obs.sync(chain(work, jnp.int32(cnt)))
        best = 1e9
        for _ in range(2):
            with obs.wall("part_bisect2/stage", record=False) as w:
                obs.sync(chain(work, jnp.int32(cnt)))
            best = min(best, w.seconds)
        print("%-44s cnt=%5d %8.1f us/call" % (name, cnt, best / REPS * 1e6))


full = dict(do_prefill=True, do_chunks=True, do_sub=True, do_flush=True,
            do_drain=True, do_rmw=True)
bench("full", **full)
bench("no rmw", **{**full, "do_rmw": False})
bench("no drain", **{**full, "do_drain": False, "do_rmw": False})
bench("no flush", **{**full, "do_flush": False, "do_drain": False,
                     "do_rmw": False})
bench("no sub", **{**full, "do_sub": False, "do_flush": False,
                   "do_drain": False, "do_rmw": False})
bench("no chunks", **{**full, "do_chunks": False, "do_sub": False,
                      "do_flush": False, "do_drain": False, "do_rmw": False})
bench("no prefill/chunks", do_prefill=False, do_chunks=False, do_sub=False,
      do_flush=False, do_drain=False, do_rmw=False)
