"""Gradient/hessian histogram construction on the MXU.

TPU-native replacement for the reference's histogram kernels — the CPU
col-wise/row-wise paths (reference: src/io/dense_bin.hpp:98
ConstructHistogramInner, src/io/train_share_states.h:46) and the OpenCL/CUDA
kernels (src/treelearner/ocl/histogram256.cl,
src/treelearner/kernels/histogram_16_64_256.cu). Design:

- The binned matrix is dense ``(rows, features)`` int8/int16 in HBM. A
  histogram holds (sum_grad, sum_hess, count) float32 a (feature, bin):
  ``(features, max_bins, 3)`` from the dense builder's ``build_histogram``,
  channel-major ``(3, features, bins padded to 128)`` from every segment
  histogram (``hist_bins``). The count channel replaces the reference's
  hessian-derived ``cnt_factor`` trick (feature_histogram.hpp:316) exactly.
- Accumulation is a one-hot × (g,h,cnt) matmul: bins one-hot encodes to
  ``(chunk, F*B)`` and a single ``(F*B, chunk) @ (chunk, 3)`` contraction
  rides the MXU. TPUs have no fast scatter-add; this keeps the hot op a
  matmul (SURVEY.md §7 "Scatter-add histogram throughput").
- Rows are processed in chunks under ``lax.scan`` so the transient one-hot
  stays small; masking (leaf membership, bagging) is pre-multiplied into the
  (g,h,cnt) channels so the same kernel serves root and per-leaf histograms.
- float32 accumulation follows the reference GPU precedent
  (config.h gpu_use_dp=false default; docs/GPU-Performance.rst accuracy
  tables) rather than the CPU's double hist_t.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import runtime
from ..obs import trace_phase, track_jit


DEFAULT_CHUNK = 4096


def _hist_chunk(bins_c: jax.Array, ghc_c: jax.Array, num_bins: int,
                mxu_bf16: bool = False) -> jax.Array:
    """(chunk, F) int bins + (chunk, C) channels -> (F*B, C) partial histogram.

    Contraction order is (C, chunk) @ (chunk, F*B): the wide F*B axis sits on
    the MXU's 128-lane output dimension; the tiny channel axis pads only the
    sublane side. On TPU (``mxu_bf16``) the one-hot materializes in bfloat16
    (exact 0/1, half the bytes of the temporary) and the f32 channels split
    hi+lo so two bf16 MXU passes keep f32 accuracy; on CPU everything stays
    exact f32 for the test reference. Not bandwidth-bound on a v5e: no
    histogram of this file comes within two orders of magnitude of its HBM
    roofline (PERF.md, PR 29); the one-hot's elements are the cost.
    """
    chunk, num_feat = bins_c.shape
    iota = jnp.arange(num_bins, dtype=bins_c.dtype)
    onehot = (bins_c[:, :, None] == iota).reshape(chunk, num_feat * num_bins)
    if mxu_bf16:
        oh = onehot.astype(jnp.bfloat16)
        hi = jax.lax.optimization_barrier(ghc_c.astype(jnp.bfloat16))
        lo = (ghc_c - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        out = jax.lax.dot(hi.T, oh, preferred_element_type=jnp.float32)
        out = out + jax.lax.dot(lo.T, oh, preferred_element_type=jnp.float32)
        return out.T
    out = jax.lax.dot(ghc_c.astype(jnp.float32).T, onehot.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
    return out.T


def build_histogram(
    bins: jax.Array,
    ghc: jax.Array,
    num_bins: int,
    chunk: int = DEFAULT_CHUNK,
    mxu_bf16: bool = False,
) -> jax.Array:
    """Accumulate ``(F, num_bins, C)`` histogram of channel sums per bin.

    bins: (N, F) integer bin codes; ghc: (N, C) float32 channels, already
    masked/weighted (out-of-leaf and out-of-bag rows carry zeros).
    """
    n, num_feat = bins.shape
    c = ghc.shape[1]
    chunk = min(chunk, max(1, n))
    pad = (-n) % chunk
    if pad:
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
        ghc = jnp.pad(ghc, ((0, pad), (0, 0)))
    nchunks = (n + pad) // chunk
    if nchunks == 1:
        flat = _hist_chunk(bins, ghc, num_bins, mxu_bf16)
        return flat.reshape(num_feat, num_bins, c)

    bins_r = bins.reshape(nchunks, chunk, num_feat)
    ghc_r = ghc.reshape(nchunks, chunk, c)

    def body(acc, xs):
        b, g = xs
        return acc + _hist_chunk(b, g, num_bins, mxu_bf16), None

    acc0 = jnp.zeros((num_feat * num_bins, c), dtype=jnp.float32)
    acc, _ = jax.lax.scan(body, acc0, (bins_r, ghc_r))
    return acc.reshape(num_feat, num_bins, c)


def build_histogram_np(bins: np.ndarray, ghc: np.ndarray, num_bins: int) -> np.ndarray:
    """Reference host implementation (used by tests to validate the MXU path)."""
    n, num_feat = bins.shape
    c = ghc.shape[1]
    out = np.zeros((num_feat, num_bins, c), dtype=np.float64)
    for f in range(num_feat):
        for ch in range(c):
            out[f, :, ch] = np.bincount(bins[:, f], weights=ghc[:, ch], minlength=num_bins)
    return out.astype(np.float32)


@partial(jax.jit, static_argnames=("num_bins", "chunk", "mxu_bf16"))
def build_histogram_jit(bins, ghc, num_bins: int, chunk: int = DEFAULT_CHUNK,
                        mxu_bf16: bool = False):
    return build_histogram(bins, ghc, num_bins, chunk, mxu_bf16)


build_histogram_jit = track_jit("ops/build_histogram", build_histogram_jit)


# ---------------------------------------------------------------------------
# Segment histogram (partitioned learner path)
# ---------------------------------------------------------------------------
#
# With rows kept leaf-contiguous (ops/partition.py), a leaf histogram reads
# exactly the child's segment — the reference's O(rows_in_leaf) contract
# (dense_bin.hpp:98). The direct one-hot matmul wastes the MXU (3-wide
# output) and materializes (rows, F*B) one-hots; instead the bin id is
# decomposed b = lo_w*hi + lo and the histogram factorizes as
#   H[f,hi,lo,c] = sum_n HiOH[n,f,hi] * (LoOH[n,f,lo] * ch[n,c])
# — a feature-batched einsum whose operands are (rows, F, B/lo_w) and
# (rows, F, lo_w*NCH): far less materialization than the direct form.
# The split width trades the two operands against each other AND shapes
# the per-feature matmul: XLA lowers the batched einsum to one MXU pass per
# feature per 128 rows of the contraction, of which 64 x 20 (lo_w 4) or
# 32 x 40 (lo_w 8) of 128 x 128 cells carry work, and writes both operands
# through HBM temporaries every chunk. Measured in situ on a v5e (ledger,
# PR 28: smaller-child rows x features over the traced histogram scope):
# 0.190 ns per (row, feature) at F = 28 (lo_w 4, chunk 4096), 0.251 at
# F = 137 (lo_w 8, chunk 1024), 0.224 at F = 10 (lo_w 4, chunk 4096),
# whatever the width, the chunk and the factorisation: the passes and the
# temporaries bound it, not the FLOPs (7.8 % of a pass) and not the bytes
# (0.0016 ns). Packing g features into one einsum batch element to fill
# the pass LOSES here (0.31 / 0.43 ns standalone at F = 28 / 137 against
# 0.22 / 0.26: the g x larger off-diagonal product goes through HBM too;
# my chip run, PR 29). On a TPU with the planes layout the segment
# histogram therefore runs in the Pallas kernel below
# (hist_pallas_segment_planes); this einsum stays as the CPU / mesh path
# and as the tests' oracle. Auto choice here: 4 for F <= 64, 8 above.
# Exactness: bf16 (hi, lo) channel splits make every product exactly
# representable; the MXU accumulates f32 — the reference's GPU
# f32-histogram precedent (docs/GPU-Performance.rst); all widths are
# bit-identical.

LO_W = 16  # legacy default for callers that don't pick per-shape


def auto_lo_w(num_feat: int) -> int:
    return 4 if num_feat <= 64 else 8


def _split_bf16(x):
    # the barrier keeps XLA from folding the round-trip under
    # --xla_allow_excess_precision (which would simplify lo to zero)
    hi = jax.lax.optimization_barrier(x.astype(jnp.bfloat16))
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _mxu_dtype():
    """bf16 operands on TPU (MXU accumulates f32 — verified pair-exact);
    f32 elsewhere (XLA CPU accumulates bf16 dots in bf16, which would lose
    the pair correction)."""
    return jnp.bfloat16 if runtime.on_tpu() else jnp.float32


_LO_SHIFT = {2: 1, 4: 2, 8: 3, 16: 4}


def _hist16_chunk(cb, cgm, num_bins: int, exact: bool, lo_w: int = LO_W):
    """(C, F) u8 + (C, 3) f32 masked channels -> (F, SH, lo_w*NCH) f32."""
    dt = _mxu_dtype()
    sh = (num_bins + lo_w - 1) // lo_w
    hi = (cb >> _LO_SHIFT[lo_w]).astype(jnp.uint8)
    lo = (cb & (lo_w - 1)).astype(jnp.uint8)
    hi_oh = (hi[:, :, None] == jnp.arange(sh, dtype=jnp.uint8)) \
        .astype(dt)                                          # (C, F, SH)
    lo_oh = (lo[:, :, None] == jnp.arange(lo_w, dtype=jnp.uint8))
    if exact:
        g_hi, g_lo = _split_bf16(cgm[:, 0])
        h_hi, h_lo = _split_bf16(cgm[:, 1])
        ch = jnp.stack([g_hi, g_lo, h_hi, h_lo,
                        cgm[:, 2].astype(jnp.bfloat16)], axis=1)  # (C, 5)
    else:
        ch = cgm.astype(jnp.bfloat16)                        # (C, 3)
    nch = ch.shape[1]
    c, f = cb.shape
    log_ = (lo_oh[:, :, :, None].astype(dt)
            * ch[:, None, None, :].astype(dt)).reshape(c, f, lo_w * nch)
    return jnp.einsum("cfh,cfx->fhx", hi_oh, log_,
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# The shape a leaf's histogram has in the split loop
# ---------------------------------------------------------------------------
#
# CHANNEL-MAJOR with the bins on the lanes: (3, F, Bp) f32, the planes of
# (sum_grad, sum_hess, count), Bp = the bins padded to whole 128-lane tiles
# (zeros past num_bins). Every segment histogram of this file, the root
# histogram the resident pack folds in (ops/partition.py) and the
# learner's pool hold it, and ops/split.py scans it plane by plane. An
# (F, B, 3) array, channels minor, costs a v5e 0.35-0.56 ms for every op
# that touches 6.1 MB of it (its tiles pad 3 -> 128): 822 of
# epsilon.train's 1,340 ms an iteration went to such ops once (PERF.md
# section 6). The dense builder
# (build_histogram, learner.build_tree) keeps (F, B, C).


def hist_bins(num_bins: int) -> int:
    """Bins of the channel-major histogram: whole 128-lane tiles."""
    return -(-int(num_bins) // 128) * 128


def hist_planes(h: jax.Array) -> jax.Array:
    """(F, B, 3) -> the loop's (3, F, hist_bins(B))."""
    b = h.shape[1]
    return jnp.pad(jnp.moveaxis(h, -1, 0),
                   ((0, 0), (0, 0), (0, hist_bins(b) - b)))


def hist_fb3(h: jax.Array, num_bins: int) -> jax.Array:
    """The loop's (3, F, Bp) -> (F, num_bins, 3): the dense builder's shape,
    for the tests' oracles and the references."""
    return jnp.moveaxis(h[..., :num_bins], 0, -1)


def _hist16_combine(acc, num_bins: int, exact: bool, lo_w: int = LO_W):
    """(F, SH, lo_w*NCH) chunk accumulator -> (3, F, hist_bins(num_bins))."""
    f, sh, _ = acc.shape
    nch = 5 if exact else 3
    h = acc.reshape(f, sh, lo_w, nch).reshape(f, sh * lo_w, nch)[:, :num_bins]
    if exact:
        h = jnp.stack([h[..., 0] + h[..., 1],
                       h[..., 2] + h[..., 3], h[..., 4]], axis=0)
    else:
        h = jnp.moveaxis(h, -1, 0)
    return jnp.pad(h, ((0, 0), (0, 0), (0, hist_bins(num_bins) - num_bins)))


def _hist16_chunk_int8(cb, gq, hq, cnt, valid, num_bins: int,
                       lo_w: int = LO_W):
    """int8 quantized chunk: one-hot x int8 dots accumulate in int32 on the
    MXU at 2x bf16 peak with ~2.5x less operand materialization."""
    sh = (num_bins + lo_w - 1) // lo_w
    hi = (cb >> _LO_SHIFT[lo_w]).astype(jnp.uint8)
    lo = (cb & (lo_w - 1)).astype(jnp.uint8)
    hi_oh = (hi[:, :, None] == jnp.arange(sh, dtype=jnp.uint8)) \
        .astype(jnp.int8)                                    # (C, F, SH)
    lo_oh = (lo[:, :, None] == jnp.arange(lo_w, dtype=jnp.uint8))
    v = valid.astype(jnp.int8)
    ch = jnp.stack([gq.astype(jnp.int8) * v, hq.astype(jnp.int8) * v,
                    cnt.astype(jnp.int8) * v], axis=1)       # (C, 3)
    c, f = cb.shape
    log_ = (lo_oh[:, :, :, None].astype(jnp.int8)
            * ch[:, None, None, :]).reshape(c, f, lo_w * 3)
    return jnp.einsum("cfh,cfx->fhx", hi_oh, log_,
                      preferred_element_type=jnp.int32)


def hist16_segment_q(work: jax.Array, plane, start, cnt, gscale, hscale, *,
                     num_bins: int, num_feat: int,
                     chunk: int = 2048, lo_w: int = 0) -> jax.Array:
    """int8-quantized segment histogram -> dequantized (3, F, Bp) f32.

    work rows are (F + 3) u8: bins then int8 g, int8 h, u8 cnt
    (ops/partition.py pack_rows_quantized). int32 accumulation bounds rows
    at ~16M per leaf (127 * N < 2^31).
    """
    from .partition import unpack_ghq

    f = num_feat
    lo_w = lo_w or auto_lo_w(f)
    sh = (num_bins + lo_w - 1) // lo_w
    nchunks = (cnt + chunk - 1) // chunk
    width = work.shape[2]

    def body(i, acc):
        off = start + i * chunk
        cw = jax.lax.dynamic_slice(work, (plane, off, 0),
                                   (1, chunk, width))[0]
        cb = cw[:, :f]
        gq, hq, cq = unpack_ghq(cw, f)
        rows_left = cnt - i * chunk
        valid = jnp.arange(chunk, dtype=jnp.int32) < rows_left
        return acc + _hist16_chunk_int8(cb, gq, hq, cq, valid, num_bins, lo_w)

    acc = jax.lax.fori_loop(
        0, nchunks, body,
        jnp.zeros((f, sh, lo_w * 3), jnp.int32))
    h = acc.reshape(f, sh, lo_w, 3).reshape(f, sh * lo_w, 3)[:, :num_bins]
    scale = jnp.stack([1.0 / gscale, 1.0 / hscale,
                       jnp.float32(1.0)])
    return hist_planes(h.astype(jnp.float32) * scale[None, None, :])


# ---------------------------------------------------------------------------
# In-VMEM Pallas segment histogram
# ---------------------------------------------------------------------------
#
# The XLA einsum path below is compute-near-optimal per chunk (the one-hot
# builds are VPU-bound), but each dynamic-trip loop iteration drags ~7.7 us
# of parasitic fusions (operand copies, valid-mask broadcasts, accumulator
# shuffling — profiled: copy.216 / broadcast.2689 / broadcast_multiply /
# dynamic-slice fusions) plus XLA while-loop overhead. This kernel runs the
# SAME hi/lo factorization with the chunk loop, channel splits and (F, SH,
# lo_w*5) accumulator all resident in VMEM: HBM traffic is one streamed
# read of the segment, and per-chunk overhead is one double-buffered DMA.
# Accumulation order matches the einsum path chunk-for-chunk (bit-identical
# at the same chunk size). Reference analog: the OpenCL histogram kernels'
# local-memory accumulators (src/treelearner/ocl/histogram256.cl:600).
#
# Mosaic notes: u8 lane tiles force W % 128 == 0 (the partitioned work
# buffer guarantees it); f32 words re-assemble from their 4 bytes with
# MULTIPLIES (vector << by >= 16 miscompiles on this toolchain — measured);
# one dot per feature (SH, C) x (C, lo_w*5) — pair-batching features into
# M=128 doubles the MACs for the cross blocks and wins nothing.


def _hist_pallas_kernel(sref, work_in, work_ref, acc_ref, cin, acc_s, sem,
                        *, ch, width, num_feat, sh, lo_w, nch):
    # work_ref is never written: it exists so the buffer ALIASES through
    # this call. Without it, XLA materializes a defensive copy of the whole
    # work buffer before every histogram (the partition kernel donates the
    # same buffer in the same loop body) — measured +100 ms/iter at 2M rows.
    # acc accumulates in SCRATCH and DMAs to the HBM output at the end: an
    # ungridded VMEM-spec output (like a VMEM-spec input) drops the call
    # onto a ~0.45 ms/call slow dispatch path.
    f32 = jnp.float32
    i32 = jnp.int32
    plane = sref[0]
    start = sref[1]
    cnt = sref[2]
    F = num_feat

    astart = (start // 32) * 32
    head = start - astart
    tot = head + cnt
    nchunks = jnp.maximum((tot + ch - 1) // ch, 1)

    acc_s[...] = jnp.zeros((F * sh, lo_w * nch), f32)

    def start_in(i, slot):
        # the (x // 32) * 32 at the USE SITE is what lets Mosaic prove the
        # u8 DMA row offset 32-aligned; an unprovable offset silently takes
        # a ~10x slower DMA path (75 vs 7.5 us per 4096-row chunk, measured)
        at = ((astart + i * ch) // 32) * 32
        pltpu.make_async_copy(
            work_in.at[plane, pl.ds(at, ch), :],
            cin.at[slot], sem.at[slot]).start()

    start_in(0, 0)

    sub_i = jax.lax.broadcasted_iota(i32, (ch, 1), 0)
    iota_sh = jax.lax.broadcasted_iota(i32, (ch, sh), 1)
    jl = jax.lax.broadcasted_iota(i32, (ch, lo_w * nch), 1) // nch

    def word(gb, o):
        # f32 word from 4 u8 bytes; multiplies, not shifts (see above).
        # i32 overflow of the top byte wraps to the sign bits — exactly
        # the bit pattern the bitcast needs.
        return jax.lax.bitcast_convert_type(
            gb[:, o:o + 1] + gb[:, o + 1:o + 2] * 256
            + gb[:, o + 2:o + 3] * 65536
            + gb[:, o + 3:o + 4] * 16777216, f32)

    def body(i, carry):
        slot = jax.lax.rem(i, 2)
        at = ((astart + i * ch) // 32) * 32
        pltpu.make_async_copy(
            work_in.at[plane, pl.ds(at, ch), :],
            cin.at[slot], sem.at[slot]).wait()

        @pl.when(i + 1 < nchunks)
        def _():
            start_in(i + 1, 1 - slot)

        cw = cin[slot].astype(i32)                      # (CH, W)
        bi = cw[:, :F]
        hi = bi // lo_w
        lo = bi - hi * lo_w
        gb = cw[:, F:F + 12]
        pos = sub_i + i * ch
        valid = ((pos >= head) & (pos < tot)).astype(f32)
        g = word(gb, 0) * valid
        h = word(gb, 4) * valid
        c = word(gb, 8) * valid
        if nch == 5:
            g_hi = g.astype(jnp.bfloat16)
            g_lo = (g - g_hi.astype(f32)).astype(jnp.bfloat16)
            h_hi = h.astype(jnp.bfloat16)
            h_lo = (h - h_hi.astype(f32)).astype(jnp.bfloat16)
            chs = jnp.concatenate(
                [g_hi, g_lo, h_hi, h_lo, c.astype(jnp.bfloat16)], axis=1)
        else:
            chs = jnp.concatenate([g, h, c], axis=1).astype(jnp.bfloat16)
        tiled = jnp.concatenate([chs] * lo_w, axis=1)   # (CH, lo_w*nch)

        for f in range(F):
            hioh = (hi[:, f:f + 1] == iota_sh).astype(jnp.bfloat16)
            logf = jnp.where(lo[:, f:f + 1] == jl, tiled,
                             jnp.bfloat16(0))
            ps = jax.lax.dot_general(
                hioh, logf, (((0,), (0,)), ((), ())),
                preferred_element_type=f32)             # (SH, lo_w*nch)
            acc_s[f * sh:(f + 1) * sh, :] += ps
        return carry

    jax.lax.fori_loop(0, nchunks, body, 0)
    out_cp = pltpu.make_async_copy(acc_s, acc_ref, sem.at[0])
    out_cp.start()
    out_cp.wait()


def hist_pallas_segment(work: jax.Array, plane, start, cnt, *,
                        num_bins: int, num_feat: int, exact: bool = True,
                        chunk: int = 4096, lo_w: int = 0):
    """Pallas twin of :func:`hist16_segment` (same contract and the same
    chunk-major f32 accumulation order). Requires the pallas-partition work
    layout: width a multiple of 128, rows start 32-aligned +/- head.

    Returns ``(hist, work)`` — callers MUST continue with the returned work
    buffer: it is byte-identical but aliased through the call, which is what
    keeps XLA from copying the whole buffer defensively per histogram."""
    f = num_feat
    lo_w = lo_w or auto_lo_w(f)
    sh = (num_bins + lo_w - 1) // lo_w
    nch = 5 if exact else 3
    width = work.shape[2]
    if width % 128:
        raise ValueError("hist_pallas_segment needs 128-lane work rows")
    if chunk % 32:
        # a misaligned chunk silently breaks the (x // 32) * 32 DMA offset
        # re-derivation inside the kernel: rows between the aligned offset
        # and the true chunk start would be double-counted. Refuse loudly;
        # the learner gate (build_kwargs) surfaces this as a config error.
        raise ValueError(
            "hist_pallas_segment chunk must be a multiple of 32 "
            "(u8 sublane DMA tiles), got %d" % chunk)
    kern = partial(_hist_pallas_kernel, ch=chunk, width=width, num_feat=f,
                   sh=sh, lo_w=lo_w, nch=nch)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=[pl.BlockSpec(memory_space=pltpu.HBM),
                   pl.BlockSpec(memory_space=pltpu.HBM)],
        scratch_shapes=[
            pltpu.VMEM((2, chunk, width), jnp.uint8),
            pltpu.VMEM((f * sh, lo_w * nch), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    scalars = jnp.stack([plane.astype(jnp.int32), start.astype(jnp.int32),
                         cnt.astype(jnp.int32)])
    from .partition import _INTERPRET
    work_out, acc = pl.pallas_call(
        kern,
        name="hist_pallas_segment",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(work.shape, work.dtype),
                   jax.ShapeDtypeStruct((f * sh, lo_w * nch), jnp.float32)],
        input_output_aliases={1: 0},
        interpret=_INTERPRET,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024),
    )(scalars, work)
    h = _hist16_combine(acc.reshape(f, sh, lo_w * nch), num_bins, exact,
                        lo_w)
    return h, work_out


def hist16_segment(work: jax.Array, plane, start, cnt, *,
                   num_bins: int, num_feat: int, exact: bool = True,
                   chunk: int = 2048, lo_w: int = 0) -> jax.Array:
    """Histogram of physical rows [start, start+cnt) of ping-pong plane
    ``plane`` -> (3, F, Bp), channel-major (``hist_bins``).

    work: (2, Npad, F+12) u8 packed working buffers (ops/partition.py
    pack_rows): bins columns followed by (g, h, cnt) f32 bytes, already
    bagging-masked. plane/start/cnt are traced scalars; one compilation
    serves every leaf.
    """
    from .partition import unpack_ghc

    f = num_feat
    lo_w = lo_w or auto_lo_w(f)
    sh = (num_bins + lo_w - 1) // lo_w
    nch = 5 if exact else 3
    nchunks = (cnt + chunk - 1) // chunk
    width = work.shape[2]

    def body(i, acc):
        off = start + i * chunk
        cw = jax.lax.dynamic_slice(work, (plane, off, 0),
                                   (1, chunk, width))[0]
        cb = cw[:, :f]
        cg = unpack_ghc(cw, f)
        rows_left = cnt - i * chunk
        valid = jnp.arange(chunk, dtype=jnp.int32) < rows_left
        cgm = cg * valid[:, None].astype(jnp.float32)
        return acc + _hist16_chunk(cb, cgm, num_bins, exact, lo_w)

    # trace_phase: metadata-only op annotation for profiler/HLO attribution
    # (host-side spans refuse to record inside a jit trace)
    with trace_phase("lgbtpu/ops/hist16_segment"):
        acc = jax.lax.fori_loop(
            0, nchunks, body,
            jnp.zeros((f, sh, lo_w * nch), jnp.float32))
        return _hist16_combine(acc, num_bins, exact, lo_w)


# ---------------------------------------------------------------------------
# Planes (feature-major) layout
# ---------------------------------------------------------------------------
#
# Transposed twin of the segment path above for the (2, W, Npad) work
# buffer (ops/partition.py pack_planes): a chunk slice is (W, chunk) —
# each one-hot build reads a CONTIGUOUS per-feature row instead of a
# strided byte column, and rows sit on the 128-lane dim where the VPU
# compares run at full occupancy. Bit-identity with the rows path is a
# hard contract (tests/test_work_layout.py asserts identical trees): same
# chunk boundaries, same lo*nch+ch x-ordering, and the per-chunk einsum
# contracts over the same rows in the same f32 accumulation order — the
# transposed einsum is verified bit-identical on the CPU backend.


def _hist16_chunk_planes(cb, cgm, num_bins: int, exact: bool,
                         lo_w: int = LO_W):
    """(F, C) u8 bin planes + (3, C) f32 masked channel planes ->
    (F, SH, lo_w*NCH) f32. Transposed twin of :func:`_hist16_chunk`."""
    dt = _mxu_dtype()
    sh = (num_bins + lo_w - 1) // lo_w
    hi = (cb >> _LO_SHIFT[lo_w]).astype(jnp.uint8)
    lo = (cb & (lo_w - 1)).astype(jnp.uint8)
    hi_oh = (hi[:, None, :]
             == jnp.arange(sh, dtype=jnp.uint8)[None, :, None]) \
        .astype(dt)                                          # (F, SH, C)
    lo_oh = (lo[:, None, :]
             == jnp.arange(lo_w, dtype=jnp.uint8)[None, :, None])
    if exact:
        g_hi, g_lo = _split_bf16(cgm[0])
        h_hi, h_lo = _split_bf16(cgm[1])
        ch = jnp.stack([g_hi, g_lo, h_hi, h_lo,
                        cgm[2].astype(jnp.bfloat16)], axis=0)  # (5, C)
    else:
        ch = cgm.astype(jnp.bfloat16)                        # (3, C)
    nch = ch.shape[0]
    f, c = cb.shape
    log_ = (lo_oh[:, :, None, :].astype(dt)
            * ch[None, None, :, :].astype(dt)).reshape(f, lo_w * nch, c)
    return jnp.einsum("fhc,fxc->fhx", hi_oh, log_,
                      preferred_element_type=jnp.float32)


def hist16_segment_planes(work: jax.Array, plane, start, cnt, *,
                          num_bins: int, num_feat: int, exact: bool = True,
                          chunk: int = 2048, lo_w: int = 0) -> jax.Array:
    """Planes-layout twin of :func:`hist16_segment` — same contract, work is
    ``(2, W, Npad)`` u8 feature-major planes (ops/partition.py pack_planes):
    bins planes followed by 12 (g, h, cnt) f32-byte planes."""
    from .partition import unpack_ghc_planes

    f = num_feat
    lo_w = lo_w or auto_lo_w(f)
    sh = (num_bins + lo_w - 1) // lo_w
    nch = 5 if exact else 3
    nchunks = (cnt + chunk - 1) // chunk
    nplanes = work.shape[1]

    def body(i, acc):
        off = start + i * chunk
        cw = jax.lax.dynamic_slice(work, (plane, 0, off),
                                   (1, nplanes, chunk))[0]    # (W, CH)
        cb = cw[:f]
        cg = unpack_ghc_planes(cw, f)                         # (3, CH)
        rows_left = cnt - i * chunk
        valid = jnp.arange(chunk, dtype=jnp.int32) < rows_left
        cgm = cg * valid[None, :].astype(jnp.float32)
        return acc + _hist16_chunk_planes(cb, cgm, num_bins, exact, lo_w)

    with trace_phase("lgbtpu/ops/hist16_segment_planes"):
        acc = jax.lax.fori_loop(
            0, nchunks, body,
            jnp.zeros((f, sh, lo_w * nch), jnp.float32))
        return _hist16_combine(acc, num_bins, exact, lo_w)


def hist16_segment_resident(work: jax.Array, resident: jax.Array, plane,
                            start, cnt, *, num_bins: int, num_feat: int,
                            exact: bool = True, chunk: int = 2048,
                            lo_w: int = 0) -> jax.Array:
    """Resident-state twin of :func:`hist16_segment_planes`.

    ``work`` is the slim (2, W>=17, Npad) buffer (route | ridx x4 | g/h/c
    x12 planes); ``resident`` is the (F, Npad) bin-plane buffer in ORIGINAL
    row order. Per chunk the permuted row-index plane is decoded and the
    bin planes are gathered through it — a unit-stride take along the lane
    axis — reproducing the planes path's leaf-order bin bytes value-for-
    value. Chunk grid, valid masking and _hist16_chunk_planes accumulation
    order are identical, so histograms (and the trees built from them) stay
    bit-identical to ``tpu_work_layout=planes``.
    """
    from .partition import (RST_GH_OFF, RST_ROUTE, RST_WIDTH, _decode_ridx,
                            unpack_ghc_planes)

    f = num_feat
    lo_w = lo_w or auto_lo_w(f)
    sh = (num_bins + lo_w - 1) // lo_w
    nch = 5 if exact else 3
    nchunks = (cnt + chunk - 1) // chunk
    npad = work.shape[2]

    def body(i, acc):
        off = start + i * chunk
        cw = jax.lax.dynamic_slice(work, (plane, 0, off),
                                   (1, RST_WIDTH, chunk))[0]
        ridx = _decode_ridx(cw[RST_ROUTE:RST_GH_OFF], npad)
        cb = jnp.take(resident, ridx, axis=1)                 # (F, CH)
        cg = unpack_ghc_planes(cw, RST_GH_OFF)                # (3, CH)
        rows_left = cnt - i * chunk
        valid = jnp.arange(chunk, dtype=jnp.int32) < rows_left
        cgm = cg * valid[None, :].astype(jnp.float32)
        return acc + _hist16_chunk_planes(cb, cgm, num_bins, exact, lo_w)

    with trace_phase("lgbtpu/ops/hist16_segment_resident"):
        acc = jax.lax.fori_loop(
            0, nchunks, body,
            jnp.zeros((f, sh, lo_w * nch), jnp.float32))
        return _hist16_combine(acc, num_bins, exact, lo_w)


def _ceil_pow2(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length()


def planes_kernel_params(num_feat: int, num_bins: int, lo_w: int = 0,
                         chunk: int = 0):
    """Static shape of the planes kernel, from what it can observe:
    ``(lo_w, shp, g, chunk)``.

    bin = shp * l + m. The one-hots of the LOW digit m (``shp`` values: the
    bins over ``lo_w``, padded to a power of two >= 16, whole bf16 sublane
    tiles) of ``g`` consecutive features stack to one (g * shp = 128, chunk)
    MXU operand, so one pass of 128 rows serves g features, not one, and
    ``shp`` consecutive bins of a feature come out side by side on the
    lanes; the high digit l (``lo_w`` values) rides the channel operand.
    lo_w 4 (g = 2 at 256 bins) at every width: the digit x channel
    operand's rows (5 lo_w a feature) cost 2.5 x what the one-hot's (256 /
    lo_w) do, see the kernel. The chunk (lanes a DMA) is the caller's: the
    work buffer's guard must cover it (``learner.build_kwargs`` derives it
    from F, ``partition.work_spec`` the guard)."""
    lo_w = lo_w or 4
    shp = max(16, _ceil_pow2((num_bins + lo_w - 1) // lo_w))
    if shp > 128 or (128 // shp * lo_w) % 8:
        raise ValueError(
            "hist_pallas_segment_planes: lo_w=%d does not tile %d bins into "
            "128-row MXU operands" % (lo_w, num_bins))
    return lo_w, shp, 128 // shp, chunk or planes_kernel_chunk(num_feat)


# what the planes histogram kernel may take of a v5e's 128 MiB of VMEM, and
# the room its chunk-sized buffers get of it
PLANES_HIST_VMEM = 100 << 20
PLANES_HIST_CHUNK_BYTES = 24 << 20


def planes_kernel_chunk(num_feat: int) -> int:
    """Lanes a DMA of the planes kernel, from F; 0 where no chunk fits VMEM
    (``learner.build_kwargs`` then keeps the rows layout).

    v5e, my chip run, PR 29: standalone 8192 / 4096 / 2048 / 1024 read
    0.074 / 0.077 / 0.079 / 0.088 ns per (row, feature) at F = 28 and 0.057
    / 0.060 / 0.062 / 0.070 at F = 137; in situ higgs.train 457.0 at 8192
    against 467.5 at 4096, mslr.train 943.2 at 4096 against 986.0 at 1024.

    A chunk holds 10 B a plane-lane in VMEM (``cin`` twice, ``bins_s`` and
    the i32 chunk value) beside the (F / 2, 40, 128) f32 accumulator, 10 KB
    a feature, and the (3, F, 256) planes the kernel's last step makes of
    it, 3 KB a feature (PR 36). The chunk halves until the chunk's buffers
    are under 24 MiB (4096 to W = 608, 2048 to 1,216, 1024 to 2,432, ...)
    and, with the accumulator and the planes, under the kernel's limit less
    4 MiB. PR 33, standalone at
    F = 2,000 on segments of 399K / 25K / 1.5K rows: 4096 reads 38.4 / 3.60
    / 1.32 ms a call, 2048 41.2 / 3.61 / 1.14, 1024 47.4 / 3.91 / 1.17, 512
    59.1 / 4.58 / 1.23: a tree of 400,000 rows makes 254 segments, three
    quarters of them under 6K rows, and a segment pays whole chunks. F =
    300 and 500 keep 4096 (0.047 against 2048's 0.051 on long segments).
    The widest table that fits is F = 6,896 (8,734 before the planes)."""
    from .partition import work_spec
    if num_feat <= 64:
        return 8192
    w = work_spec(num_feat, False, "pallas", 0, 0, layout="planes")[1]
    acc = -(-num_feat // 2) * 40 * 128 * 4 + 8 * -(-num_feat // 8) * 3 * 256 * 4
    chunk = 4096
    while chunk >= 128 and (
            10 * w * chunk > PLANES_HIST_CHUNK_BYTES
            or acc + 10 * w * chunk > PLANES_HIST_VMEM - (4 << 20)):
        chunk //= 2
    return chunk if chunk >= 128 else 0


# the XLA einsum loop's two one-hot operands of one chunk, (chunk, F, SH)
# and (chunk, F, 5 lo_w) bf16, stay in VMEM (128 MiB on a v5e) while they
# are this small, and go through HBM past it
EINSUM_OPERAND_BYTES = 80 << 20


def einsum_chunk(num_feat: int) -> int:
    """Rows a pass of the XLA segment loops (``hist16_segment`` and its
    planes twin), from F alone. Measured on v5e (lo_w-tuned einsum):
    4096-row chunks win at F <= 64; wider matrices spill VMEM, 1024 is ~8%
    faster than 2048 at F = 137. Past that the chunk halves until the
    operands fit again: at F = 2,000 standalone 1024 / 512 / 256 / 128 / 64
    read 0.522 / 0.369 / 0.172 / 0.195 / 0.473 ns per (row, feature), 295 /
    147 / 74 / 37 / 18 MB of operands (my chip run, PR 32)."""
    if num_feat <= 64:
        return 4096
    lo_w = auto_lo_w(num_feat)
    row = num_feat * (256 // lo_w + 5 * lo_w) * 2       # bf16, 256 bins
    chunk = 1024
    while chunk > 128 and chunk * row > EINSUM_OPERAND_BYTES:
        chunk //= 2
    return chunk


def _hist_pallas_kernel_planes(sref, work_in, work_ref, hist_ref, cin, bins_s,
                               acc_s, out_s, sem, *, ch, num_feat, shp, lo_w,
                               g, nch, dt):
    # One chunk DMA is a contiguous (W, ch) lane slice of the plane-major
    # work buffer: bins arrive as whole per-feature sublane rows, the f32
    # channels re-assemble from 4 byte PLANES each. work_ref is never
    # written: it only keeps the donated buffer from being copied.
    #
    # bin = shp * l + m. Per chunk and per GROUP of g features one MXU
    # contraction over the chunk's rows (lanes):
    #   acc[(c, j, l), (j', m)] = sum_rows DigitCh[(c, j, l), row]
    #                                      * OneHot[(j', m), row]
    # whose g diagonal blocks (j == j') are the g features' histograms;
    # the off-diagonal cells are cells the pass computed anyway. The whole
    # product accumulates in VMEM. The last step (``finish``) takes the
    # diagonal and the channel pairs' sums there too and leaves the (3, F,
    # Bp) planes, bins on the lanes: shp consecutive bins of a feature sit
    # side by side in a row of acc already, so a feature's row of bins is
    # lo_w lane-shifted pieces, and no array with the channels or a digit
    # minor ever reaches HBM (6.1 MB a call at F = 2,000 where the
    # accumulator is 20.5; before PR 36 XLA took it apart in four ops of
    # 0.4-0.56 ms each, PERF.md section 6).
    #
    # Measured on a v5e (my chip run, PR 29), standalone on 1-2M-row
    # segments, ns per (row, feature): 0.077 / 0.060 / 0.120 at F = 28 /
    # 137 / 10 (lo_w 4, 4096 lanes a chunk) against the XLA loop's 0.224 /
    # 0.264 / 0.32 and the one-feature-a-pass kernel's 0.115 / 0.064 /
    # 0.167. A row of the digit x channel operand (the one the MXU streams)
    # costs ~0.0013 ns a row of data, a row of the one-hot (the one it
    # latches) ~0.0005: lo_w 4 (20 + 64 operand rows a feature) beats 8
    # (40 + 32: 0.087 / 0.071) and 16 (80 + 16: 0.16 / 0.13); which digit
    # of the bin rides which operand is free (the measurements were made
    # with the high digit latched). Building the
    # one-hot as packed bf16 pairs (one compare for two rows) and
    # assembling the channel words on the MXU changed nothing (0.075 /
    # 0.060): the element's arithmetic is not the cost. The chunk DMA alone
    # runs at 0.9 ns a row (W <= 64; 1.8 at W = 160), hidden behind the
    # groups everywhere but at F = 10.
    f32 = jnp.float32
    i32 = jnp.int32
    bf16 = jnp.bfloat16
    plane = sref[0]
    start = sref[1]
    cnt = sref[2]
    F = num_feat
    glw = g * lo_w
    shift = shp.bit_length() - 1

    astart = (start // 128) * 128
    head = start - astart
    tot = head + cnt
    nchunks = jnp.maximum((tot + ch - 1) // ch, 1)

    acc_s[...] = jnp.zeros(acc_s.shape, f32)

    def start_in(i, slot):
        # (x // 128) * 128 at the USE SITE proves the u8 lane-dim DMA
        # offset is whole 128-lane tiles
        at = ((astart + i * ch) // 128) * 128
        pltpu.make_async_copy(
            work_in.at[plane, :, pl.ds(at, ch)],
            cin.at[slot], sem.at[slot]).start()

    start_in(0, 0)

    lane_i = jax.lax.broadcasted_iota(i32, (1, ch), 1)
    iota_hi = jax.lax.broadcasted_iota(i32, (shp, ch), 0)
    iota_lo = jax.lax.broadcasted_iota(i32, (glw, ch), 0) & (lo_w - 1)
    sub8 = jax.lax.broadcasted_iota(i32, (8, ch), 0) // lo_w

    def word(gb, o):
        # f32 plane from its 4 u8 byte planes; multiplies, not shifts
        # (vector << by >= 16 miscompiles on this toolchain). i32 overflow
        # of the top byte wraps to the sign bits.
        return jax.lax.bitcast_convert_type(
            gb[o:o + 1] + gb[o + 1:o + 2] * 256
            + gb[o + 2:o + 3] * 65536
            + gb[o + 3:o + 4] * 16777216, f32)

    def lo_rows(lo8, j0):
        """(glw, ch): row j * lo_w + l holds feature j0 + j's high digit."""
        rows = [lo8[j0 + j:j0 + j + 1] for j in range(g)]
        if lo_w % 8 == 0:
            return jnp.concatenate(
                [jnp.broadcast_to(r, (lo_w, ch)) for r in rows], axis=0)
        per = 8 // lo_w                      # features a sublane tile
        tiles = []
        for t in range(0, g, per):
            v = jnp.broadcast_to(rows[t], (8, ch))
            for q in range(1, per):
                v = jnp.where(sub8 == q,
                              jnp.broadcast_to(rows[t + q], (8, ch)), v)
            tiles.append(v)
        return jnp.concatenate(tiles, axis=0)

    def group(hi8, lo8, j0, grp, chs):
        # features j0 .. j0 + g - 1 of an 8-feature block -> acc_s[grp]
        hioh = jnp.concatenate(
            [hi8[j0 + j:j0 + j + 1] == iota_hi for j in range(g)],
            axis=0).astype(dt)                          # (g * shp, ch)
        lomask = lo_rows(lo8, j0) == iota_lo            # (glw, ch)
        zero = jnp.zeros((), f32)
        loch = jnp.concatenate(
            [jnp.where(lomask, jnp.broadcast_to(c, (glw, ch)), zero)
             for c in chs], axis=0).astype(dt)          # (nch * glw, ch)
        acc_s[grp] += jax.lax.dot_general(
            loch, hioh, (((1,), (1,)), ((), ())),
            preferred_element_type=f32)                 # (nch*glw, g*shp)

    def digits(b8):
        # (m, l) of bin = shp * l + m (shifts under 16 are safe here)
        return b8 & (shp - 1), b8 >> shift

    nblk = F // 8
    per_blk = 8 // g
    tail = -(-(F - 8 * nblk) // g)                      # groups after nblk

    def body(i, carry):
        slot = jax.lax.rem(i, 2)
        at = ((astart + i * ch) // 128) * 128
        pltpu.make_async_copy(
            work_in.at[plane, :, pl.ds(at, ch)],
            cin.at[slot], sem.at[slot]).wait()

        @pl.when(i + 1 < nchunks)
        def _():
            start_in(i + 1, 1 - slot)

        cw = cin[slot].astype(i32)                      # (W, CH)
        bins_s[...] = cw[:bins_s.shape[0]]
        gb = cw[F:F + 12]
        pos = lane_i + i * ch
        valid = ((pos >= head) & (pos < tot)).astype(f32)
        g_ = word(gb, 0) * valid
        h_ = word(gb, 4) * valid
        c_ = word(gb, 8) * valid
        if nch == 5:
            # bf16 (hi, lo) pairs held as f32: every one-hot product is
            # exact, the MXU accumulates f32
            g_hi = g_.astype(bf16).astype(f32)
            h_hi = h_.astype(bf16).astype(f32)
            chs = [g_hi, (g_ - g_hi).astype(bf16).astype(f32),
                   h_hi, (h_ - h_hi).astype(bf16).astype(f32), c_]
        else:
            chs = [x.astype(bf16).astype(f32) for x in (g_, h_, c_)]

        def block(b, carry):
            hi8, lo8 = digits(bins_s[pl.ds(pl.multiple_of(b * 8, 8), 8), :])
            for q in range(per_blk):
                group(hi8, lo8, q * g, b * per_blk + q, chs)
            return carry

        if nblk:
            jax.lax.fori_loop(0, nblk, block, 0)
        if tail:
            hi8, lo8 = digits(bins_s[8 * nblk:8 * nblk + 8, :])
            for q in range(tail):
                group(hi8, lo8, q * g, nblk * per_blk + q, chs)
        return carry

    jax.lax.fori_loop(0, nchunks, body, 0)

    # ---- the planes of the accumulator, one 8-feature block a step ----
    bp = out_s.shape[2]
    seg = 128 // shp                    # pieces a 128-lane tile of bins
    # (sublane, piece) code of every cell of an (8, 128) tile
    code = jax.lax.broadcasted_iota(i32, (8, 128), 0) * seg \
        + jax.lax.broadcasted_iota(i32, (8, 128), 1) // shp

    def finish(b, nq):
        # features 8 b .. 8 b + g nq - 1 (groups b * per_blk ..) -> out_s
        tiles = [[jnp.zeros((8, 128), f32) for _ in range(bp // 128)]
                 for _ in range(3)]
        for q in range(nq):
            a = acc_s[b * per_blk + q]                  # (nch * glw, 128)
            if nch == 5:
                xs = [a[:glw] + a[glw:2 * glw],
                      a[2 * glw:3 * glw] + a[3 * glw:4 * glw], a[4 * glw:]]
            else:
                xs = [a[c * glw:(c + 1) * glw] for c in range(3)]
            for c, x in enumerate(xs):
                # piece (j, l): row j * lo_w + l, lanes shp * j .. + shp,
                # goes to row q * g + j, bins shp * l .. + shp: seg lane
                # shifts serve every piece of x
                rolled = [x] + [pltpu.roll(x, k * shp, 1)
                                for k in range(1, seg)]
                for j in range(g):
                    for l in range(min(lo_w, bp // shp)):
                        t, d = divmod(l, seg)
                        r = j * lo_w + l
                        row = jnp.broadcast_to(
                            rolled[(d - j) % seg][r:r + 1], (8, 128))
                        tiles[c][t] = jnp.where(
                            code == (q * g + j) * seg + d, row, tiles[c][t])
        at = b * 8 if isinstance(b, int) else pl.multiple_of(b * 8, 8)
        for c in range(3):
            for t in range(bp // 128):
                out_s[c, pl.ds(at, 8), t * 128:(t + 1) * 128] = tiles[c][t]

    if nblk:
        def fin(b, carry):
            finish(b, per_blk)
            return carry
        jax.lax.fori_loop(0, nblk, fin, 0)
    if tail:
        finish(nblk, tail)
    out_cp = pltpu.make_async_copy(out_s, hist_ref, sem.at[0])
    out_cp.start()
    out_cp.wait()


def hist_pallas_segment_planes(work: jax.Array, plane, start, cnt, *,
                               num_bins: int, num_feat: int,
                               exact: bool = True, chunk: int = 0,
                               lo_w: int = 0):
    """Pallas twin of :func:`hist16_segment_planes` for the (2, W, Npad)
    plane-major work buffer. Requires the planes pallas work layout: W a
    multiple of 32 sublanes, lane starts 128-aligned +/- head, chunk a
    multiple of 128.

    Returns ``(hist, work)``, ``hist`` the channel-major (3, F, Bp) planes
    (``hist_bins``) as the kernel wrote them — callers MUST continue with
    the returned work buffer: it is byte-identical but aliased through the
    call, which is what keeps XLA from copying the whole buffer defensively
    per histogram.
    Runs under the pallas interpreter off-TPU (LGBTPU_PALLAS_INTERPRET=1)
    with f32 operands. Same operands and f32 accumulation as the XLA path,
    another ORDER of additions (the MXU's grouping of the contraction):
    sums agree to rounding, counts exactly.
    """
    from .partition import _INTERPRET

    f = num_feat
    lo_w, shp, g, chunk = planes_kernel_params(f, num_bins, lo_w, chunk)
    nch = 5 if exact else 3
    nplanes = work.shape[1]
    if nplanes % 32:
        raise ValueError(
            "hist_pallas_segment_planes needs whole 32-sublane u8 plane "
            "tiles, got W=%d" % nplanes)
    if chunk % 128:
        # a misaligned chunk breaks the (x // 128) * 128 lane-offset
        # re-derivation inside the kernel (lanes between the aligned offset
        # and the true chunk start would be double-counted)
        raise ValueError(
            "hist_pallas_segment_planes chunk must be a multiple of 128 "
            "(lane DMA tiles), got %d" % chunk)
    ngrp = -(-f // g)
    fp8 = 8 * (-(-f // 8))
    if fp8 > nplanes:
        raise ValueError(
            "hist_pallas_segment_planes reads bins in blocks of 8 planes: "
            "F=%d needs W >= %d, got %d" % (f, fp8, nplanes))
    acc_shape = (ngrp, nch * g * lo_w, g * shp)
    out_shape = (3, fp8, hist_bins(num_bins))
    kern = partial(_hist_pallas_kernel_planes, ch=chunk, num_feat=f, shp=shp,
                   lo_w=lo_w, g=g, nch=nch, dt=_mxu_dtype())
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=[pl.BlockSpec(memory_space=pltpu.HBM),
                   pl.BlockSpec(memory_space=pltpu.HBM)],
        scratch_shapes=[
            pltpu.VMEM((2, nplanes, chunk), jnp.uint8),
            pltpu.VMEM((fp8, chunk), jnp.int32),
            pltpu.VMEM(acc_shape, jnp.float32),
            pltpu.VMEM(out_shape, jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    scalars = jnp.stack([plane.astype(jnp.int32), start.astype(jnp.int32),
                         cnt.astype(jnp.int32)])
    work_out, h = pl.pallas_call(
        kern,
        name="hist_pallas_segment_planes",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(work.shape, work.dtype),
                   jax.ShapeDtypeStruct(out_shape, jnp.float32)],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=PLANES_HIST_VMEM),
        interpret=_INTERPRET,
    )(scalars, work)
    # rows past F (the last block's) hold what the g/h/c byte planes read
    # as bins: cut off
    return h[:, :f], work_out
