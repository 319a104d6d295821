"""Leaf-contiguous row partition, the device analog of DataPartition.

The reference keeps per-leaf row-index lists and stably partitions the
parent's indices on every split (reference: src/treelearner/
data_partition.hpp:101 Split, via ParallelPartitionRunner, threading.h:22).
That contract — per-split work proportional to the PARENT leaf, histograms
proportional to the CHILD leaf — is what makes 255-leaf trees affordable;
an O(N)-per-split design pays ~num_leaves/log(num_leaves) times more.

TPU-native form: rows are kept PHYSICALLY grouped by leaf in a packed
working buffer, so the histogram kernel streams a contiguous segment with
zero gathers (TPU row-gathers measured ~60ns/row — unusable; contiguous
dynamic slices run at HBM bandwidth). The working row layout is

    [ bins u8 x F | g f32 as 4 bytes | h f32 | cnt f32 ]   -> (Npad, F+12) u8

one array, one dtype: a split is ONE dynamic_slice per chunk, one in-chunk
compaction, two blended writes. f32 channels ride the compaction matmul as
their four u8 bytes — each byte is an integer <= 255, exactly representable
in bf16, so a 0/1 permutation matmul moves rows bit-exactly.

A split stably partitions the parent's segment [start, start+cnt):

- chunks of CH rows are compacted in-register via a (CH, CH) permutation
  one-hot matmul (MXU), left rows to the chunk front, right rows to the
  chunk back;
- compacted chunks are written with two cursors (left ascending from
  ``start``, right descending from ``start+cnt``) into the OTHER buffer of
  a ping-pong pair — children flip parity, nothing is copied back. Writes
  are blended read-modify-writes that touch only the valid rows, so the
  result is exact with no variable-length writes anywhere. The right
  child's rows land chunk-reversed — leaf row order is insignificant
  (histograms are order-free; sub-splits re-partition).

All ops are dynamic_slice / dynamic_update_slice / small matmuls — plain
XLA, so the same code runs on TPU, on the CPU test mesh, and inside
shard_map for the distributed learners.

Buffers carry a CH-row guard region at BOTH ends (rows live in
[GUARD, GUARD + n)) so slice windows never clamp.
"""
from __future__ import annotations

import math
import os
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import trace_phase


# CPU-mesh validation hook: run the pallas kernels under the pallas
# interpreter (tests/test_work_layout.py). Kernels that read the dst plane
# through the ALIASED OUTPUT ref are bit-faithful under it (the interpreter
# honors input_output_aliases and performs DMAs at .start()).
_INTERPRET = os.environ.get("LGBTPU_PALLAS_INTERPRET", "") not in ("", "0")

DEFAULT_CH = 2048
GH_BYTES = 12   # g, h, cnt as f32 bytes
GH_BYTES_Q = 3  # quantized: g, h as int8 bits, cnt as u8

# Resident-state slim work buffer (tpu_resident_state): the bin planes stay
# put in ORIGINAL row order and the partition permutes only a route byte, an
# i32 row-index plane (4 byte-planes) and the g/h/c payload.
RST_ROUTE = 1                        # plane 0: split feature's bin byte
RST_RIDX = 4                         # planes 1..4: row index, LE byte planes
RST_GH_OFF = RST_ROUTE + RST_RIDX    # planes 5..16: g/h/c f32 bytes
RST_WIDTH = RST_GH_OFF + GH_BYTES


def guard_rows(ch: int = DEFAULT_CH) -> int:
    return ch


def pack_rows(bins: jax.Array, ghc: jax.Array) -> jax.Array:
    """(N, F) u8 + (N, 3) f32 -> (N, F+12) u8 packed working rows."""
    gb = jax.lax.bitcast_convert_type(ghc.astype(jnp.float32), jnp.uint8)
    return jnp.concatenate([bins, gb.reshape(ghc.shape[0], GH_BYTES)], axis=1)


def unpack_ghc(rows: jax.Array, num_feat: int) -> jax.Array:
    """(N, F+12) u8 packed rows -> (N, 3) f32 channels."""
    gb = rows[:, num_feat:num_feat + GH_BYTES].reshape(rows.shape[0], 3, 4)
    return jax.lax.bitcast_convert_type(gb, jnp.float32)


def pack_rows_quantized(bins: jax.Array, ghc: jax.Array, key: jax.Array,
                        gscale, hscale) -> jax.Array:
    """(N, F) u8 + (N, 3) f32 -> (N, F+3) u8 with int8-quantized gradients.

    Stochastic rounding (floor(x*scale + u), u ~ U[0,1)) keeps histogram
    sums unbiased — the LightGBM quantized-training recipe (NeurIPS'22;
    LightGBM 4.x use_quantized_grad) at 8 bits instead of 2-5.
    """
    n = ghc.shape[0]
    u = jax.random.uniform(key, (n, 2))
    gq = jnp.clip(jnp.floor(ghc[:, 0] * gscale + u[:, 0]), -127, 127) \
        .astype(jnp.int8)
    hq = jnp.clip(jnp.floor(ghc[:, 1] * hscale + u[:, 1]), -127, 127) \
        .astype(jnp.int8)
    cnt = ghc[:, 2].astype(jnp.uint8)
    qb = jnp.stack([jax.lax.bitcast_convert_type(gq, jnp.uint8),
                    jax.lax.bitcast_convert_type(hq, jnp.uint8), cnt], axis=1)
    return jnp.concatenate([bins, qb], axis=1)


def unpack_ghq(rows: jax.Array, num_feat: int):
    """(N, F+3) u8 packed rows -> int8 g, int8 h, u8 cnt columns."""
    gq = jax.lax.bitcast_convert_type(rows[:, num_feat], jnp.int8)
    hq = jax.lax.bitcast_convert_type(rows[:, num_feat + 1], jnp.int8)
    return gq, hq, rows[:, num_feat + 2]


def _compact_chunk(cw, go, valid):
    """Stable in-chunk compaction: left rows to the front, right rows to the
    back, invalid (out-of-segment) rows parked in the middle gap.

    cw: (CH, W) u8 packed rows; go/valid: (CH,) bool.
    Returns (cw', nl, nr).
    """
    ch = cw.shape[0]
    gl = go & valid
    gr = (~go) & valid
    # one fused (CH, 3) prefix scan instead of three (profiled: each scan
    # is a separate ~2 us reduce-window per chunk)
    flags = jnp.stack([gl, gr, ~valid], axis=1).astype(jnp.int32)
    ranks = jnp.cumsum(flags, axis=0) - flags
    lrank, rrank, irank = ranks[:, 0], ranks[:, 1], ranks[:, 2]
    nl = ranks[-1, 0] + flags[-1, 0]
    nr = ranks[-1, 1] + flags[-1, 1]
    dest = jnp.where(gl, lrank,
                     jnp.where(gr, ch - nr + rrank, nl + irank))
    # permutation one-hot: P[j, i] = (dest_i == j); compacted = P @ rows.
    # u8 payload bytes are integers <= 255: exact under a 0/1 bf16 matmul.
    iota = jnp.arange(ch, dtype=jnp.int32)
    perm = (dest[None, :] == iota[:, None]).astype(jnp.bfloat16)
    cw2 = jax.lax.dot(perm, cw.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)
    return cw2.astype(jnp.uint8), nl, nr


def partition_segment(
    work: jax.Array,     # (2, Npad, F+12) u8 ping-pong buffer pair
    src_plane: jax.Array,  # scalar i32 plane holding the parent's rows
    start: jax.Array,    # scalar i32 physical start (includes guard offset)
    cnt: jax.Array,      # scalar i32 physical rows in the segment
    feat: jax.Array,     # scalar i32 split feature
    go_left: jax.Array,  # (B,) bool bin routing table
    *,
    ch: int = DEFAULT_CH,
) -> Tuple[jax.Array, jax.Array]:
    """Stable-partition rows [start, start+cnt) of plane ``src_plane`` into
    plane ``1 - src_plane`` (children flip parity — the plane index is a
    traced scalar, so no lax.cond / buffer copy is ever needed).

    Returns (work, left_cnt): left child at [start, start+left_cnt),
    right child rows (unordered) at [start+left_cnt, start+cnt).
    """
    num_bin = go_left.shape[0]
    table = go_left.astype(jnp.float32)
    nchunks = (cnt + ch - 1) // ch
    width = work.shape[2]
    dst_plane = 1 - src_plane

    def body(i, carry):
        work, lcur, rcur = carry
        off = start + i * ch
        cw = jax.lax.dynamic_slice(work, (src_plane, off, 0),
                                   (1, ch, width))[0]
        col = jax.lax.dynamic_index_in_dim(cw, feat, axis=1,
                                           keepdims=False).astype(jnp.int32)
        # gather-free table lookup: one-hot contraction over the bin axis
        oh = (col[:, None] == jnp.arange(num_bin, dtype=jnp.int32)[None, :])
        go = (oh.astype(jnp.float32) @ table) > 0.5
        pos = off + jnp.arange(ch, dtype=jnp.int32)
        valid = pos < start + cnt
        cw2, nl, nr = _compact_chunk(cw, go, valid)

        # blended read-modify-writes touch only the valid rows: exact, no
        # branches (lax.cond here would force buffer copies and break XLA's
        # in-place aliasing of the fori carry)
        j = jnp.arange(ch, dtype=jnp.int32)[:, None]

        def blend_at(work, at, keep_left):
            cur = jax.lax.dynamic_slice(work, (dst_plane, at, 0),
                                        (1, ch, width))[0]
            m = (j < nl) if keep_left else (j >= ch - nr)
            return jax.lax.dynamic_update_slice(
                work, jnp.where(m, cw2, cur)[None], (dst_plane, at, 0))

        work = blend_at(work, lcur, True)
        work = blend_at(work, rcur - ch, False)
        return work, lcur + nl, rcur - nr

    with trace_phase("lgbtpu/ops/partition_segment"):
        work, lcur, _ = jax.lax.fori_loop(
            0, nchunks, body, (work, start, start + cnt))
        return work, lcur - start


# ---------------------------------------------------------------------------
# Transposed (W, N) work-plane layout
# ---------------------------------------------------------------------------
#
# The row-major buffer streams 128-lane rows of which only F+12 (~40) bytes
# are real — a ~3x lane-occupancy waste on every partition DMA and VPU
# convert (PERF.md wall-true attribution: partition is ~65% of the ~2.08
# ms/split cost). The planes layout stores the SAME packed bytes transposed,
#
#     work[p]: (W, Npad) u8 — plane w holds byte column w of every row
#
# so each 128-lane tile carries 128 rows of ONE byte column: no dead lanes.
# A segment is a contiguous LANE range; a split is still one dynamic slice
# per chunk + one compaction matmul + two blended writes, just transposed —
# and the compaction matmul contracts over W (~40) instead of the padded 128.
# Row identity per chunk (dest computation) matches _compact_chunk exactly,
# so the XLA planes path produces BIT-IDENTICAL trees to the rows path.


def pack_planes(bins: jax.Array, ghc: jax.Array) -> jax.Array:
    """(N, F) u8 + (N, 3) f32 -> (F+12, N) u8 plane-major working columns."""
    return pack_rows(bins, ghc).T


def unpack_ghc_planes(planes: jax.Array, num_feat: int) -> jax.Array:
    """(F+12, C) u8 planes -> (3, C) f32 channels."""
    gb = planes[num_feat:num_feat + GH_BYTES].reshape(3, 4, -1)
    return jax.lax.bitcast_convert_type(gb.transpose(0, 2, 1), jnp.float32)


def _compact_chunk_planes(cw, go, valid):
    """Transposed twin of :func:`_compact_chunk`: cw is (W, CH) planes;
    go/valid are (CH,) bool over the chunk's columns (rows of data).

    dest is computed identically, so the produced row ORDER matches the
    row-major path bit-for-bit (this is what makes trees bit-identical
    across layouts: f32 histogram accumulation order is preserved)."""
    ch = cw.shape[1]
    gl = go & valid
    gr = (~go) & valid
    flags = jnp.stack([gl, gr, ~valid], axis=1).astype(jnp.int32)
    ranks = jnp.cumsum(flags, axis=0) - flags
    lrank, rrank, irank = ranks[:, 0], ranks[:, 1], ranks[:, 2]
    nl = ranks[-1, 0] + flags[-1, 0]
    nr = ranks[-1, 1] + flags[-1, 1]
    dest = jnp.where(gl, lrank,
                     jnp.where(gr, ch - nr + rrank, nl + irank))
    # P[i, j] = (dest_i == j); compacted = planes @ P — the contraction runs
    # over the CH source columns, costing W*CH MACs/column (W ~ 40 real
    # bytes) instead of the rows path's 128-padded width
    iota = jnp.arange(ch, dtype=jnp.int32)
    perm = (dest[:, None] == iota[None, :]).astype(jnp.bfloat16)
    cw2 = jax.lax.dot(cw.astype(jnp.bfloat16), perm,
                      preferred_element_type=jnp.float32)
    return cw2.astype(jnp.uint8), nl, nr


def partition_segment_planes(
    work: jax.Array,     # (2, W, Npad) u8 ping-pong plane pair
    src_plane: jax.Array,
    start: jax.Array,    # scalar i32 physical start LANE (includes guard)
    cnt: jax.Array,
    feat: jax.Array,
    go_left: jax.Array,  # (B,) bool bin routing table
    *,
    ch: int = DEFAULT_CH,
) -> Tuple[jax.Array, jax.Array]:
    """Planes-layout :func:`partition_segment` (same contract, same row
    order — left child stable, right child chunk-reversed — bit-identical
    to the rows path)."""
    num_bin = go_left.shape[0]
    table = go_left.astype(jnp.float32)
    nchunks = (cnt + ch - 1) // ch
    w = work.shape[1]
    dst_plane = 1 - src_plane

    def body(i, carry):
        work, lcur, rcur = carry
        off = start + i * ch
        cw = jax.lax.dynamic_slice(work, (src_plane, 0, off),
                                   (1, w, ch))[0]           # (W, CH)
        col = jax.lax.dynamic_index_in_dim(cw, feat, axis=0,
                                           keepdims=False).astype(jnp.int32)
        oh = (col[:, None] == jnp.arange(num_bin, dtype=jnp.int32)[None, :])
        go = (oh.astype(jnp.float32) @ table) > 0.5
        pos = off + jnp.arange(ch, dtype=jnp.int32)
        valid = pos < start + cnt
        cw2, nl, nr = _compact_chunk_planes(cw, go, valid)

        j = jnp.arange(ch, dtype=jnp.int32)[None, :]

        def blend_at(work, at, keep_left):
            cur = jax.lax.dynamic_slice(work, (dst_plane, 0, at),
                                        (1, w, ch))[0]
            m = (j < nl) if keep_left else (j >= ch - nr)
            return jax.lax.dynamic_update_slice(
                work, jnp.where(m, cw2, cur)[None], (dst_plane, 0, at))

        work = blend_at(work, lcur, True)
        work = blend_at(work, rcur - ch, False)
        return work, lcur + nl, rcur - nr

    with trace_phase("lgbtpu/ops/partition_segment_planes"):
        work, lcur, _ = jax.lax.fori_loop(
            0, nchunks, body, (work, start, start + cnt))
        return work, lcur - start


def _write_planes_chunk(work, cb, cg, at):
    """Write a (chunk, F) bins + (chunk, 3) f32 row-major chunk into plane 0
    of the work buffer as its (F + 12, chunk) transposed columns at lane
    ``at``."""
    gb = jax.lax.bitcast_convert_type(cg, jnp.uint8) \
        .reshape(cb.shape[0], GH_BYTES)
    cw_t = jnp.concatenate([cb, gb], axis=1).T              # (W, chunk)
    return jax.lax.dynamic_update_slice(work, cw_t[None], (0, 0, at))


def pack_planes_write(work: jax.Array, bins: jax.Array, ghc: jax.Array,
                      guard, *, chunk: int) -> jax.Array:
    """The planes pack pass: ``work[0][:, guard : guard + n]`` gets the
    transposed planes of (bins, ghc) (:func:`pack_planes`), ``chunk`` rows
    a pass and the last ``n % chunk`` in one pass of their own, so nothing
    is padded and nothing is written outside the segment. Nothing else is
    computed: the learner builds the root histogram from the packed
    segment afterwards, with the segment histogram of its children."""
    n, f = bins.shape
    chunk = min(chunk, n)
    full = n // chunk

    def body(i, work):
        off = i * chunk
        cb = jax.lax.dynamic_slice(bins, (off, 0), (chunk, f))
        cg = jax.lax.dynamic_slice(ghc, (off, 0), (chunk, 3))
        return _write_planes_chunk(work, cb, cg, guard + off)

    work = jax.lax.fori_loop(0, full, body, work)
    if n % chunk:
        off = full * chunk
        work = _write_planes_chunk(work, bins[off:], ghc[off:], guard + off)
    return work


# ---------------------------------------------------------------------------
# Resident permuted state: partition a row-index plane, not the packed row
# ---------------------------------------------------------------------------
#
# The planes partition rewrites every plane of the work buffer per split —
# bins AND g/h/c. With tpu_resident_state the bin planes live ONCE in a
# (F, Npad) resident buffer in original row order, and the slim work buffer
# carries only [route | ridx x4 | g/h/c x12] = 17 planes. Before each
# partition a chunked gather pass writes the split feature's resident bin
# byte into the route plane (write_route_plane); partition_segment_planes
# and partition_segment_planes_fused then run UNCHANGED with feat=0,
# inheriting the Mosaic path (circular f32 stages, 128-aligned pure-write
# flushes, scalar-prefetched routing table) and — because the gathered
# route byte equals the leaf-order bin column value-for-value — the exact
# _compact_chunk_planes dest arithmetic, so trees stay bit-identical.
# Segment histograms gather the bin planes through the permuted row-index
# plane (hist16_segment_resident) with the planes path's chunking and f32
# accumulation order.


def resident_bin_planes(bins: jax.Array, guard, npad: int) -> jax.Array:
    """(N, F) u8 grouped bins -> (F, npad) u8 resident planes, original row
    i at lane guard + i. Written once per dataset; never re-partitioned."""
    res = jnp.zeros((bins.shape[1], npad), jnp.uint8)
    return jax.lax.dynamic_update_slice(res, bins.T, (0, guard))


def _decode_ridx(planes: jax.Array, npad: int) -> jax.Array:
    """(4, C) u8 LE byte-planes -> (C,) i32 row indices, clamped to the
    buffer. Lanes outside the live segment hold stale dst-parity bytes that
    can decode to anything (including negative i32); the clamp keeps the
    gather in bounds — every consumer valid-masks those lanes anyway."""
    b = planes.astype(jnp.int32)
    ridx = b[0] + b[1] * 256 + b[2] * 65536 + b[3] * 16777216
    return jnp.clip(ridx, 0, npad - 1)


def _encode_ridx(pos: jax.Array) -> jax.Array:
    """(C,) i32 -> (4, C) u8 little-endian byte planes."""
    sh = jnp.arange(RST_RIDX, dtype=jnp.int32)[:, None] * 8
    return ((pos[None, :] >> sh) & 255).astype(jnp.uint8)


def write_route_plane(work: jax.Array, resident: jax.Array, plane, start,
                      cnt, feat, *, ch: int = DEFAULT_CH) -> jax.Array:
    """Write the split feature's bin byte for each segment row into the
    route plane (plane 0) of the slim work buffer's ``plane`` parity.

    Decodes the permuted row-index planes on the SAME chunk grid the
    partition uses and gathers the feature's resident bin plane — the
    result is value-for-value the routing column the planes layout reads
    from its leaf-order work buffer, so the planes partition runs unchanged
    with feat=0. O(parent): ~6 bytes/row (4 ridx read + 1 gather read +
    1 route write) against the planes path's full-width read.
    """
    npad = work.shape[2]
    col = jax.lax.dynamic_index_in_dim(resident, feat, axis=0, keepdims=False)
    nchunks = (cnt + ch - 1) // ch

    def body(i, work):
        off = start + i * ch
        rb = jax.lax.dynamic_slice(work, (plane, RST_ROUTE, off),
                                   (1, RST_RIDX, ch))[0]
        route = jnp.take(col, _decode_ridx(rb, npad), axis=0)
        return jax.lax.dynamic_update_slice(
            work, route[None, None, :], (plane, 0, off))

    return jax.lax.fori_loop(0, nchunks, body, work)


def pack_resident_fold_root(work: jax.Array, bins: jax.Array, ghc: jax.Array,
                            guard, *, num_bins: int, exact: bool, chunk: int,
                            lo_w: int = 0):
    """Resident-state pack pass with the root histogram folded in.

    One chunked loop reads (bins, ghc) once and accumulates the root
    histogram from the SAME row-major chunk, so iteration 0 never re-reads
    the matrix: the chunk grid and _hist16_chunk accumulation order of
    hist16_segment_planes over the planes path's packed root segment, so
    the root histogram is bit-identical to that path's. Returns (work,
    (3, F, Bp) LOCAL root histogram; callers reduce it via comm.hist). It
    writes the SLIM planes: a zeroed route plane, row-index byte planes
    holding ABSOLUTE lane positions (guard offset included, so gathers need
    no offset arithmetic), and the g/h/c bytes. The bin planes live in the
    resident buffer and are never packed.
    """
    from .histogram import _hist16_chunk, _hist16_combine, auto_lo_w

    n, f = bins.shape
    lo_w = lo_w or auto_lo_w(f)
    sh = (num_bins + lo_w - 1) // lo_w
    nch = 5 if exact else 3
    nchunks = (n + chunk - 1) // chunk
    npc = nchunks * chunk
    binsp = jnp.pad(bins, ((0, npc - n), (0, 0)))
    ghcp = jnp.pad(ghc, ((0, npc - n), (0, 0)))

    def body(i, carry):
        work, acc = carry
        off = i * chunk
        cb = jax.lax.dynamic_slice(binsp, (off, 0), (chunk, f))
        cg = jax.lax.dynamic_slice(ghcp, (off, 0), (chunk, 3))
        valid = jnp.arange(chunk, dtype=jnp.int32) < n - off
        cgm = cg * valid[:, None].astype(jnp.float32)
        acc = acc + _hist16_chunk(cb, cgm, num_bins, exact, lo_w)
        pos = guard + off + jnp.arange(chunk, dtype=jnp.int32)
        gb = jax.lax.bitcast_convert_type(cg, jnp.uint8) \
            .reshape(chunk, GH_BYTES)
        cw_t = jnp.concatenate([jnp.zeros((RST_ROUTE, chunk), jnp.uint8),
                                _encode_ridx(pos), gb.T], axis=0)
        work = jax.lax.dynamic_update_slice(
            work, cw_t[None], (0, 0, guard + off))
        return work, acc

    work, acc = jax.lax.fori_loop(
        0, nchunks, body,
        (work, jnp.zeros((f, sh, lo_w * nch), jnp.float32)))
    return work, _hist16_combine(acc, num_bins, exact, lo_w)


# ---------------------------------------------------------------------------
# Fused Pallas kernel: the whole per-split pipeline in one device call
# ---------------------------------------------------------------------------
#
# partition_segment is ~10 XLA ops per chunk; at 2048-row chunks the fixed
# per-op cost (~19 us/chunk profiled) dominates the actual work (~4 us).
# A 255-leaf tree partitions ~5.6k chunks, so the op soup costs ~100 ms per
# tree at 2M rows — the single largest line in the round-2 profile. The
# Pallas kernel runs ONE call per split: an in-kernel chunk loop with
# manual HBM<->VMEM DMA and the route/rank/permute math on the MXU.
#
# v2 design (round 4; ~3x the v1 kernel, measured 1.7-2.4 vs 5-8 ns/row
# interleaved at the bench shape):
# - compaction permutation matmuls run per SB=256-row sub-block instead of
#   per CH-row chunk — the perm matmul costs SB*W MACs/row, so sub-blocks
#   cut the dominant MXU term ~4x;
# - left/right frontier rows accumulate in circular VMEM stages (2*CH
#   logical rows + CH of wrap margin) and flush to HBM as ALIGNED PURE
#   WRITES of whole CH-row tiles — v1 paid a read-modify-write of ~CH+32
#   rows on BOTH sides of every chunk plus a serializing lout.wait();
# - aligned-edge neighbor bytes prefill once per call; the final sub-CH
#   leftovers drain as full tiles plus one overlapping RMW tile.
# Row order inside a leaf is insignificant (histograms are order-free;
# sub-splits re-partition), so the kernel guarantees the row SET per side,
# byte-preserving neighbors outside [start, start+cnt).


ALIGN = 32  # Mosaic requires u8 DMA row offsets provably 32-aligned
PLANE_ALIGN = 128  # planes layout: lane-dim DMA offsets are whole 128-lane tiles
TABLE_WORDS = 8  # (B<=256,) bool routing table bit-packed into i32 scalars


def pack_table_bits(go_left: jax.Array) -> jax.Array:
    """(B,) bool -> (TABLE_WORDS,) i32 bit-packed (bit b of word w = bin
    32*w + b). Rides the kernel's scalar prefetch — full-array VMEM-spec
    pallas inputs trigger a device-wide ~400 us/op dispatch mode."""
    b = go_left.shape[0]
    bits = go_left
    if b < 32 * TABLE_WORDS:
        bits = jnp.pad(bits, (0, 32 * TABLE_WORDS - b))
    bits = bits.reshape(TABLE_WORDS, 32).astype(jnp.int32)
    weights = jnp.left_shift(jnp.int32(1), jnp.arange(32, dtype=jnp.int32))
    return jnp.sum(bits * weights[None, :], axis=1, dtype=jnp.int32)


def work_spec(num_groups: int, quantized: bool, part_kernel: str,
              part_chunk: int, hist_chunk: int, layout: str = "rows"):
    """(guard, width) of the packed ping-pong working buffer.

    Single source of truth shared by the tree builder and the fused
    trainer's carried-buffer allocation. Row-major layout: ``width`` is the
    packed row width (the fused pallas kernel needs 128-lane rows and
    guards covering aligned windows up to ALIGN rows past a segment).
    Planes layout: ``width`` is the PLANE count (sublane dim of the
    (2, W, Npad) buffer; the pallas kernel needs whole 32-sublane u8 tiles
    and guards covering 128-lane-aligned windows — see planes_npad for the
    lane-dim padding).
    """
    width = num_groups + (GH_BYTES_Q if quantized else GH_BYTES)
    guard = max(part_chunk, hist_chunk)
    if layout in ("planes", "resident"):
        if layout == "resident":
            width = RST_WIDTH    # slim payload; bin planes live elsewhere
        if part_kernel == "pallas":
            width = 32 * ((width + 31) // 32)  # whole u8 sublane tiles
            guard += 2 * PLANE_ALIGN
        return guard, width
    if part_kernel == "pallas":
        width = 128 * ((width + 127) // 128)   # whole 128-lane DMA tiles
        guard += 2 * ALIGN
    return guard, width


def goss_compact_rows(n: int, top_rate: float, other_rate: float) -> int:
    """Static compact-row count M for GOSS device compaction.

    top_k rows survive deterministically; of the remaining ``rest`` each
    survives independently with p = other_rate / (1 - top_rate), so the
    surviving count is top_k + Binomial(rest, p). M adds a 4-sigma margin
    (+32 slack for tiny shapes) so the in-graph compact/dense cond takes
    the compact branch for essentially every draw; the rare overflow
    (and every GOSS warmup iteration, which samples ALL rows) falls back
    to the verbatim dense-mask path inside the same jitted graph. M is a
    pure function of (n, rates) — shapes stay bucket-stable and the
    zero-recompile contract holds.
    """
    top_k = max(1, int(n * top_rate))
    rest = max(0, n - top_k)
    p = min(1.0, other_rate / max(1e-12, 1.0 - top_rate))
    slack = 4.0 * math.sqrt(rest * p * (1.0 - p)) + 32.0
    return min(n, top_k + int(math.ceil(rest * p + slack)))


def compact_rows_by_inbag(bins: jax.Array, ghc: jax.Array, m: int):
    """Gather the first M in-bag rows (original relative order) to the top.

    Returns (bins[:M] packed, ghc[:M] packed, in-bag count C). The sort key
    is the integer ``row + n*outbag`` — distinct per row, so argsort is
    order-deterministic without relying on a stable-sort kwarg: in-bag rows
    first, each side in original row order. When C > M the gather is
    truncated (caller must take the dense branch — checked via C).
    """
    n = bins.shape[0]
    inbag = ghc[:, 2] > 0
    iota = jnp.arange(n, dtype=jnp.int32)
    order = jnp.argsort(jnp.where(inbag, iota, iota + n))
    idx = jax.lax.slice_in_dim(order, 0, m)
    return (jnp.take(bins, idx, axis=0), jnp.take(ghc, idx, axis=0),
            jnp.sum(inbag.astype(jnp.int32)))


def planes_part_chunk(row_w: int) -> int:
    """Lanes a chunk of the fused planes partition, from the packed row's
    bytes or its padded plane count W (the steps are multiples of 32, so
    both give the same answer). The sub-block stays 256 at every width.

    The kernel holds a chunk of every plane as one (W, chunk) f32 value.
    (1024, 256) is what the chip chose at W = 64 and W = 160 (PR 27) and
    what W = 320 and 512 were timed at (3.86 and 5.87 ns a row visit, PR
    33). At W = 2,016 (my chip run, PR 33, alone on 399K-row segments; a
    1,500-row segment in brackets, us a call), (chunk, SB): (2048, 256)
    23.4 ns a row visit [72.0], (1024, 512) 26.6 [91.6], (1024, 256) 21.9
    [60.9], (512, 256) 19.2 [53.5], (1024, 128) 19.6 [52.2], (512, 128)
    18.3 [47.1], (256, 256) 17.1 [46.6], (256, 128) 17.7 [44.1], (128, 128)
    19.1 [44.3]: the chunk value is 8 MB at 1024 lanes and the smaller one
    wins, down to the sub-block. So the chunk halves while W x chunk is
    over 512K elements (a 2 MiB value): 1024 to W = 512, 512 to 1,024, 256
    past it."""
    ch = 1024
    while ch > 256 and row_w * ch > (512 << 10):
        ch //= 2
    return ch


# lanes a pass of the write-only pack, and the bytes of W planes a pass
PACK_CHUNK_MAX = 1 << 17
PACK_CHUNK_BYTES = 32 << 20


def planes_pack_chunk(row_w: int) -> int:
    """Rows a pass of :func:`pack_planes_write`, from the padded plane
    count W: the largest power of two to 131,072 whose (W, chunk) pass
    stays within 32 MiB (131,072 to W = 256, 65,536 to 512, 16,384 at
    2,016). The write costs a fixed time a pass besides its bytes, so it
    falls as the pass grows, until the pass is some tens of MB: at W =
    384 it was least at 25 MB and rose at 50; at W = 2,016 it still fell
    at 33 MB and rose at 132 (66 was not timed); at W = 160 it still fell
    at 42 MB. The cap of 32 MiB was picked between those points and was
    not itself measured. The ceiling of 131,072 costs W = 64 and W = 160
    0.4 ms a tree against 262,144. The block's temporaries do not follow
    the chunk (the ``v5e:2x2`` compile, 4 to 32 MiB). Measured on a v5e,
    the write alone on device data at each cell's (N, W), ms a tree at
    chunks 4096 / 8192 / 16384 / 32768 / 65536 / 131072 / 262144: W = 64,
    16.8M rows -, 20.18, 15.52, 13.10 (12.90), 11.91 (11.57), 11.00
    (10.76), (10.35); W = 32, 13.75M: -, 12.67, 9.56, 7.86, 7.26, 6.83,
    -; W = 160, 5.448M: 19.74, 18.36, 14.04, 12.15 (12.10), 11.08
    (11.08), (10.49), (10.12); W = 384, 1M: 8.45, 7.89, 6.37, 5.71
    (5.74), 5.11 (5.18), (5.25), (5.64); W = 2,016, 400K (chunks 1024 /
    2048 / 4096 / 8192 / 16384 / 65536): 16.80, 16.22, 16.19, 15.45,
    12.29, 13.89. In brackets: a later run on another machine."""
    ch = PACK_CHUNK_MAX
    while ch > 128 and row_w * ch > PACK_CHUNK_BYTES:
        ch //= 2
    return ch


def planes_npad(n: int, guard: int, part_kernel: str = "xla") -> int:
    """Lane count of the planes work buffer: segment lanes + guards, padded
    to whole 128-lane tiles when the pallas kernel DMAs it."""
    npad = n + 2 * guard
    if part_kernel == "pallas":
        npad = 128 * ((npad + 127) // 128)
    return npad


def _partition_kernel(sref, work_in, work_ref, lt_ref,
                      tril, cin, pre, lstage, rstage, lfb, rfb, sem,
                      *, ch, sb, width, num_bin):
    f32 = jnp.float32
    lcap = 2 * ch
    nsub = ch // sb
    src_plane = sref[0]
    start = sref[1]
    cnt = sref[2]
    feat = sref[3]
    dst_plane = 1 - src_plane

    def a32(x):
        # Mosaic must PROVE u8 DMA row offsets divisible by the sublane
        # tiling; loop-carried multiples of 32 are not provable, so every
        # HBM offset is re-derived as (x // 32) * 32 at the use site.
        return (x // ALIGN) * ALIGN

    lbase0 = (start // ALIGN) * ALIGN
    head_l = start - lbase0                      # 0..31 neighbor rows below
    end = start + cnt
    rtop = ((end - 1) // ALIGN) * ALIGN          # rbase0 - ALIGN, provable
    rbase0 = rtop + ALIGN
    tail_r = rbase0 - end                        # 0..31 neighbor rows above

    astart = lbase0
    head = head_l
    tot = head + cnt
    nchunks = (tot + ch - 1) // ch

    # strict lower-triangular ones: ranks[i] = sum_{j<i} flags[j].
    # Arithmetic construction (clamped integer difference) — boolean
    # selects hit Mosaic relayout limits on i1 vectors.
    row_i = jax.lax.broadcasted_iota(jnp.int32, (sb, sb), 0)
    col_i = jax.lax.broadcasted_iota(jnp.int32, (sb, sb), 1)
    tril[:] = jnp.clip(row_i - col_i, 0, 1).astype(f32).astype(jnp.bfloat16)

    iota_sb = jax.lax.broadcasted_iota(jnp.int32, (sb, 1), 0)
    lane_w = jax.lax.broadcasted_iota(jnp.int32, (ch, width), 1)
    sub_i = jax.lax.broadcasted_iota(jnp.int32, (ch, 1), 0)

    # ---- prefills: neighbor rows of the aligned edge tiles ----
    pl_in = pltpu.make_async_copy(
        work_in.at[dst_plane, pl.ds(lbase0, ALIGN), :], pre.at[0], sem.at[2])
    pl_in.start()
    pr_in = pltpu.make_async_copy(
        work_in.at[dst_plane, pl.ds(rtop, ALIGN), :], pre.at[1], sem.at[3])
    pr_in.start()

    def start_in(i, slot):
        pltpu.make_async_copy(
            work_in.at[src_plane, pl.ds(a32(astart + i * ch), ch), :],
            cin.at[slot], sem.at[slot]).start()

    start_in(0, 0)

    pl_in.wait()
    lstage[0:ALIGN, :] = pre[0].astype(jnp.int32).astype(f32)
    pr_in.wait()
    rstage[ch - ALIGN:ch, :] = pre[1].astype(jnp.int32).astype(f32)

    def flush(stage, fb, flushed, left, sem_base):
        """Convert the ready CH-row stage half, start its pure HBM write."""
        half = jax.lax.rem(flushed // ch, 2)
        slot = half
        nflush = flushed // ch

        # slot reuse: wait the DMA issued 2 flushes ago (size-matched
        # reconstruction; .wait() only consumes the semaphore)
        @pl.when(nflush >= 2)
        def _():
            pltpu.make_async_copy(
                fb.at[slot], work_ref.at[dst_plane, pl.ds(0, ch), :],
                sem.at[sem_base + slot]).wait()
        hs = (half * ch // 8) * 8  # == half*ch; the pattern proves alignment
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

    def append(stage, out8, n, ws, dlt, fill_sel_left):
        """Blend `n` compacted rows into the circular stage at window ws.

        Mosaic requires dynamic sublane window offsets provably 8-aligned
        for wide loads, so the window is [ws8, ws8 + sb + 8) with
        ws8 = align8(ws); ``out8`` rows are pre-shifted by dlt = ws - ws8
        (the permutation matmul absorbs the shift into its dest indices).
        """
        ws8 = (ws // 8) * 8
        win = stage[pl.ds(ws8, sb + 8)]
        if fill_sel_left:
            m = (iota_sb8 >= dlt) & (iota_sb8 < dlt + n)
        else:
            m = (iota_sb8 >= dlt + sb - n) & (iota_sb8 < dlt + sb)
        stage[pl.ds(ws8, sb + 8)] = jnp.where(m, out8, win)

        @pl.when(ws + sb > lcap)
        def _():
            # wrap: append dests in the margin [lcap, ws+sb) are logical
            # [0, ov). Blend ONLY those — on the descending (right) side
            # the rows at [ov, sb) hold current, not-yet-flushed data, and
            # the 8-row alignment pad beyond ws+sb holds stale bytes.
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

        # Mosaic has no direct u8<->f32 casts; bounce through i32
        cf = cin[slot].astype(jnp.int32).astype(f32)          # (CH, W)
        col = jnp.sum(jnp.where(lane_w == feat, cf, 0.0), axis=1,
                      keepdims=True)                          # (CH, 1)
        # routing table lookup: the (B,) bool table rides the scalar
        # prefetch as 8 bit-packed i32 words (a full-array VMEM-spec input
        # here put the WHOLE device into a ~400 us/op dispatch mode —
        # measured pre-round, PERF.md section 6 — and poisoned every
        # subsequent op in the process, pallas or XLA alike)
        coli = col.astype(jnp.int32)
        word = jax.lax.shift_right_logical(coli, 5)
        wvals = jnp.zeros((ch, 1), jnp.int32)
        for w in range(TABLE_WORDS):
            wvals = jnp.where(word == w, sref[4 + w], wvals)
        bit = jnp.bitwise_and(coli, 31)
        go = jnp.bitwise_and(
            jax.lax.shift_right_logical(wvals, bit), 1) > 0
        pos = sub_i + i * ch
        valid = (pos >= head) & (pos < tot)                   # (CH, 1)

        for s in range(nsub):
            sub = cf[s * sb:(s + 1) * sb]                     # (SB, W)
            gl = go[s * sb:(s + 1) * sb] & valid[s * sb:(s + 1) * sb]
            gr = (~go[s * sb:(s + 1) * sb]) & valid[s * sb:(s + 1) * sb]
            flags = jnp.concatenate(
                [gl.astype(jnp.bfloat16), gr.astype(jnp.bfloat16)], axis=1)
            ranks = jax.lax.dot(tril[:], flags,
                                preferred_element_type=f32)   # (SB, 2)
            nl = jnp.sum(gl.astype(jnp.int32))
            nr = jnp.sum(gr.astype(jnp.int32))
            lrank = ranks[:, 0:1].astype(jnp.int32)
            rrank = ranks[:, 1:2].astype(jnp.int32)
            ws_l = jax.lax.rem(p_l, lcap)
            dlt_l = ws_l - (ws_l // 8) * 8
            # window start (CH - p_r - SB) mod LCAP, kept positive before
            # rem (lax.rem keeps the dividend's sign)
            ws_r = jax.lax.rem(ch - jax.lax.rem(p_r, lcap) - sb + 2 * lcap,
                               lcap)
            dlt_r = ws_r - (ws_r // 8) * 8
            # left rows rank to the window front; right rows to window
            # offsets sb-1-rrank (descending cursor); unrouted rows get -1;
            # dests shift by the window's 8-row alignment remainder
            dest_l = jnp.where(gl, lrank + dlt_l, -1)
            dest_r = jnp.where(gr, sb - 1 - rrank + dlt_r, -1)
            j_i = jax.lax.broadcasted_iota(jnp.int32, (sb + 8, sb), 0)
            perm_l = (1 - jnp.clip(jnp.abs(j_i - dest_l.reshape(1, sb)),
                                   0, 1)).astype(f32).astype(jnp.bfloat16)
            perm_r = (1 - jnp.clip(jnp.abs(j_i - dest_r.reshape(1, sb)),
                                   0, 1)).astype(f32).astype(jnp.bfloat16)
            # u8 payload bytes are integers <= 255: exact under a 0/1 bf16
            # permutation matmul with f32 accumulation
            sub_bf = sub.astype(jnp.bfloat16)
            out_l = jax.lax.dot(perm_l, sub_bf, preferred_element_type=f32)
            out_r = jax.lax.dot(perm_r, sub_bf, preferred_element_type=f32)

            append(lstage, out_l, nl, ws_l, dlt_l, True)
            p_l = p_l + nl

            @pl.when(p_l - fl_l >= ch)
            def _():
                flush(lstage, lfb, fl_l, True, 4)
            fl_l = jnp.where(p_l - fl_l >= ch, fl_l + ch, fl_l)

            append(rstage, out_r, nr, ws_r, dlt_r, False)
            p_r = p_r + nr

            @pl.when(p_r - fl_r >= ch)
            def _():
                flush(rstage, rfb, fl_r, False, 6)
            fl_r = jnp.where(p_r - fl_r >= ch, fl_r + ch, fl_r)

        return p_l, p_r, fl_l, fl_r

    p_l, p_r, fl_l, fl_r = jax.lax.fori_loop(
        0, nchunks, body, (head_l, tail_r, jnp.int32(0), jnp.int32(0)))

    # ---- drain leftovers: [lbase0+fl_l, rbase0-fl_r), all 32-aligned ----
    fill_l = p_l - fl_l
    fill_r = p_r - fl_r
    d = fill_l + fill_r
    dstart = lbase0 + fl_l

    # wait outstanding flush DMAs (the drain RMW tile may read their rows,
    # and kernel exit requires drained semaphores). The reconstruction uses
    # lfb for both sides — only the semaphore index and byte count matter.
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
        """(ch, W) rows of the circular stage starting at logical qstart.
        Robust to any-sign qstart (true mathematical mod). The load is
        8-aligned (Mosaic wide-load constraint); the remainder is absorbed
        by a roll."""
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
        """(ch, W) drain rows for drain offsets [o, o+ch)."""
        lrows = read_circ(lstage, fl_l + o)
        rrows = read_circ(rstage, qr0 + (o - fill_l))
        off = sub_i[:ch] + o
        return jnp.where(off < fill_l, lrows, rrows)

    nfull = d // ch
    # d < 2*(ch+sb): at the default sb <= ch/2 that is <= 3*ch (nfull <= 2);
    # at part_chunk <= 256 sb == ch and the bound is 4*ch (nfull <= 3) —
    # MAXT must cover BOTH, so 4 is load-bearing, not slack
    MAXT = 4

    def dbody(t, _):
        @pl.when(t < nfull)
        def _():
            slot = jax.lax.rem(t, 2)

            @pl.when(t >= 2)
            def _():
                pltpu.make_async_copy(
                    lfb.at[slot], work_ref.at[dst_plane, pl.ds(0, ch), :],
                    sem.at[4 + slot]).wait()
            lfb[slot] = drain_tile(t * ch).astype(jnp.int32).astype(jnp.uint8)
            pltpu.make_async_copy(
                lfb.at[slot], work_ref.at[dst_plane,
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

    @pl.when(rem_ > 0)
    def _():
        # one overlapping RMW tile ending exactly at the region end: rows
        # with drain offset in [nfull*ch, d) are fresh; below that the RMW
        # re-reads what full tiles just wrote (identical) or, when d < ch,
        # pre-segment bytes that must be preserved
        at = a32(dstart + d - ch)
        # read via the OUTPUT ref: on TPU it aliases work_in, but interpret
        # mode keeps distinct buffers and only work_ref holds the rows the
        # full drain tiles just wrote (planes kernel precedent, line ~1205)
        rd = pltpu.make_async_copy(
            work_ref.at[dst_plane, pl.ds(at, ch), :], lfb.at[0], sem.at[4])
        rd.start()
        rd.wait()
        tile = drain_tile(d - ch)
        old = lfb[0].astype(jnp.int32).astype(f32)
        off = sub_i[:ch] + (d - ch)
        keep_new = (off >= jnp.int32(nfull) * ch) & (off >= 0)
        merged = jnp.where(keep_new, tile, old)
        lfb[0] = merged.astype(jnp.int32).astype(jnp.uint8)
        wr = pltpu.make_async_copy(
            lfb.at[0], work_ref.at[dst_plane, pl.ds(at, ch), :], sem.at[4])
        wr.start()
        wr.wait()

    lt_ref[0] = p_l - head_l


def partition_segment_fused(
    work: jax.Array,       # (2, Npad, W) u8 ping-pong buffer pair
    src_plane: jax.Array,
    start: jax.Array,
    cnt: jax.Array,
    feat: jax.Array,
    go_left: jax.Array,    # (B,) bool
    *,
    ch: int = DEFAULT_CH,
    sb: int = 256,
) -> Tuple[jax.Array, jax.Array]:
    """Pallas form of :func:`partition_segment` (same contract, except row
    order WITHIN each side is unspecified — insignificant for this
    framework: histograms are order-free and sub-splits re-partition).

    Requires the work buffer's row width padded to 128 (DMA slices must
    cover whole 128-lane tiles) and guard regions of at least ch + 32 rows
    (edge tiles and input reads extend past the segment on both sides).
    """
    num_bin = go_left.shape[0]
    width = work.shape[2]
    if width % 128:
        raise ValueError(
            "fused partition needs width as whole 128-lane tiles, got %d"
            % width)
    sb = min(sb, ch)
    if ch % sb:
        raise ValueError("partition chunk %d must be a multiple of the "
                         "sub-block %d" % (ch, sb))
    scalars = jnp.concatenate([
        jnp.stack([src_plane.astype(jnp.int32), start.astype(jnp.int32),
                   cnt.astype(jnp.int32), feat.astype(jnp.int32)]),
        pack_table_bits(go_left)])

    kern = partial(_partition_kernel, ch=ch, sb=sb, width=width,
                   num_bin=num_bin)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((sb, sb), jnp.bfloat16),            # tril
            pltpu.VMEM((2, ch, width), jnp.uint8),         # cin x2
            pltpu.VMEM((2, ALIGN, width), jnp.uint8),      # edge prefills
            pltpu.VMEM((3 * ch, width), jnp.float32),      # lstage
            pltpu.VMEM((3 * ch, width), jnp.float32),      # rstage
            pltpu.VMEM((2, ch, width), jnp.uint8),         # lfb x2
            pltpu.VMEM((2, ch, width), jnp.uint8),         # rfb x2
            pltpu.SemaphoreType.DMA((8,)),
        ],
    )
    work_out, lt = pl.pallas_call(
        kern,
        name="partition_segment_fused",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(work.shape, work.dtype),
                   jax.ShapeDtypeStruct((1,), jnp.int32)],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=_INTERPRET,
    )(scalars, work)
    return work_out, lt[0]


# ---------------------------------------------------------------------------
# Fused Pallas kernel, planes layout
# ---------------------------------------------------------------------------
#
# Transposed twin of _partition_kernel. All DYNAMIC offsets live on the
# LANE dim (rows of data are lanes), where Mosaic's tiling is strictest —
# so the kernel never slices VMEM dynamically on lanes at all:
#
# - HBM chunk reads use 128-lane-aligned windows derived as (x//128)*128
#   at every use site (the lane twin of the rows kernel's (x//32)*32);
# - in-chunk compaction runs per SB-lane sub-block in two halves. The
#   CURSOR-FREE half, for all sub-blocks of a chunk before any cursor is
#   read: one rank matmul a chunk ((2*NSUB, SB) flags @ triu), then per
#   sub-block ONE (SB, SB) one-hot shared by both sides — lefts packed up
#   from lane 0 at their rank, rights packed down from lane SB-1 — built
#   transposed (dst on sublanes, src on the lanes the ranks already sit
#   on: one compare, no lane-to-sublane relayout) and ONE (W, SB) payload
#   matmul contracting its lane dim. The CURSOR WALK then, per sub-block,
#   rotates that product twice on the lanes (pltpu.roll by the traced
#   cursor mod SB) and adds each side to its stage under (1, SB) lane
#   masks: the lanes of the side's run, split between the stage's two
#   static halves where the run wraps past lane SB-1;
# - the circular stages are (W, LCAP=2*SB) f32: logical left lane q at
#   slot q % LCAP, right descending index q at slot LCAP-1-(q % LCAP); a
#   flush converts one STATIC half to u8 and pure-writes it to an aligned
#   HBM window, then zeroes the half (future adds land on zeros);
# - leftovers drain as up to 2 serial RMW tiles per side, left fully
#   before right (their windows can overlap in the middle of the segment).
#
# Row placement is a pure function of row order and the cursors — lefts
# ascending from start in row order, rights descending from start+cnt —
# whatever SB is and whatever shape the one-hot has, so the work buffer is
# byte-identical across such choices (tests/test_work_layout.py holds it
# to that order, at SB 128 and 256).
#
# dst-plane state (edge prefills, drain RMW reads) is read through
# work_ref — the ALIASED OUTPUT — which is the same HBM buffer on device
# and keeps the kernel bit-faithful under the pallas interpreter, so the
# CPU suite validates it end-to-end (tests/test_work_layout.py).
#
# Cost, measured on a v5e with the kernel alone (PR 27; 4M / 1.6M-row
# segments, chunk 1024, SB 256): 1.32 ns per row visit at W=64 planes and
# 2.39 at W=160, i.e. 0.61 ns a row whatever the width + 0.011 ns a plane;
# the bytes would take 0.16 / 0.39 ns at HBM speed. Why the shape above
# (same runs; PERF.md section 6): a sub-block's cost is mostly its serial
# latency, not its one-hot's area — SB 128 costs 1.5-1.7x a row, SB 512 no
# less than 256 at W=64, more at W=160; a one-hot with dest on sublanes
# needs a lane-to-sublane relayout that costs more than the one-hot
# (+1.0 ns a row); and a one-hot that depends on a cursor keeps every
# sub-block's matmuls behind the previous sub-block's flush (+0.15 to
# +0.7 ns a row), which is what the shared, rank-only one-hot avoids.


def _partition_planes_kernel(sref, work_in, work_ref, lt_ref,
                             triu, cin, pre, lstage, rstage, lfb, rfb, sem,
                             *, ch, sb, nplanes):
    f32 = jnp.float32
    lcap = 2 * sb
    nsub = ch // sb
    W = nplanes
    src_plane = sref[0]
    start = sref[1]
    cnt = sref[2]
    feat = sref[3]
    dst_plane = 1 - src_plane

    def a128(x):
        # lane twin of the rows kernel's a32: re-derive every HBM lane
        # offset as (x // 128) * 128 at the use site so Mosaic can PROVE
        # whole-tile alignment
        return (x // PLANE_ALIGN) * PLANE_ALIGN

    lbase0 = (start // PLANE_ALIGN) * PLANE_ALIGN
    head_l = start - lbase0                  # 0..127 neighbor lanes below
    end = start + cnt
    rtop = ((end - 1) // PLANE_ALIGN) * PLANE_ALIGN
    rbase0 = rtop + PLANE_ALIGN
    tail_r = rbase0 - end                    # 0..127 neighbor lanes above

    tot = head_l + cnt
    nchunks = (tot + ch - 1) // ch

    # strict upper-triangular ones: ranks[j] = sum_{i<j} flags[i], flags
    # along the LANE dim (flags (2*NSUB, SB) @ triu (SB, SB), once a chunk)
    row_i = jax.lax.broadcasted_iota(jnp.int32, (sb, sb), 0)
    col_i = jax.lax.broadcasted_iota(jnp.int32, (sb, sb), 1)
    triu[:] = jnp.clip(col_i - row_i, 0, 1).astype(f32).astype(jnp.bfloat16)

    lane_c = jax.lax.broadcasted_iota(jnp.int32, (1, ch), 1)
    sub_w = jax.lax.broadcasted_iota(jnp.int32, (W, 1), 0)
    lane_128 = jax.lax.broadcasted_iota(jnp.int32, (W, PLANE_ALIGN), 1)
    lane_sb_w = jax.lax.broadcasted_iota(jnp.int32, (W, sb), 1)
    lane_sb = jax.lax.broadcasted_iota(jnp.int32, (1, sb), 1)

    # ---- prefills: neighbor lanes of the aligned edge tiles ----
    pl_in = pltpu.make_async_copy(
        work_ref.at[dst_plane, :, pl.ds(lbase0, PLANE_ALIGN)],
        pre.at[0], sem.at[2])
    pl_in.start()
    pr_in = pltpu.make_async_copy(
        work_ref.at[dst_plane, :, pl.ds(rtop, PLANE_ALIGN)],
        pre.at[1], sem.at[3])
    pr_in.start()

    def start_in(i, slot):
        pltpu.make_async_copy(
            work_in.at[src_plane, :, pl.ds(a128(lbase0 + i * ch), ch)],
            cin.at[slot], sem.at[slot]).start()

    start_in(0, 0)

    # left stage: logical lane q (from lbase0, ascending) at slot q % LCAP.
    # right stage: descending index q (from rbase0) at slot LCAP-1-(q%LCAP)
    # — chosen so every flush half maps to its HBM window IN ORDER.
    lstage[...] = jnp.zeros((W, lcap), f32)
    rstage[...] = jnp.zeros((W, lcap), f32)
    pl_in.wait()
    lstage[:, 0:PLANE_ALIGN] = jnp.where(
        lane_128 < head_l, pre[0].astype(jnp.int32).astype(f32), 0.0)
    pr_in.wait()
    rstage[:, lcap - PLANE_ALIGN:lcap] = jnp.where(
        lane_128 >= PLANE_ALIGN - tail_r,
        pre[1].astype(jnp.int32).astype(f32), 0.0)

    def stage_half(stage, h):
        """STATIC half selected by a traced bit (no dynamic lane slicing)."""
        return jnp.where(h == 1, stage[:, sb:lcap], stage[:, 0:sb])

    def flush(stage, fb, flushed, left, sem_base):
        """Convert the completed SB-lane half, zero it, start its pure
        aligned HBM write."""
        nflush = flushed // sb
        slot = jax.lax.rem(nflush, 2)

        # slot reuse: wait the DMA issued 2 flushes ago (size-matched
        # reconstruction; .wait() only consumes the semaphore)
        @pl.when(nflush >= 2)
        def _():
            pltpu.make_async_copy(
                fb.at[slot], work_ref.at[dst_plane, :, pl.ds(0, sb)],
                sem.at[sem_base + slot]).wait()
        h = slot if left else 1 - slot
        lo_half = stage[:, 0:sb]
        hi_half = stage[:, sb:lcap]
        hb = h == 1
        fb[slot] = jnp.where(hb, hi_half, lo_half) \
            .astype(jnp.int32).astype(jnp.uint8)
        stage[:, 0:sb] = jnp.where(hb, lo_half, 0.0)
        stage[:, sb:lcap] = jnp.where(hb, 0.0, hi_half)
        if left:
            at = a128(lbase0 + flushed)
        else:
            at = a128(rbase0 - flushed) - sb
        pltpu.make_async_copy(
            fb.at[slot], work_ref.at[dst_plane, :, pl.ds(at, sb)],
            sem.at[sem_base + slot]).start()

    def place(stage, y, p, n, left):
        """Add one side's run of y (W, SB) — n rows, packed from lane 0 up
        (left) or from lane SB-1 down (right) — to its circular stage at
        the side's cursor p. Positions count lanes upward on the left and
        downward on the right (the right stage mirrors its slots): after
        the rotate the run sits at positions (p % SB + k) % SB, k < n, in
        the cursor's half of the stage and, past the wrap, in the other.
        Each lane goes to ONE of the two STATIC halves under a (1, SB)
        mask; payload bytes times 1.0 or 0.0 are exact."""
        pos = lane_sb if left else sb - 1 - lane_sb
        a = jax.lax.rem(p, sb)
        h = jax.lax.rem(p // sb, 2)
        run = pos - a
        run = jnp.where(run < 0, run + sb, run)
        half = jnp.where(pos >= a, h, 1 - h)
        if not left:
            half = 1 - half
        y = pltpu.roll(y, a if left else jax.lax.rem(sb - a, sb), 1)
        stage[:, 0:sb] += y * ((run < n) & (half == 0)).astype(f32)
        stage[:, sb:lcap] += y * ((run < n) & (half == 1)).astype(f32)

    def body(i, carry):
        p_l, p_r, fl_l, fl_r = carry
        slot = jax.lax.rem(i, 2)
        pltpu.make_async_copy(
            work_in.at[src_plane, :, pl.ds(a128(lbase0 + i * ch), ch)],
            cin.at[slot], sem.at[slot]).wait()

        @pl.when(i + 1 < nchunks)
        def _():
            start_in(i + 1, 1 - slot)

        cf = cin[slot].astype(jnp.int32).astype(f32)          # (W, CH)
        # split column: one sublane reduction (feat is a traced sublane
        # index — never a dynamic VMEM slice)
        col = jnp.sum(jnp.where(sub_w == feat, cf, 0.0), axis=0,
                      keepdims=True)                          # (1, CH)
        coli = col.astype(jnp.int32)
        word = jax.lax.shift_right_logical(coli, 5)
        wvals = jnp.zeros((1, ch), jnp.int32)
        for w_ in range(TABLE_WORDS):
            wvals = jnp.where(word == w_, sref[4 + w_], wvals)
        bit = jnp.bitwise_and(coli, 31)
        go = jnp.bitwise_and(
            jax.lax.shift_right_logical(wvals, bit), 1) > 0
        pos = lane_c + i * ch
        valid = (pos >= head_l) & (pos < tot)                 # (1, CH)

        # ---- the cursor-free half of every sub-block, all sub-blocks of
        # the chunk AHEAD of the cursor walk: nothing here waits for a
        # cursor, so the rank matmul runs once a chunk and the one-hots and
        # payload matmuls of the sub-blocks overlap ----
        gl_c = go & valid
        gr_c = (~go) & valid
        flags = []
        for s in range(nsub):
            sl = slice(s * sb, (s + 1) * sb)
            flags += [gl_c[:, sl], gr_c[:, sl]]
        ranks = jax.lax.dot(
            jnp.concatenate([g.astype(jnp.bfloat16) for g in flags], axis=0),
            triu[:], preferred_element_type=f32).astype(jnp.int32)
        ahead = []
        for s in range(nsub):
            gl, gr = flags[2 * s], flags[2 * s + 1]
            # ONE sub-block-local permutation for both sides: lefts packed
            # up from lane 0, rights packed down from lane SB-1 (they never
            # collide: nl + nr <= SB); unrouted rows get -1 (no lane).
            # Built TRANSPOSED, (SB dst, SB src), so that dest stays on the
            # lanes it was computed on — one compare against a sublane iota,
            # no lane-to-sublane relayout — and contracted on its lane dim.
            dest = jnp.where(gl, ranks[2 * s:2 * s + 1],
                             jnp.where(gr, sb - 1 - ranks[2 * s + 1:2 * s + 2],
                                       -1))                   # (1, SB)
            perm_t = jnp.where(row_i == dest, 1.0, 0.0).astype(jnp.bfloat16)
            # u8 payload bytes are integers <= 255: exact under a 0/1 bf16
            # permutation matmul with f32 accumulation
            y = jax.lax.dot_general(
                cf[:, s * sb:(s + 1) * sb].astype(jnp.bfloat16), perm_t,
                (((1,), (1,)), ((), ())), preferred_element_type=f32)
            ahead.append((jnp.sum(gl.astype(jnp.int32)),
                          jnp.sum(gr.astype(jnp.int32)), y))  # y: (W, SB)

        # ---- the cursor walk: rotate each side to its stage slots. Left
        # row k goes to logical lane p_l + k, slot (p_l + k) % LCAP; right
        # row k to descending index p_r + k, slot LCAP-1-((p_r + k) % LCAP)
        for nl, nr, y in ahead:
            place(lstage, y, p_l, nl, True)
            place(rstage, y, p_r, nr, False)
            p_l = p_l + nl
            p_r = p_r + nr

            @pl.when(p_l - fl_l >= sb)
            def _():
                flush(lstage, lfb, fl_l, True, 4)
            fl_l = jnp.where(p_l - fl_l >= sb, fl_l + sb, fl_l)

            @pl.when(p_r - fl_r >= sb)
            def _():
                flush(rstage, rfb, fl_r, False, 6)
            fl_r = jnp.where(p_r - fl_r >= sb, fl_r + sb, fl_r)

        return p_l, p_r, fl_l, fl_r

    p_l, p_r, fl_l, fl_r = jax.lax.fori_loop(
        0, nchunks, body,
        (head_l, tail_r, jnp.int32(0), jnp.int32(0)))

    # ---- drain: wait ALL outstanding flushes first (their tiles can sit
    # inside the other side's drain windows), then up to 2 serial RMW
    # tiles per side, LEFT fully before RIGHT (windows may overlap where
    # the frontiers meet) ----
    for base, fb, fl in ((4, lfb, fl_l), (6, rfb, fl_r)):
        nf = fl // sb
        for back in (1, 2):
            @pl.when(nf >= back)
            def _(base=base, fb=fb, nf=nf, back=back):
                pltpu.make_async_copy(
                    fb.at[jax.lax.rem(nf - back, 2)],
                    work_ref.at[dst_plane, :, pl.ds(0, sb)],
                    sem.at[base + jax.lax.rem(nf - back, 2)]).wait()

    for t in (0, 1):
        @pl.when(t * sb < p_l - fl_l)
        def _(t=t):
            at = a128(lbase0 + fl_l) + t * sb
            rd = pltpu.make_async_copy(
                work_ref.at[dst_plane, :, pl.ds(at, sb)], lfb.at[0],
                sem.at[4])
            rd.start()
            rd.wait()
            h = jax.lax.rem(fl_l // sb + t, 2)
            fresh = stage_half(lstage, h)
            old = lfb[0].astype(jnp.int32).astype(f32)
            qpos = fl_l + t * sb + lane_sb_w
            merged = jnp.where(qpos < p_l, fresh, old)
            lfb[0] = merged.astype(jnp.int32).astype(jnp.uint8)
            wr = pltpu.make_async_copy(
                lfb.at[0], work_ref.at[dst_plane, :, pl.ds(at, sb)],
                sem.at[4])
            wr.start()
            wr.wait()

    for t in (0, 1):
        @pl.when(t * sb < p_r - fl_r)
        def _(t=t):
            at = a128(rbase0 - fl_r) - (t + 1) * sb
            rd = pltpu.make_async_copy(
                work_ref.at[dst_plane, :, pl.ds(at, sb)], rfb.at[0],
                sem.at[6])
            rd.start()
            rd.wait()
            h = 1 - jax.lax.rem(fl_r // sb + t, 2)
            fresh = stage_half(rstage, h)
            old = rfb[0].astype(jnp.int32).astype(f32)
            # window lane c holds descending index q = fl_r+(t+1)*sb-1-c
            keep = lane_sb_w >= (t + 1) * sb - (p_r - fl_r)
            merged = jnp.where(keep, fresh, old)
            rfb[0] = merged.astype(jnp.int32).astype(jnp.uint8)
            wr = pltpu.make_async_copy(
                rfb.at[0], work_ref.at[dst_plane, :, pl.ds(at, sb)],
                sem.at[6])
            wr.start()
            wr.wait()

    lt_ref[0] = p_l - head_l


def partition_segment_planes_fused(
    work: jax.Array,       # (2, W, Npad) u8 ping-pong plane pair
    src_plane: jax.Array,
    start: jax.Array,
    cnt: jax.Array,
    feat: jax.Array,
    go_left: jax.Array,    # (B,) bool
    *,
    ch: int = DEFAULT_CH,
    sb: int = 256,
) -> Tuple[jax.Array, jax.Array]:
    """Pallas form of :func:`partition_segment_planes` (same contract and
    same left child; the right child holds the same rows in fully REVERSED
    row order, where the XLA form reverses chunk by chunk).

    Requires whole-tile dims: Npad % 128 == 0 (lane DMA windows), plane
    count a multiple of 32 (u8 sublane tiles), ch a multiple of 128 and of
    the sub-block, and guards of at least ch + 2*PLANE_ALIGN lanes
    (work_spec/planes_npad provide all four).
    """
    num_bin = go_left.shape[0]
    _, nplanes, npad = work.shape
    if npad % 128:
        raise ValueError(
            "fused planes partition needs whole 128-lane tiles in the lane "
            "dim, got Npad=%d" % npad)
    if nplanes % 32:
        raise ValueError(
            "fused planes partition needs whole 32-sublane u8 tiles, got "
            "W=%d planes" % nplanes)
    sb = min(sb, ch)
    if ch % sb or ch % 128:
        raise ValueError(
            "planes partition chunk %d must be a multiple of 128 and of "
            "the sub-block %d" % (ch, sb))
    scalars = jnp.concatenate([
        jnp.stack([src_plane.astype(jnp.int32), start.astype(jnp.int32),
                   cnt.astype(jnp.int32), feat.astype(jnp.int32)]),
        pack_table_bits(go_left)])

    kern = partial(_partition_planes_kernel, ch=ch, sb=sb, nplanes=nplanes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((sb, sb), jnp.bfloat16),              # triu
            pltpu.VMEM((2, nplanes, ch), jnp.uint8),         # cin x2
            pltpu.VMEM((2, nplanes, PLANE_ALIGN), jnp.uint8),  # prefills
            pltpu.VMEM((nplanes, 2 * sb), jnp.float32),      # lstage
            pltpu.VMEM((nplanes, 2 * sb), jnp.float32),      # rstage
            pltpu.VMEM((2, nplanes, sb), jnp.uint8),         # lfb x2
            pltpu.VMEM((2, nplanes, sb), jnp.uint8),         # rfb x2
            pltpu.SemaphoreType.DMA((8,)),
        ],
    )
    work_out, lt = pl.pallas_call(
        kern,
        name="partition_segment_planes_fused",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(work.shape, work.dtype),
                   jax.ShapeDtypeStruct((1,), jnp.int32)],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=_INTERPRET,
    )(scalars, work)
    return work_out, lt[0]
