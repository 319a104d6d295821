"""Pallas row-routing kernel: the whole tree's split log in one pass.

The XLA form of ``assign_leaves`` (learner.py) walks the split log with a
254-round ``fori_loop``, each round a full-N elementwise pass — ~30 ms/tree
at 2M rows (the per-round fusions are small and latency-bound). This kernel
streams each row tile through VMEM ONCE and applies all rounds in-register:
HBM traffic drops to one read of the transposed binned matrix plus one
write of the leaf vector, and the per-round work is a handful of VPU ops on
a resident (rows/128, 128) tile (~5 ms/tree).

That streaming form holds a block of EVERY column in VMEM, so it stops at
a few hundred columns; a wider table takes the wide form (``route_form``):
one grid step a (row tile, round), handed the one column its round splits
on, so a tree reads its own ``num_leaves - 1`` columns and never the
table's width.

Scope: numerical splits, with or without EFB bundles (all per-round
quantities reduce to SMEM scalars), and categorical splits: a job with
categorical columns carries each round's kind and its (B <= 256,) go-left
table as ``TABLE_WORDS`` bit-packed SMEM words (the partition kernels' form,
``ops/partition.py`` ``pack_table_bits``), and a categorical round replaces
the threshold comparison by bit ``eff`` of those words. ``categorical`` is a
STATIC argument: a job without such columns compiles the ten-column table
and the numerical round alone.

Reference analog: Tree::PredictLeafIndex over pre-binned data
(src/io/tree.cpp), used for score updates via the data partition
(score_updater.hpp:88).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .partition import TABLE_WORDS, pack_table_bits


# SMEM table layout: per round r the columns are
#   0 col      matrix column to read (bundle group or feature)
#   1 leaf     leaf id split this round
#   2 bin      threshold bin (feature-space)
#   3 miss     movable-missing bin (-1: none)
#   4 dl       default-left flag
#   5 plain    1 = no bundle arithmetic for this column
#   6 off      bundle: sub-feature's slot offset
#   7 dpos     bundle: shared default-bin slot position
#   8 nbm1     bundle: sub-feature slots (num_bins - 1)
#   9 rest     bundle: direction of out-of-range slots
# and, in a job with categorical columns only (``table_width``):
#   10 cat     1 = categorical round
#   11.. words the round's go-left table, bit b of word w = bin 32*w + b
TBL_W = 10
ROUTE_BLOCK_ROWS = 16384  # rows per grid block (shared with assign_leaves)


# Mosaic gives a kernel 16 MiB of scoped VMEM on a v5e, and the streaming
# form holds one block of EVERY matrix column twice (the pipeline's two
# buffers): F x 32 KB, which the ``v5e:2x2`` compiler accepts to F = 500 and
# refuses from 504 (PERF.md, PR 32). Tables whose pair of blocks passes this
# budget take the wide form, which holds one column a step.
ROUTE_VMEM_BUDGET = 12 << 20


def route_form(num_feat: int, rows_per_block: int = ROUTE_BLOCK_ROWS,
               itemsize: int = 1) -> str:
    """``stream`` while a block of all ``num_feat`` columns fits VMEM twice
    (the pipeline's two buffers), ``wide`` past that: the static shape
    alone decides."""
    fits = 2 * num_feat * rows_per_block * itemsize <= ROUTE_VMEM_BUDGET
    return "stream" if fits else "wide"


def table_width(categorical: bool) -> int:
    """Scalars a round holds in the SMEM table."""
    return TBL_W + 1 + TABLE_WORDS if categorical else TBL_W


def pallas_routes(has_categorical: bool, num_bin: int) -> bool:
    """Whether ``route_rows`` can take a job's trees: always, but for
    categorical columns of more bins than the bit words hold."""
    return not has_categorical or num_bin <= 32 * TABLE_WORDS


def _route_round(sref, r, num_splits, read_col, state, categorical):
    """One round of the split log on a resident (csub, 128) tile;
    ``read_col(column index)`` -> that tile's bins of the round's column."""
    i32 = jnp.int32
    base = 1 + r * table_width(categorical)
    col_idx = sref[base + 0]
    leaf = sref[base + 1]
    tbin = sref[base + 2]
    miss = sref[base + 3]
    dl = sref[base + 4]
    plain = sref[base + 5]
    off = sref[base + 6]
    dpos = sref[base + 7]
    nbm1 = sref[base + 8]
    rest = sref[base + 9]
    col = read_col(col_idx).astype(i32)            # (csub, 128)
    # bundle slot -> feature bin (identity when plain): slots above the
    # shared default position shift down by one. All routing flags stay
    # in i32 0/1 form — Mosaic cannot truncate i8 vectors to i1 data.
    rank = col - off
    fb = rank + jnp.clip(rank - dpos + 1, 0, 1)    # +1 when rank >= dpos
    in_r = jnp.clip(col - off + 1, 0, 1) \
        * jnp.clip(off + nbm1 - col, 0, 1)         # 1 when in range
    eff = jnp.where(plain == 1, col, fb)

    def go_numerical():
        go = jnp.clip(tbin - eff + 1, 0, 1)        # 1 when eff <= tbin
        is_miss = 1 - jnp.clip(jnp.abs(eff - miss), 0, 1)
        return jnp.where((miss >= 0) & (is_miss == 1), dl, go)

    def go_categorical():
        # bit ``eff`` of the round's table: the word by eight selects on
        # scalars (as the partition kernels read theirs), then shift + mask
        word = jax.lax.shift_right_logical(eff, 5)
        wvals = jnp.zeros_like(eff)
        for w in range(TABLE_WORDS):
            wvals = jnp.where(word == w, sref[base + TBL_W + 1 + w], wvals)
        return jnp.bitwise_and(jax.lax.shift_right_logical(
            wvals, jnp.bitwise_and(eff, 31)), 1)

    if categorical:
        # the round's kind is a scalar: only its own comparison runs
        go = jax.lax.cond(sref[base + TBL_W] > 0, go_categorical,
                          go_numerical)
    else:
        go = go_numerical()
    go = jnp.where((plain == 1) | (in_r == 1), go, rest)
    upd = jnp.where((state == leaf) & (go == 0), r + 1, state)
    return jnp.where(r < num_splits, upd, state)


def _route_kernel(sref, binst_ref, out_ref, *, rounds, csub, categorical):
    i32 = jnp.int32
    num_splits = sref[0]
    state = jnp.zeros((csub, 128), i32)

    def body(r, state):
        return _route_round(sref, r, num_splits,
                            lambda col_idx: binst_ref[col_idx], state,
                            categorical)

    state = jax.lax.fori_loop(0, rounds, body, state)
    out_ref[:, :] = state


def _route_kernel_wide(sref, col_ref, out_ref, *, categorical):
    """One grid step a (row block, round): the leaf tile stays in its output
    block across the rounds, and the pipeline fetches the next round's
    column (its index comes from the prefetched table) behind this one."""
    r = pl.program_id(1)

    @pl.when(r == 0)
    def _():
        out_ref[:, :] = jnp.zeros(out_ref.shape, jnp.int32)

    out_ref[:, :] = _route_round(sref, r, sref[0], lambda _: col_ref[0],
                                 out_ref[:, :], categorical)


def route_rows(bins_t: jax.Array, table: jax.Array, num_splits: jax.Array,
               n: int, *, rows_per_block: int = ROUTE_BLOCK_ROWS,
               categorical: bool = False) -> jax.Array:
    """(F, Npad/128, 128) u8 tiles + (R*width,) i32 table -> (Npad,) i32;
    ``table`` is ``build_route_table``'s for the same ``categorical``.

    ``bins_t`` must be the transposed binned matrix reshaped to
    (F, Npad/128, 128) with Npad a multiple of rows_per_block; padding rows
    route harmlessly (callers slice [:n]).

    Two forms, chosen by the static shape alone. While a block of all F
    columns fits VMEM twice (``ROUTE_VMEM_BUDGET``), each row block is
    streamed through once and the rounds run over it in registers. Past
    that, the grid gains a round axis and each step is handed the ONE column
    its round splits on, straight from HBM: a tree reads at most
    ``num_leaves - 1`` columns, never the table's width.
    """
    num_feat, nsub, _ = bins_t.shape
    width = table_width(categorical)
    rounds = (table.shape[0]) // width
    csub = rows_per_block // 128
    assert nsub % csub == 0, (nsub, csub)
    grid = nsub // csub
    scalars = jnp.concatenate([num_splits.reshape(1).astype(jnp.int32),
                               table.astype(jnp.int32)])
    if route_form(num_feat, rows_per_block,
                  bins_t.dtype.itemsize) == "stream":
        kern = partial(_route_kernel, rounds=rounds, csub=csub,
                       categorical=categorical)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(grid,),
            in_specs=[pl.BlockSpec((num_feat, csub, 128),
                                   lambda i, s: (0, i, 0))],
            out_specs=pl.BlockSpec((csub, 128), lambda i, s: (i, 0)),
        )
        semantics = ("arbitrary",)
    else:
        def column(i, r, s):
            # rounds past the tree's last split re-name its column, which
            # the pipeline does not fetch again
            live = jnp.minimum(r, jnp.maximum(s[0] - 1, 0))
            return jnp.clip(s[1 + live * width], 0, num_feat - 1), i, 0

        kern = partial(_route_kernel_wide, categorical=categorical)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(grid, rounds),
            in_specs=[pl.BlockSpec((1, csub, 128), column)],
            out_specs=pl.BlockSpec((csub, 128), lambda i, r, s: (i, 0)),
        )
        semantics = ("arbitrary", "arbitrary")
    from .partition import _INTERPRET
    out = pl.pallas_call(
        kern,
        name="route_rows",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nsub, 128), jnp.int32),
        interpret=_INTERPRET,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics),
    )(scalars, bins_t)
    return out.reshape(-1)


def build_route_table(log, meta, bundle: Optional[dict],
                      categorical: bool = False) -> jax.Array:
    """Assemble the per-round SMEM scalar table from a TreeLog (in-graph;
    all gathers are over (R,)-sized arrays). ``categorical`` adds each
    round's kind and its bit-packed go-left table."""
    r_iota = jnp.arange(log.split_leaf.shape[0], dtype=jnp.int32)
    feat = log.feature
    if bundle is not None:
        colv = bundle["group"][feat]
        plain = ~bundle["has_rest"][feat]
        off = bundle["offset"][feat]
        dpos = bundle["dpos"][feat]
        nbm1 = bundle["nbm1"][feat]
        rest = jnp.take_along_axis(
            log.go_left, dpos[:, None], axis=1)[:, 0]
    else:
        colv = feat
        plain = jnp.ones_like(feat, dtype=bool)
        off = jnp.zeros_like(feat)
        dpos = jnp.zeros_like(feat)
        nbm1 = jnp.zeros_like(feat)
        rest = jnp.zeros_like(feat, dtype=bool)
    miss = jnp.where(log.movable, log.miss_bin, -1)
    cols = [colv, log.split_leaf, log.bin, miss,
            log.default_left.astype(jnp.int32), plain.astype(jnp.int32),
            off, dpos, nbm1, rest.astype(jnp.int32)]
    del r_iota, meta
    table = jnp.stack([c.astype(jnp.int32) for c in cols], axis=1)
    if categorical:
        table = jnp.concatenate(
            [table, (log.kind > 0).astype(jnp.int32)[:, None],
             jax.vmap(pack_table_bits)(log.go_left)], axis=1)
    return table.reshape(-1)
