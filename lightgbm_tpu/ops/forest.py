"""Forest-at-once ensemble inference: one Pallas launch per row tile.

The serving predict path (``ops/predict.py predict_raw_impl``) walks the
packed ensemble as a ``fori_loop`` of per-split elementwise passes per
tree group — dozens of small launches per bucket dispatch, each reading
the full (N, F) raw matrix from HBM. This module reshapes the MODEL for
inference instead (the accelerator-GBDT literature's move: arXiv
1706.08359, arXiv 2011.02022):

- :class:`ForestPack` is an inference-shaped repack of ``PackedSplits``:
  node tables are SPLIT-MAJOR ``(R rounds, T trees)`` so round ``r``
  streams one contiguous row of every per-split quantity, and thresholds
  live in BIN space (derived through the same per-split conversion
  ``tree_to_bin_log`` uses — see ``split_bin_table`` in ops/predict.py),
  so every comparison is a small-int compare instead of an f32 one.
- :func:`forest_predict_impl` evaluates the WHOLE ensemble for a row
  tile in ONE ``pl.pallas_call``: the (tile, T) traversal front lives in
  VMEM/registers, each routing round gathers the per-tree feature column
  with a one-hot MXU contraction (``bins_f32 @ onehot(feature_r)`` — the
  ``leaf_values_by_row`` gather-to-matmul trick), and leaf values are
  accumulated in-kernel in the ORACLE'S exact grouping/order so the
  result is byte-identical to ``predict_raw_impl``.

Bit-parity discipline (PR 12): the per-depth-gather path stays the
serving default and the oracle; this kernel is behind the
``tpu_forest_kernel`` knob, proven byte-identical under the pallas
interpreter (tests/test_forest_kernel.py), and ``auto`` resolves to
``off`` until ``scripts/forest_bisect.py`` validates the Mosaic lowering
and a wall win on real hardware.
"""
from __future__ import annotations

from typing import List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: Row-tile width of one kernel program. Bucket rungs need not be
#: multiples of it — the wrapper pads (padding rows route harmlessly and
#: are sliced off).
FOREST_TILE = 256

#: VMEM budget for the resident node tables + per-tile working set; a
#: model whose tables exceed it is ineligible (the front + tables must
#: stay resident for the one-launch traversal to make sense).
FOREST_VMEM_BUDGET = 8 << 20

_HIGH = jax.lax.Precision.HIGHEST


class ForestPack(NamedTuple):
    """Inference-shaped ensemble tables, BIN space, split-major.

    (R routing rounds, T trees — padded to the tree_batch multiple, L
    leaf slots, Kc max left-routing category bins, Km max linear leaf
    features). ``default_left``/``movable`` ride as i32 0/1 and
    ``coeff_mask`` as f32 0/1: Mosaic cannot truncate i8/i1 vectors, and
    the f32 mask feeds the oracle-mirroring ``> 0.5`` compare.
    """
    slot: jax.Array          # (R, T) i32 leaf slot split in round r
    feature: jax.Array       # (R, T) i32 INNER feature index (bin matrix)
    tbin: jax.Array          # (R, T) i32 threshold bin (go left: b <= tbin)
    kind: jax.Array          # (R, T) i32 0 numerical / 1 categorical
    default_left: jax.Array  # (R, T) i32 0/1
    miss_bin: jax.Array      # (R, T) i32 movable-missing bin
    movable: jax.Array       # (R, T) i32 0/1 miss_bin overrides the compare
    num_splits: jax.Array    # (T,) i32
    value_of_slot: jax.Array  # (T, L) f32 leaf outputs by slot
    tree_class: jax.Array    # (T,) i32
    cat_bins: jax.Array      # (R, T, Kc) i32 bins routed LEFT, pad -2
    # linear-leaf tables (RAW-space: evaluated against the raw row tile,
    # exactly like linear_values_by_row in the oracle)
    const_of_slot: jax.Array  # (T, L) f32
    coeff: jax.Array          # (T, L, Km) f32
    coeff_feat: jax.Array     # (T, L, Km) i32 inner feature index
    coeff_mask: jax.Array     # (T, L, Km) f32 0/1


def forest_table_bytes(fp: ForestPack) -> int:
    """Device bytes of the resident node tables (the eligibility bound)."""
    return int(sum(np.prod(a.shape) * a.dtype.itemsize for a in fp))


def forest_pack(trees: List, dataset, num_class: int = 1,
                tree_batch: int = 8) -> Tuple[ForestPack, bool, bool]:
    """Pack host trees into BIN-space split-major device tables.

    ``dataset`` supplies the bin mappers (the booster's constructed
    train_set). Raises ``ValueError`` when a split's feature has no inner
    index in the dataset (loaded models splitting on features the
    mappers never saw cannot route in BIN space — the raw oracle path
    serves those). Returns ``(pack, has_cat, has_linear)``.
    """
    from .predict import split_bin_table

    T = max(len(trees), 1)
    pad_t = (-T) % tree_batch
    Tp = T + pad_t
    arrs = [t.to_split_arrays() for t in trees]
    tables = []
    for t, a in zip(trees, arrs):
        tbl = split_bin_table(a, dataset)
        if not bool(tbl["valid"].all()):
            raise ValueError(
                "forest pack: split feature(s) absent from the dataset's "
                "bin mappers (loaded model?) — BIN-space routing undefined")
        tables.append(tbl)
    R = max((len(a["slot"]) for a in arrs), default=0)
    R = max(R, 1)
    L = R + 1
    Kc = max((len(c) for tbl in tables for c in tbl["cat_bins"].values()),
             default=0)
    has_cat = Kc > 0
    Kc = max(Kc, 1)

    slot = np.zeros((Tp, R), np.int32)
    feature = np.zeros((Tp, R), np.int32)
    tbin = np.zeros((Tp, R), np.int32)
    kind = np.zeros((Tp, R), np.int32)
    default_left = np.zeros((Tp, R), np.int32)
    miss_bin = np.zeros((Tp, R), np.int32)
    movable = np.zeros((Tp, R), np.int32)
    num_splits = np.zeros(Tp, np.int32)
    value_of_slot = np.zeros((Tp, L), np.float32)
    tree_class = np.zeros(Tp, np.int32)
    cat_bins = np.full((Tp, R, Kc), -2, np.int64)
    for ti, (t, a, tbl) in enumerate(zip(trees, arrs, tables)):
        r = len(a["slot"])
        num_splits[ti] = r
        tree_class[ti] = ti % num_class
        slot[ti, :r] = a["slot"]
        feature[ti, :r] = tbl["feature"][:r]
        tbin[ti, :r] = tbl["tbin"][:r]
        kind[ti, :r] = a["kind"]
        default_left[ti, :r] = a["default_left"]
        miss_bin[ti, :r] = tbl["miss_bin"][:r]
        movable[ti, :r] = tbl["movable"][:r]
        lv = t.leaf_value[a["leaf_of_slot"][:r + 1]] if t.num_leaves > 1 \
            else t.leaf_value[:1]
        value_of_slot[ti, :len(lv)] = lv
        for rr, bins_left in tbl["cat_bins"].items():
            cat_bins[ti, rr, :len(bins_left)] = bins_left
    from ..linear.pack import linear_pack_arrays
    const_of_slot, coeff, coeff_feat, coeff_mask, has_linear = \
        linear_pack_arrays(trees, arrs, value_of_slot[:T])
    # linear tables come back (T, L, Km); pad trees and remap coeff
    # features to INNER indices (the kernel gathers from the raw tile in
    # inner-feature column order)
    Km = coeff.shape[2]
    cfeat_inner = np.zeros((Tp, L, Km), np.int32)
    if has_linear:
        inner_of = np.array(
            [dataset.inner_feature_index(j)
             for j in range(int(dataset.num_total_features))], np.int64)
        cf = np.asarray(coeff_feat, np.int64)
        mapped = inner_of[np.clip(cf, 0, len(inner_of) - 1)]
        if bool(((mapped < 0) & np.asarray(coeff_mask, bool)).any()):
            raise ValueError(
                "forest pack: linear-leaf feature absent from the "
                "dataset's bin mappers — raw gather column undefined")
        cfeat_inner[:T] = np.where(np.asarray(coeff_mask, bool),
                                   np.clip(mapped, 0, None), 0)

    def _pad(a):
        out = np.zeros((Tp,) + a.shape[1:], a.dtype)
        out[:T] = a
        return out

    fp = ForestPack(
        slot=jnp.asarray(slot.T, jnp.int32),
        feature=jnp.asarray(feature.T, jnp.int32),
        tbin=jnp.asarray(tbin.T, jnp.int32),
        kind=jnp.asarray(kind.T, jnp.int32),
        default_left=jnp.asarray(default_left.T, jnp.int32),
        miss_bin=jnp.asarray(miss_bin.T, jnp.int32),
        movable=jnp.asarray(movable.T, jnp.int32),
        num_splits=jnp.asarray(num_splits, jnp.int32),
        value_of_slot=jnp.asarray(value_of_slot, jnp.float32),
        tree_class=jnp.asarray(tree_class, jnp.int32),
        cat_bins=jnp.asarray(np.transpose(cat_bins, (1, 0, 2)), jnp.int32),
        const_of_slot=jnp.asarray(_pad(np.asarray(const_of_slot)),
                                  jnp.float32),
        coeff=jnp.asarray(_pad(np.asarray(coeff)), jnp.float32),
        coeff_feat=jnp.asarray(cfeat_inner, jnp.int32),
        coeff_mask=jnp.asarray(
            _pad(np.asarray(coeff_mask, np.float32)), jnp.float32))
    return fp, has_cat, bool(has_linear)


def _dot(a: jax.Array, b: jax.Array) -> jax.Array:
    # 0/1 f32 contractions select exactly at HIGHEST (leaf_values_by_row)
    return jax.lax.dot(a, b, precision=_HIGH,
                       preferred_element_type=jnp.float32)


def _halving_sum(rows: List[jax.Array]) -> jax.Array:
    """f32 sum of a static list in XLA's reduce association.

    ``jnp.sum`` written INSIDE the interpreted kernel body lowers to a
    sequential chain, but the oracle's reductions compile to XLA's
    recursive halving over the next power of two with implicit zeros —
    ``((v0+v4)+(v2+v6)) + ((v1+v5)+(v3+v7))`` for 8 terms. Spelling that
    association out (zero pads included, so ``-0.0`` partials flush to
    ``+0.0`` exactly like XLA's) is what makes the kernel's f32 adds land
    bit-identically to ``predict_raw_impl``'s."""
    n = 1
    while n < len(rows):
        n *= 2
    rows = list(rows) + [jnp.zeros_like(rows[0])] * (n - len(rows))
    while len(rows) > 1:
        half = len(rows) // 2
        rows = [rows[i] + rows[i + half] for i in range(half)]
    return rows[0]


def _linear_leaf_values(X, oh, val_t, const_t, coeff_t, cfeat_t, cmask_t):
    """Per-row linear-leaf outputs for one tree, mirroring
    ``linear_values_by_row`` op-for-op (selections are exact, the km
    contraction runs in the oracle's index order) — except the raw-value
    gather, which becomes a NaN-split one-hot contraction: Mosaic has no
    ``take_along_axis``, and gathering value and NaN-mask separately
    keeps the selected bits identical."""
    f32 = jnp.float32
    base = _dot(oh, val_t[:, None])[:, 0]
    cst = _dot(oh, const_t[:, None])[:, 0]
    cf = _dot(oh, coeff_t)                                   # (tile, km)
    fi = _dot(oh, cfeat_t.astype(f32)).astype(jnp.int32)     # (tile, km)
    cm = _dot(oh, cmask_t) > f32(0.5)
    xnan = jnp.isnan(X)
    xz = jnp.where(xnan, f32(0), X)
    xnan_f = xnan.astype(f32)
    km = coeff_t.shape[1]
    zs, nans = [], []
    fiota = jax.lax.broadcasted_iota(jnp.int32, X.shape, 1)
    for k in range(km):
        ohf = (fi[:, k][:, None] == fiota).astype(f32)       # (tile, F)
        # batched 1xF @ Fx1 dot, not an elementwise mask-and-sum: a dot
        # MATERIALIZES, so the gathered value is rounded on its own
        # instead of fusing into the km contraction below (fused, the
        # compiler reassociates across both reduces and the low bit
        # diverges from the oracle's take_along_axis + sum)
        zs.append(jax.lax.dot_general(
            xz[:, None, :], ohf[:, :, None],
            (((2,), (1,)), ((0,), (0,))), precision=_HIGH,
            preferred_element_type=f32)[:, 0, 0])
        nans.append(jnp.sum(xnan_f * ohf, axis=1) > f32(0.5))
    z = jnp.stack(zs, axis=1)                                # (tile, km)
    nan = jnp.stack(nans, axis=1)
    nanrow = jnp.any(nan & cm, axis=1)
    zz = jnp.where(cm & jnp.logical_not(nan), z, f32(0))
    # the oracle's exact expression: an axis-1 mul+reduce lowers to the
    # same halving reduction here as in predict_raw_impl's program (the
    # axis-0 TREE sum does not — see _halving_sum)
    contrib = jnp.sum(zz * cf, axis=1)
    return jnp.where(nanrow, base, cst + contrib)


def forest_predict_impl(bins: jax.Array, X: jax.Array, fp: ForestPack, *,
                        num_class: int = 1, has_cat: bool = False,
                        has_linear: bool = False, tree_batch: int = 8,
                        tile: int = FOREST_TILE,
                        interpret=None) -> jax.Array:
    """(N, F) inner-feature bins (+ raw rows for linear leaves) -> raw
    ensemble scores, byte-identical to ``predict_raw_impl``.

    One kernel program per row tile; all node tables resident. ``X`` is
    only an operand when ``has_linear`` (it is ignored — and never
    shipped into VMEM — otherwise). N is padded up to the tile multiple
    and sliced back. ``interpret=None`` follows the explicit
    ``LGBTPU_PALLAS_INTERPRET`` flag like every other kernel — never the
    backend: off a TPU without the flag this fails to lower, loudly.
    """
    n, F = bins.shape
    R, T = fp.slot.shape
    L = fp.value_of_slot.shape[1]
    K = max(1, int(num_class))
    assert T % tree_batch == 0, (T, tree_batch)
    pad = (-n) % tile
    if pad:
        bins = jnp.concatenate(
            [bins, jnp.zeros((pad, F), bins.dtype)], axis=0)
        if has_linear:
            X = jnp.concatenate(
                [X, jnp.zeros((pad, F), jnp.float32)], axis=0)
    npad = n + pad
    grid = npad // tile
    if interpret is None:
        from .partition import _INTERPRET
        interpret = _INTERPRET

    def kernel(*refs):
        out_ref = refs[-1]
        it = iter(refs[:-1])
        binsf = next(it)[...].astype(jnp.float32)            # (tile, F)
        xraw = next(it)[...] if has_linear else None         # (tile, F)
        slot_t = next(it)[...]                               # (R, T)
        feat_t = next(it)[...]
        tbin_t = next(it)[...]
        kind_t = next(it)[...]
        dl_t = next(it)[...]
        miss_t = next(it)[...]
        mov_t = next(it)[...]
        ns = next(it)[...]                                   # (T,)
        val = next(it)[...]                                  # (T, L)
        cls = next(it)[...]                                  # (T,)
        cat = next(it)[...] if has_cat else None             # (R, T, Kc)
        if has_linear:
            const = next(it)[...]
            coeff = next(it)[...]
            cfeat = next(it)[...]
            cmask = next(it)[...]
        fiota = jax.lax.broadcasted_iota(jnp.int32, (F, T), 0)

        def step(r, front):
            idx = lambda tab: jax.lax.dynamic_index_in_dim(  # noqa: E731
                tab, r, 0, keepdims=False)
            srow, frow, trow = idx(slot_t), idx(feat_t), idx(tbin_t)
            krow, dlrow = idx(kind_t), idx(dl_t)
            mrow, movrow = idx(miss_t), idx(mov_t)
            # gather-to-matmul: per-tree feature column for this round
            oh = (fiota == frow[None, :]).astype(jnp.float32)
            colb = _dot(binsf, oh).astype(jnp.int32)         # (tile, T)
            go = colb <= trow[None, :]
            go = jnp.where((movrow[None, :] == 1) & (colb == mrow[None, :]),
                           dlrow[None, :] == 1, go)
            if has_cat:
                crow = jax.lax.dynamic_index_in_dim(cat, r, 0,
                                                    keepdims=False)
                in_set = jnp.any(colb[:, :, None] == crow[None, :, :],
                                 axis=-1)
                go = jnp.where(krow[None, :] > 0, in_set, go)
            upd = jnp.where((front == srow[None, :]) & ~go, r + 1, front)
            return jnp.where(r < ns[None, :], upd, front)

        front = jax.lax.fori_loop(
            0, R, step, jnp.zeros((tile, T), jnp.int32))     # (tile, T)

        # leaf accumulation mirrors the oracle: static loop over
        # tree_batch groups, per-group sums in XLA's halving association
        # (_halving_sum above), group partials chained in the order the
        # oracle's scan carries them

        liota = jax.lax.broadcasted_iota(jnp.int32, (tile, L), 1)
        if K > 1:
            score = jnp.zeros((tile, K), jnp.float32)
            kiota = jnp.arange(K, dtype=jnp.int32)
        else:
            score = jnp.zeros((tile,), jnp.float32)
        for g in range(T // tree_batch):
            vals_rows = []
            for j in range(tree_batch):
                t = g * tree_batch + j
                oh = (front[:, t][:, None] == liota).astype(jnp.float32)
                if has_linear:
                    v = _linear_leaf_values(xraw, oh, val[t], const[t],
                                            coeff[t], cfeat[t], cmask[t])
                else:
                    v = _dot(oh, val[t][:, None])[:, 0]
                vals_rows.append(v)
            if K > 1:
                cls_g = cls[g * tree_batch:(g + 1) * tree_batch]
                cls_oh = (cls_g[:, None] == kiota[None, :]).astype(
                    jnp.float32)
                vals = jnp.stack(vals_rows, axis=0)          # (tb, tile)
                score = score + vals.T @ cls_oh
            else:
                score = score + _halving_sum(vals_rows)
        out_ref[...] = score[:, None] if K == 1 else score

    def _whole(a):
        nd = a.ndim
        return pl.BlockSpec(a.shape, lambda i, _n=nd: (0,) * _n)

    operands = [bins]
    in_specs = [pl.BlockSpec((tile, F), lambda i: (i, 0))]
    if has_linear:
        operands.append(X.astype(jnp.float32))
        in_specs.append(pl.BlockSpec((tile, F), lambda i: (i, 0)))
    tables = [fp.slot, fp.feature, fp.tbin, fp.kind, fp.default_left,
              fp.miss_bin, fp.movable, fp.num_splits, fp.value_of_slot,
              fp.tree_class]
    if has_cat:
        tables.append(fp.cat_bins)
    if has_linear:
        tables += [fp.const_of_slot, fp.coeff, fp.coeff_feat,
                   fp.coeff_mask]
    operands += tables
    in_specs += [_whole(a) for a in tables]
    kwargs = {}
    if not interpret:  # pragma: no cover - needs real TPU
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))
    out = pl.pallas_call(
        kernel,
        name="forest_predict",
        grid=(grid,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tile, K), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((npad, K), jnp.float32),
        interpret=interpret,
        **kwargs,
    )(*operands)
    out = out[:n]
    return out[:, 0] if num_class <= 1 else out
