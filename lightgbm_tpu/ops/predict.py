"""Batched on-device prediction over packed tree arrays.

TPU-native replacement for the reference's per-row pointer walk
(reference: include/LightGBM/tree.h:133 Tree::Predict,
src/boosting/gbdt_prediction.cpp, src/application/predictor.hpp:29).

Design: every tree flattens into leaf-slot split order
(Tree.to_split_arrays — the learner's TreeLog convention), and rows are
routed ARITHMETICALLY: split r tests raw values against its threshold and
moves non-left rows from slot[r] to slot r+1. No per-row pointer chasing,
no table gathers (TPU element gathers are ~60ns/row); every step is a
bandwidth-bound elementwise op over all rows, batched over trees with vmap.
Missing handling mirrors tree.h NumericalDecision: NaN follows the default
direction for MissingType::NaN, otherwise becomes 0; zeros follow the
default direction for MissingType::Zero. Categorical splits test set
membership against padded category tables.

Routing works on RAW feature values, so it serves trained boosters and
models loaded from reference-format text identically (no bin mappers
needed).
"""
from __future__ import annotations

from typing import List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import track_jit

K_ZERO = 1e-35


class PackedSplits(NamedTuple):
    """(T trees, R max splits, L max leaves, Kc max categories, Km max
    linear leaf features)"""
    slot: jax.Array          # (T, R) i32
    feature: jax.Array       # (T, R) i32 column index into X
    threshold: jax.Array     # (T, R) f32
    kind: jax.Array          # (T, R) i32  0 numerical / 1 categorical
    default_left: jax.Array  # (T, R) bool
    missing_type: jax.Array  # (T, R) i32
    num_splits: jax.Array    # (T,) i32
    value_of_slot: jax.Array  # (T, L) f32 leaf outputs by slot
    tree_class: jax.Array    # (T,) i32
    cat_values: jax.Array    # (T, R, Kc) i32, padded with -2 (never matches)
    # linear-leaf tables (lightgbm_tpu/linear/pack.py): non-linear trees
    # carry const == value and an all-false mask, which evaluates to the
    # plain leaf output — one program shape serves mixed ensembles
    const_of_slot: jax.Array  # (T, L) f32 linear constant terms by slot
    coeff: jax.Array          # (T, L, Km) f32 leaf coefficients
    coeff_feat: jax.Array     # (T, L, Km) i32 column index into X
    coeff_mask: jax.Array     # (T, L, Km) bool valid coefficient slots


def pack_splits(trees: List, num_class: int = 1) -> PackedSplits:
    """Pack host Tree models into device arrays (raw-value routing).
    Returns ``(pack, has_cat, has_linear)``."""
    T = max(len(trees), 1)
    arrs = [t.to_split_arrays() for t in trees] or \
        [dict(slot=np.zeros(0, np.int32), feature=np.zeros(0, np.int32),
              threshold=np.zeros(0), kind=np.zeros(0, np.int32),
              default_left=np.zeros(0, bool), missing_type=np.zeros(0, np.int32),
              cat_values={}, leaf_of_slot=np.zeros(1, np.int32))]
    R = max((len(a["slot"]) for a in arrs), default=0)
    R = max(R, 1)
    L = R + 1
    Kc = max((len(v) for a in arrs for v in a["cat_values"].values()),
             default=0)
    has_cat = Kc > 0
    Kc = max(Kc, 1)

    slot = np.zeros((T, R), np.int32)
    feature = np.zeros((T, R), np.int32)
    threshold = np.zeros((T, R), np.float32)
    kind = np.zeros((T, R), np.int32)
    default_left = np.zeros((T, R), bool)
    missing_type = np.zeros((T, R), np.int32)
    num_splits = np.zeros(T, np.int32)
    value_of_slot = np.zeros((T, L), np.float32)
    tree_class = np.zeros(T, np.int32)
    cat_values = np.full((T, R, Kc), -2, np.int64)
    for ti, (t, a) in enumerate(zip(trees, arrs)):
        r = len(a["slot"])
        num_splits[ti] = r
        tree_class[ti] = ti % num_class
        slot[ti, :r] = a["slot"]
        feature[ti, :r] = a["feature"]
        threshold[ti, :r] = a["threshold"]
        kind[ti, :r] = a["kind"]
        default_left[ti, :r] = a["default_left"]
        missing_type[ti, :r] = a["missing_type"]
        lv = t.leaf_value[a["leaf_of_slot"][:r + 1]] if t.num_leaves > 1 \
            else t.leaf_value[:1]
        value_of_slot[ti, :len(lv)] = lv
        for rr, cats in a["cat_values"].items():
            cat_values[ti, rr, :len(cats)] = cats
    from ..linear.pack import linear_pack_arrays
    const_of_slot, coeff, coeff_feat, coeff_mask, has_linear = \
        linear_pack_arrays(trees, arrs, value_of_slot)
    pk = PackedSplits(
        slot=jnp.asarray(slot, jnp.int32),
        feature=jnp.asarray(feature, jnp.int32),
        threshold=jnp.asarray(threshold, jnp.float32),
        kind=jnp.asarray(kind, jnp.int32),
        default_left=jnp.asarray(default_left, jnp.bool_),
        missing_type=jnp.asarray(missing_type, jnp.int32),
        num_splits=jnp.asarray(num_splits, jnp.int32),
        value_of_slot=jnp.asarray(value_of_slot, jnp.float32),
        tree_class=jnp.asarray(tree_class, jnp.int32),
        cat_values=jnp.asarray(cat_values, jnp.int32),
        const_of_slot=jnp.asarray(const_of_slot, jnp.float32),
        coeff=jnp.asarray(coeff, jnp.float32),
        coeff_feat=jnp.asarray(coeff_feat, jnp.int32),
        coeff_mask=jnp.asarray(coeff_mask, jnp.bool_))
    return pk, has_cat, has_linear


def _route_tree(X, tp, has_cat: bool):
    """Route all rows through one packed tree -> (N,) leaf slots."""
    n = X.shape[0]
    max_r = tp.slot.shape[0]

    def step(r, row_slot):
        active = r < tp.num_splits
        col = jnp.take(X, tp.feature[r], axis=1)
        mt = tp.missing_type[r]
        nan = jnp.isnan(col)
        v = jnp.where(nan & (mt != 2), 0.0, col)
        go = v <= tp.threshold[r]
        go = jnp.where((mt == 2) & nan, tp.default_left[r], go)
        go = jnp.where((mt == 1) & (jnp.abs(v) <= K_ZERO),
                       tp.default_left[r], go)
        if has_cat:
            # upstream's CategoricalDecision: NaN right, truncated toward
            # zero, negative right (-2.7 truncates to the padding's -2)
            iv = jnp.where(jnp.isfinite(col), col, -1.0).astype(jnp.int32)
            in_set = jnp.any(iv[:, None] == tp.cat_values[r][None, :], axis=1) \
                & (iv >= 0)
            go = jnp.where(tp.kind[r] > 0, in_set, go)
        upd = jnp.where((row_slot == tp.slot[r]) & ~go, r + 1, row_slot)
        return jnp.where(active, upd, row_slot)

    return jax.lax.fori_loop(0, max_r, step, jnp.zeros((n,), jnp.int32))


def predict_raw_impl(X: jax.Array, pack: PackedSplits, *, num_class: int = 1,
                     has_cat: bool = False, has_linear: bool = False,
                     tree_batch: int = 8, init_score=None) -> jax.Array:
    """(N, F) raw rows -> (N,) or (N, K) raw ensemble scores.

    Un-jitted body shared by the training-path ``predict_raw`` below and
    the serving path's shape-bucketed jit (serve/session.py) — both wrap
    it with their own ``jax.jit`` + ``track_jit`` label so compile counts
    stay attributable per entry point."""
    from ..learner import leaf_values_by_row
    from ..linear.pack import linear_values_by_row

    n = X.shape[0]
    X = X.astype(jnp.float32)
    T = pack.slot.shape[0]
    pad_t = (-T) % tree_batch
    if pad_t:
        pack = jax.tree.map(
            lambda a: jnp.concatenate(
                [a, jnp.zeros((pad_t,) + a.shape[1:], a.dtype)]), pack)
    num_l = pack.value_of_slot.shape[1]
    grouped = jax.tree.map(
        lambda a: a.reshape(-1, tree_batch, *a.shape[1:]), pack)

    def one_batch(score, tb):
        slots = jax.vmap(lambda tp: _route_tree(X, tp, has_cat))(tb)  # (tb, N)
        if has_linear:
            vals = jax.vmap(
                lambda tp, s: linear_values_by_row(X, s, tp, num_l))(
                    tb, slots)                                        # (tb, N)
        else:
            vals = jax.vmap(lambda lv, s: leaf_values_by_row(lv, s, num_l))(
                tb.value_of_slot, slots)                              # (tb, N)
        # unsplit and padding trees both carry all-zero slot values
        if num_class > 1:
            cls_oh = (tb.tree_class[:, None]
                      == jnp.arange(num_class, dtype=jnp.int32)[None, :]
                      ).astype(jnp.float32)
            score = score + vals.T @ cls_oh
        else:
            score = score + jnp.sum(vals, axis=0)
        return score, None

    shape = (n, num_class) if num_class > 1 else (n,)
    score0 = jnp.zeros(shape, jnp.float32)
    if init_score is not None:
        score0 = score0 + init_score
    score, _ = jax.lax.scan(one_batch, score0, grouped)
    return score


predict_raw = track_jit("ops/predict_raw", jax.jit(
    predict_raw_impl,
    static_argnames=("num_class", "has_cat", "has_linear", "tree_batch")))


def split_bin_table(a, dataset):
    """Per-split BIN-space routing quantities for one tree's
    ``to_split_arrays`` dict, from which ``tree_to_bin_log`` builds the
    go_left tables for ``assign_leaves``.

    Returns a dict of per-split arrays — ``feature`` (inner index),
    ``tbin`` (threshold bin: go left iff ``bin <= tbin``), ``miss_bin``/
    ``movable`` (missing-bin override), ``valid`` (False where the split
    feature has no inner index in the dataset) — plus ``cat_bins``
    mapping categorical split index -> bins routed LEFT."""
    from .binning import BIN_CATEGORICAL, MISSING_NAN, MISSING_ZERO

    r = len(a["slot"])
    feature = np.zeros(r, np.int32)
    tbin = np.zeros(r, np.int32)
    miss_bin = np.zeros(r, np.int32)
    movable = np.zeros(r, bool)
    valid = np.ones(r, bool)
    cat_bins = {}
    for i in range(r):
        inner = dataset.inner_feature_index(int(a["feature"][i]))
        if inner < 0:
            valid[i] = False
            continue
        m = dataset.bin_mappers[inner]
        feature[i] = inner
        if a["kind"][i]:
            cats = a["cat_values"].get(i, np.array([], np.int64))
            cat_bins[i] = np.flatnonzero(
                np.isin(m.categories, cats)).astype(np.int64)
        else:
            tb = int(np.searchsorted(m.upper_bounds, float(a["threshold"][i]),
                                     side="left"))
            tb = min(tb, m.num_bins - 1)
            tbin[i] = tb
            if m.missing_type in (MISSING_ZERO, MISSING_NAN) \
                    and m.bin_type != BIN_CATEGORICAL:
                miss_bin[i] = m.missing_bin
                movable[i] = True
    return dict(feature=feature, tbin=tbin, miss_bin=miss_bin,
                movable=movable, valid=valid, cat_bins=cat_bins)


def tree_to_bin_log(tree, dataset):
    """Convert a host Tree into a TreeLog-compatible record routing in BIN
    space over the dataset's (bundled) training matrix — lets DART score
    replay, rollback and continued-training valid replay reuse
    ``assign_leaves`` on device instead of walking trees in Python
    (reference analogs: dart.hpp score updates, gbdt.cpp:454
    RollbackOneIter)."""
    from ..learner import TreeLog

    a = tree.to_split_arrays()
    r = len(a["slot"])
    num_bin = int(dataset.feature_num_bins().max()) if dataset.num_features \
        else 1
    # pad split count to a power-of-two bucket so assign_leaves compiles a
    # handful of signatures instead of one per distinct tree size
    rp = 16
    while rp < r:
        rp *= 2
    tbl_r = split_bin_table(a, dataset)
    feature = np.zeros(rp, np.int32)
    tbin = np.zeros(rp, np.int32)
    kind = np.zeros(rp, np.int32)
    miss_bin = np.zeros(rp, np.int32)
    movable = np.zeros(rp, bool)
    go_left = np.zeros((rp, num_bin), bool)
    b_iota = np.arange(num_bin)
    feature[:r] = tbl_r["feature"]
    tbin[:r] = tbl_r["tbin"]
    miss_bin[:r] = tbl_r["miss_bin"]
    movable[:r] = tbl_r["movable"]
    for i in range(r):
        if not tbl_r["valid"][i]:
            continue
        if a["kind"][i]:
            kind[i] = 1
            go_left[i, tbl_r["cat_bins"][i]] = True
        else:
            tbl = b_iota <= tbin[i]
            if movable[i]:
                tbl = tbl.copy()
                tbl[miss_bin[i]] = bool(a["default_left"][i])
            go_left[i] = tbl
    slot = np.zeros(rp, np.int32)
    slot[:r] = a["slot"]
    default_left = np.zeros(rp, bool)
    default_left[:r] = a["default_left"]
    leaf_value = np.zeros(rp + 1, np.float32)
    leaf_value[:r + 1] = tree.leaf_value[a["leaf_of_slot"][:r + 1]] \
        if r else tree.leaf_value[:1]
    return TreeLog(
        num_splits=jnp.int32(r),
        split_leaf=jnp.asarray(slot, jnp.int32),
        feature=jnp.asarray(feature, jnp.int32),
        bin=jnp.asarray(tbin, jnp.int32),
        kind=jnp.asarray(kind, jnp.int32),
        default_left=jnp.asarray(default_left, jnp.bool_),
        gain=jnp.zeros(rp, jnp.float32),
        left_sum=jnp.zeros((rp, 3), jnp.float32),
        right_sum=jnp.zeros((rp, 3), jnp.float32),
        go_left=jnp.asarray(go_left, jnp.bool_),
        miss_bin=jnp.asarray(miss_bin, jnp.int32),
        movable=jnp.asarray(movable, jnp.bool_),
        leaf_value=jnp.asarray(leaf_value, jnp.float32),
        leaf_sum=jnp.zeros((rp + 1, 3), jnp.float32),
        row_leaf=jnp.zeros(1, jnp.int32),
    )
