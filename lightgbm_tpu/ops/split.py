"""Vectorized best-split search over histograms.

TPU-native replacement of the reference per-feature sequential threshold scan
(reference: src/treelearner/feature_histogram.hpp:858
FindBestThresholdSequentially, :278 FindBestThresholdCategoricalInner). Instead
of a bidirectional pointer walk per feature, the whole ``(features, bins)``
plane is scanned at once with prefix sums; missing-value direction is handled
by evaluating both default-left and default-right assignments; categorical
splits use a one-vs-rest scan (<= max_cat_to_onehot categories) or a
sorted-by-(grad/hess) many-vs-many prefix scan via ``argsort`` over the bin
axis. Everything is shape-static and jit/shard_map friendly.

Split-gain semantics mirror feature_histogram.hpp GetSplitGains /
CalculateSplittedLeafOutput: L1 thresholding, L2, max_delta_step clipping,
path smoothing, and basic monotone-constraint clamping; counts come from the
histogram's dedicated count channel (instead of the reference's
hessian-derived cnt_factor trick, feature_histogram.hpp:316).
"""
from __future__ import annotations

import contextlib
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import runtime
from ..obs import trace_phase

NEG_INF = -jnp.inf
K_EPSILON = 1e-15
# numerical split kinds
KIND_NUMERICAL = 0
KIND_CAT_ONEHOT = 1
KIND_CAT_MVM_ASC = 2
KIND_CAT_MVM_DESC = 3


class FeatureMeta(NamedTuple):
    """Per-feature static metadata as device arrays (F,)."""
    num_bins: jax.Array        # int32 total bins incl. missing bin
    movable_missing: jax.Array # bool: feature has a bin routed with the
                               # missing direction (NaN bin for MISSING_NAN,
                               # zero/default bin for MISSING_ZERO)
    missing_bin: jax.Array     # int32 index of the NaN bin (num_bins-1) or 0
    is_categorical: jax.Array  # bool
    monotone: jax.Array        # int8 in {-1, 0, +1}
    penalty: jax.Array         # float32 split-gain multiplier (feature_contri)
    cegb_coupled: jax.Array    # float32 per-feature coupled CEGB penalty


class SplitHyper(NamedTuple):
    """Static hyperparameters closed over at trace time
    (reference: the Config fields read by FeatureHistogram)."""
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_data_in_leaf: float = 20.0
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    max_delta_step: float = 0.0
    cat_smooth: float = 10.0
    cat_l2: float = 10.0
    max_cat_threshold: int = 32
    max_cat_to_onehot: int = 4
    min_data_per_group: float = 100.0
    path_smooth: float = 0.0
    has_categorical: bool = False
    # the categorical columns of the (3, F, B) view, sorted: the
    # many-against-many search runs over these alone (a column of any other
    # kind has no group, so its gains there are -inf whatever it holds).
    # Empty with ``has_categorical``: every column.
    cat_features: tuple = ()
    has_monotone: bool = False
    # monotone constraint propagation method: basic bounds children by the
    # split midpoint; intermediate by the sibling's output
    # (reference: monotone_constraints.hpp:327 Basic, :463 Intermediate)
    mono_intermediate: bool = False
    # advanced: per-threshold piecewise bounds per (leaf, feature) with an
    # all-leaf refresh at every commit (reference: AdvancedLeafConstraints,
    # monotone_constraints.hpp:856 — reformulated as dense (L, F, B) bound
    # arrays + (L, F) bin-range boxes instead of pointer-walking)
    mono_advanced: bool = False
    # gain multiplier for splits on monotone features, decaying with leaf
    # depth (reference: monotone_constraints.hpp:355
    # ComputeMonotoneSplitGainPenalty)
    monotone_penalty: float = 0.0
    # CEGB (reference: cost_effective_gradient_boosting.hpp:66 DetlaGain)
    cegb_tradeoff: float = 1.0
    cegb_penalty_split: float = 0.0
    use_cegb: bool = False


def scan_phase(hp: SplitHyper, inside: bool = False):
    """The device scope of a node search. A job without categorical columns
    is named whole by its caller, ``lgbtpu/split_scan`` around the call, and
    nothing in here names anything (the ``inside`` sites are no-ops). A job
    WITH them names its phases in here instead, as ``objective.py``'s
    ``names_own_phases`` does for ``rank_*``: ``lgbtpu/cat_scan`` must be a
    SIBLING of ``lgbtpu/split_scan`` (the benchmark books an op to the
    outermost scope of its name), so the caller's wrap stays off and the
    numerical scan, the combine and the winner's sums take ``split_scan``
    from the inside."""
    if hp.has_categorical == inside:
        return trace_phase("lgbtpu/split_scan")
    return contextlib.nullcontext()


class SplitInfo(NamedTuple):
    """Best split for one leaf — fixed-shape device pytree
    (reference analog: src/treelearner/split_info.hpp SplitInfo)."""
    gain: jax.Array          # scalar f32; -inf when no valid split
    feature: jax.Array       # scalar i32 inner feature index
    bin: jax.Array           # scalar i32: threshold bin / category / prefix len
    kind: jax.Array          # scalar i32 KIND_*
    default_left: jax.Array  # scalar bool
    go_left: jax.Array       # (B,) bool bin routing table
    left_sum: jax.Array      # (3,) g,h,cnt
    right_sum: jax.Array     # (3,)
    left_output: jax.Array   # scalar f32
    right_output: jax.Array  # scalar f32


def _threshold_l1(g: jax.Array, l1: float) -> jax.Array:
    return jnp.sign(g) * jnp.maximum(jnp.abs(g) - l1, 0.0)


def calc_leaf_output(g, h, hp: SplitHyper, extra_l2: float = 0.0):
    """CalculateSplittedLeafOutput (feature_histogram.hpp): -TL1(g)/(h+l2),
    clipped by max_delta_step when set."""
    denom = h + hp.lambda_l2 + extra_l2
    w = jnp.where(denom > 0, -_threshold_l1(g, hp.lambda_l1) / jnp.maximum(denom, 1e-38), 0.0)
    if hp.max_delta_step > 0:
        w = jnp.clip(w, -hp.max_delta_step, hp.max_delta_step)
    return w


def _smoothed(w, cnt, parent_output, hp: SplitHyper):
    """Path smoothing (feature_histogram.hpp USE_SMOOTHING branch):
    w' = w * n/(n+smooth) + parent * smooth/(n+smooth)."""
    if hp.path_smooth <= 0:
        return w
    n = jnp.maximum(cnt, 1.0)
    alpha = n / (n + hp.path_smooth)
    return w * alpha + parent_output * (1.0 - alpha)


def _gain_given_output(g, h, w, hp: SplitHyper, extra_l2: float = 0.0):
    """GetLeafGainGivenOutput: -(2 g w + (h+l2) w^2) - 2 l1 |w| — equals
    TL1(g)^2/(h+l2) at the unconstrained optimum."""
    l2 = hp.lambda_l2 + extra_l2
    return -(2.0 * g * w + (h + l2) * w * w) - 2.0 * hp.lambda_l1 * jnp.abs(w)


def leaf_objective_value(g, h, hp: SplitHyper):
    """Gain of keeping a leaf unsplit (GetLeafGain)."""
    w = calc_leaf_output(g, h, hp)
    return _gain_given_output(g, h, w, hp)


def _split_gain_pair(gl, hl, cl, gr, hr, cr, hp: SplitHyper, *,
                     extra_l2=0.0, parent_output=0.0, lower=None, upper=None,
                     monotone=None, child_bounds=None):
    """Gain of a candidate split + the (possibly constrained) child outputs.

    Broadcasts over any leading shape. Returns (gain, w_left, w_right,
    constraint_ok). ``child_bounds``, when given, carries per-candidate
    (lower_l, upper_l, lower_r, upper_r) arrays (the advanced monotone
    method's per-threshold constraints); it overrides the shared
    [lower, upper] clamp."""
    wl = calc_leaf_output(gl, hl, hp, extra_l2)
    wr = calc_leaf_output(gr, hr, hp, extra_l2)
    wl = _smoothed(wl, cl, parent_output, hp)
    wr = _smoothed(wr, cr, parent_output, hp)
    ok = jnp.ones(jnp.broadcast_shapes(jnp.shape(wl), jnp.shape(wr)), dtype=bool)
    if hp.has_monotone and monotone is not None:
        # basic method (reference: monotone_constraints.hpp:327): child outputs
        # must respect the feature's direction and the leaf's inherited bounds
        viol = ((monotone > 0) & (wl > wr)) | ((monotone < 0) & (wl < wr))
        ok = ok & ~viol
        if child_bounds is not None:
            lo_l, up_l, lo_r, up_r = child_bounds
            wl = jnp.clip(wl, lo_l, up_l)
            wr = jnp.clip(wr, lo_r, up_r)
            # per-child bounds can invert the sibling order after clamping
            # (the shared-clamp path cannot); re-check on clamped outputs
            viol2 = ((monotone > 0) & (wl > wr)) | ((monotone < 0) & (wl < wr))
            ok = ok & ~viol2
        elif lower is not None:
            wl = jnp.clip(wl, lower, upper)
            wr = jnp.clip(wr, lower, upper)
    gain = _gain_given_output(gl, hl, wl, hp, extra_l2) + \
        _gain_given_output(gr, hr, wr, hp, extra_l2)
    return gain, wl, wr, ok


# A bin's place in the sorted order of its column is a COUNT while the
# (C, B, B) compare over the C categorical columns is small: how many bins
# sort before it, ties by bin index, which is the stable sort's order. On a
# v5e the two argsorts of a node search, the gather of the histogram by their
# order and the scatter of the winner's table took 35 of expo_cat.train's
# 253 ms an iteration (an XLA gather costs 5-15 ns an index there); the
# compare and a one-hot select are elementwise. Past the limit (C > 64 at
# 256 bins) XLA on a CPU would hold the select in memory (it does not fuse
# it into its sum: 24 B a cell), and the sorts stay. Both forms give the
# same bits, so a wide table with a few categorical columns keeps the count
# whatever its width.
_COUNT_MAX_CELLS = 1 << 22


def _col_runs(cols: tuple, num_feat: int) -> tuple:
    """The sorted static columns ``cols`` (empty: all ``num_feat``) as
    (start, stop) runs of consecutive indices, so that taking them and
    putting them back are slices and a concatenation, never a gather."""
    runs = []
    for c in cols or range(num_feat):
        if runs and runs[-1][1] == c:
            runs[-1][1] = c + 1
        else:
            runs.append([c, c + 1])
    return tuple(map(tuple, runs))


def _cat_width(c: int) -> int:
    """The width the many-against-many search runs at for ``c`` columns:
    XLA:TPU lays the pair's (2, 2, W, B) rank count out with the columns
    minor only where W divides 8 or 8 divides W (v5e compiles of W = 1-24,
    72, 73); at any other width it puts the two sort directions on the
    lanes. One pair search on a v5e: 0.18 ms at six columns against 0.08
    at eight, 0.72 at 73 against 0.14 at 72."""
    return 1 << (c - 1).bit_length() if c <= 4 else -(-c // 8) * 8


def _take_cols(x: jax.Array, runs: tuple, axis: int) -> jax.Array:
    """The columns of ``runs`` along ``axis``, in order."""
    if runs == ((0, x.shape[axis]),):
        return x
    return jnp.concatenate([jax.lax.slice_in_dim(x, a, b, axis=axis)
                            for a, b in runs], axis=axis)


def _put_cols(xc: jax.Array, runs: tuple, num_feat: int, axis: int) -> jax.Array:
    """:func:`_take_cols` undone: ``xc``'s columns at their places along
    ``axis`` of ``num_feat``, every other column -inf."""
    if runs == ((0, num_feat),):
        return xc

    def filled(n):
        shape = list(xc.shape)
        shape[axis] = n
        return jnp.full(shape, NEG_INF, xc.dtype)

    parts, at, done = [], 0, 0
    for a, b in runs + ((num_feat, num_feat),):
        if a > done:
            parts.append(filled(a - done))
        if b > a:
            parts.append(jax.lax.slice_in_dim(xc, at, at + b - a, axis=axis))
        at, done = at + b - a, b
    return jnp.concatenate(parts, axis=axis)


def _stable_rank(keys: jax.Array, b_iota: jax.Array) -> jax.Array:
    """(..., B) sort keys -> (..., B) i32: each bin's position in the stable
    ascending sort. A NaN key (0 / 0: a group without hessian under
    ``cat_smooth=0``) ranks with the unused bins, as +inf."""
    keys = jnp.where(jnp.isnan(keys), jnp.inf, keys)
    mine, other = keys[..., :, None], keys[..., None, :]
    before = (other < mine) | ((other == mine)
                               & (b_iota[None, :] < b_iota[:, None]))
    return jnp.sum(before, axis=-1, dtype=jnp.int32)


def find_best_split(hist: jax.Array, parent_sum: jax.Array,
                    meta: FeatureMeta, feature_mask: jax.Array,
                    hp: SplitHyper, **kw):
    """:func:`find_best_split_planes` over the dense builder's ``(F, B, 3)``
    histogram (``learner.build_tree``, the tests, the references): the
    channels move to the front once, same search, same bits."""
    return find_best_split_planes(jnp.moveaxis(hist, -1, 0), parent_sum,
                                  meta, feature_mask, hp, **kw)


# find_best_split_planes' default arguments are device scalars: defining it
# brings the XLA backend up while the package imports. Asked here first, so
# that the runtime's start has its own record and phase (runtime.start) and
# the package_import record holds Python's importing alone.
runtime.start()


def find_best_split_planes(
    hist: jax.Array,          # (3, F, B) f32: the g, h, count planes
    parent_sum: jax.Array,    # (3,)
    meta: FeatureMeta,
    feature_mask: jax.Array,  # (F,) bool — col sampling / interaction constraints
    hp: SplitHyper,
    *,
    parent_output: jax.Array = jnp.float32(0.0),
    leaf_lower: jax.Array = jnp.float32(-jnp.inf),
    leaf_upper: jax.Array = jnp.float32(jnp.inf),
    rand_threshold: Optional[jax.Array] = None,  # (F,) extra-trees random bins
    want_feature_gains: bool = False,
    cegb_delta: Optional[jax.Array] = None,      # (F,) CEGB gain penalties
    node_depth: Optional[jax.Array] = None,      # scalar i32 leaf depth
    adv_bounds=None,  # advanced monotone: (lo_l, up_l, lo_r, up_r) (F, B)
    # per-candidate child bounds (reference: monotone_constraints.hpp:856
    # AdvancedLeafConstraints — per-threshold constraints in the scan)
) -> SplitInfo:
    """Best split over all features for one leaf's histogram, CHANNEL-MAJOR:
    ``hist[0]``, ``hist[1]``, ``hist[2]`` are the (F, B) planes of gradient
    sums, hessian sums and counts, bins on the minor axis (the split loop's
    shape, ``ops/histogram.py`` ``hist_bins``, cut to the B feature bins).
    Nothing in here builds an array with the channels minor.

    With ``want_feature_gains`` (static), returns only the per-feature max
    gains (F,) — the voting-parallel learner's local vote input (reference:
    voting_parallel_tree_learner.cpp:322 local top-k votes)."""
    with scan_phase(hp, inside=True):
        _, num_feat, num_bin = hist.shape
        b_iota = jnp.arange(num_bin, dtype=jnp.int32)
        bin_valid = b_iota[None, :] < meta.num_bins[:, None]            # (F, B)
        hist = jnp.where(bin_valid[None], hist, 0.0)
        parent_gain = leaf_objective_value(parent_sum[0], parent_sum[1], hp)

        # ---------- numerical thresholds ----------
        is_missing_bin = meta.movable_missing[:, None] & (b_iota[None, :] == meta.missing_bin[:, None])
        miss = jnp.sum(jnp.where(is_missing_bin[None], hist, 0.0), axis=2)     # (3, F)
        hist_nm = jnp.where(is_missing_bin[None], 0.0, hist)
        cum = jnp.cumsum(hist_nm, axis=2)                                # (3, F, B)
        # (g, h, count) of the node, against planes with any leading axes
        total = parent_sum[:, None, None, None]

        def eval_dir(left):
            # left: (3, D, F, B), D the candidates stacked behind the planes
            right = total - left
            gl, hl, cl = left
            gr, hr, cr = right
            gain, _, _, ok = _split_gain_pair(
                gl, hl, cl, gr, hr, cr, hp,
                parent_output=parent_output, lower=leaf_lower, upper=leaf_upper,
                monotone=meta.monotone[:, None] if hp.has_monotone else None,
                child_bounds=adv_bounds)
            ok = ok & (cl >= hp.min_data_in_leaf) & (cr >= hp.min_data_in_leaf) \
                & (hl >= hp.min_sum_hessian_in_leaf) & (hr >= hp.min_sum_hessian_in_leaf)
            return jnp.where(ok, gain - parent_gain, NEG_INF)

        # threshold t means bins <= t go left; missing assigned per direction.
        # Both directions ride ONE stacked (2, F, B) eval — _split_gain_pair
        # broadcasts over leading axes, so this halves the per-round op chain
        # the 254-round scan dispatches (split-scan diet).
        t_valid = (b_iota[None, :] < meta.num_bins[:, None] - 1) & ~meta.is_categorical[:, None]
        if rand_threshold is not None:
            # extra-trees: only one random threshold per feature is considered
            # (reference: USE_RAND_SPLIT in FindBestThresholdSequentially)
            t_valid = t_valid & (b_iota[None, :] == rand_threshold[:, None])
        gains2 = eval_dir(jnp.stack([cum, cum + miss[:, :, None]], axis=1))
        # nothing to gain from dl when there is no missing mass (no ROW: a
        # bundled feature's missing bin is total - sum(own), whose g and h
        # keep the rounding of that sum where its count is exactly 0);
        # keep dr on ties
        has_miss = meta.movable_missing & (miss[2] > 0)
        gains2 = jnp.where(
            jnp.stack([t_valid, t_valid & has_miss[:, None]], axis=0),
            gains2, NEG_INF)
        gain_dr, gain_dl = gains2[0], gains2[1]
        num_gain = jnp.maximum(gain_dr, gain_dl)                 # (F, B)
        num_dl = gain_dl > gain_dr

    # ---------- categorical ----------
    if hp.has_categorical:
        with trace_phase("lgbtpu/cat_scan"):
            extra_l2 = hp.cat_l2
            # candidate categories exclude the trailing other/missing bin
            cat_bin_ok = meta.is_categorical[:, None] & (b_iota[None, :] < meta.num_bins[:, None] - 1)

            # one-vs-rest (reference: one-hot when #cats <= max_cat_to_onehot)
            num_cats = meta.num_bins - 1
            use_onehot = meta.is_categorical & (num_cats <= hp.max_cat_to_onehot)
            left = hist
            right = total[:, 0] - left
            oh_gain, _, _, _ = _split_gain_pair(
                left[0], left[1], left[2], right[0], right[1], right[2], hp,
                extra_l2=extra_l2, parent_output=parent_output)
            oh_ok = (left[2] >= hp.min_data_in_leaf) & (right[2] >= hp.min_data_in_leaf) \
                & (left[1] >= hp.min_sum_hessian_in_leaf) \
                & (right[1] >= hp.min_sum_hessian_in_leaf) \
                & cat_bin_ok & use_onehot[:, None] & (left[2] > 0)
            oh_gain = jnp.where(oh_ok, oh_gain - parent_gain, NEG_INF)

            # many-vs-many: sort categories by g/(h+cat_smooth), scan prefixes
            # (reference: FindBestThresholdCategoricalInner sorted scan), over
            # the C categorical columns alone: a static take, (3, C, B), and
            # zero columns that hold no group up to the width W
            runs = _col_runs(hp.cat_features, num_feat)
            hist_c = _take_cols(hist, runs, 1)
            group_ok = _take_cols(cat_bin_ok & ~use_onehot[:, None], runs, 0) \
                & (hist_c[2] >= hp.min_data_per_group)
            num_cat = hist_c.shape[1]
            width = _cat_width(num_cat)
            if width > num_cat:
                hist_c = jnp.pad(hist_c, ((0, 0), (0, width - num_cat), (0, 0)))
                group_ok = jnp.pad(group_ok, ((0, width - num_cat), (0, 0)))
            # the two sort keys of a column: ascending and, negated, descending;
            # a bin that is no group sorts last either way
            ratio = hist_c[0] / (hist_c[1] + hp.cat_smooth)
            keys2 = jnp.stack([jnp.where(group_ok, ratio, jnp.inf),
                               jnp.where(group_ok, -ratio, jnp.inf)], axis=0)
            by_count = width * num_bin * num_bin <= _COUNT_MAX_CELLS
            if by_count:
                rank2 = _stable_rank(keys2, b_iota)                      # (2, W, B)
            else:
                order2 = jnp.argsort(keys2, axis=2)
            n_groups = jnp.sum(group_ok, axis=1)                         # (W,)

            def mvm_gains():
                # both sort directions in ONE stacked (2, W, B) eval, same
                # collapse as the numerical missing-direction pair above
                if by_count:
                    # the bin of rank k, by a one-hot select: one term a sum
                    at = rank2[:, :, None, :] == b_iota[None, None, :, None]
                    h_sorted = jnp.sum(jnp.where(
                        at[None], hist_c[:, None, :, None, :], 0.0), axis=4)
                else:
                    h_sorted = jnp.take_along_axis(hist_c[:, None], order2[None],
                                                   axis=3)             # (3, 2, W, B)
                # prefix of k+1 bins: a product with a triangle of ones (the MXU
                # in f32, the same op for both forms) where a cumsum over 256
                # becomes a two-level reduce-window that XLA gives no op_name
                # and that costs more: 8.6 of expo_cat.train's 228 ms an
                # iteration outside every scope, 1.3 as this product (chip
                # runs, PR 35; the gains sit as close to the float64
                # reference either way)
                csum = jnp.einsum(
                    "kj,cdfj->cdfk", (b_iota[:, None] >= b_iota[None, :])
                    .astype(jnp.float32), h_sorted,
                    precision=jax.lax.Precision.HIGHEST)
                k1 = b_iota[None, :] + 1.0                               # prefix size
                left = csum
                right = total - left
                gain, _, _, _ = _split_gain_pair(
                    left[0], left[1], left[2], right[0], right[1], right[2], hp,
                    extra_l2=extra_l2, parent_output=parent_output)
                ok = (k1 <= hp.max_cat_threshold) & (k1 < n_groups[:, None]) \
                    & (left[2] >= hp.min_data_in_leaf) & (right[2] >= hp.min_data_in_leaf) \
                    & (left[1] >= hp.min_sum_hessian_in_leaf) \
                    & (right[1] >= hp.min_sum_hessian_in_leaf)
                # back to (2, F, B): every other column -inf
                gains = jnp.where(ok, gain - parent_gain, NEG_INF)
                return _put_cols(gains[:, :num_cat], runs, num_feat, 1)

            mvm_asc, mvm_desc = mvm_gains()
            num_gain = jnp.where(meta.is_categorical[:, None], NEG_INF, num_gain)
    else:
        oh_gain = jnp.full_like(num_gain, NEG_INF)
        mvm_asc = jnp.full_like(num_gain, NEG_INF)
        mvm_desc = jnp.full_like(num_gain, NEG_INF)
        num_gain = jnp.where(meta.is_categorical[:, None], NEG_INF, num_gain)

    with scan_phase(hp, inside=True):
        # ---------- combine ----------
        # One live-lane mask and ONE final select instead of a chain of
        # per-adjustment wheres over the full (4, F, B) plane: every adjustment
        # runs unguarded on the adjusted values (keeping the reference op order
        # gain*penalty, *mono_pen, -cegb — bit-identical on live lanes) and
        # dead lanes are forced to -inf once at the end.
        stacked = jnp.stack([num_gain, oh_gain, mvm_asc, mvm_desc], axis=0)  # (4, F, B)
        live = (stacked > NEG_INF) & feature_mask[None, :, None]
        adj = stacked * meta.penalty[None, :, None]
        if hp.has_monotone and hp.monotone_penalty > 0 and node_depth is not None:
            # reference: monotone_constraints.hpp:355 — splits on monotone
            # features at shallow depths are discounted (and forbidden while
            # penalization >= depth + 1)
            p = jnp.float32(hp.monotone_penalty)
            d = node_depth.astype(jnp.float32)
            eps = jnp.float32(K_EPSILON)
            pen = jnp.where(p >= d + 1.0, eps,
                            jnp.where(p <= 1.0, 1.0 - p / (2.0 ** d) + eps,
                                      1.0 - 2.0 ** (p - 1.0 - d) + eps))
            mono_f = meta.monotone != 0
            adj = jnp.where(mono_f[None, :, None], adj * pen, adj)
        if hp.use_cegb and cegb_delta is not None:
            adj = adj - cegb_delta[None, :, None]
        stacked = jnp.where(live, adj, NEG_INF)
        if want_feature_gains:
            return jnp.max(stacked, axis=(0, 2))                 # (F,)
        flat = stacked.reshape(-1)
        best_idx = jnp.argmax(flat)
        best_gain = flat[best_idx]
        kind = (best_idx // (num_feat * num_bin)).astype(jnp.int32)
        rem = best_idx % (num_feat * num_bin)
        feat = (rem // num_bin).astype(jnp.int32)
        tbin = (rem % num_bin).astype(jnp.int32)

        # ---------- routing table for the winner ----------
        def tbl_numerical():
            base = b_iota <= tbin
            dl = num_dl[feat, tbin]
            base = jnp.where(meta.movable_missing[feat] & (b_iota == meta.missing_bin[feat]),
                             dl, base)
            return base, dl

        def tbl_onehot():
            return b_iota == tbin, jnp.bool_(False)

        def tbl_mvm(direction):
            # the first (tbin + 1) bins of the winner's sorted order go left;
            # the winner's row of the (2, W, B) order, in range for any feat
            fc = feat
            if num_cat < num_feat:
                pos = np.zeros(num_feat, np.int32)
                pos[list(hp.cat_features)] = np.arange(num_cat)
                fc = jnp.asarray(pos, jnp.int32)[feat]
            if by_count:
                return rank2[direction, fc] <= tbin, jnp.bool_(False)
            row = order2[direction, fc]
            tbl = jnp.zeros((num_bin,), bool).at[row].set(b_iota <= tbin)
            return tbl, jnp.bool_(False)

    if hp.has_categorical:
        # the winner's table: an mvm winner scatters its prefix by the sort
        # order, so the whole pick belongs to the categorical search
          with trace_phase("lgbtpu/cat_scan"):
            go_left, default_left = jax.lax.switch(
                kind,
                [lambda: tbl_numerical(), lambda: tbl_onehot(),
                 lambda: tbl_mvm(0), lambda: tbl_mvm(1)],
            )
    else:
        go_left, default_left = tbl_numerical()

    with scan_phase(hp, inside=True):
        left_sum = jnp.sum(jnp.where(go_left[None, :], hist[:, feat], 0.0), axis=1)
        right_sum = parent_sum - left_sum
        is_cat_win = kind > 0
        extra = jnp.where(is_cat_win, hp.cat_l2, 0.0)
        wl = _smoothed(calc_leaf_output(left_sum[0], left_sum[1], hp, extra),
                       left_sum[2], parent_output, hp)
        wr = _smoothed(calc_leaf_output(right_sum[0], right_sum[1], hp, extra),
                       right_sum[2], parent_output, hp)
        if hp.has_monotone:
            if adv_bounds is not None:
                lo_l, up_l, lo_r, up_r = adv_bounds
                wl = jnp.clip(wl, lo_l[feat, tbin], up_l[feat, tbin])
                wr = jnp.clip(wr, lo_r[feat, tbin], up_r[feat, tbin])
            else:
                wl = jnp.clip(wl, leaf_lower, leaf_upper)
                wr = jnp.clip(wr, leaf_lower, leaf_upper)

        valid = best_gain > jnp.float32(hp.min_gain_to_split)
        best_gain = jnp.where(valid, best_gain, NEG_INF)
        return SplitInfo(
            gain=best_gain.astype(jnp.float32),
            feature=feat,
            bin=tbin,
            kind=kind,
            default_left=default_left,
            go_left=go_left,
            left_sum=left_sum,
            right_sum=right_sum,
            left_output=wl.astype(jnp.float32),
            right_output=wr.astype(jnp.float32),
        )
