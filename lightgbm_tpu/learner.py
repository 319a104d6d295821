"""Leaf-wise tree growth, fully on device.

TPU-native replacement of the reference's SerialTreeLearner hot loop
(reference: src/treelearner/serial_tree_learner.cpp:158 Train, :324
FindBestSplits, :564 SplitInner) and of the Data/Feature-parallel learners'
collective hooks (src/treelearner/data_parallel_tree_learner.cpp:155). Design
differences, by intent (SURVEY.md §7):

- The whole per-tree split loop runs inside ONE jitted ``lax.while_loop`` —
  no host round-trips per split, no dynamic shapes, one compilation per
  (N, F, B, num_leaves) signature. The reference keeps this loop in C++ and
  pays a kernel launch per phase; XLA fuses ours.
- ``DataPartition`` (data_partition.hpp) index shuffling is replaced by a
  ``row_leaf`` int32 vector: a split is a masked vector update, no data
  movement.
- The smaller/larger-leaf histogram-subtraction trick
  (serial_tree_learner.cpp:418: parent − smaller = larger) is kept: one
  masked histogram pass per split round for the smaller child only.
- Distribution: rows shard over a 1-D mesh; every histogram / root-sum is
  wrapped in ``comm.psum`` so the same builder runs single-chip (no-op comm)
  or under ``shard_map`` with XLA collectives over ICI — the seam the
  reference implements with Network::ReduceScatter + SyncUpGlobalBestSplit.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import runtime
from .config import Config
from .dataset import BinnedDataset, BundleView
from .obs import trace_phase, track_jit
from .ops.histogram import build_histogram, hist_bins
from .ops.split import (
    FeatureMeta,
    SplitHyper,
    SplitInfo,
    calc_leaf_output,
    find_best_split,
    find_best_split_planes,
    scan_phase,
)
from .tree import Tree
from .utils.log import Log


class Comm:
    """Collective seam (reference analog: static class Network,
    include/LightGBM/network.h:89, and the per-strategy hooks of the
    Data/Feature/Voting-parallel tree learners). ``axis=None`` = single
    device no-op; otherwise collectives run over the named mesh axis
    inside shard_map.

    Modes (reference: src/treelearner/tree_learner.cpp:15 factory):
    - ``serial``/``data``: rows sharded; histograms are globally reduced
      (data_parallel_tree_learner.cpp:169) and every shard computes the
      same best split — no split sync needed.
    - ``feature``: rows REPLICATED, the split SEARCH is sharded by feature
      ownership; the winning SplitInfo is argmax-synced across shards
      (feature_parallel_tree_learner.cpp:40, parallel_tree_learner.h:191
      SyncUpGlobalBestSplit).
    - ``voting``: rows sharded, histograms stay LOCAL; shards vote local
      top-k features, the global top-2k features' histograms are merged,
      and the best split comes from the merged histograms — comm volume is
      O(top_k * B) per round instead of O(F * B)
      (voting_parallel_tree_learner.cpp:151 GlobalVoting).
    """

    def __init__(self, axis: Optional[str] = None, mode: Optional[str] = None,
                 top_k: int = 20, num_machines: int = 1,
                 hist_scatter: bool = True) -> None:
        self.axis = axis
        self.mode = mode or ("data" if axis else "serial")
        self.top_k = int(top_k)
        self.num_machines = int(num_machines)
        # comm-optimal data-parallel: reduce-scatter histograms by feature
        # GROUP blocks + per-shard owned-feature search + argmax split sync
        # (reference: data_parallel_tree_learner.cpp:155-251 ReduceScatter +
        # FindBestSplits over owned features + SyncUpGlobalBestSplit).
        # Halves histogram comm bytes vs full psum and divides scan work.
        self.hist_scatter = bool(hist_scatter) and self.mode == "data" \
            and axis is not None and self.num_machines > 1

    def psum(self, x):
        if self.axis is None:
            return x
        return jax.lax.psum(x, self.axis)

    def _gpad(self, g: int) -> int:
        d = self.num_machines
        return -(-g // d) * d

    def hist(self, h):
        """Leaf-histogram reduction of a channel-major (3, G, Bp) histogram:
        reduce-scatter by group blocks (axis 1) for data-parallel (each
        shard owns [idx*blk, (idx+1)*blk) re-embedded into the full shape,
        zeros elsewhere); identity when rows are replicated (feature) or
        hists stay local (voting)."""
        if self.axis is None or self.mode in ("feature", "voting"):
            return h
        if self.hist_scatter:
            g = h.shape[1]
            gpad = self._gpad(g)
            blk = gpad // self.num_machines
            hp = jnp.pad(h, ((0, 0), (0, gpad - g), (0, 0)))
            sc = jax.lax.psum_scatter(hp, self.axis, scatter_dimension=1,
                                      tiled=True)
            idx = jax.lax.axis_index(self.axis)
            out = jax.lax.dynamic_update_slice(
                jnp.zeros_like(hp), sc, (0, idx * blk, 0))
            return out[:, :g]
        return jax.lax.psum(h, self.axis)

    def owned_group_mask(self, feat_group, num_groups: int):
        """(F,) bool: this shard owns feature f's histogram block (data
        mode with hist_scatter); None otherwise. ``num_groups`` must be the
        static bundled-column count so the block size matches hist()."""
        if not self.hist_scatter:
            return None
        idx = jax.lax.axis_index(self.axis)
        blk = self._gpad(num_groups) // self.num_machines
        return (feat_group >= idx * blk) & (feat_group < (idx + 1) * blk)

    def root(self, x):
        """Root gradient-sum reduction (replicated rows: identity)."""
        if self.axis is None or self.mode == "feature":
            return x
        return jax.lax.psum(x, self.axis)

    def owned_mask(self, num_feat: int):
        """Feature-parallel search ownership (reference balances by bin
        count, feature_parallel_tree_learner.cpp:40; modulo striping gives
        the same asymptotic balance)."""
        if self.mode != "feature" or self.axis is None:
            return None
        idx = jax.lax.axis_index(self.axis)
        return (jnp.arange(num_feat, dtype=jnp.int32)
                % self.num_machines) == idx

    def sync_split(self, info):
        """Broadcast the globally-best SplitInfo (SyncUpGlobalBestSplit,
        parallel_tree_learner.h:191): allgather gains, argmax (ties to the
        lowest shard), then a masked psum carries every field over. Used by
        feature-parallel and by scatter-mode data-parallel (each shard
        searched only its owned feature blocks)."""
        if self.axis is None or not (self.mode == "feature"
                                     or self.hist_scatter):
            return info
        idx = jax.lax.axis_index(self.axis)
        gains = jax.lax.all_gather(info.gain, self.axis)          # (D,)
        win = jnp.argmax(jnp.where(jnp.isnan(gains), -jnp.inf, gains))
        mine = (idx == win).astype(jnp.float32)

        def bcast(x):
            guarded = jnp.where(jnp.isfinite(x.astype(jnp.float32)),
                                x.astype(jnp.float32), 0.0) \
                if x.dtype == jnp.float32 else x.astype(jnp.float32)
            out = jax.lax.psum(guarded * mine, self.axis)
            if x.dtype == jnp.float32:
                # restore -inf gains the masking zeroed out
                neg = jax.lax.psum(
                    jnp.isneginf(x.astype(jnp.float32)).astype(jnp.float32)
                    * mine, self.axis) > 0.5
                out = jnp.where(neg, -jnp.inf, out)
            return out.astype(x.dtype)

        return jax.tree.map(bcast, info)


class TreeLog(NamedTuple):
    """Device-side record of one grown tree (host rebuilds a Tree from it)."""
    num_splits: jax.Array     # scalar i32
    split_leaf: jax.Array     # (L-1,) i32
    feature: jax.Array        # (L-1,) i32
    bin: jax.Array            # (L-1,) i32
    kind: jax.Array           # (L-1,) i32
    default_left: jax.Array   # (L-1,) bool
    gain: jax.Array           # (L-1,) f32
    left_sum: jax.Array       # (L-1, 3) f32
    right_sum: jax.Array      # (L-1, 3) f32
    go_left: jax.Array        # (L-1, B) bool
    miss_bin: jax.Array       # (L-1,) i32 movable-missing bin of the feature
    movable: jax.Array        # (L-1,) bool feature has missing-directed bin
    leaf_value: jax.Array     # (L,) f32 raw outputs (pre-shrinkage)
    leaf_sum: jax.Array       # (L, 3) f32
    row_leaf: jax.Array       # (N,) i32 final leaf of every training row


def _empty_best(num_leaves: int, num_bin: int) -> SplitInfo:
    z = jnp.zeros
    return SplitInfo(
        gain=jnp.full((num_leaves,), -jnp.inf, jnp.float32),
        feature=z((num_leaves,), jnp.int32),
        bin=z((num_leaves,), jnp.int32),
        kind=z((num_leaves,), jnp.int32),
        default_left=z((num_leaves,), bool),
        go_left=z((num_leaves, num_bin), bool),
        left_sum=z((num_leaves, 3), jnp.float32),
        right_sum=z((num_leaves, 3), jnp.float32),
        left_output=z((num_leaves,), jnp.float32),
        right_output=z((num_leaves,), jnp.float32),
    )


def _set_best(best: SplitInfo, idx, info: SplitInfo) -> SplitInfo:
    return jax.tree.map(lambda b, v: b.at[idx].set(v), best, info)



# The histogram pool of the partitioned builder: (num_leaves, 3, G, Bp) f32,
# a leaf's row IS its channel-major histogram (ops/histogram.py hist_bins).
# Rows are read and written by dynamic slices on the leaf axis alone: every
# op between the histogram kernel, the pool and the scan is elementwise on
# lane-dense (3, G, Bp) arrays, and no tile is ever reshaped: on a v5e at
# F = 2,000 the row's read, the subtraction, the select and both writes
# cost 52 us a split together (tree_state 13.2 ms an iteration of 254
# splits; chip run, PR 36). Until PR 36 the pool held k-minor flat rows of
# (G, B, 3) and each of the six ops a split that carried 6.1 MB in or out
# of it cost 0.35-0.56 ms (tiles padded 3 -> 128): 822 of epsilon.train's
# 1,340 ms an iteration (PERF.md section 6).


def pool_read(pool: jax.Array, leaf) -> jax.Array:
    """A leaf's (3, G, Bp) histogram, as an array of its own: left to
    itself XLA fuses this slice into BOTH children's writes, the second of
    which then reads the pool as it was before the first, and to keep that
    it copies the whole pool (1.57 GB at F = 2,000) twice a split (read in
    the block compiled for v5e:2x2, PR 36)."""
    return jax.lax.optimization_barrier(
        jax.lax.dynamic_index_in_dim(pool, leaf, 0, keepdims=False))


def pool_write(pool: jax.Array, leaf, h: jax.Array) -> jax.Array:
    """The pool with ``leaf``'s row replaced, in place inside the loop."""
    return jax.lax.dynamic_update_index_in_dim(pool, h, leaf, 0)


def _make_best_for(meta: FeatureMeta, hp: SplitHyper, key, feature_mask,
                   num_feat: int, feature_fraction_bynode: float,
                   extra_trees: bool, constraint_sets, extra_seed: int = 6,
                   search=find_best_split):
    """Shared per-node split evaluation: by-node column sampling,
    extra-trees random thresholds, interaction constraints, then the
    vectorized (F, B) best-split scan: ``search`` is ``find_best_split`` over
    the dense builder's (F, B, 3) histograms, ``find_best_split_planes``
    over the partitioned builder's channel-major (3, F, B) ones."""

    def allowed_mask(used_row):
        """Interaction constraints (reference: col_sampler.hpp:94 GetByNode):
        a branch may only use features from constraint sets compatible with
        the features already used on its path."""
        if constraint_sets is None:
            return jnp.ones((num_feat,), bool)
        compat = jnp.all(~used_row[None, :] | constraint_sets, axis=1)  # (S,)
        return jnp.any(constraint_sets & compat[:, None], axis=0)

    def node_inputs(r, leaf):
        """Per-node RNG-driven feature mask and extra-trees thresholds."""
        fmask = feature_mask
        if feature_fraction_bynode < 1.0:
            k = jax.random.fold_in(key, r * 2 + 1000 + leaf)
            u = jax.random.uniform(k, (num_feat,))
            kth = max(1, int(np.ceil(feature_fraction_bynode * num_feat)))
            rank = jnp.argsort(jnp.argsort(u))
            fmask = fmask & (rank < kth)
        rand_thr = None
        if extra_trees:
            # extra_seed gives the random-threshold stream its own seed
            # (reference: config.h extra_seed)
            k = jax.random.fold_in(jax.random.fold_in(key, 2000 + extra_seed),
                                   r * 2 + 1 + leaf)
            u = jax.random.uniform(k, (num_feat,))
            rand_thr = (u * jnp.maximum(meta.num_bins - 1, 1).astype(jnp.float32)) \
                .astype(jnp.int32)
        return fmask, rand_thr

    def best_for(r, leaf, hist, parent_sum, parent_out, lower, upper,
                 used_row, extra_mask=None, want_feature_gains=False,
                 use_hp=None, cegb_delta=None, node_depth=None,
                 adv_bounds=None):
        with scan_phase(use_hp if use_hp is not None else hp, inside=True):
            fmask, rand_thr = node_inputs(r, leaf)
            fmask = fmask & allowed_mask(used_row)
            if extra_mask is not None:
                fmask = fmask & extra_mask
        return search(
            hist, parent_sum, meta, fmask, use_hp if use_hp is not None else hp,
            parent_output=parent_out, leaf_lower=lower, leaf_upper=upper,
            rand_threshold=rand_thr, want_feature_gains=want_feature_gains,
            cegb_delta=cegb_delta, node_depth=node_depth,
            adv_bounds=adv_bounds)

    return best_for


def build_tree(
    bins: jax.Array,          # (N, F) uint8/16 — row shard on this device
    ghc: jax.Array,           # (N, 3) f32 (grad, hess, inbag) — masked already
    meta: FeatureMeta,
    feature_mask: jax.Array,  # (F,) bool, per-tree column sample
    key: jax.Array,           # PRNG for by-node sampling / extra-trees
    cegb_used: jax.Array,     # (F,) bool — accepted for signature parity
    hp: SplitHyper,           # (CEGB needs tree_builder=partition)
    *,
    num_leaves: int,
    num_bin: int,
    max_depth: int = -1,
    feature_fraction_bynode: float = 1.0,
    extra_trees: bool = False,
    comm: Comm = Comm(),
    hist_chunk: int = 2048,
    constraint_sets: Optional[jax.Array] = None,   # (S, F) bool, static presence
    forced: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
    # forced = (leaf (R,), feature (R,), bin (R,)) BFS-ordered forced splits
    mxu_bf16: bool = False,
    extra_seed: int = 6,
) -> TreeLog:
    """Grow one leaf-wise tree entirely on device. jit/shard_map once."""
    n, num_feat = bins.shape
    max_splits = num_leaves - 1
    n_forced = 0 if forced is None else int(forced[0].shape[0])

    def hist_of_leaf(row_leaf, leaf_id):
        """Histogram of the rows currently on ``leaf_id`` (all rows when
        leaf_id < 0): masked one-hot matmul over the full row set."""
        mask = (jnp.asarray(leaf_id) < 0) | (row_leaf == leaf_id)
        h = build_histogram(bins, ghc * mask[:, None].astype(jnp.float32),
                            num_bin, hist_chunk, mxu_bf16=mxu_bf16)
        return comm.psum(h)

    best_for = _make_best_for(meta, hp, key, feature_mask, num_feat,
                              feature_fraction_bynode, extra_trees,
                              constraint_sets, extra_seed)

    # ---- init: root ----
    root_sum = comm.psum(jnp.sum(ghc, axis=0))
    root_hist = hist_of_leaf(jnp.zeros((n,), jnp.int32), jnp.int32(-1))
    hist_pool = jnp.zeros((num_leaves, num_feat, num_bin, 3), jnp.float32)
    hist_pool = hist_pool.at[0].set(root_hist)
    leaf_sum = jnp.zeros((num_leaves, 3), jnp.float32).at[0].set(root_sum)
    leaf_out = jnp.zeros((num_leaves,), jnp.float32).at[0].set(
        calc_leaf_output(root_sum[0], root_sum[1], hp))
    leaf_depth = jnp.zeros((num_leaves,), jnp.int32)
    leaf_lower = jnp.full((num_leaves,), -jnp.inf, jnp.float32)
    leaf_upper = jnp.full((num_leaves,), jnp.inf, jnp.float32)
    leaf_used = jnp.zeros((num_leaves, num_feat), bool)
    best = _empty_best(num_leaves, num_bin)
    best = _set_best(best, 0, best_for(0, jnp.int32(0), root_hist, root_sum,
                                       leaf_out[0], leaf_lower[0], leaf_upper[0],
                                       leaf_used[0], node_depth=jnp.int32(0)))
    row_leaf = jnp.zeros((n,), jnp.int32)
    log = TreeLog(
        num_splits=jnp.int32(0),
        split_leaf=jnp.zeros((max_splits,), jnp.int32),
        feature=jnp.zeros((max_splits,), jnp.int32),
        bin=jnp.zeros((max_splits,), jnp.int32),
        kind=jnp.zeros((max_splits,), jnp.int32),
        default_left=jnp.zeros((max_splits,), bool),
        gain=jnp.zeros((max_splits,), jnp.float32),
        left_sum=jnp.zeros((max_splits, 3), jnp.float32),
        right_sum=jnp.zeros((max_splits, 3), jnp.float32),
        go_left=jnp.zeros((max_splits, num_bin), bool),
        miss_bin=jnp.zeros((max_splits,), jnp.int32),
        movable=jnp.zeros((max_splits,), bool),
        leaf_value=leaf_out,
        leaf_sum=leaf_sum,
        row_leaf=row_leaf,
    )

    def depth_ok(depth):
        if max_depth <= 0:
            return jnp.bool_(True)
        return depth < max_depth

    force_live = jnp.bool_(n_forced > 0)
    carry0 = (jnp.int32(0), row_leaf, hist_pool, leaf_sum, leaf_out,
              leaf_depth, leaf_lower, leaf_upper, best, log, leaf_used,
              force_live)

    def cond(carry):
        r = carry[0]
        best = carry[8]
        log = carry[9]
        force_live = carry[11]
        forcing = force_live & (r < n_forced) if n_forced else False
        return (log.num_splits < max_splits) & (r < max_splits + n_forced) \
            & ((jnp.max(best.gain) > 0.0) | forcing)

    def body(carry):
        (r, row_leaf, hist_pool, leaf_sum, leaf_out, leaf_depth,
         leaf_lower, leaf_upper, best, log, leaf_used, force_live) = carry
        leaf = jnp.argmax(best.gain).astype(jnp.int32)
        info: SplitInfo = jax.tree.map(lambda a: a[leaf], best)
        if n_forced:
            # forced splits (reference: serial_tree_learner.cpp:450
            # ForceSplits — BFS-ordered (leaf, feature, bin) applied before
            # gain-driven growth; an invalid forced split aborts forcing)
            f_leaf, f_feat, f_bin = forced

            def pick_forced(_):
                ri = jnp.minimum(r, n_forced - 1)
                fl = f_leaf[ri]
                fi = find_best_split(
                    hist_pool[fl], leaf_sum[fl], meta,
                    jnp.arange(num_feat) == f_feat[ri], hp,
                    parent_output=leaf_out[fl], leaf_lower=leaf_lower[fl],
                    leaf_upper=leaf_upper[fl],
                    rand_threshold=jnp.full((num_feat,), f_bin[ri], jnp.int32),
                    node_depth=leaf_depth[fl])
                ok = fi.gain > -jnp.inf
                return (jnp.where(ok, fl, leaf),
                        jax.tree.map(lambda a, b: jnp.where(ok, a, b), fi, info),
                        ok)

            with trace_phase("lgbtpu/tree_state"):
                use_forced = force_live & (r < n_forced)
            leaf, info, force_live = jax.lax.cond(
                use_forced, pick_forced,
                lambda _: (leaf, info, jnp.bool_(False)), operand=None)
        valid = info.gain > -jnp.inf
        s = log.num_splits
        new_leaf = s + 1

        prev = (row_leaf, hist_pool, leaf_sum, leaf_out, leaf_depth,
                leaf_lower, leaf_upper, best, log, leaf_used)

        # ---- apply split to the row partition (DataPartition::Split analog) ----
        bins_col = jnp.take(bins, info.feature, axis=1).astype(jnp.int32)
        go_left_rows = info.go_left[bins_col]
        on_leaf = row_leaf == leaf
        row_leaf = jnp.where(on_leaf & ~go_left_rows, new_leaf, row_leaf)

        # ---- record ----
        log = log._replace(
            num_splits=new_leaf,
            split_leaf=log.split_leaf.at[s].set(leaf),
            feature=log.feature.at[s].set(info.feature),
            bin=log.bin.at[s].set(info.bin),
            kind=log.kind.at[s].set(info.kind),
            default_left=log.default_left.at[s].set(info.default_left),
            gain=log.gain.at[s].set(info.gain),
            left_sum=log.left_sum.at[s].set(info.left_sum),
            right_sum=log.right_sum.at[s].set(info.right_sum),
            go_left=log.go_left.at[s].set(info.go_left),
            miss_bin=log.miss_bin.at[s].set(meta.missing_bin[info.feature]),
            movable=log.movable.at[s].set(meta.movable_missing[info.feature]),
        )

        # ---- stats bookkeeping ----
        leaf_sum = leaf_sum.at[leaf].set(info.left_sum).at[new_leaf].set(info.right_sum)
        leaf_out = leaf_out.at[leaf].set(info.left_output) \
                           .at[new_leaf].set(info.right_output)
        d = leaf_depth[leaf] + 1
        leaf_depth = leaf_depth.at[leaf].set(d).at[new_leaf].set(d)
        if hp.has_monotone:
            mono = meta.monotone[info.feature]
            mid = (info.left_output + info.right_output) * 0.5
            lo_l, up_l = leaf_lower[leaf], leaf_upper[leaf]
            new_up_l = jnp.where(mono > 0, jnp.minimum(up_l, mid), up_l)
            new_lo_r = jnp.where(mono > 0, jnp.maximum(lo_l, mid), lo_l)
            new_lo_l = jnp.where(mono < 0, jnp.maximum(lo_l, mid), lo_l)
            new_up_r = jnp.where(mono < 0, jnp.minimum(up_l, mid), up_l)
            leaf_lower = leaf_lower.at[leaf].set(new_lo_l).at[new_leaf].set(new_lo_r)
            leaf_upper = leaf_upper.at[leaf].set(new_up_l).at[new_leaf].set(new_up_r)

        # ---- histograms: masked pass for the smaller child, subtract for the
        # larger (serial_tree_learner.cpp:418) ----
        left_smaller = info.left_sum[2] <= info.right_sum[2]
        small_id = jnp.where(left_smaller, leaf, new_leaf)
        hist_small = hist_of_leaf(row_leaf, small_id)
        parent_hist = hist_pool[leaf]
        hist_large = parent_hist - hist_small
        hist_left = jnp.where(left_smaller, hist_small, hist_large)
        hist_right = jnp.where(left_smaller, hist_large, hist_small)
        hist_pool = hist_pool.at[leaf].set(hist_left).at[new_leaf].set(hist_right)

        # ---- refresh best splits for the two children ----
        # interaction-constraint bookkeeping: children inherit path features
        used_new = leaf_used[leaf].at[info.feature].set(True)
        leaf_used = leaf_used.at[leaf].set(used_new).at[new_leaf].set(used_new)

        info_l = best_for(r, leaf, hist_left, info.left_sum,
                          leaf_out[leaf], leaf_lower[leaf], leaf_upper[leaf],
                          used_new, node_depth=leaf_depth[leaf])
        info_r = best_for(r, new_leaf, hist_right, info.right_sum,
                          leaf_out[new_leaf], leaf_lower[new_leaf],
                          leaf_upper[new_leaf], used_new,
                          node_depth=leaf_depth[new_leaf])
        gate_l = depth_ok(leaf_depth[leaf])
        gate_r = depth_ok(leaf_depth[new_leaf])
        info_l = info_l._replace(gain=jnp.where(gate_l, info_l.gain, -jnp.inf))
        info_r = info_r._replace(gain=jnp.where(gate_r, info_r.gain, -jnp.inf))
        best = _set_best(best, leaf, info_l)
        best = _set_best(best, new_leaf, info_r)

        new = (row_leaf, hist_pool, leaf_sum, leaf_out, leaf_depth,
               leaf_lower, leaf_upper, best, log, leaf_used)
        # an invalid round (forced split impossible and no positive-gain
        # split) advances the round counter but commits nothing
        committed = jax.tree.map(lambda a, b: jnp.where(valid, a, b), new, prev)
        return (r + 1,) + committed + (force_live,)

    carry = jax.lax.while_loop(cond, body, carry0)
    (_, row_leaf, _, leaf_sum, leaf_out, _, _, _, _, log, _, _) = carry
    return log._replace(leaf_value=leaf_out, leaf_sum=leaf_sum, row_leaf=row_leaf)




# ---------------------------------------------------------------------------
# Advanced monotone constraints (reference: monotone_constraints.hpp:856
# AdvancedLeafConstraints). The reference walks the tree per split to
# collect piecewise per-threshold bounds; the TPU-native form keeps DENSE
# state — per-leaf per-feature per-bin bound arrays (L, F, B) plus bin-range
# boxes (L, F) — and refreshes ALL leaves vectorized at every commit: each
# new child broadcasts its output as a bound to every leaf whose box
# overlaps the child's in all other features, over the bins beyond the
# child's own range in each monotone dimension. Candidate-threshold bounds
# then come from prefix/suffix extrema of the bin arrays, so the split scan
# sees per-threshold constraints exactly where the reference recomputes
# them. Sound by construction (every committed output is sandwiched against
# all earlier overlapping neighbors); tighter than `intermediate`, which
# collapses each leaf's constraints to two scalars.


def _adv_boxes_init(num_leaves: int, num_feat: int, meta):
    """(L, F) bin-range boxes — all intermediate mode needs."""
    rng_lo = jnp.zeros((num_leaves, num_feat), jnp.int32)
    rng_hi = jnp.broadcast_to(meta.num_bins[None, :],
                              (num_leaves, num_feat)).astype(jnp.int32)
    return (rng_lo, rng_hi)


def _adv_init(num_leaves: int, num_feat: int, num_bin: int, meta):
    cons_lo = jnp.full((num_leaves, num_feat, num_bin), -jnp.inf, jnp.float32)
    cons_hi = jnp.full((num_leaves, num_feat, num_bin), jnp.inf, jnp.float32)
    return (cons_lo, cons_hi) + _adv_boxes_init(num_leaves, num_feat, meta)


def _adv_bounds_of(adv, leaf):
    """Per-candidate child bounds (lo_l, up_l, lo_r, up_r), each (F, B):
    entry [f, t] bounds the child of a split on feature f at threshold t
    (left = bins <= t)."""
    cons_lo, cons_hi, rng_lo, rng_hi = adv
    lo = cons_lo[leaf]
    hi = cons_hi[leaf]                                # (F, B)
    rlo = rng_lo[leaf]
    rhi = rng_hi[leaf]                                # (F,)
    num_bin = lo.shape[1]
    b = jnp.arange(num_bin, dtype=jnp.int32)[None, :]
    inr = (b >= rlo[:, None]) & (b < rhi[:, None])
    hi_m = jnp.where(inr, hi, jnp.inf)
    lo_m = jnp.where(inr, lo, -jnp.inf)
    hi_f = jnp.min(hi_m, axis=1)                      # (F,) whole-range bound
    lo_f = jnp.max(lo_m, axis=1)
    # min/max over all features EXCEPT f (two-extremum trick; the +/-inf
    # sentinel makes the "no other features" case — F == 1 — unconstrained)
    hi_s = jnp.sort(jnp.concatenate([hi_f, jnp.array([jnp.inf])]))
    hi1, hi2 = hi_s[0], hi_s[1]
    hi_exc = jnp.where((hi_f == hi1) & (jnp.sum(hi_f == hi1) == 1), hi2, hi1)
    lo_s = jnp.sort(jnp.concatenate([lo_f, jnp.array([-jnp.inf])]))
    lo1, lo2 = lo_s[-1], lo_s[-2]
    lo_exc = jnp.where((lo_f == lo1) & (jnp.sum(lo_f == lo1) == 1), lo2, lo1)
    # prefix extrema cover the left child's bins [0, t]; suffix (shifted
    # one left) the right child's bins (t, B)
    pre_hi = jax.lax.cummin(hi_m, axis=1)
    pre_lo = jax.lax.cummax(lo_m, axis=1)
    suf_hi = jnp.flip(jax.lax.cummin(jnp.flip(hi_m, 1), axis=1), 1)
    suf_lo = jnp.flip(jax.lax.cummax(jnp.flip(lo_m, 1), axis=1), 1)
    inf_c = jnp.full((hi_m.shape[0], 1), jnp.inf)
    suf_hi = jnp.concatenate([suf_hi[:, 1:], inf_c], axis=1)
    suf_lo = jnp.concatenate([suf_lo[:, 1:], -inf_c], axis=1)
    up_l = jnp.minimum(hi_exc[:, None], pre_hi)
    lo_l = jnp.maximum(lo_exc[:, None], pre_lo)
    up_r = jnp.minimum(hi_exc[:, None], suf_hi)
    lo_r = jnp.maximum(lo_exc[:, None], suf_lo)
    return lo_l, up_l, lo_r, up_r


def _adv_child_boxes(rng_lo, rng_hi, sel, leaf, new_leaf, info):
    """Split the parent's bin box along a numerical winner's feature and
    commit the children's boxes. Returns the updated (rng_lo, rng_hi) plus
    the two child boxes (left keeps the parent's slot)."""
    is_num = info.kind == 0
    fs = info.feature
    t1 = info.bin + 1
    p_rlo = rng_lo[leaf]
    p_rhi = rng_hi[leaf]
    rhi_l = p_rhi.at[fs].set(jnp.where(is_num, t1, p_rhi[fs]))
    rlo_r = p_rlo.at[fs].set(jnp.where(is_num, t1, p_rlo[fs]))
    rng_lo = rng_lo.at[new_leaf].set(sel(rlo_r, rng_lo[new_leaf]))
    rng_hi = rng_hi.at[leaf].set(sel(rhi_l, p_rhi)) \
        .at[new_leaf].set(sel(p_rhi, rng_hi[new_leaf]))
    return rng_lo, rng_hi, (p_rlo, rhi_l), (rlo_r, p_rhi)


def _adv_overlap_except(rng_lo, rng_hi, c_rlo, c_rhi):
    """(L, F) mask: leaf boxes overlapping child box C in every feature BUT
    the column's own (the dimension a bound would apply along)."""
    ov = (rng_lo < c_rhi[None, :]) & (c_rlo[None, :] < rng_hi)
    nfalse = jnp.sum(~ov, axis=1)
    return (nfalse == 0)[:, None] | ((nfalse == 1)[:, None] & ~ov)


def _adv_commit(adv, meta, sel, leaf, new_leaf, info, num_bin: int):
    """Split commit: children inherit the parent's constraint entries, the
    split feature's box tightens (numerical winners), and both children
    broadcast their outputs as bounds to every box-overlapping leaf:

    - along each MONOTONE dimension, at the bins beyond the child's own
      range (the original dense analog of the reference's per-threshold
      constraints, monotone_constraints.hpp:856);
    - along each OTHER dimension f', at the bins INSIDE the child's
      f'-range, for leaves wholly ordered against the child in some
      monotone dimension. This second write is what separates `advanced`
      from `intermediate`: without it, a neighbor whose bound only applies
      to part of a leaf's f'-range (because the neighbor is itself split
      on f') degenerates to a whole-leaf scalar clamp. The (L, F, B)
      per-dimension representation cannot express joint restrictions over
      several dimensions, so these writes are CONSERVATIVE (sound: only
      ever tighter than the reference's re-searched bounds, never looser
      than monotonicity requires)."""
    cons_lo, cons_hi, rng_lo, rng_hi = adv
    cons_lo = cons_lo.at[new_leaf].set(sel(cons_lo[leaf], cons_lo[new_leaf]))
    cons_hi = cons_hi.at[new_leaf].set(sel(cons_hi[leaf], cons_hi[new_leaf]))
    rng_lo, rng_hi, box_l, box_r = _adv_child_boxes(
        rng_lo, rng_hi, sel, leaf, new_leaf, info)
    b = jnp.arange(num_bin, dtype=jnp.int32)[None, None, :]
    mono = meta.monotone[None, :]
    inc = (mono > 0)[:, :, None]
    dec = (mono < 0)[:, :, None]
    incv = mono > 0
    decv = mono < 0
    valid_b = sel(jnp.bool_(True), jnp.bool_(False))
    for (c_rlo, c_rhi), out in ((box_l, info.left_output),
                                (box_r, info.right_output)):
        # along-m writes apply a BLANKET per-m-bin bound over the whole
        # leaf; that claim is precise only when C covers the leaf's box in
        # every other dimension (always true at F == 1). When C is
        # restricted in some free dimension, the free-dimension writes
        # below carry the bound with its restriction instead — gating the
        # blanket here is what lets a split on a free dimension escape a
        # neighbor's bound outside that neighbor's range (the reference's
        # motivating per-threshold case).
        cover = (c_rlo[None, :] <= rng_lo) & (rng_hi <= c_rhi[None, :])
        ncov = jnp.sum(~cover, axis=1)                             # (L,)
        cov_exc = (ncov == 0)[:, None] | ((ncov == 1)[:, None] & ~cover)
        below = b < c_rlo[None, :, None]
        above = b >= c_rhi[None, :, None]
        hi_upd = (inc & below) | (dec & above)
        lo_upd = (inc & above) | (dec & below)
        gate = cov_exc[:, :, None] & valid_b
        cons_hi = jnp.where(gate & hi_upd, jnp.minimum(cons_hi, out), cons_hi)
        cons_lo = jnp.where(gate & lo_upd, jnp.maximum(cons_lo, out), cons_lo)

        # ---- free-dimension writes (restricted to C's own bin range) ----
        ov = (rng_lo < c_rhi[None, :]) & (c_rlo[None, :] < rng_hi)  # (L, F)
        nonov = (~ov).astype(jnp.int32)
        nov = jnp.sum(nonov, axis=1)                               # (L,)
        # leaf wholly ordered against C in monotone dim m: C bounds it
        # from above (ub) or below (lb) in value space
        ub_ord = (incv & (rng_hi <= c_rlo[None, :])) \
            | (decv & (rng_lo >= c_rhi[None, :]))                  # (L, F)
        lb_ord = (incv & (rng_lo >= c_rhi[None, :])) \
            | (decv & (rng_hi <= c_rlo[None, :]))
        # exists an ordering dim m != f' (each ordered m is disjoint, so
        # requiring overlap in all dims except {m, f'} is nov-nonov[f']==1)
        ub_any = (jnp.sum(ub_ord, axis=1)[:, None]
                  - ub_ord.astype(jnp.int32)) > 0                  # (L, F)
        lb_any = (jnp.sum(lb_ord, axis=1)[:, None]
                  - lb_ord.astype(jnp.int32)) > 0
        free_gate = (nov[:, None] - nonov) == 1                    # (L, F)
        in_rng = (b >= c_rlo[None, :, None]) & (b < c_rhi[None, :, None])
        g_ub = (ub_any & free_gate)[:, :, None] & in_rng & valid_b
        g_lb = (lb_any & free_gate)[:, :, None] & in_rng & valid_b
        cons_hi = jnp.where(g_ub, jnp.minimum(cons_hi, out), cons_hi)
        cons_lo = jnp.where(g_lb, jnp.maximum(cons_lo, out), cons_lo)
    return (cons_lo, cons_hi, rng_lo, rng_hi)


def build_tree_partitioned(
    bins: jax.Array,          # (N, F) uint8 — row shard on this device
    ghc: jax.Array,           # (N, 3) f32 (grad, hess, inbag) — masked already
    meta: FeatureMeta,
    feature_mask: jax.Array,  # (F,) bool, per-tree column sample
    key: jax.Array,           # PRNG for by-node sampling / extra-trees
    cegb_used: jax.Array,     # (F,) bool — features already used by the model
    hp: SplitHyper,
    *,
    num_leaves: int,
    num_bin: int,
    max_depth: int = -1,
    feature_fraction_bynode: float = 1.0,
    extra_trees: bool = False,
    extra_seed: int = 6,
    comm: Comm = Comm(),
    hist_chunk: int = 2048,
    part_chunk: int = 2048,
    hist_mode: str = "hilo",  # hilo (bf16-pair) | bf16 | int8 (quantized)
    hist_lo: int = 0,         # hi/lo einsum split width (0 = auto by F)
    num_bin_hist: Optional[int] = None,   # bundled-column bins (defaults num_bin)
    bundle: Optional[dict] = None,        # EFB maps (dataset.bundle_maps)
    bundle_view: Optional[BundleView] = None,  # their static half
    constraint_sets: Optional[jax.Array] = None,   # (S, F) bool
    forced: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
    part_kernel: str = "xla",  # xla | pallas (fused DMA kernel, TPU only)
    hist_kernel: str = "xla",  # xla (einsum) | pallas (in-VMEM, TPU only:
    # several features an MXU pass on planes, one a pass on rows)
    work_buf: Optional[jax.Array] = None,  # carried (2, Npad, W) u8 buffer
    return_work: bool = False,
    bins_t: Optional[jax.Array] = None,    # (F, N) transposed bins — pass a
    # block-hoisted copy when building many trees (the transpose costs
    # ~20 ms at 2M x 28; assign_leaves needs the transposed layout)
    work_layout: str = "rows",  # rows ((2, Npad, W) row-major) | planes
    # ((2, W, Npad) feature-major: 128-lane tiles carry 128 rows of ONE
    # byte column, and the root histogram folds into the pack pass) |
    # resident (planes family: bin planes live once in bins_res and the
    # slim 17-plane work buffer moves only route/ridx/g/h/c per split)
    bins_res: Optional[jax.Array] = None,  # (F, Npad) resident bin planes
    # (work_layout=resident) — pass a block-hoisted copy when building
    # many trees; derived in-graph from ``bins`` when None
    goss_compact_rows: int = 0,  # static compact row count M (tpu_goss_compact):
    # when 0 < M < N, the inbag mask is turned into a device gather that
    # packs the surviving rows to the top and the WHOLE tree build runs
    # over M rows; GOSS warmup iterations (all rows in-bag) and the rare
    # margin overflow fall back to the verbatim dense-mask build inside
    # the same jitted graph (lax.cond) — bit-identical trees either way
    route_bins: Optional[Tuple[jax.Array, Optional[jax.Array]]] = None,
    # (bins_full, bins_t_full): route ALL original rows through the grown
    # tree in assign_leaves (set by the compaction wrapper so row_leaf
    # keeps the full (N,) shape the score update expects)
    root_sum_in: Optional[jax.Array] = None,  # (3,) precomputed local root
    # (g, h, cnt) sums. The compaction wrapper computes them over the
    # DENSE ghc: XLA's row reduce uses strided accumulators, so summing
    # the compacted array would regroup the f32 additions (+/-1 ulp) —
    # histogram matmuls accumulate sequentially over rows and are immune
) -> TreeLog:
    """Grow one leaf-wise tree with a physical row partition.

    The scaling-correct builder (reference contract:
    src/treelearner/serial_tree_learner.cpp:324 FindBestSplits over the
    smaller leaf + histogram subtraction, src/treelearner/data_partition.hpp
    :101 Split): per split, the parent's rows are stably partitioned into
    leaf-contiguous segments (ops/partition.py) and only the SMALLER child's
    segment is histogrammed (ops/histogram.py hist16_segment); the larger
    child's histogram is parent - smaller. Per-split cost is O(parent rows),
    per-histogram cost O(child rows) — round 1 paid O(N) for both, ~100x
    more arithmetic at 255 leaves.

    Same in/out contract as ``build_tree``; runs identically single-device
    or under shard_map (all collectives go through ``comm``).
    """
    if goss_compact_rows and 0 < goss_compact_rows < bins.shape[0]:
        # ---- GOSS device compaction (tpu_goss_compact=on) ----
        # Gather the in-bag rows to the top and build the tree over a
        # STATIC M-row prefix; removed rows carry exact (+/-0.0, 0) ghc so
        # the compact build's sums, partitions and histograms match the
        # dense-mask build bit-for-bit. The in-graph cond keeps the dense
        # path for GOSS warmup iterations (sampler emits all-ones inbag,
        # so C = N > M) and for binomial overflow beyond the 4-sigma
        # margin. Both branches route ALL N original rows in
        # assign_leaves, so row_leaf (and the score update) are
        # shape-identical either way.
        from .ops.partition import compact_rows_by_inbag
        if return_work and work_buf is None:
            raise ValueError("goss_compact_rows with return_work=True needs "
                             "a carried work_buf (its M-sized shape is the "
                             "cond's common work signature)")
        m = goss_compact_rows
        with trace_phase("lgbtpu/sample"):
            bins_c, ghc_c, c_in = compact_rows_by_inbag(bins, ghc, m)
        sub = dict(
            num_leaves=num_leaves, num_bin=num_bin, max_depth=max_depth,
            feature_fraction_bynode=feature_fraction_bynode,
            extra_trees=extra_trees, extra_seed=extra_seed, comm=comm,
            hist_chunk=hist_chunk, part_chunk=part_chunk,
            hist_mode=hist_mode, hist_lo=hist_lo,
            num_bin_hist=num_bin_hist, bundle=bundle,
            bundle_view=bundle_view,
            constraint_sets=constraint_sets, forced=forced,
            part_kernel=part_kernel, hist_kernel=hist_kernel,
            work_layout=work_layout, goss_compact_rows=0,
            return_work=return_work)

        def _compact(_):
            # root sums come from the DENSE ghc: the row reduce's strided
            # accumulators would regroup f32 additions over the compacted
            # array (+/-1 ulp — enough to flip near-tie splits)
            with trace_phase("lgbtpu/tree_state"):
                root_sum_dense = jnp.sum(ghc, axis=0)
            return build_tree_partitioned(
                bins_c, ghc_c, meta, feature_mask, key, cegb_used, hp,
                work_buf=work_buf, bins_t=None, bins_res=None,
                route_bins=(bins, bins_t),
                root_sum_in=root_sum_dense, **sub)

        def _dense(_):
            # fresh internal N-sized buffers; the carried M-sized work_buf
            # passes through untouched so both cond branches return the
            # same work signature
            out = build_tree_partitioned(
                bins, ghc, meta, feature_mask, key, cegb_used, hp,
                work_buf=None, bins_t=bins_t, bins_res=bins_res,
                route_bins=route_bins, **dict(sub, return_work=False))
            return (out, work_buf) if return_work else out

        return jax.lax.cond(c_in <= m, _compact, _dense, 0)

    from .ops.histogram import (hist16_segment, hist16_segment_planes,
                                hist16_segment_q, hist16_segment_resident,
                                hist_pallas_segment,
                                hist_pallas_segment_planes)
    from .ops.partition import (pack_planes_fold_root,
                                pack_resident_fold_root, pack_rows,
                                pack_rows_quantized, partition_segment,
                                partition_segment_fused,
                                partition_segment_planes,
                                partition_segment_planes_fused, planes_npad,
                                resident_bin_planes, write_route_plane)

    n, num_grp = bins.shape
    num_feat = int(meta.num_bins.shape[0])
    max_splits = num_leaves - 1
    n_forced = 0 if forced is None else int(forced[0].shape[0])
    fused_part = part_kernel == "pallas"
    quantized = hist_mode == "int8"
    resident = work_layout == "resident"
    planes = work_layout == "planes" or resident
    from .ops.partition import work_spec
    guard, buf_width = work_spec(num_grp, quantized, part_kernel,
                                 part_chunk, hist_chunk, layout=work_layout)
    bm = num_bin_hist if num_bin_hist is not None else num_bin
    # a leaf's histogram in this loop: (3, num_grp, bp), channel-major with
    # the bins on the lanes (ops/histogram.py hist_bins); every hist_of
    # branch, the packs' root histogram, the pool and the scan hold it
    bp = hist_bins(bm)

    # ---- packed ping-pong working buffers with guard rows ----
    # the matrix columns are EFB bundles (== features when no bundling)
    if planes:
        if quantized:
            raise ValueError("tpu_work_layout=planes does not support int8 "
                             "quantized training (the learner gate keeps "
                             "auto on rows for int8)")
        # transposed (2, W, Npad) plane pair. The pack pass ALSO produces
        # the root histogram — iteration 0 never re-reads the full matrix
        # (stale bytes in a carried buffer's guard lanes are never consumed:
        # partitions only commit valid rows and histograms mask by count)
        if work_buf is not None:
            work = work_buf
        else:
            work = jnp.zeros(
                (2, buf_width, planes_npad(n, guard, part_kernel)),
                jnp.uint8)
        base_part = partition_segment_planes_fused if fused_part \
            else partition_segment_planes
        if resident:
            # bin planes live ONCE (original row order, never partitioned);
            # the slim work buffer carries route/ridx/g/h/c only
            if bins_res is None:
                bins_res = resident_bin_planes(bins, guard, work.shape[2])
            with trace_phase("lgbtpu/pack"):
                work, root_hist_loc = pack_resident_fold_root(
                    work, bins, ghc, guard, num_bins=bm,
                    exact=hist_mode != "bf16", chunk=hist_chunk,
                    lo_w=hist_lo)

            def part_fn(work, plane, start, cnt, feat, table, *, ch):
                # gather the split feature's resident bin bytes through the
                # permuted row-index plane into the route plane, then
                # stream the slim payload through the UNCHANGED planes
                # partition (XLA or fused Mosaic) routing on plane 0 — the
                # gathered column equals the planes path's leaf-order bin
                # column value-for-value, so dest arithmetic (and trees)
                # stay bit-identical
                work = write_route_plane(work, bins_res, plane, start, cnt,
                                         feat, ch=ch)
                return base_part(work, plane, start, cnt, jnp.int32(0),
                                 table, ch=ch)
        else:
            from .ops.histogram import root_einsum_chunk
            with trace_phase("lgbtpu/pack"):
                work, root_hist_loc = pack_planes_fold_root(
                    work, bins, ghc, guard, num_bins=bm,
                    exact=hist_mode != "bf16",
                    chunk=root_einsum_chunk(num_grp, hist_chunk),
                    lo_w=hist_lo)
            part_fn = base_part
    else:
        pad = ((guard, guard), (0, 0))
        if quantized:
            # per-tree local quantization scales; histograms dequantize
            # before any collective, so shards may scale independently
            gscale = 127.0 / (jnp.max(jnp.abs(ghc[:, 0])) + 1e-12)
            hscale = 127.0 / (jnp.max(jnp.abs(ghc[:, 1])) + 1e-12)
            with trace_phase("lgbtpu/pack"):
                work0 = pack_rows_quantized(
                    jnp.pad(bins, pad), jnp.pad(ghc, pad),
                    jax.random.fold_in(key, 987123), gscale, hscale)
        else:
            with trace_phase("lgbtpu/pack"):
                work0 = pack_rows(jnp.pad(bins, pad), jnp.pad(ghc, pad))
        with trace_phase("lgbtpu/pack"):
            if work_buf is not None:
                # reuse the caller's ping-pong pair (fused blocks carry it
                # across trees): only plane 0's used columns need writing —
                # stale bytes elsewhere are never consumed (blends commit
                # only valid rows, and the histogram/route reads touch only
                # the used columns)
                work = work_buf.at[0, :, :work0.shape[1]].set(work0)
            else:
                if work0.shape[1] < buf_width:
                    # the fused kernel DMAs whole 128-lane tiles; pad row
                    # width
                    work0 = jnp.pad(
                        work0, ((0, 0), (0, buf_width - work0.shape[1])))
                work = jnp.stack([work0, jnp.zeros_like(work0)])  # (2,Npad,W)
        part_fn = partition_segment_fused if fused_part else partition_segment

    def hist_of(work, plane, start, cnt):
        """-> ((3, G, Bp) reduced histogram, work): the g, h and count
        planes of the G device columns, bins padded to whole lane tiles
        (zeros). Callers must continue with the RETURNED work: the pallas
        kernel aliases the buffer through the call (identical bytes) so XLA
        never copies it."""
        if resident:
            # unit-stride gather over the resident bin planes through the
            # permuted row-index plane; same chunking and f32 accumulation
            # order as the planes path
            h = hist16_segment_resident(work, bins_res, plane, start, cnt,
                                        num_bins=bm, num_feat=num_grp,
                                        exact=hist_mode != "bf16",
                                        chunk=hist_chunk, lo_w=hist_lo)
        elif planes and hist_kernel == "pallas":
            h, work = hist_pallas_segment_planes(work, plane, start, cnt,
                                                 num_bins=bm,
                                                 num_feat=num_grp,
                                                 exact=hist_mode != "bf16",
                                                 chunk=hist_chunk,
                                                 lo_w=hist_lo)
        elif planes:
            h = hist16_segment_planes(work, plane, start, cnt, num_bins=bm,
                                      num_feat=num_grp,
                                      exact=hist_mode != "bf16",
                                      chunk=hist_chunk, lo_w=hist_lo)
        elif quantized:
            h = hist16_segment_q(work, plane, start, cnt, gscale, hscale,
                                 num_bins=bm, num_feat=num_grp,
                                 chunk=hist_chunk, lo_w=hist_lo)
        elif hist_kernel == "pallas":
            # in-VMEM chunk loop + accumulator: one streamed read of the
            # segment, none of the XLA loop's per-chunk parasitic fusions
            h, work = hist_pallas_segment(work, plane, start, cnt,
                                          num_bins=bm, num_feat=num_grp,
                                          exact=hist_mode != "bf16",
                                          chunk=hist_chunk, lo_w=hist_lo)
        else:
            h = hist16_segment(work, plane, start, cnt, num_bins=bm,
                               num_feat=num_grp, exact=hist_mode != "bf16",
                               chunk=hist_chunk, lo_w=hist_lo)
        return comm.hist(h), work                         # (3, G, Bp)

    def feat_view(hg, total_sum):
        """(3, G, Bp) device-column histogram -> the (3, F, B) planes the
        scan reads; without bundles the columns ARE the features and the
        pad bins are cut off."""
        if bundle is None:
            return hg[..., :num_bin]
        return bundle_feature_view(hg, total_sum, bundle, bm, bundle_view)

    def feat_views(hists, tot_g, tot_l):
        """The per-feature views of K nodes' histograms, (K, 3, F, B), for
        ``node_best_pair``: with bundles a phase of its own beside
        ``split_scan`` (the benchmark books an op to its outermost phase).
        Voting searches LOCAL histograms, so their default bins come from
        the local totals."""
        if bundle is None:
            return feat_view(hists, None)
        with trace_phase("lgbtpu/efb_view"):
            return jax.vmap(feat_view)(hists, tot_l if voting else tot_g)

    def route_table(info):
        """Feature-space (B,) routing table -> bundle-column (Bm,) table
        (alien sub-features' slots and the shared zero follow the feature's
        default-bin direction). Called beside ``lgbtpu/partition``, not
        under it."""
        if bundle is None:
            return info.go_left
        with trace_phase("lgbtpu/efb_view"):
            row = bundle["map_fb"][info.feature]                  # (Bm,)
            oh = row[:, None] == jnp.arange(num_bin, dtype=jnp.int32)[None, :]
            return (oh.astype(jnp.float32)
                    @ info.go_left.astype(jnp.float32)) > 0.5

    fmask_search = feature_mask
    owned = comm.owned_mask(num_feat)
    if owned is not None:
        fmask_search = feature_mask & owned
    grp_of_feat = bundle["group"] if bundle is not None \
        else jnp.arange(num_feat, dtype=jnp.int32)
    owned_g = comm.owned_group_mask(grp_of_feat, num_grp)
    if owned_g is not None:
        # scatter-mode data-parallel: search only the features whose
        # reduced histogram block this shard owns; sync_split broadcasts
        # the global winner afterwards
        fmask_search = fmask_search & owned_g
    best_raw = _make_best_for(meta, hp, key, fmask_search, num_feat,
                              feature_fraction_bynode, extra_trees,
                              constraint_sets, extra_seed,
                              search=find_best_split_planes)
    voting = comm.mode == "voting"
    if voting:
        d = float(max(comm.num_machines, 1))
        # local vote constraints are scaled by 1/num_machines
        # (reference: voting_parallel_tree_learner.cpp:62-64)
        hp_loc = hp._replace(
            min_data_in_leaf=hp.min_data_in_leaf / d,
            min_sum_hessian_in_leaf=hp.min_sum_hessian_in_leaf / d)

    def cegb_penalty(tot_g, tree_used):
        """Per-feature CEGB gain penalty (reference:
        cost_effective_gradient_boosting.hpp:66 DetlaGain): split penalty
        scales with the leaf's row count; coupled feature penalties apply
        until the model first uses the feature."""
        if not hp.use_cegb:
            return None
        return hp.cegb_tradeoff * (
            hp.cegb_penalty_split * tot_g[2]
            + meta.cegb_coupled * (~tree_used).astype(jnp.float32))

    def node_best(r, leaf, fv, tot_g, tot_l, parent_out, lower, upper,
                  used_row, tree_used, depth, adv_b=None):
        """Best split for a node under the active comm strategy. ``fv`` is
        the node's per-feature histogram view (``feat_views``) — global for
        serial/data/feature, LOCAL for voting; ``tot_g``/``tot_l`` the
        node's global/local (g,h,cnt)."""
        with scan_phase(hp, inside=True):
            delta = cegb_penalty(tot_g, tree_used)
        if not voting:
            info = best_raw(r, leaf, fv, tot_g, parent_out,
                            lower, upper, used_row, cegb_delta=delta,
                            node_depth=depth, adv_bounds=adv_b)
            return comm.sync_split(info)
        # ---- voting parallel (reference: GlobalVoting,
        # voting_parallel_tree_learner.cpp:151,322) ----
        fv_loc = fv
        fg = best_raw(r, leaf, fv_loc, tot_l, parent_out, lower, upper,
                      used_row, want_feature_gains=True, use_hp=hp_loc,
                      node_depth=depth)
        k = min(comm.top_k, num_feat)
        k2 = min(2 * comm.top_k, num_feat)
        _, top_idx = jax.lax.top_k(fg, k)
        votes = jnp.zeros((num_feat,), jnp.float32).at[top_idx].add(1.0)
        votes = comm.psum(votes)
        # deterministic global top-2k (ties resolve to the lowest index)
        bias = -jnp.arange(num_feat, dtype=jnp.float32) * 1e-6
        _, sel = jax.lax.top_k(votes + bias, k2)
        selmat = (sel[:, None]
                  == jnp.arange(num_feat, dtype=jnp.int32)[None, :]) \
            .astype(jnp.float32)                               # (k2, F)
        merged = comm.psum(jnp.einsum("kf,cfb->ckb", selmat, fv_loc))
        full = jnp.einsum("kf,ckb->cfb", selmat, merged)       # voted rows only
        selmask = jnp.any(selmat > 0.5, axis=0)
        return best_raw(r, leaf, full, tot_g, parent_out, lower, upper,
                        used_row, extra_mask=selmask, cegb_delta=delta,
                        node_depth=depth, adv_bounds=adv_b)

    # ---- init: root ----
    with trace_phase("lgbtpu/tree_state"):
        root_sum_loc = jnp.sum(ghc, axis=0) if root_sum_in is None \
            else root_sum_in
        root_sum = comm.root(root_sum_loc)
    if planes:
        # folded into the pack pass above: bit-identical accumulation to
        # the XLA segment histogram over the root segment (same chunking,
        # same einsum order). The Pallas hist_of adds the same exact
        # products in another order: equal counts, sums within 1e-6 of a
        # cell's sum of |terms| (tests/test_histogram.py)
        root_hist = comm.hist(root_hist_loc)
    else:
        with trace_phase("lgbtpu/root_hist"):
            root_hist, work = hist_of(work, jnp.int32(0), jnp.int32(guard),
                                      jnp.int32(n))
    with trace_phase("lgbtpu/tree_state"):
        # a leaf's row of the pool IS its histogram, (3, G, Bp): read and
        # written by dynamic slices on the leaf axis alone (pool_read,
        # pool_write), so no tile of it is ever reshaped
        hist_pool = pool_write(
            jnp.zeros((num_leaves, 3, num_grp, bp), jnp.float32),
            jnp.int32(0), root_hist)
        leaf_sum = jnp.zeros((num_leaves, 3), jnp.float32).at[0].set(root_sum)
        leaf_sum_loc = jnp.zeros((num_leaves, 3), jnp.float32).at[0].set(
            root_sum_loc)
        leaf_out = jnp.zeros((num_leaves,), jnp.float32).at[0].set(
            calc_leaf_output(root_sum[0], root_sum[1], hp))
        leaf_depth = jnp.zeros((num_leaves,), jnp.int32)
        leaf_lower = jnp.full((num_leaves,), -jnp.inf, jnp.float32)
        leaf_upper = jnp.full((num_leaves,), jnp.inf, jnp.float32)
        leaf_used = jnp.zeros((num_leaves, num_feat), bool)
        leaf_start = jnp.zeros((num_leaves,), jnp.int32).at[0].set(guard)
        leaf_cnt = jnp.zeros((num_leaves,), jnp.int32).at[0].set(n)
        leaf_parity = jnp.zeros((num_leaves,), jnp.int32)
        tree_used0 = cegb_used.astype(bool)
        if hp.mono_advanced:
            adv0 = _adv_init(num_leaves, num_feat, num_bin, meta)
        elif hp.has_monotone and hp.mono_intermediate:
            # intermediate's neighbor refresh needs only the (L, F) bin boxes
            adv0 = _adv_boxes_init(num_leaves, num_feat, meta)
        else:
            adv0 = ()
        if hp.mono_advanced:
            node_best_pair = jax.vmap(
                node_best, in_axes=(None, 0, 0, 0, 0, 0, 0, 0, None, None,
                                    None, 0))
        else:
            node_best_pair = jax.vmap(
                node_best, in_axes=(None, 0, 0, 0, 0, 0, 0, 0, None, None, None))

        # the root's initial search rides the SAME batched callable as the
        # per-round two-child refresh (batch of 1): one traced split-scan chain
        # serves every node_best call instead of compiling a second unbatched
        # variant of the whole reduce-window/select pipeline
        root_ix = jnp.array([0], jnp.int32)
        best = _empty_best(num_leaves, num_bin)
    root_view = feat_views(root_hist[None], root_sum[None], root_sum_loc[None])
    with scan_phase(hp):
        root_info = node_best_pair(
            0, root_ix, root_view, root_sum[None], root_sum_loc[None],
            leaf_out[:1], leaf_lower[:1], leaf_upper[:1], leaf_used[0],
            tree_used0, jnp.int32(0),
            *((jax.tree.map(lambda a: a[None],
                            _adv_bounds_of(adv0, jnp.int32(0))),)
              if hp.mono_advanced else ()))
    with trace_phase("lgbtpu/tree_state"):
        best = jax.tree.map(lambda b, v: b.at[root_ix].set(v), best, root_info)
        log = TreeLog(
            num_splits=jnp.int32(0),
            split_leaf=jnp.zeros((max_splits,), jnp.int32),
            feature=jnp.zeros((max_splits,), jnp.int32),
            bin=jnp.zeros((max_splits,), jnp.int32),
            kind=jnp.zeros((max_splits,), jnp.int32),
            default_left=jnp.zeros((max_splits,), bool),
            gain=jnp.zeros((max_splits,), jnp.float32),
            left_sum=jnp.zeros((max_splits, 3), jnp.float32),
            right_sum=jnp.zeros((max_splits, 3), jnp.float32),
            go_left=jnp.zeros((max_splits, num_bin), bool),
            miss_bin=jnp.zeros((max_splits,), jnp.int32),
            movable=jnp.zeros((max_splits,), bool),
            leaf_value=leaf_out,
            leaf_sum=leaf_sum,
            row_leaf=jnp.zeros((n,), jnp.int32),
        )

        def depth_ok(depth):
            if max_depth <= 0:
                return jnp.bool_(True)
            return depth < max_depth

        force_live = jnp.bool_(n_forced > 0)
        carry0 = (jnp.int32(0), work, leaf_start, leaf_cnt, leaf_parity,
                  hist_pool, leaf_sum, leaf_sum_loc, leaf_out, leaf_depth,
                  leaf_lower, leaf_upper, best, log, leaf_used, tree_used0,
                  force_live, adv0)

    def cond(carry):
        r, best, log, force_live = carry[0], carry[12], carry[13], carry[16]
        with trace_phase("lgbtpu/tree_state"):
            forcing = force_live & (r < n_forced) if n_forced else False
            return (log.num_splits < max_splits) & (r < max_splits + n_forced) \
                & ((jnp.max(best.gain) > 0.0) | forcing)

    def body(carry):
        (r, work, leaf_start, leaf_cnt, leaf_parity, hist_pool, leaf_sum,
         leaf_sum_loc, leaf_out, leaf_depth, leaf_lower, leaf_upper, best,
         log, leaf_used, tree_used, force_live, adv) = carry
        with trace_phase("lgbtpu/tree_state"):
            leaf = jnp.argmax(best.gain).astype(jnp.int32)
            info: SplitInfo = jax.tree.map(lambda a: a[leaf], best)
        if n_forced:
            # forced splits (reference: serial_tree_learner.cpp:450
            # ForceSplits) — same protocol as build_tree; its view and its
            # search sit under the phases of the ordinary ones
            f_leaf, f_feat, f_bin = forced

            def pick_forced(_):
                with trace_phase("lgbtpu/tree_state"):
                    ri = jnp.minimum(r, n_forced - 1)
                    fl = f_leaf[ri]
                    # voting keeps hist_pool LOCAL; a forced split must still be
                    # identical on every shard (default_left/gain derive from
                    # missing mass), so globalize the leaf histogram first. The
                    # cond predicate is replicated, so the psum is uniform.
                    hg_forced = pool_read(hist_pool, fl)
                    if voting or comm.hist_scatter:
                        hg_forced = comm.psum(hg_forced)
                if bundle is None:
                    fv_forced = feat_view(hg_forced, None)
                else:
                    with trace_phase("lgbtpu/efb_view"):
                        fv_forced = feat_view(hg_forced, leaf_sum[fl])
                with scan_phase(hp):
                    fi = find_best_split_planes(
                        fv_forced, leaf_sum[fl], meta,
                        jnp.arange(num_feat) == f_feat[ri], hp,
                        parent_output=leaf_out[fl], leaf_lower=leaf_lower[fl],
                        leaf_upper=leaf_upper[fl],
                        rand_threshold=jnp.full((num_feat,), f_bin[ri], jnp.int32),
                        node_depth=leaf_depth[fl],
                        adv_bounds=(_adv_bounds_of(adv, fl)
                                    if hp.mono_advanced else None))
                with trace_phase("lgbtpu/tree_state"):
                    ok = fi.gain > -jnp.inf
                    return (jnp.where(ok, fl, leaf),
                            jax.tree.map(lambda a, b: jnp.where(ok, a, b), fi, info),
                            ok)

            with trace_phase("lgbtpu/tree_state"):
                use_forced = force_live & (r < n_forced)
            leaf, info, force_live = jax.lax.cond(
                use_forced, pick_forced,
                lambda _: (leaf, info, jnp.bool_(False)), operand=None)
        with trace_phase("lgbtpu/tree_state"):
            s = log.num_splits
            new_leaf = s + 1

            if hp.has_monotone and (hp.mono_intermediate or hp.mono_advanced):
                # the stored best split was evaluated under the bounds current
                # at the leaf's LAST evaluation; neighbor refreshes may have
                # tightened them since. The reference re-searches affected
                # leaves (GoDownToFindLeavesToUpdate -> RecomputeBestSplit);
                # we keep the chosen split but re-clamp its outputs against the
                # parent's CURRENT bounds and re-enforce sibling order — the
                # committed values then respect every earlier neighbor, which
                # is what the soundness induction needs.
                mono_f = meta.monotone[info.feature]
                if hp.mono_advanced:
                    lo_l, up_l, lo_r, up_r = _adv_bounds_of(adv, leaf)
                    wl = jnp.clip(info.left_output,
                                  lo_l[info.feature, info.bin],
                                  up_l[info.feature, info.bin])
                    wr = jnp.clip(info.right_output,
                                  lo_r[info.feature, info.bin],
                                  up_r[info.feature, info.bin])
                else:
                    lo_p, up_p = leaf_lower[leaf], leaf_upper[leaf]
                    wl = jnp.clip(info.left_output, lo_p, up_p)
                    wr = jnp.clip(info.right_output, lo_p, up_p)
                swap = ((mono_f > 0) & (wl > wr)) | ((mono_f < 0) & (wl < wr))
                wl, wr = jnp.where(swap, wr, wl), jnp.where(swap, wl, wr)
                info = info._replace(left_output=wl, right_output=wr)

            if n_forced:
                valid = info.gain > -jnp.inf

                def sel(a, b):
                    """Commit only when the round produced a valid split."""
                    return jnp.where(valid, a, b)
            else:
                # Without forced splits the loop cond guarantees the picked
                # leaf's gain is positive, so every round commits. Skipping the
                # where() means no update reads the OLD pool value after the
                # write — without this, XLA cannot prove the dynamic-update-
                # slices on the 22 MB hist_pool in-place and inserts two full
                # copies per split (~72 ms/tree at 255 leaves, profiled).
                valid = jnp.bool_(True)

                def sel(a, b):
                    return a

            # ---- physical partition of the parent's segment ----
            # (invalid rounds write garbage into dead regions of the other
            # plane — harmless, since parity/segments only commit when valid)
            start = leaf_start[leaf]
            cnt = leaf_cnt[leaf]
            parity = leaf_parity[leaf]
            split_col = bundle["group"][info.feature] if bundle is not None \
                else info.feature
            # smaller child by GLOBAL in-bag count, so all shards agree
            # (serial_tree_learner.cpp:418)
            left_smaller = info.left_sum[2] <= info.right_sum[2]
        go_left_cols = route_table(info)
        with trace_phase("lgbtpu/partition"):
            work, lt = part_fn(work, parity, start, cnt, split_col,
                               go_left_cols, ch=part_chunk)
        with trace_phase("lgbtpu/tree_state"):
            new_parity = 1 - parity

            # ---- record ----
            log = log._replace(
                num_splits=sel(new_leaf, log.num_splits),
                split_leaf=log.split_leaf.at[s].set(sel(leaf, log.split_leaf[s])),
                feature=log.feature.at[s].set(sel(info.feature, log.feature[s])),
                bin=log.bin.at[s].set(sel(info.bin, log.bin[s])),
                kind=log.kind.at[s].set(sel(info.kind, log.kind[s])),
                default_left=log.default_left.at[s].set(
                    sel(info.default_left, log.default_left[s])),
                gain=log.gain.at[s].set(sel(info.gain, log.gain[s])),
                left_sum=log.left_sum.at[s].set(sel(info.left_sum, log.left_sum[s])),
                right_sum=log.right_sum.at[s].set(
                    sel(info.right_sum, log.right_sum[s])),
                go_left=log.go_left.at[s].set(sel(info.go_left, log.go_left[s])),
                miss_bin=log.miss_bin.at[s].set(
                    sel(meta.missing_bin[info.feature], log.miss_bin[s])),
                movable=log.movable.at[s].set(
                    sel(meta.movable_missing[info.feature], log.movable[s])),
            )

            # ---- segment bookkeeping ----
            leaf_start = leaf_start.at[new_leaf].set(
                sel(start + lt, leaf_start[new_leaf]))
            leaf_cnt = leaf_cnt.at[leaf].set(sel(lt, cnt)) \
                .at[new_leaf].set(sel(cnt - lt, leaf_cnt[new_leaf]))
            leaf_parity = leaf_parity.at[leaf].set(sel(new_parity, parity)) \
                .at[new_leaf].set(sel(new_parity, leaf_parity[new_leaf]))

            # ---- stats bookkeeping ----
            leaf_sum = leaf_sum.at[leaf].set(sel(info.left_sum, leaf_sum[leaf])) \
                .at[new_leaf].set(sel(info.right_sum, leaf_sum[new_leaf]))
            leaf_out = leaf_out.at[leaf].set(sel(info.left_output, leaf_out[leaf])) \
                .at[new_leaf].set(sel(info.right_output, leaf_out[new_leaf]))
            d = leaf_depth[leaf] + 1
            leaf_depth = leaf_depth.at[leaf].set(sel(d, leaf_depth[leaf])) \
                .at[new_leaf].set(sel(d, leaf_depth[new_leaf]))
            if hp.has_monotone and hp.mono_advanced:
                pass  # per-threshold bounds handled via _adv_commit below
            elif hp.has_monotone and hp.mono_intermediate:
                # intermediate: children inherit the parent's scalar bounds,
                # then BOTH children broadcast their committed outputs as
                # bounds to every box-overlapping leaf wholly below/above them
                # in each monotone dimension. The broadcast includes the
                # sibling constraint (left is wholly below right on the split
                # feature) AND the reference's neighbor refresh
                # (monotone_constraints.hpp:463 GoDownToFindLeavesToUpdate) —
                # without which a neighbor's later sub-split can drop below an
                # earlier committed output (observed monotonicity violations).
                lo_p, up_p = leaf_lower[leaf], leaf_upper[leaf]
                leaf_lower = leaf_lower.at[new_leaf].set(
                    sel(lo_p, leaf_lower[new_leaf]))
                leaf_upper = leaf_upper.at[new_leaf].set(
                    sel(up_p, leaf_upper[new_leaf]))
                rng_lo, rng_hi = adv
                rng_lo, rng_hi, box_l, box_r = _adv_child_boxes(
                    rng_lo, rng_hi, sel, leaf, new_leaf, info)
                adv = (rng_lo, rng_hi)
                monov = meta.monotone[None, :]                  # (1, F)
                inc = monov > 0
                dec = monov < 0
                valid_b = sel(jnp.bool_(True), jnp.bool_(False))
                for (c_rlo, c_rhi), out in ((box_l, info.left_output),
                                            (box_r, info.right_output)):
                    ov_exc = _adv_overlap_except(rng_lo, rng_hi, c_rlo, c_rhi)
                    below = rng_hi <= c_rlo[None, :]            # wholly below C
                    above = rng_lo >= c_rhi[None, :]            # wholly above C
                    hi_m = jnp.any(ov_exc & ((inc & below) | (dec & above)),
                                   axis=1) & valid_b            # (L,)
                    lo_m = jnp.any(ov_exc & ((inc & above) | (dec & below)),
                                   axis=1) & valid_b
                    leaf_upper = jnp.where(hi_m, jnp.minimum(leaf_upper, out),
                                           leaf_upper)
                    leaf_lower = jnp.where(lo_m, jnp.maximum(leaf_lower, out),
                                           leaf_lower)
            elif hp.has_monotone:
                # basic bounds both children by the split midpoint (reference:
                # monotone_constraints.hpp:327 BasicLeafConstraints)
                mono = meta.monotone[info.feature]
                bl = br = (info.left_output + info.right_output) * 0.5
                lo_l, up_l = leaf_lower[leaf], leaf_upper[leaf]
                new_up_l = jnp.where(mono > 0, jnp.minimum(up_l, bl), up_l)
                new_lo_r = jnp.where(mono > 0, jnp.maximum(lo_l, br), lo_l)
                new_lo_l = jnp.where(mono < 0, jnp.maximum(lo_l, bl), lo_l)
                new_up_r = jnp.where(mono < 0, jnp.minimum(up_l, br), up_l)
                leaf_lower = leaf_lower.at[leaf].set(sel(new_lo_l, lo_l)) \
                    .at[new_leaf].set(sel(new_lo_r, leaf_lower[new_leaf]))
                leaf_upper = leaf_upper.at[leaf].set(sel(new_up_l, up_l)) \
                    .at[new_leaf].set(sel(new_up_r, leaf_upper[new_leaf]))

            # ---- histograms: the smaller child gets a fresh pass over its
            # contiguous segment; the larger child is parent - smaller ----
            parent_hist = pool_read(hist_pool, leaf)
            pair = jnp.stack([leaf, new_leaf])
            small_start = jnp.where(left_smaller, start, start + lt)
            small_cnt = jnp.where(left_smaller, lt, cnt - lt)
        with trace_phase("lgbtpu/histogram"):
            hist_small, work = hist_of(work, new_parity, small_start,
                                       small_cnt)
        with trace_phase("lgbtpu/tree_state"):
            hist_large = parent_hist - hist_small
            hist_left = jnp.where(left_smaller, hist_small, hist_large)
            hist_right = jnp.where(left_smaller, hist_large, hist_small)
            if n_forced:
                old_right = pool_read(hist_pool, new_leaf)
                hist_pool = pool_write(hist_pool, leaf,
                                       sel(hist_left, parent_hist))
                hist_pool = pool_write(hist_pool, new_leaf,
                                       sel(hist_right, old_right))
            else:
                hist_pool = pool_write(hist_pool, leaf, hist_left)
                hist_pool = pool_write(hist_pool, new_leaf, hist_right)
            # local (g,h,cnt) totals per child (voting mode votes with these;
            # any group's bins partition the rows, so group 0 sums the leaf)
            loc_parent = leaf_sum_loc[leaf]
            loc_left = jnp.sum(hist_left[:, 0], axis=1)
            loc_right = loc_parent - loc_left
            leaf_sum_loc = leaf_sum_loc.at[leaf].set(sel(loc_left, loc_parent)) \
                .at[new_leaf].set(sel(loc_right, leaf_sum_loc[new_leaf]))

            # ---- refresh best splits for the two children ----
            used_new = leaf_used[leaf].at[info.feature].set(True)
            leaf_used = leaf_used.at[leaf].set(sel(used_new, leaf_used[leaf])) \
                .at[new_leaf].set(sel(used_new, leaf_used[new_leaf]))
            tree_used = tree_used.at[info.feature].set(
                sel(jnp.bool_(True), tree_used[info.feature]))

        # one vmapped search over both children: the scan ops are tiny at
        # (F, B), so two separate calls pay the per-op dispatch cost twice
        with trace_phase("lgbtpu/tree_state"):
            extra_pair = ()
            if hp.mono_advanced:
                adv = _adv_commit(adv, meta, sel, leaf, new_leaf, info,
                                  num_bin)
                ab_l = _adv_bounds_of(adv, leaf)
                ab_r = _adv_bounds_of(adv, new_leaf)
                extra_pair = (jax.tree.map(lambda a, b: jnp.stack([a, b]),
                                           ab_l, ab_r),)
        pair_g = jnp.stack([info.left_sum, info.right_sum])
        pair_l = jnp.stack([loc_left, loc_right])
        pair_view = feat_views(jnp.stack([hist_left, hist_right]),
                               pair_g, pair_l)
        with scan_phase(hp):
            infos = node_best_pair(
                r, pair, pair_view, pair_g, pair_l, leaf_out[pair],
                leaf_lower[pair], leaf_upper[pair], used_new, tree_used,
                d, *extra_pair)
        with trace_phase("lgbtpu/tree_state"):
            gates = jnp.stack([depth_ok(leaf_depth[leaf]),
                               depth_ok(leaf_depth[new_leaf])]) & valid
            infos = infos._replace(gain=jnp.where(gates, infos.gain, -jnp.inf))
            if n_forced:
                olds = jax.tree.map(lambda a: a[pair], best)
                infos = jax.tree.map(
                    lambda a, b: jnp.where(valid, a, b), infos, olds)
            best = jax.tree.map(lambda b, v: b.at[pair].set(v), best, infos)

        return (r + 1, work, leaf_start, leaf_cnt, leaf_parity, hist_pool,
                leaf_sum, leaf_sum_loc, leaf_out, leaf_depth, leaf_lower,
                leaf_upper, best, log, leaf_used, tree_used, force_live, adv)

    carry = jax.lax.while_loop(cond, body, carry0)
    (_, work_fin, _, _, _, _, leaf_sum, _, leaf_out, _, _, _, _, log, _, _,
     _, _) = carry
    rb, rbt = (bins, bins_t) if route_bins is None else route_bins
    row_leaf = assign_leaves(rb, log, has_categorical=hp.has_categorical,
                             bundle=bundle, bins_t=rbt)
    log = log._replace(leaf_value=leaf_out, leaf_sum=leaf_sum,
                       row_leaf=row_leaf)
    if return_work:
        return log, work_fin
    return log


def bundle_feature_view(hg: jax.Array, total_sum: jax.Array, bundle: dict,
                        num_bin_hist: int, view: BundleView) -> jax.Array:
    """Bundled (3, G, Bp) histogram -> per-feature (3, F, B) view, both
    channel-major, by ``BinnedDataset.bundle_maps`` (the arrays) and
    ``bundle_view`` (the static half, which names the form).

    Each feature's own slots are placed at its own bins, zeros elsewhere,
    in one of two forms; then a bundled feature's shared default bin is
    recovered as total - sum(own slots): the reference's FixHistogram
    contract (include/LightGBM/dataset.h:503). Both forms hand that step
    the same values, so the view is the same bit for bit.
    """
    own = _own_slots_runs(hg, bundle, view) if view.form == "runs" \
        else _own_slots_gather(hg, bundle, num_bin_hist)
    rest = total_sum[:, None] - jnp.sum(own, axis=2)             # (3, F)
    return jnp.where(bundle["put"], rest[:, :, None], own)


def _own_slots_gather(hg, bundle, num_bin_hist):
    """The view's own slots by an index a (feature, bin): F x B triples
    gathered out of the bundled histogram (``proj`` counts ``num_bin_hist``
    slots a device column). A gather's price on a v5e follows its index
    count, not its bytes (PERF.md section 6, PR 31): 0.32 ms for
    expo.train's 168,000 indices, of which 1,140 hold a slot, and 0.11 ms
    more to turn the (F x B, 3) result channel-major. The form of a table
    whose selection matrix would pass ``dataset.VIEW_SEL_MAX_BYTES``."""
    num_feat, num_bin = bundle["proj"].shape
    bp = hg.shape[-1]
    proj = bundle["proj"] // num_bin_hist * bp + bundle["proj"] % num_bin_hist
    flat = jnp.moveaxis(hg, 0, -1).reshape(-1, 3)                # (G*Bp, 3)
    fh = jnp.take(flat, proj.reshape(-1), axis=0).T \
        .reshape(3, num_feat, num_bin)
    return fh * bundle["valid"][None]


def _own_slots_runs(hg, bundle, view):
    """The view's own slots placed run by run, with no index a bin and no
    array that has the channels minor.

    A bundled feature's run leaves its column by a product with ``sel``,
    the 0/1 matrix that also spreads the run around the default bin. The
    product must copy, not round: the histogram goes in as three bfloat16
    terms (hi, mid, lo: 3 x 8 mantissa bits hold an f32's 24), each term's
    product with 1.0 is exact, every output holds one non-zero product,
    and hi + mid + lo is the f32 again. All columns meet all of ``sel`` in
    ONE product (3 terms x 3 channels x G rows are far under an MXU pass),
    and each output column keeps its own device column's row.
    A feature alone in its column is the column's row cut to its bins.
    """
    num_feat = bundle["dpos"].shape[0]
    num_bin, width = view.num_bin, view.width
    x = hg[..., :bundle["sel"].shape[0]]                         # (3, G, Bm)
    # reduce_precision, not a round trip through bfloat16: XLA may
    # elide f32 -> bf16 -> f32 (xla_allow_excess_precision)
    hi = jax.lax.reduce_precision(x, 8, 7)
    mid = jax.lax.reduce_precision(x - hi, 8, 7)
    terms = jnp.stack([hi, mid, x - hi - mid]).astype(jnp.bfloat16)
    prod = jnp.einsum("tcgs,sn->tcgn", terms, bundle["sel"],
                      preferred_element_type=jnp.float32)
    picked = jnp.sum(jnp.where(bundle["sel_mine"],
                               prod[0] + prod[1] + prod[2], 0.0),
                     axis=1)                                      # (3, F*W)
    own = jnp.pad(picked.reshape(3, num_feat, width),
                  ((0, 0), (0, 0), (0, num_bin - width)))
    for k, col in enumerate(view.slices):
        own = jnp.where(bundle["alone_cell"] == k + 1,
                        hg[:, col, None, :num_bin], own)
    if view.alone and not view.slices:
        rows = jnp.take(hg[..., :num_bin], bundle["alone_col"], axis=1)
        own = jnp.where(bundle["alone_cell"] > 0, rows, own)
    return own


@partial(jax.jit, static_argnames=("has_categorical",))
def assign_leaves(bins: jax.Array, log: TreeLog,
                  has_categorical: bool = True,
                  bundle: Optional[dict] = None,
                  bins_t: Optional[jax.Array] = None) -> jax.Array:
    """Route binned rows through a tree's split log (device analog of
    Tree::PredictLeafIndex over pre-binned data; used for valid-set score
    updates, mirroring ScoreUpdater's use of the data partition,
    score_updater.hpp:88).

    Numerical splits route arithmetically (bin <= threshold, with the
    movable-missing bin overridden to the recorded default direction) —
    no table gathers, which are slow on TPU. With EFB bundles the matrix
    columns are bundle-bin coded: the sub-feature's slots translate back
    to feature bins arithmetically and all alien slots follow the shared
    default bin's direction. Categorical splits need the full (B,) routing
    table; when the dataset has no categorical features (static
    ``has_categorical=False``) that path is skipped entirely.
    """
    # the one site of the phase: it serves the end of every tree build, the
    # eager loop and valid-set routing, and no caller sits under another
    with trace_phase("lgbtpu/route"):
        return _route_rows(bins, log, has_categorical, bundle, bins_t)


def _route_rows(bins, log, has_categorical, bundle, bins_t):
    n = bins.shape[0]
    max_splits = log.split_leaf.shape[0]
    # fast path on a TPU: every tree, categorical rounds too, routes in ONE
    # streaming Pallas pass (ops/route.py) — the fori form below re-reads
    # the matrix and the leaf vector once per round (~30 ms/tree at 2M x 28
    # vs ~5 ms), and a categorical round of it is an (N, B) one-hot. It
    # stays as the CPU path and the tests' oracle
    from .ops.route import (ROUTE_BLOCK_ROWS, build_route_table,
                            pallas_routes, route_rows)
    if runtime.on_tpu() and pallas_routes(has_categorical,
                                          log.go_left.shape[1]):
        if bins_t is not None and bins_t.ndim == 3:
            btr = bins_t   # pre-padded (F, npad/128, 128) block form
        else:
            bt = bins_t if bins_t is not None else bins.T
            rb = ROUTE_BLOCK_ROWS
            npad = ((n + rb - 1) // rb) * rb
            if npad != n:
                bt = jnp.pad(bt, ((0, 0), (0, npad - n)))
            btr = bt.reshape(bins.shape[1], npad // 128, 128)
        table = build_route_table(log, None, bundle, has_categorical)
        return route_rows(btr, table, log.num_splits, n,
                          categorical=has_categorical)[:n]
    # the routing state is pure HBM traffic (a full-N read-modify-write per
    # round): u8 leaf ids cut it 4x whenever they fit (num_leaves <= 256 —
    # always true for the partitioned builder's default shapes)
    small = max_splits + 1 <= 256
    ldt = jnp.uint8 if small else jnp.int32
    row_leaf = jnp.zeros((n,), ldt)
    # one transpose up front: each routing round then reads ONE contiguous
    # (N,) row instead of gathering a strided column from the row-major
    # matrix (the column gather re-streams the whole matrix per round —
    # measured ~30 ms/tree at 2M x 28; transposed rounds are ~6 ms total).
    # Callers building many trees pass a hoisted bins_t (the u8 transpose
    # itself costs ~20 ms at 2M x 28).
    if bins_t is None:
        bins_t = bins.T

    def body(r, row_leaf):
        active = r < log.num_splits
        leaf = log.split_leaf[r]
        fid = log.feature[r]
        col_idx = bundle["group"][fid] if bundle is not None else fid
        col = jax.lax.dynamic_index_in_dim(
            bins_t, col_idx, axis=0, keepdims=False).astype(jnp.int32)

        def go_numerical(col):
            if bundle is not None:
                off = bundle["offset"][fid]
                d = bundle["dpos"][fid]
                rest_dir = log.go_left[r][d]
                rank = col - off
                fb = rank + (rank >= d)
                in_range = bundle["has_rest"][fid] \
                    & (col >= off) & (col < off + bundle["nbm1"][fid])
                plain = ~bundle["has_rest"][fid]
                eff = jnp.where(plain, col, fb)
                go = eff <= log.bin[r]
                go = jnp.where(log.movable[r] & (eff == log.miss_bin[r]),
                               log.default_left[r], go)
                return jnp.where(plain | in_range, go, rest_dir)
            go = col <= log.bin[r]
            return jnp.where(log.movable[r] & (col == log.miss_bin[r]),
                             log.default_left[r], go)

        if has_categorical:
            num_bin = log.go_left.shape[1]

            def go_categorical(col):
                oh = (col[:, None]
                      == jnp.arange(num_bin, dtype=jnp.int32)[None, :])
                return (oh.astype(jnp.float32)
                        @ log.go_left[r].astype(jnp.float32)) > 0.5

            # only the winning branch runs: numerical rounds skip the
            # O(N*B) one-hot entirely
            go = jax.lax.cond(log.kind[r] > 0, go_categorical, go_numerical,
                              col)
        else:
            go = go_numerical(col)
        upd = jnp.where((row_leaf == leaf.astype(ldt)) & ~go,
                        (r + 1).astype(ldt), row_leaf)
        return jnp.where(active, upd, row_leaf)

    out = jax.lax.fori_loop(0, max_splits, body, row_leaf)
    return out.astype(jnp.int32)


def leaf_values_by_row(leaf_value: jax.Array, row_leaf: jax.Array,
                       num_leaves: int, chunk: int = 65536) -> jax.Array:
    """(L,) leaf outputs + (N,) leaf ids -> (N,) per-row values.

    TPU element gathers run at ~60ns/row (latency-bound); a chunked one-hot
    contraction is bandwidth-bound instead (~50x faster at N=2M). Exact:
    f32 HIGHEST matmul with a 0/1 operand.
    """
    n = row_leaf.shape[0]
    iota = jnp.arange(num_leaves, dtype=row_leaf.dtype)
    lv = leaf_value.astype(jnp.float32)

    def one(rl_c):
        oh = (rl_c[:, None] == iota[None, :]).astype(jnp.float32)
        return jax.lax.dot(oh, lv[:, None],
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)[:, 0]

    if n <= chunk:
        # no padding below one chunk — serving buckets sit far under the
        # chunk size and must not pay a 65536-row contraction for 256 rows
        return one(row_leaf)
    pad = (-n) % chunk
    rl = jnp.pad(row_leaf, (0, pad)) if pad else row_leaf
    out = jax.lax.map(one, rl.reshape(-1, chunk))
    return out.reshape(-1)[:n]


# --------------------------------------------------------------------------
# Host wrapper
# --------------------------------------------------------------------------

class SerialTreeLearner:
    """Host orchestration around the jitted device builder
    (reference analog: SerialTreeLearner + the factory at
    src/treelearner/tree_learner.cpp:15 — device offload is the default
    here, so the 4×3 learner matrix collapses to {serial, data-parallel}
    over the same builder)."""

    def __init__(self, config: Config, dataset: BinnedDataset,
                 comm_axis: Optional[str] = None) -> None:
        self.config = config
        self.dataset = dataset
        self.num_leaves = max(2, int(config.num_leaves))
        nb = dataset.feature_num_bins()
        self.num_bin = int(max(2, nb.max() if len(nb) else 2))
        from .ops.binning import BIN_CATEGORICAL, MISSING_NAN, MISSING_ZERO
        mono = np.zeros(dataset.num_features, dtype=np.int8)
        if dataset.monotone_constraints is not None:
            mono = dataset.monotone_constraints.astype(np.int8)
        pen = np.ones(dataset.num_features, dtype=np.float32)
        if dataset.feature_penalty is not None:
            pen = dataset.feature_penalty.astype(np.float32)
        cegb_coupled = np.zeros(dataset.num_features, dtype=np.float32)
        if config.cegb_penalty_feature_coupled:
            for i, f in enumerate(dataset.used_feature_indices):
                if f < len(config.cegb_penalty_feature_coupled):
                    cegb_coupled[i] = config.cegb_penalty_feature_coupled[f]
        if config.cegb_penalty_feature_lazy:
            Log.warning("cegb_penalty_feature_lazy is not supported; "
                        "use cegb_penalty_feature_coupled")
        self.meta = FeatureMeta(
            num_bins=jnp.asarray(nb, jnp.int32),
            movable_missing=jnp.asarray(
                [m.missing_type in (MISSING_NAN, MISSING_ZERO)
                 and m.bin_type != BIN_CATEGORICAL
                 for m in dataset.bin_mappers], bool),
            missing_bin=jnp.asarray([m.missing_bin for m in dataset.bin_mappers], jnp.int32),
            is_categorical=jnp.asarray(
                [m.bin_type == BIN_CATEGORICAL for m in dataset.bin_mappers], bool),
            monotone=jnp.asarray(mono),
            penalty=jnp.asarray(pen),
            cegb_coupled=jnp.asarray(cegb_coupled),
        )
        self.hp = SplitHyper(
            lambda_l1=float(config.lambda_l1),
            lambda_l2=float(config.lambda_l2),
            min_data_in_leaf=float(config.min_data_in_leaf),
            min_sum_hessian_in_leaf=float(config.min_sum_hessian_in_leaf),
            min_gain_to_split=float(config.min_gain_to_split),
            max_delta_step=float(config.max_delta_step),
            cat_smooth=float(config.cat_smooth),
            cat_l2=float(config.cat_l2),
            max_cat_threshold=int(config.max_cat_threshold),
            max_cat_to_onehot=int(config.max_cat_to_onehot),
            min_data_per_group=float(config.min_data_per_group),
            path_smooth=float(config.path_smooth),
            has_categorical=any(m.bin_type == BIN_CATEGORICAL for m in dataset.bin_mappers),
            has_monotone=dataset.monotone_constraints is not None,
            mono_intermediate=config.monotone_constraints_method
            in ("intermediate", "advanced"),
            mono_advanced=(config.monotone_constraints_method == "advanced"
                           and dataset.monotone_constraints is not None),
            monotone_penalty=float(config.monotone_penalty),
            cegb_tradeoff=float(config.cegb_tradeoff),
            cegb_penalty_split=float(config.cegb_penalty_split),
            # gate on an actually non-zero penalty: cegb_tradeoff alone is a
            # multiplier with nothing to multiply, and enabling CEGB forces
            # the partitioned builder for runs that would train identically
            use_cegb=bool(config.cegb_penalty_split > 0
                          or config.cegb_penalty_feature_coupled),
        )
        self.bins = dataset.device_bins()
        self.num_bin_hist = int(max(2, dataset.group_num_bins().max()
                                    if dataset.num_groups else 2))
        self.bundle = self.bundle_view = None
        if dataset.has_bundles:
            self.bundle = {k: jnp.asarray(v)
                           for k, v in dataset.bundle_maps().items()}
            self.bundle_view = dataset.bundle_view()
        if self.hp.mono_advanced and not self.use_partition():
            Log.warning("monotone_constraints_method=advanced needs the "
                        "partitioned builder (max_bin <= 256); the dense "
                        "builder applies the basic (midpoint) method")
            self.hp = self.hp._replace(mono_advanced=False)
        if self.hp.use_cegb and not self.use_partition():
            Log.fatal("CEGB penalties require the partitioned builder "
                      "(max_bin <= 256, tree_builder != dense)")
        if (config.use_quantized_grad
                or config.tpu_hist_precision == "int8") \
                and not self.use_partition():
            Log.fatal("use_quantized_grad requires the partitioned builder "
                      "(max_bin <= 256, tree_builder != dense)")
        self.comm = self._make_comm(comm_axis)
        self._build = track_jit("learner/build", jax.jit(self.make_build_fn()))

    def _make_comm(self, axis: Optional[str]) -> Comm:
        return Comm(axis)

    def use_partition(self) -> bool:
        """Partitioned (leaf-contiguous) builder unless disabled or the bin
        count exceeds the packed-u8 layout (max_bin > 256 -> u16 bins)."""
        mode = self.config.tree_builder
        if mode == "dense":
            if self.bundle is not None:
                Log.fatal("tree_builder=dense does not support EFB bundles; "
                          "set enable_bundle=false or use the partitioned "
                          "builder")
            return False
        ok = self.num_bin <= 256 and self.num_bin_hist <= 256 \
            and self.bins.dtype == jnp.uint8
        if mode == "partition" and not ok:
            Log.fatal(
                "tree_builder=partition requires max_bin <= 256 (uint8 "
                "bins); got %d bins. Use tree_builder=dense or lower "
                "max_bin.", self.num_bin)
        if not ok and self.bundle is not None:
            Log.fatal("EFB bundles require the partitioned builder "
                      "(max_bin <= 256)")
        return ok

    def make_build_fn(self):
        """The tree-builder callable with static arguments closed over —
        shared by the serial, data-parallel and fused training paths."""
        if self.use_partition():
            return partial(build_tree_partitioned, **self.build_kwargs())
        return partial(build_tree, **self.build_kwargs())

    def build_kwargs(self) -> dict:
        config = self.config
        kw = dict(
            hp=self.hp,
            num_leaves=self.num_leaves,
            num_bin=self.num_bin,
            max_depth=int(config.max_depth),
            feature_fraction_bynode=float(config.feature_fraction_bynode),
            extra_trees=bool(config.extra_trees),
            extra_seed=int(config.extra_seed),
            comm=self.comm,
            constraint_sets=self._constraint_sets(),
            forced=self._forced_splits(),
        )
        if self.use_partition():
            from .obs import telemetry
            mode = config.tpu_hist_precision
            if config.use_quantized_grad:
                mode = "int8"
            tpu = runtime.on_tpu()
            backend = "tpu" if tpu else jax.default_backend()
            # Ledger preresolution (ROADMAP self-calibration): a previous
            # run on this (machine, dataset-shape, config) key already
            # resolved the auto knobs; reuse its answers instead of
            # re-deriving them, recording under ledger_preresolution so
            # the knob set still persists forward (and the acceptance
            # test can assert ZERO new auto_resolution records). Values
            # come from a JSON file: sanitize here, and every validation
            # gate below still applies to them.
            pre = {}
            if config.obs_ledger:
                from . import obs_ledger
                raw = obs_ledger.preresolve(config, self.dataset.num_data,
                                            self.dataset.num_features)
                valid = {"tpu_partition_kernel": ("pallas", "xla"),
                         "tpu_hist_kernel": ("pallas", "xla"),
                         "tpu_work_layout": ("planes", "rows"),
                         "tpu_resident_state": ("resident", "off"),
                         "tpu_goss_compact": ("on", "off")}
                for k, v in raw.items():
                    if k in valid and v in valid[k]:
                        pre[k] = v
                    elif k in ("tpu_part_chunk", "tpu_hist_chunk") \
                            and isinstance(v, int) and v > 0:
                        pre[k] = v

            def _pre(knob):
                """Consume a preresolved knob value (records + counts)."""
                v = pre[knob]
                telemetry.record("ledger_preresolution",
                                 dedupe_key=(knob, v), knob=knob,
                                 configured="auto", value=v,
                                 reason="preresolved from run ledger")
                telemetry.count("ledger/preresolved_knobs")
                return v

            part_kernel = config.tpu_partition_kernel
            auto_kernel = part_kernel == "auto"
            part_why = ""
            if auto_kernel and "tpu_partition_kernel" in pre:
                part_kernel = _pre("tpu_partition_kernel")
                auto_kernel = False   # resolved; no fresh record below
            elif auto_kernel:
                # the fused DMA kernel needs Mosaic; CPU test meshes and
                # non-TPU backends use the portable XLA pipeline
                part_kernel = "pallas" if tpu else "xla"
                part_why = ("backend %s has Mosaic: fused DMA kernel"
                            % backend if part_kernel == "pallas" else
                            "backend %s has no Mosaic: portable XLA pipeline"
                            % backend)
            from .ops.histogram import planes_kernel_chunk
            from .ops.partition import (GH_BYTES, GH_BYTES_Q,
                                        planes_part_chunk)
            n_col = int(self.bins.shape[1])
            row_w = n_col + (GH_BYTES_Q if mode == "int8" else GH_BYTES)
            # both planes kernels hold every plane of a chunk in VMEM; the
            # widest table whose smallest chunk still fits (the histogram's
            # accumulator is 10 KB a column: planes_kernel_chunk)
            planes_fit = planes_kernel_chunk(n_col) > 0
            layout = config.tpu_work_layout
            auto_layout = layout == "auto"
            layout_why = ""
            if auto_layout and "tpu_work_layout" in pre:
                layout = _pre("tpu_work_layout")
                auto_layout = False
            elif auto_layout:
                # planes at every width the kernels' VMEM holds: the two
                # planes kernels are the chip's fast path (PRs 27, 29) and
                # neither unrolls over the planes, so a wide row pays them
                # per plane what a narrow one does (PERF.md, PR 33: at
                # W = 2,016 17.1 ns a row visit and 0.048-0.059 ns a (row,
                # feature) alone, where the rows layout's XLA loops paid
                # 136 and 0.29; at W = 320 and 512 3.9 and 5.9 ns against
                # the rows kernel's 5.9 and 6.8, 0.047 against the einsum's
                # 0.24 and 0.22). int8 keeps rows (no quantized planes pack
                # pass yet)
                layout = "planes" if (
                    tpu and planes_fit and mode != "int8") else "rows"
                if layout == "planes":
                    layout_why = ("packed row %d B on %s: both planes "
                                  "kernels hold a chunk of it in VMEM"
                                  % (row_w, backend))
                elif not tpu:
                    layout_why = "backend %s: row-major default" % backend
                elif mode == "int8":
                    layout_why = "int8 mode has no quantized planes pack"
                else:
                    layout_why = ("packed row %d B: the planes histogram's "
                                  "accumulator does not fit VMEM" % row_w)
            elif layout == "planes" and mode == "int8":
                Log.warning("tpu_work_layout=planes does not support int8 "
                            "quantized training; using rows")
                layout = "rows"
            if part_kernel == "pallas" and (
                    (row_w > 512 and layout == "rows")
                    or (not planes_fit and layout != "rows")):
                # the ROWS kernel's DMA window is 4 lane-tiles of 128 B
                # (int8 and an explicit rows layout are what still reach
                # it); the planes kernel stops where its VMEM does
                if not auto_kernel:
                    Log.warning(
                        "tpu_partition_kernel=pallas needs packed rows "
                        "<= 512 bytes in the rows layout and a width the "
                        "planes kernels' VMEM holds (got %d, layout %s); "
                        "using the XLA kernel", row_w, layout)
                part_kernel = "xla"
                part_why = ("packed row %d B in the %s layout is past the "
                            "pallas kernel's window" % (row_w, layout))
            part_chunk = int(config.tpu_part_chunk)
            auto_part_chunk = part_chunk <= 0
            if auto_part_chunk and "tpu_part_chunk" in pre:
                part_chunk = _pre("tpu_part_chunk")
                auto_part_chunk = False
            elif auto_part_chunk:
                # measured on v5e: the XLA path optimum is 2048 (per-op
                # overhead vs O(ch^2) compaction matmul); the pallas kernels
                # have no per-op overhead, so 1024 halves the matmul work,
                # and the planes kernel's chunk follows its plane count
                if part_kernel != "pallas":
                    part_chunk = 2048
                elif layout == "rows":
                    part_chunk = 1024
                else:
                    part_chunk = planes_part_chunk(row_w)
            if part_kernel == "pallas" and (
                    part_chunk % 32
                    or (part_chunk > 256 and part_chunk % 256)):
                Log.fatal("tpu_part_chunk must be a multiple of 32 and, "
                          "above 256, a multiple of the 256-row compaction "
                          "sub-block (got %d)", part_chunk)
            hist_chunk = int(config.tpu_hist_chunk)
            auto_hist_chunk = hist_chunk <= 0
            if auto_hist_chunk and "tpu_hist_chunk" in pre:
                hist_chunk = _pre("tpu_hist_chunk")
                auto_hist_chunk = False
            elif auto_hist_chunk:
                from .ops.histogram import einsum_chunk
                hist_chunk = einsum_chunk(self.bins.shape[1])
            hist_kernel = config.tpu_hist_kernel
            auto_hist = hist_kernel == "auto"
            if auto_hist and "tpu_hist_kernel" in pre:
                hist_kernel = _pre("tpu_hist_kernel")
                auto_hist = False
            elif hist_kernel == "pallas" and (part_kernel != "pallas"
                                              or mode == "int8"):
                Log.warning("tpu_hist_kernel=pallas needs the pallas "
                            "partition layout and a non-quantized mode; "
                            "using the XLA einsum")
                hist_kernel = "xla"
            rs = config.tpu_resident_state
            auto_rs = rs == "auto"
            if rs == "on":
                if config.tpu_work_layout == "rows":
                    Log.fatal("tpu_resident_state=on requires the planes "
                              "work layout (got tpu_work_layout=rows)")
                if mode == "int8":
                    Log.fatal("tpu_resident_state=on does not support int8 "
                              "quantized training (plane-family layouts "
                              "are hilo/bf16 only)")
                layout = "resident"
            elif auto_rs and "tpu_resident_state" in pre:
                if _pre("tpu_resident_state") == "resident" \
                        and layout == "planes":
                    layout = "resident"
                auto_rs = False
            # auto = off on every backend. Resident state cuts partition
            # traffic but its histogram row-gathers through the permuted
            # index: on a v5e it ran 2.3 s/iter against plain planes'
            # 0.63 (10.5M x 28) and 1.17 against 0.66 (2.27M x 137) with
            # byte-identical models (PERF.md, PR 21). Selectable with
            # tpu_resident_state=on until queue 3 repairs or deletes it.
            hist_why = ""
            if auto_hist:
                # auto follows backend and layout: the planes kernel builds
                # its one-hots in VMEM and serves several features an MXU
                # pass; in situ on a v5e it took
                # higgs.train (F = 28) from 636.6 to 458.1 ms an iteration,
                # mslr.train (F = 137) from 1395.1 to 940.3, expo.train
                # (F = 10) from 654.8 to 610.1 (PERF.md, PR 29; the
                # one-feature-a-pass kernel it replaces read 517 / 1002 /
                # 606 in 13-s windows where the XLA loop read 639 / 1399 /
                # 637: "slower in situ" does not hold on this machine).
                # The rows twin and the CPU / mesh paths keep the einsum.
                if tpu and layout == "planes" and part_kernel == "pallas" \
                        and self.comm.axis is None:
                    hist_kernel = "pallas"
                    hist_why = ("planes layout + pallas partition on %s: "
                                "one-hots built in VMEM, several features "
                                "an MXU pass" % backend)
                else:
                    # the mesh learners keep the XLA einsum until a
                    # four-chip run has timed the kernel under shard_map
                    hist_kernel = "xla"
                    hist_why = ("layout %s, partition %s, comm axis %s on "
                                "%s: XLA einsum" % (layout, part_kernel,
                                                    self.comm.axis, backend))
            if auto_hist_chunk and hist_kernel == "pallas" \
                    and layout == "planes":
                # the planes kernel owns its VMEM: longer DMAs than the XLA
                # loop's chunk (which spills at F > 64)
                hist_chunk = planes_kernel_chunk(n_col)
            if hist_kernel == "pallas" and hist_chunk % 32:
                # the kernel re-derives DMA offsets as (x // 32) * 32; a
                # misaligned chunk would double-count the rows between the
                # aligned offset and the true chunk start — silently wrong
                # histograms (ADVICE: refuse loudly, like part_chunk % 32)
                Log.fatal("tpu_hist_chunk must be a multiple of 32 with "
                          "the pallas histogram kernel (got %d)", hist_chunk)
            if layout == "resident" and hist_kernel == "pallas":
                Log.warning("tpu_hist_kernel=pallas has no resident gather "
                            "path; using the XLA gather einsum")
                hist_kernel = "xla"
            if layout == "planes" and hist_kernel == "pallas" \
                    and hist_chunk % 128:
                # the planes kernel re-derives lane DMA offsets as
                # (x // 128) * 128 — a misaligned chunk double-counts rows
                Log.fatal("tpu_hist_chunk must be a multiple of 128 with "
                          "the planes pallas histogram kernel (got %d)",
                          hist_chunk)
            if layout in ("planes", "resident") and part_kernel == "pallas" \
                    and (part_chunk % 128
                         or (part_chunk > 256 and part_chunk % 256)):
                Log.fatal("planes layout needs tpu_part_chunk a multiple "
                          "of 128 and, above 256, of the 256-row "
                          "compaction sub-block (got %d)", part_chunk)
            from .ops.partition import goss_compact_rows as _gcr
            n_rows = int(self.bins.shape[0])
            goss_active = (config.data_sample_strategy == "goss"
                           and float(config.top_rate)
                           + float(config.other_rate) < 1.0)
            m_rows = _gcr(n_rows, float(config.top_rate),
                          float(config.other_rate)) if goss_active else 0
            gc = config.tpu_goss_compact
            auto_gc = gc == "auto"
            gc_why = ""
            if auto_gc and "tpu_goss_compact" in pre:
                gc = _pre("tpu_goss_compact")
                auto_gc = False
            elif auto_gc:
                # auto = off: compaction's bit-parity with the dense-mask
                # path holds under the CPU interpreter and the program
                # compiles for a v5e (tests/test_aot_tpu.py); its
                # wall-clock effect has not been measured on a chip.
                gc = "off"
                if goss_active:
                    gc_why = ("GOSS compaction parity proven under "
                              "interpret only; gather + compact-build "
                              "unmeasured on TPU — run "
                              "scripts/goss_bisect.py to validate, then "
                              "enable via knob or ledger")
                else:
                    gc_why = ("no GOSS sampling in this config "
                              "(data_sample_strategy=%s)"
                              % config.data_sample_strategy)
            if gc == "on":
                bad = []
                if not goss_active:
                    bad.append("no GOSS sampling in this config")
                if mode == "int8":
                    bad.append("int8 stochastic-rounding draws are "
                               "row-position seeded (compaction would "
                               "change the quantization stream)")
                if self.comm.axis is not None:
                    bad.append("multi-device comm unsupported (per-shard "
                               "compact/dense cond would diverge)")
                if goss_active and m_rows >= n_rows:
                    bad.append("sample rates leave no rows to drop")
                if bad:
                    Log.warning("tpu_goss_compact=on is not eligible here "
                                "(%s); using the dense-mask path",
                                "; ".join(bad))
                    gc = "off"
                    if auto_gc:
                        gc_why = "structurally ineligible: " + "; ".join(bad)
            # auto-knob resolution records: what auto chose and why
            # (deduped, so repeated build_kwargs calls keep one record per
            # distinct resolution)
            def _rec(knob, value, reason):
                telemetry.record("auto_resolution",
                                 dedupe_key=(knob, value, reason),
                                 knob=knob, configured="auto",
                                 value=value, reason=reason)

            if auto_kernel:
                _rec("tpu_partition_kernel", part_kernel, part_why)
            if auto_hist:
                _rec("tpu_hist_kernel", hist_kernel, hist_why)
            if auto_layout:
                _rec("tpu_work_layout", layout if layout != "resident"
                     else "planes", layout_why)
            if auto_rs:
                _rec("tpu_resident_state", "off",
                     "layout %s on %s: the resident histogram gather "
                     "measured 1.8-3.6x slower per iteration than plain "
                     "planes on a v5e (PERF.md, PR 21)" % (layout, backend))
            if auto_part_chunk:
                _rec("tpu_part_chunk", part_chunk,
                     "%s kernel default chunk" % part_kernel)
            if auto_hist_chunk:
                _rec("tpu_hist_chunk", hist_chunk,
                     "packed width %d default chunk" % self.bins.shape[1])
            if auto_gc:
                _rec("tpu_goss_compact", gc, gc_why)
            kw.update(
                hist_chunk=hist_chunk,
                part_chunk=part_chunk,
                hist_mode=mode,
                hist_lo=int(config.tpu_hist_lo),
                num_bin_hist=self.num_bin_hist,
                bundle=self.bundle,
                bundle_view=self.bundle_view,
                part_kernel=part_kernel,
                hist_kernel=hist_kernel,
                work_layout=layout,
                goss_compact_rows=m_rows if gc == "on" else 0,
            )
            # which layout, kernels and chunks this job's width took and what
            # its two largest device buffers hold: one record per distinct
            # resolution (build_kwargs runs several times a job)
            from .ops.route import pallas_routes, route_form
            path = dict(
                packed_row_bytes=row_w, work_layout=layout,
                part_kernel=part_kernel, hist_kernel=hist_kernel,
                route_kernel="pallas_" + route_form(self.bins.shape[1])
                if tpu and pallas_routes(self.hp.has_categorical,
                                         self.num_bin) else "xla",
                part_chunk=part_chunk, hist_chunk=hist_chunk,
                hist_pool_gb=self.num_leaves * self.bins.shape[1]
                * hist_bins(self.num_bin_hist) * 12 / 1e9,
                work_buffer_gb=float(np.prod(
                    self._work_buf_shape(kw), dtype=np.float64)) / 1e9)
            if self.bundle_view is not None:
                # which form builds the bundled table's per-feature view
                # (dataset.VIEW_SEL_MAX_BYTES) and over what
                bv = self.bundle_view
                path.update(efb_view=bv.form, efb_alone=bv.alone,
                            efb_bundled=bv.bundled, efb_sel_bytes=bv.sel_bytes)
            telemetry.record("learner_path",
                             dedupe_key=tuple(path.values()), **path)
        else:
            kw.update(
                hist_chunk=min(int(config.tpu_rows_per_chunk), 8192),
                # measured on v5e: XLA fuses the f32 HIGHEST one-hot matmul
                # better than the bf16 hi/lo two-dot variant
                mxu_bf16=False,
            )
        return kw

    def _constraint_sets(self) -> Optional[jax.Array]:
        """Parse interaction_constraints "[0,1],[2,3]" into (S, F) bool over
        inner feature indices (reference: col_sampler.hpp:27)."""
        spec = self.config.interaction_constraints
        if not spec:
            return None
        import re
        groups = re.findall(r"\[([^\]]*)\]", str(spec))
        if not groups:
            return None
        F = self.dataset.num_features
        sets = np.zeros((len(groups), F), dtype=bool)
        for s, grp in enumerate(groups):
            for tok in grp.split(","):
                tok = tok.strip()
                if tok == "":
                    continue
                inner = self.dataset.inner_feature_index(int(tok))
                if inner >= 0:
                    sets[s, inner] = True
        return jnp.asarray(sets)

    def _forced_splits(self):
        """Load forcedsplits_filename JSON into BFS (leaf, feature, bin)
        arrays (reference: serial_tree_learner.cpp:450 ForceSplits)."""
        fname = self.config.forcedsplits_filename
        if not fname:
            return None
        import json as _json
        import os
        if not os.path.exists(fname):
            Log.warning("forced splits file %s not found", fname)
            return None
        with open(fname) as f:
            root = _json.load(f)
        leaves, feats, bins_ = [], [], []
        queue = [(root, 0)]
        n_created = 0
        while queue and n_created < self.num_leaves - 1:
            node, leaf = queue.pop(0)
            if not node or "feature" not in node:
                continue
            inner = self.dataset.inner_feature_index(int(node["feature"]))
            if inner < 0:
                continue
            mapper = self.dataset.bin_mappers[inner]
            tbin = int(mapper.value_to_bin(
                np.asarray([float(node["threshold"])]))[0])
            tbin = min(tbin, mapper.num_bins - 2) if mapper.num_bins > 1 else 0
            leaves.append(leaf)
            feats.append(inner)
            bins_.append(tbin)
            n_created += 1
            new_leaf = n_created
            if "left" in node and node["left"]:
                queue.append((node["left"], leaf))
            if "right" in node and node["right"]:
                queue.append((node["right"], new_leaf))
        if not leaves:
            return None
        return (jnp.asarray(leaves, jnp.int32), jnp.asarray(feats, jnp.int32),
                jnp.asarray(bins_, jnp.int32))

    def work_buf_spec(self):
        """(shape, dtype) of the carried work buffer for the partitioned
        builder, or None (fused blocks preallocate it once per block instead
        of paying a fresh 2x(N,W) alloc+zero per tree)."""
        if not self.use_partition():
            return None
        return self._work_buf_shape(self.build_kwargs()), jnp.uint8

    def _work_buf_shape(self, kw) -> tuple:
        from .ops.partition import planes_npad, work_spec
        guard, w = work_spec(self.bins.shape[1],
                             kw["hist_mode"] == "int8", kw["part_kernel"],
                             kw["part_chunk"], kw["hist_chunk"],
                             layout=kw["work_layout"])
        n = self.bins.shape[0]
        m = kw.get("goss_compact_rows", 0)
        if 0 < m < n:
            # GOSS compaction: the carried buffer serves the compact
            # branch (the dense warmup/overflow branch allocates its own
            # N-sized buffers in-graph)
            n = m
        if kw["work_layout"] in ("planes", "resident"):
            return (2, w, planes_npad(n, guard, kw["part_kernel"]))
        return (2, n + 2 * guard, w)

    def resident_spec(self):
        """(guard, npad) of the resident bin-plane buffer, or None when the
        resolved layout is not resident. Shared by the fused trainer's
        per-block hoist and the dataset's version-token device cache."""
        if not self.use_partition():
            return None
        from .ops.partition import planes_npad, work_spec
        kw = self.build_kwargs()
        if kw["work_layout"] != "resident":
            return None
        guard, _ = work_spec(self.bins.shape[1],
                             kw["hist_mode"] == "int8", kw["part_kernel"],
                             kw["part_chunk"], kw["hist_chunk"],
                             layout=kw["work_layout"])
        return guard, planes_npad(self.bins.shape[0], guard,
                                  kw["part_kernel"])

    def traffic_spec(self):
        """Deterministic bytes-moved accounting of the per-split hot loop
        for the resolved config (bench observability; PERF.md traffic
        tables). Per PARENT ROW per split: the partition reads the src
        chunk and writes the dst chunk at the moved work width (plus the
        resident route pre-pass: 4 ridx read + 1 gather read + 1 route
        write); the smaller-child histogram reads the payload planes plus,
        for resident, the F gathered bin bytes."""
        if not self.use_partition():
            return None
        from .ops.partition import RST_GH_OFF, work_spec
        kw = self.build_kwargs()
        layout = kw["work_layout"]
        _, w = work_spec(self.bins.shape[1], kw["hist_mode"] == "int8",
                         kw["part_kernel"], kw["part_chunk"],
                         kw["hist_chunk"], layout=layout)
        f = self.bins.shape[1]
        part = 2 * w
        if layout == "resident":
            part += RST_GH_OFF + 1      # route pre-pass gather traffic
            hist = w + f                # slim payload + gathered bin bytes
        elif layout == "planes":
            hist = w
        else:
            hist = w                    # row-major reads the packed row
        n = int(self.bins.shape[0])
        m = int(kw.get("goss_compact_rows", 0))
        return {"work_layout": layout, "work_width": int(w),
                "partition_bytes_per_row": int(part),
                "hist_bytes_per_row": int(hist),
                # rows every downstream pass scans per tree: the GOSS
                # compact prefix when compaction resolved on, else N
                "effective_rows": m if 0 < m < n else n,
                "goss_compact": "on" if 0 < m < n else "off"}

    def train(self, ghc: jax.Array, feature_mask: jax.Array, key: jax.Array,
              cegb_used: Optional[jax.Array] = None) -> TreeLog:
        """One tree from (grad, hess, inbag) channels. Returns the device log."""
        if cegb_used is None:
            cegb_used = jnp.zeros((self.dataset.num_features,), bool)
        rspec = getattr(self, "_rspec_cache", False)
        if rspec is False:
            rspec = self._rspec_cache = self.resident_spec()
        if rspec is not None:
            # one cached device copy of the resident bin planes per dataset
            # (original row order, training-invariant) instead of an
            # in-graph transpose per tree
            return self._build(
                self.bins, ghc, self.meta, feature_mask, key, cegb_used,
                bins_res=self.dataset.device_resident_planes(*rspec))
        return self._build(self.bins, ghc, self.meta, feature_mask, key,
                           cegb_used)

    def log_to_tree(self, log: TreeLog) -> Tree:
        """Pull the split log to host and rebuild the Tree model.

        One batched transfer: per-field np.asarray would cost a blocking
        device->host round-trip each.
        ``row_leaf`` (O(rows)) stays on device — only O(leaves) data moves.
        """
        (num_splits, split_leaf, feature, bin_, default_left, gain, left_sum,
         right_sum, leaf_value, kind, go_left) = jax.device_get(
            (log.num_splits, log.split_leaf, log.feature, log.bin,
             log.default_left, log.gain, log.left_sum, log.right_sum,
             log.leaf_value, log.kind, log.go_left))
        return Tree.from_split_log(
            int(num_splits),
            split_leaf, feature, bin_, default_left, gain, left_sum, right_sum,
            leaf_value,
            bin_mappers=self.dataset.bin_mappers,
            real_feature_index=self.dataset.used_feature_indices,
            go_left_table=go_left,
            is_categorical=kind > 0,
        )
