"""Fused multi-iteration training blocks.

TPU-first restructuring of the boosting loop: the reference pays a C++
function call per phase (gbdt.cpp:369 TrainOneIter — Boosting, Bagging,
learner Train, UpdateScore); a naive port pays a *device launch* per phase,
and the host round trip between them. Instead, when no
per-iteration host observation is needed (no valid-set eval, no
objective leaf renewal, no custom fobj), K whole boosting iterations —
gradients, in-graph bagging/GOSS sampling, tree growth, score update — run
as ONE jitted ``lax.scan``: one launch and one small device->host transfer
of the stacked split logs per K trees.

In-graph sampling reproduces the reference semantics (bagging re-drawn every
``bagging_freq`` iters, gbdt.cpp:228; GOSS top-|g·h| with amplification,
goss.hpp:103) using jax.random instead of the host RNG.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import obs_device, runtime
from .config import Config
from .learner import SerialTreeLearner, TreeLog, leaf_values_by_row
from .obs import (count_trees, host_phase, monotonic, sync, telemetry,
                  trace_phase, track_jit)

# Process-wide cache of jitted block functions. A Booster's jitted callables
# die with the Booster, so back-to-back train() calls with identical
# config/shape fingerprints (the bench's warmup+timed pair, CV folds, the
# test suite) would re-pay trace+lower+compile (~20-30 s at 2M rows) per
# call. All data-dependent arrays are passed as jit ARGUMENTS (never closure
# constants), so a fingerprint hit is safe across Booster instances: the
# cached trace reads its array state from the call's operands.
_BLOCK_CACHE: dict = {}  # graftlint: disable=module-mutable-state -- cross-Booster jit cache; keyed by shape fingerprint
_BLOCK_CACHE_MAX = 64
# the fused_block records a job keeps: its first and the newest 1,023
_BLOCK_RECORDS = 1024


def _fp_hash(x) -> str:
    import hashlib
    h = hashlib.sha1()
    if isinstance(x, np.ndarray):
        h.update(str(x.dtype).encode()); h.update(str(x.shape).encode())
        h.update(np.ascontiguousarray(x).tobytes())
    elif isinstance(x, jax.Array):
        return _fp_hash(np.asarray(x))
    elif isinstance(x, (list, tuple)):
        for v in x:
            h.update(_fp_hash(v).encode())
    elif isinstance(x, dict):
        for k in sorted(x):
            h.update(str(k).encode()); h.update(_fp_hash(x[k]).encode())
    else:
        h.update(repr(x).encode())
    return h.hexdigest()


def _config_fp(cfg: Config) -> str:
    import dataclasses
    items = []
    for f in dataclasses.fields(cfg):
        v = getattr(cfg, f.name)
        if isinstance(v, (list, dict)):
            v = repr(v)
        items.append((f.name, v))
    return _fp_hash(items)


def _is_array_tree(v) -> bool:
    """True for a non-empty pytree (list/tuple/dict nesting) whose leaves
    are ALL jax.Arrays — e.g. the ranking objectives' per-bucket tables."""
    if isinstance(v, jax.Array):
        return True
    if isinstance(v, (list, tuple)):
        return bool(v) and all(_is_array_tree(x) for x in v)
    if isinstance(v, dict):
        return bool(v) and all(_is_array_tree(x) for x in v.values())
    return False


def _obj_array_state(obj) -> dict:
    """The objective's jax.Array(-pytree) attributes, passed as jit
    operands so no N-sized data embeds in the trace."""
    return {k: v for k, v in vars(obj).items() if _is_array_tree(v)}


def _obj_static_fp(obj) -> str:
    """Fingerprint of everything on the objective that is NOT passed as an
    operand (python scalars, np arrays — these embed in the trace). Array
    pytrees contribute their structure + leaf signatures only."""
    items = []
    skip = getattr(obj, "fp_skip_attrs", ())
    for k in sorted(vars(obj)):
        if k in skip:
            # host mirrors of device operands: never read by traced code,
            # and hashing 2M-row arrays per block fingerprint is waste
            continue
        v = getattr(obj, k)
        if _is_array_tree(v):
            sig = [(str(a.shape), str(a.dtype)) for a in jax.tree.leaves(v)]
            items.append((k, "arrtree", repr(jax.tree.structure(v)),
                          repr(sig)))
        else:
            items.append((k, _fp_hash(v)))
    return _fp_hash([type(obj).__name__, items])


class BlockLogs(NamedTuple):
    """Stacked per-tree split logs for one fused block: (k, T_per_iter, ...)"""
    num_splits: jax.Array
    split_leaf: jax.Array
    feature: jax.Array
    bin: jax.Array
    kind: jax.Array
    default_left: jax.Array
    gain: jax.Array
    left_sum: jax.Array
    right_sum: jax.Array
    go_left: jax.Array
    leaf_value: jax.Array


def _small(log: TreeLog, has_categorical: bool) -> BlockLogs:
    # go_left is only consumed for categorical splits (numerical routing
    # rebuilds from feature/bin/default_left); dropping the (R, B) table
    # from the per-block device->host transfer saves its payload entirely
    # on categorical-free datasets
    return BlockLogs(
        num_splits=log.num_splits, split_leaf=log.split_leaf,
        feature=log.feature, bin=log.bin, kind=log.kind,
        default_left=log.default_left, gain=log.gain,
        left_sum=log.left_sum, right_sum=log.right_sum,
        go_left=log.go_left if has_categorical else log.go_left[:0],
        leaf_value=log.leaf_value)


def _seed_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)


def make_sampler(config: Config, num_data: int):
    """In-graph (inbag, amplification) masks; None when sampling is off.

    The RNG streams derive from ``bagging_seed`` alone (NOT the boosting
    key), so the eager host loop and the fused device blocks draw IDENTICAL
    masks for the same config — the reference's seed contract
    (config.h bagging_seed; gbdt.cpp:228 Bagging uses its own Random).
    """
    cfg = config
    if cfg.data_sample_strategy == "goss":
        warmup = int(1.0 / max(cfg.learning_rate, 1e-12))
        top_rate, other_rate = cfg.top_rate, cfg.other_rate
        if top_rate + other_rate >= 1.0:
            return None
        base = _seed_key(cfg.bagging_seed)

        def goss(key, it, g, h):
            s = jnp.abs(g * h) if g.ndim == 1 else jnp.sum(jnp.abs(g * h), axis=1)
            top_k = max(1, int(num_data * top_rate))
            # k-th largest via top_k (O(N log k)) — same multiset element as
            # jnp.sort(s)[num_data - top_k], so `is_top` is bit-compatible
            # with the full-sort threshold (pinned in test_goss_compact.py)
            thr = jax.lax.top_k(s, top_k)[0][top_k - 1]
            is_top = s >= thr
            rest_rate = other_rate / max(1e-12, 1.0 - top_rate)
            u = jax.random.uniform(jax.random.fold_in(base, 7000 + it),
                                   (num_data,))
            sampled = (u < rest_rate) & ~is_top
            amp = (1.0 - top_rate) / max(other_rate, 1e-12)
            inbag = (is_top | sampled).astype(jnp.float32)
            ampv = jnp.where(sampled, amp, 1.0).astype(jnp.float32)
            warm = it < warmup
            ones = jnp.ones((num_data,), jnp.float32)
            return (jnp.where(warm, ones, inbag), jnp.where(warm, ones, ampv))

        return goss
    need = cfg.bagging_freq > 0 and (
        cfg.bagging_fraction < 1.0 or cfg.pos_bagging_fraction < 1.0
        or cfg.neg_bagging_fraction < 1.0)
    if not need:
        return None
    freq = max(1, cfg.bagging_freq)
    base = _seed_key(cfg.bagging_seed)

    def bagging(key, it, g, h):
        rnd = it // freq
        u = jax.random.uniform(jax.random.fold_in(base, 9000 + rnd),
                               (num_data,))
        mask = (u < cfg.bagging_fraction).astype(jnp.float32)
        return mask, jnp.ones((num_data,), jnp.float32)

    return bagging


def make_balanced_sampler(config: Config, label: jax.Array):
    cfg = config
    freq = max(1, cfg.bagging_freq)
    pos = label > 0
    base = _seed_key(cfg.bagging_seed)

    def bagging(key, it, g, h):
        rnd = it // freq
        u = jax.random.uniform(jax.random.fold_in(base, 9000 + rnd),
                               label.shape)
        mask = jnp.where(pos, u < cfg.pos_bagging_fraction,
                         u < cfg.neg_bagging_fraction).astype(jnp.float32)
        return mask, jnp.ones(label.shape, jnp.float32)

    return bagging


def make_feature_mask_fn(config: Config, num_feat: int):
    """Per-iteration by-tree column mask; shared by eager and fused paths
    (stream derives from feature_fraction_seed)."""
    cfg = config
    if cfg.feature_fraction >= 1.0:
        return None
    kk = max(1, int(np.ceil(cfg.feature_fraction * num_feat)))
    base = _seed_key(cfg.feature_fraction_seed)

    def fmask(it):
        u = jax.random.uniform(jax.random.fold_in(base, 555 + it),
                               (num_feat,))
        rank = jnp.argsort(jnp.argsort(u))
        return rank < kk

    return fmask


class FusedTrainer:
    """Builds and caches the jitted K-iteration block function for a GBDT."""

    def __init__(self, gbdt) -> None:
        self.gbdt = gbdt
        self.learner: SerialTreeLearner = gbdt.learner
        self.config: Config = gbdt.config
        cfg = self.config
        self._balanced = bool(
            cfg.data_sample_strategy != "goss"
            and (cfg.pos_bagging_fraction < 1.0
                 or cfg.neg_bagging_fraction < 1.0)
            and cfg.bagging_freq > 0 and gbdt.objective.label is not None)
        self.num_feat = gbdt.train_set.num_features
        # pipeline state: the dispatched-but-not-finalized block and the
        # device-resident cegb feature-used mask
        self._pending = None
        self._cegb_used_dev = None
        self._blocks_recorded = 0   # the next fused_block record's index

    def _fingerprint(self, k: int) -> tuple:
        """Everything that shapes the traced block computation but is not a
        jit operand: the resolved config, the objective's static state, the
        learner's closed-over arrays (EFB bundle, forced splits, interaction
        constraints), and the operand shape signature."""
        g = self.gbdt
        lrn = self.learner
        bins = lrn.bins
        return (
            k, g.num_tree_per_iteration, type(lrn).__name__,
            _config_fp(g.config), _obj_static_fp(g.objective),
            str(bins.shape), str(bins.dtype), str(g.train_score.score.shape),
            lrn.num_bin_hist,
            # hp derives from config AND dataset facts (categorical columns
            # arrive via the Dataset API, not Config) — e.g.
            # has_categorical shapes the traced go_left output
            tuple(lrn.hp),
            (lrn.comm.axis, lrn.comm.mode, lrn.comm.top_k,
             lrn.comm.num_machines),
            _fp_hash(lrn.bundle), lrn.bundle_view,
            _fp_hash(lrn._forced_splits()),
            _fp_hash(lrn._constraint_sets()),
        )

    def _block_fn(self, k: int):
        fp = self._fingerprint(k)
        fn = _BLOCK_CACHE.get(fp)
        if fn is not None:
            return fn
        gbdt = self.gbdt
        learner = self.learner
        cfg = self.config
        obj = gbdt.objective
        K = gbdt.num_tree_per_iteration
        lr = float(cfg.learning_rate)
        balanced = self._balanced
        nf = self.num_feat
        fmask_fn = make_feature_mask_fn(cfg, nf)
        build = learner.make_build_fn()
        wspec = learner.work_buf_spec()
        rspec = learner.resident_spec()

        def one_iter(sampler, bins, bins_t, bins_res, meta, score, cegb_used,
                     wbuf, key, it):
            g, h = obj.gradients(score, it)
            with trace_phase("lgbtpu/sample"):
                if sampler is not None:
                    inbag, amp = sampler(key, it, g, h)
                else:
                    inbag = amp = None
                if fmask_fn is not None:
                    fmask = fmask_fn(it)
                else:
                    fmask = jnp.ones((nf,), bool)
            logs = []
            for c in range(K):
                with trace_phase("lgbtpu/sample"):
                    gc = g if g.ndim == 1 else g[:, c]
                    hc = h if h.ndim == 1 else h[:, c]
                    if inbag is not None:
                        gc, hc = gc * amp * inbag, hc * amp * inbag
                        cnt = inbag
                    else:
                        cnt = jnp.ones_like(gc)
                    ghc = jnp.stack([gc, hc, cnt], axis=1)
                if wspec is not None:
                    log, wbuf = build(
                        bins, ghc, meta, fmask,
                        jax.random.fold_in(key, it * 131 + c), cegb_used,
                        work_buf=wbuf, return_work=True, bins_t=bins_t,
                        bins_res=bins_res)
                else:
                    log = build(bins, ghc, meta, fmask,
                                jax.random.fold_in(key, it * 131 + c),
                                cegb_used)
                with trace_phase("lgbtpu/tree_log"):
                    valid_r = jnp.arange(log.feature.shape[0]) \
                        < log.num_splits
                    cegb_used = cegb_used.at[
                        jnp.where(valid_r, log.feature, nf)].set(
                            True, mode="drop")
                with trace_phase("lgbtpu/score_update"):
                    vals = log.leaf_value * jnp.float32(lr)
                    upd = leaf_values_by_row(vals, log.row_leaf,
                                             vals.shape[0]) \
                        * (log.num_splits > 0)
                    if K > 1:
                        score = score.at[:, c].add(upd)
                    else:
                        score = score + upd
                with trace_phase("lgbtpu/tree_log"):
                    logs.append(_small(log, learner.hp.has_categorical))
            with trace_phase("lgbtpu/tree_log"):
                stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *logs) \
                    if K > 1 else logs[0]
            return score, cegb_used, wbuf, stacked

        @jax.jit
        def run_block(score, cegb_used, key, it0, bins, meta, ostate):
            # Array state rides in as operands; swap it onto the objective
            # for the duration of the trace so nothing N-sized embeds in the
            # program (embedded constants made lowering + compile-cache
            # serialization scale with the dataset: ~30 s/call at 2M rows).
            saved = {a: getattr(obj, a) for a in ostate}
            for a, v in ostate.items():
                setattr(obj, a, v)
            try:
                with trace_phase("lgbtpu/sample"):
                    if balanced:
                        sampler = make_balanced_sampler(cfg, obj.label)
                    else:
                        sampler = make_sampler(cfg, score.shape[0])
                with trace_phase("lgbtpu/block_setup"):
                    # one ping-pong work buffer allocated per block and
                    # carried across the k trees (a fresh alloc+zero per
                    # tree costs ~260 MB of HBM writes at 2M rows). The spec
                    # is layout-aware: (2, Npad, W) row-major or (2, W, Npad)
                    # transposed planes (learner.work_buf_spec /
                    # tpu_work_layout) — this loop never looks inside it
                    wbuf = jnp.zeros(wspec[0], wspec[1]) \
                        if wspec is not None else jnp.zeros((), jnp.uint8)
                    # transposed bins for the per-tree routing pass,
                    # computed once per block (loop-invariant; ~20 ms at
                    # 2M x 28). When the Pallas route kernel applies, hoist
                    # its padded (F, npad/128, 128) block form so no
                    # per-tree pad/reshape copy rides inside the scan body.
                    bins_t = None
                    if wspec is not None:
                        from .ops.route import (ROUTE_BLOCK_ROWS,
                                                pallas_routes)
                        bins_t = bins.T
                        if runtime.on_tpu() and pallas_routes(
                                learner.hp.has_categorical,
                                learner.num_bin):
                            n_ = bins.shape[0]
                            npad = ((n_ + ROUTE_BLOCK_ROWS - 1)
                                    // ROUTE_BLOCK_ROWS) * ROUTE_BLOCK_ROWS
                            if npad != n_:
                                bins_t = jnp.pad(bins_t,
                                                 ((0, 0), (0, npad - n_)))
                            bins_t = bins_t.reshape(bins.shape[1],
                                                    npad // 128, 128)
                    # resident bin planes for tpu_resident_state: uploaded
                    # once per block in ORIGINAL row order; the per-split
                    # partition only permutes the slim route/ridx/g/h/c
                    # payload and the histogram gathers bins through the
                    # row-index plane.
                    bins_res = None
                    if rspec is not None:
                        from .ops.partition import resident_bin_planes
                        bins_res = resident_bin_planes(bins, *rspec)

                def body(carry, i):
                    score, used, wbuf = carry
                    score, used, wbuf, stacked = one_iter(
                        sampler, bins, bins_t, bins_res, meta, score, used,
                        wbuf, key, it0 + i)
                    return (score, used, wbuf), stacked
                (score, used, _), stacked = jax.lax.scan(
                    body, (score, cegb_used, wbuf), jnp.arange(k))
                return (score, used), stacked
            finally:
                for a, v in saved.items():
                    setattr(obj, a, v)

        if len(_BLOCK_CACHE) >= _BLOCK_CACHE_MAX:
            _BLOCK_CACHE.clear()
        run_block = track_jit("fused/run_block", run_block)
        _BLOCK_CACHE[fp] = run_block
        return run_block

    def run(self, k: int) -> bool:
        """Run k fused iterations. Returns True when training should stop.

        Pipelined: the device block is dispatched (async) and the PREVIOUS
        block's host-side work — the blocking logs transfer and per-tree
        reconstruction (~80 ms/iter on a 1-core host) — happens while the
        new block executes on device. The returned stop signal therefore
        refers to the previous block; when it fires, the in-flight block's
        state is rolled back so the model matches the non-pipelined
        semantics exactly (training stops at the first all-constant
        iteration; reference: gbdt.cpp:379 "no more leaves"). Callers must
        invoke :meth:`flush` when the training loop ends.

        Every tree a kept block computed is appended (constant trees
        contributed zero score in-graph via the num_splits mask), so model
        and score stay consistent for rollback/continued training."""
        gbdt = self.gbdt
        with host_phase("lgbtpu/fused_block_fn"):
            fn = self._block_fn(k)
        prev = self._pending
        with host_phase("lgbtpu/fused_args"):
            # iter_ only advances when a block is FINALIZED (keeps iter_
            # and models consistent if finalization fails); schedule from
            # iter_ plus the not-yet-finalized block's length
            it0 = gbdt.iter_ + (prev[1] if prev is not None else 0)
            pre_score = gbdt.train_score.score
            pre_used = self._used_dev()
            # host-side counters only — the dispatch stays async (no sync
            # here; the real device wait is the logs transfer in _finalize)
            telemetry.count("fused/blocks_dispatched")
            telemetry.count("fused/iters_dispatched", k)
            args = (pre_score, pre_used, gbdt._key, jnp.int32(it0),
                    self.learner.bins, self.learner.meta,
                    _obj_array_state(gbdt.objective))
        with host_phase("lgbtpu/fused_dispatch"):
            (score, used), logs = fn.dispatch(*args)
        dispatched_s = monotonic()
        job, gbdt._job_start = gbdt._job_start, None
        if job is not None:     # the first block of an lgb.train call
            job.dispatched("fused")
        with host_phase("lgbtpu/fused_after_call"):
            fn.after_call(args, {})     # compile count, cost capture
        gbdt.train_score.score = score
        self._cegb_used_dev = used
        if self.config.obs_check_finite != "off":
            # opt-in watchdog: one fused isfinite reduction over the
            # block's output scores. The scalar fetch waits on THIS block,
            # trading the one-block pipeline overlap for catching a NaN
            # blow-up at the block it happened (grads are internal to the
            # scan; a non-finite grad surfaces in the scores it produces).
            obs_device.check_finite("scores", (score,),
                                    self.config.obs_check_finite)
        # pre_score/pre_used ride along for the rollback paths below, the
        # first iteration and the dispatch's stamp for the block's record
        self._pending = (logs, k, pre_score, pre_used, it0, dispatched_s)
        stopped = self._finalize(prev)
        if stopped:
            # previous block ended all-constant: drop the in-flight block
            # (its trees would all be constant too, but the reference model
            # stops at the first all-constant iteration)
            self._rollback(pre_score, pre_used)
        return stopped

    def _used_dev(self) -> jax.Array:
        dev = self._cegb_used_dev
        if dev is None:
            dev = jnp.asarray(self.gbdt._cegb_used)
        return dev

    def _rollback(self, pre_score, pre_used) -> None:
        """Drop the in-flight block and restore pre-block device state."""
        self.gbdt.train_score.score = pre_score
        self._cegb_used_dev = pre_used
        self._pending = None

    def flush(self, reason: str = "unspecified") -> bool:
        """Finalize the in-flight block (if any) and sync host-side state.
        Returns True when the finalized block ended all-constant.

        ``reason`` names which read API forced the flush (predict,
        model_to_string, train_end, ...) — counted under
        ``fused/flush/<reason>`` only when a block was actually in flight,
        so the counters show exactly which entry points break the
        pipeline's one-block overlap."""
        pending = self._pending
        self._pending = None
        if pending is not None:
            telemetry.count("fused/flush/" + reason)
        try:
            stopped = self._finalize(pending)
        except BaseException:
            # best-effort sync while an exception is already propagating —
            # only here is swallowing a secondary failure acceptable
            dev = self._cegb_used_dev
            if dev is not None:
                try:
                    # np.array, not asarray: a device buffer viewed through
                    # asarray is read-only, which breaks continued training
                    self.gbdt._cegb_used = np.array(dev)
                    self._cegb_used_dev = None
                except Exception:
                    pass
            raise
        dev = self._cegb_used_dev
        if dev is not None:
            self.gbdt._cegb_used = np.array(dev)
            self._cegb_used_dev = None
        return stopped

    def _finalize(self, pending) -> bool:
        """Append a dispatched block's trees and advance iter_. On failure
        (device error, interrupt during the transfer or the host tree loop)
        the booster rolls back to its last finalized state: score/used
        revert to the block's inputs, no partial trees are kept, and any
        in-flight successor block is dropped."""
        if pending is None:
            return False
        logs, k, pre_score, pre_used, it0, dispatched_s = pending
        gbdt = self.gbdt
        K = gbdt.num_tree_per_iteration
        last_iter_constant = False
        trees = []
        try:
            # Device-time attribution (ADVICE item 4): the old single
            # logs_transfer block conflated waiting for the device with
            # pulling the payload, making "transfer" a >90% catch-all in
            # the bench breakdown. Split per discipline v2: a forced
            # 1-element transfer (obs.sync — the only trusted completion
            # barrier) bounds non-overlapped DEVICE time as the host
            # experiences it; the device_get that follows is then the
            # pure host<-device payload pull. Pipelining is preserved:
            # _finalize waits on the PREVIOUS block while the freshly
            # dispatched one executes.
            with host_phase("lgbtpu/fused_device_wait"):
                sync(logs)
            wait_end_s = monotonic()
            with host_phase("lgbtpu/fused_flush"):
                host = jax.device_get(logs)
            with host_phase("lgbtpu/fused_host_trees"):
                for i in range(k):
                    all_constant = True
                    for c in range(K):
                        pick = (lambda a: a[i, c] if K > 1 else a[i])
                        tree = self._host_tree(host, pick)
                        tree.apply_shrinkage(
                            float(self.config.learning_rate))
                        trees.append(tree)
                        if tree.num_leaves > 1:
                            all_constant = False
                    last_iter_constant = all_constant
        except BaseException:
            self._rollback(pre_score, pre_used)
            raise
        with host_phase("lgbtpu/fused_commit"):
            # atomic commit: models/iter_/version move together only on
            # full success, under the model lock so serving never packs
            # mid-commit
            with gbdt._cache_lock:
                gbdt.models.extend(trees)
                gbdt.iter_ += k
                gbdt._bump_model_version()
            # dispatched - finalized = the iterations in flight
            telemetry.count("fused/iters_finalized", k)
            obs_device.maybe_sample_hbm()   # block-boundary HBM watermark
            grown = count_trees(trees, self.learner.dataset.num_data)
            work = [t.work() for t in trees]
            grown.update(row_visits=sum(w["row_visits"] for w in work),
                         hist_rows=sum(w["hist_rows"] for w in work))
        self._record_block(it0, k, dispatched_s, wait_end_s, grown)
        return last_iter_constant

    def _record_block(self, it0, k, dispatched_s, wait_end_s, grown) -> None:
        """One ``fused_block`` record a finalized block: the job's timeline
        on the host's ``time.perf_counter()``, with the growth of the
        block's trees (``obs.count_trees``) and their work
        (``tree.Tree.work``: ``row_visits``, the partition's rows, and
        ``hist_rows``, the histogram kernel's, from counts the split log
        already carried: no transfer and no sync of its own).

        ``index`` counts this trainer's finalized blocks from 0,
        ``first_iter`` and ``iters`` place the block in the model, ``rows``
        is the training set's. ``dispatched_s`` is read when the block's
        ``fn.dispatch`` returned, ``wait_end_s`` when ``sync(logs)``
        returned, ``finalized_s`` at the end of ``lgbtpu/fused_commit``.
        On a TPU the forced read of block i's logs is queued behind block
        i + 1, which was dispatched before it: ``wait_end_s`` comes when
        block i + 1 is done, so the difference of two consecutive records'
        ``wait_end_s`` is the device's period a block (the later record's
        successor's, to be exact), with no profiler; and the first record's
        ``wait_end_s - dispatched_s`` spans blocks 0 and 1. A block that a
        read API flushes has no successor in flight and waits for itself.
        ``engine.train`` starts the list anew; it holds the job's first
        record and its newest ``_BLOCK_RECORDS - 1``."""
        telemetry.record(
            "fused_block", keep=_BLOCK_RECORDS, index=self._blocks_recorded,
            first_iter=it0, iters=k, rows=self.learner.dataset.num_data,
            dispatched_s=dispatched_s, wait_end_s=wait_end_s,
            finalized_s=monotonic(),
            **{f: grown[f] for f in ("splits", "splits_categorical",
                                     "splits_default_left", "leaves",
                                     "row_visits", "hist_rows")})
        self._blocks_recorded += 1

    def _host_tree(self, host: BlockLogs, pick):
        from .tree import Tree
        ds = self.learner.dataset
        has_tbl = host.go_left.shape[-2] > 0
        return Tree.from_split_log(
            int(pick(host.num_splits)),
            pick(host.split_leaf), pick(host.feature), pick(host.bin),
            pick(host.default_left), pick(host.gain), pick(host.left_sum),
            pick(host.right_sum), pick(host.leaf_value),
            bin_mappers=ds.bin_mappers,
            real_feature_index=ds.used_feature_indices,
            go_left_table=pick(host.go_left) if has_tbl else None,
            is_categorical=pick(host.kind) > 0,
        )
